"""Plain reference for the `resnet50` configuration: ResNet-50 v1 (He et al.
2015, arXiv:1512.03385) training with softmax cross-entropy and SGD with
Nesterov momentum, in straightforward `jax.numpy`, float32, with
`jax.default_matmul_precision("highest")`.

It imports nothing of the program. The benchmark makes the weights here, from
the seed, in one jitted call, and hands the same values to the program; the
tree is keyed by the names the zoo gives its vertices so the harness can place
it leaf for leaf.

As published: 7x7/2 stem, 3x3/2 max pool, [3, 4, 6, 3] bottleneck blocks with
the stride on the first 1x1 convolution (v1), a projection shortcut on each
stage's first block, batch normalisation (biased batch variance, eps 1e-5)
after every convolution, global average pool, a 1000-way dense head.
Departures, shared with the program under test: pixels enter as raw 0..255
values (the stem's batch normalisation absorbs the scale), and there is no
weight decay.

`precision` selects the arithmetic: "float32" is the reference; "float8" is
the control of the correctness check, the step below the bfloat16 the
configuration states: as the program keeps its activations and multiplies in
bfloat16, the control keeps them in float8_e4m3 (per-tensor scaled) — the
operands of every convolution and of the head, and what every batch norm,
ReLU and residual sum hands on, forward; and backward the gradient that
reaches each convolution in float8_e5m2, as float8 training recipes keep
it. Gradients pass the forward rounding straight through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

STAGES = (("s2", (64, 64, 256), 3, 1), ("s3", (128, 128, 512), 4, 2),
          ("s4", (256, 256, 1024), 6, 2), ("s5", (512, 512, 2048), 3, 2))
BN_EPS = 1e-5
BRANCH_GAMMA = 0.05


def conv_shapes(classes=1000):
    """[(name, (kh, kw, cin, cout), stride)] of every convolution, in order,
    and the head's (in, out)."""
    convs = [("stem_conv", (7, 7, 3, 64), 2)]
    cin = 64
    for stage, (f1, f2, f3), blocks, stride in STAGES:
        for b in range(1, blocks + 1):
            s = stride if b == 1 else 1
            n = f"{stage}b{b}"
            convs.append((f"{n}_c1", (1, 1, cin, f1), s))
            convs.append((f"{n}_c2", (3, 3, f1, f2), 1))
            convs.append((f"{n}_c3", (1, 1, f2, f3), 1))
            if b == 1:
                convs.append((f"{n}_proj", (1, 1, cin, f3), s))
            cin = f3
    return convs, (cin, classes)


def forward_macs(args):
    """Multiply-accumulates of one image's forward pass through the
    convolutions and the head, for the configuration's `args` (image_size,
    num_classes): 3.86e9 at 224x224, the paper's "3.8 x 10^9" (4.09e9 is the
    later v1.5 with the stride on the 3x3). Read by layer_metrics/
    train_mfu_pct.py through the configuration's `reference` name."""
    from benchmarks.flops import conv_macs, conv_out
    convs, (hin, hout) = conv_shapes(args["num_classes"])
    total = 0
    cur = block_in = args["image_size"]     # extent entering the next conv
    for name, (kh, kw, cin, cout), stride in convs:
        if name == "stem_conv":
            out = conv_out(cur, stride)
            total += conv_macs(out, out, kh, kw, cin, cout)
            cur = conv_out(out, 2)          # 3x3/2 max pool
            continue
        if name.endswith("_c1"):
            block_in = cur
            out = conv_out(block_in, stride)
            total += conv_macs(out, out, kh, kw, cin, cout)
            cur = out
        elif name.endswith("_proj"):
            out = conv_out(block_in, stride)
            total += conv_macs(out, out, kh, kw, cin, cout)
        else:
            total += conv_macs(cur, cur, kh, kw, cin, cout)
    return total + hin * hout


def bn_of(conv_name):
    if conv_name == "stem_conv":
        return "stem_bn"
    if conv_name.endswith("_proj"):
        return conv_name + "bn"
    return conv_name[:-2] + "bn" + conv_name[-1]


@functools.partial(jax.jit, static_argnums=(1,))
def init_params(key, classes=1000):
    """(params, bn_state) from a PRNG key (seeds.key_of(seed)): He-normal
    convolutions (fan in), unit gamma (BRANCH_GAMMA on a branch's last batch
    norm), zero beta, a head of std 0.01, in one
    jitted call on the device."""
    convs, (hin, hout) = conv_shapes(classes)
    keys = jax.random.split(key, len(convs) + 1)
    params, state = {}, {}
    for k, (name, shape, _) in zip(keys, convs):
        fan_in = shape[0] * shape[1] * shape[2]
        params[name] = {"W": jax.random.normal(k, shape, jnp.float32)
                        * jnp.sqrt(2.0 / fan_in)}
        c = shape[3]
        # the last batch norm of a residual branch starts small (Goyal et
        # al. 2017 start it at zero), so a block starts near the identity
        # and the gradients at the seeded weights are well conditioned
        g = BRANCH_GAMMA if name.endswith("_c3") else 1.0
        params[bn_of(name)] = {"gamma": jnp.full((c,), g, jnp.float32),
                               "beta": jnp.zeros((c,), jnp.float32)}
        state[bn_of(name)] = {"mean": jnp.zeros((c,), jnp.float32),
                              "var": jnp.ones((c,), jnp.float32)}
    params["out"] = {"W": jax.random.normal(keys[-1], (hin, hout),
                                            jnp.float32) * 0.01,
                     "b": jnp.zeros((hout,), jnp.float32)}
    return params, state


def _fp8(x):
    """x rounded to float8_e4m3 under a per-tensor scale, gradient passed
    straight through."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


@jax.custom_vjp
def _fp8_grad(y):
    """Identity forward; the gradient coming back is rounded to float8_e5m2
    under a per-tensor scale, as float8 training keeps its gradients."""
    return y


def _fp8_grad_fwd(y):
    return y, None


def _fp8_grad_bwd(_, g):
    scale = jnp.max(jnp.abs(g)) / 57344.0 + 1e-30
    return ((g / scale).astype(jnp.float8_e5m2).astype(jnp.float32) * scale,)


_fp8_grad.defvjp(_fp8_grad_fwd, _fp8_grad_bwd)


def _conv(x, w, stride, precision):
    if precision == "float8":
        x, w = _fp8(x), _fp8(w)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    return _fp8_grad(y) if precision == "float8" else y


def _bn(x, p, relu, precision="float32"):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + BN_EPS) * p["gamma"] + p["beta"]
    y = jnp.maximum(y, 0.0) if relu else y
    return _fp8(y) if precision == "float8" else y


def _block(x, params, name, stride, project, precision):
    def cbn(h, c, s, relu):
        return _bn(_conv(h, params[f"{name}_{c}"]["W"], s, precision),
                   params[bn_of(f"{name}_{c}")], relu, precision)
    y = cbn(x, "c1", stride, True)
    y = cbn(y, "c2", 1, True)
    y = cbn(y, "c3", 1, False)
    skip = cbn(x, "proj", stride, False) if project else x
    y = jnp.maximum(y + skip, 0.0)
    return _fp8(y) if precision == "float8" else y


def loss_fn(params, pixels, labels, precision="float32"):
    """Mean softmax cross-entropy of one batch: uint8 pixels [B, H, W, 3],
    int labels [B]. Batch-norm in training mode. Each bottleneck block is
    rematerialised so a batch of 256 at 224x224 fits a 16 GB chip in
    float32."""
    x = pixels.astype(jnp.float32)
    x = _bn(_conv(x, params["stem_conv"]["W"], 2, precision),
            params["stem_bn"], True, precision)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for stage, _, blocks, stride in STAGES:
        for b in range(1, blocks + 1):
            names = [k for k in params if k.startswith(f"{stage}b{b}_")]
            sub = {k: params[k] for k in names}
            blk = jax.checkpoint(functools.partial(
                _block, name=f"{stage}b{b}", stride=stride if b == 1 else 1,
                project=b == 1, precision=precision))
            x = blk(x, sub)
    feats = jnp.mean(x, axis=(1, 2))
    w = params["out"]["W"]
    if precision == "float8":
        feats, w = _fp8(feats), _fp8(w)
    logits = jnp.dot(feats, w, precision=lax.Precision.HIGHEST) \
        + params["out"]["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels.astype(jnp.int32)[:, None],
                                 axis=1)[:, 0]
    return -jnp.mean(picked)


@functools.partial(jax.jit, static_argnames=("precision",),
                   donate_argnums=(0, 1))
def train_step(params, trace, pixels, labels, lr, momentum,
               precision="float32"):
    """One SGD step with Nesterov momentum (Sutskever et al. 2013, as optax
    writes it): t <- g + m t; p <- p - lr (g + m t). Returns (params, trace,
    loss)."""
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(params, pixels, labels,
                                                  precision)
    trace = jax.tree_util.tree_map(lambda g, t: g + momentum * t, grads,
                                   trace)
    params = jax.tree_util.tree_map(
        lambda p, g, t: p - lr * (g + momentum * t), params, grads, trace)
    return params, trace, loss


def follow(key, batches, lrs, momentum, classes=1000, precision="float32"):
    """The first len(batches) steps from the key's weights, step i at the
    learning rate lrs[i]. Returns
    (losses, trace, params0, params): the loss of each step, the momentum
    trace after the last (the gradients as the optimizer got them), and the
    parameters before and after."""
    params0, _ = init_params(key, classes)
    params, _ = init_params(key, classes)
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for (pixels, labels), lr in zip(batches, lrs):
        params, trace, loss = train_step(params, trace, jnp.asarray(pixels),
                                         jnp.asarray(labels), lr, momentum,
                                         precision=precision)
        losses.append(float(loss))
    return losses, trace, params0, params
