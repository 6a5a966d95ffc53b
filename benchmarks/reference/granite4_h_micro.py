"""Plain reference for the `granite4_h_micro` configuration: the
`granitemoehybrid` decoder of ibm-granite/granite-4.0-h-micro (config.json;
Mamba-2: Dao & Gu 2024, arXiv:2405.21060) in straightforward `jax.numpy`,
float32, `jax.default_matmul_precision("highest")`: no kernels, no cache, no
batching, no chunking. It imports nothing of the program.

    h_0 = 12 E[ids]                      (embedding_multiplier)
    per block, pre-norm:  h += 0.22 mixer(RMSNorm(h));  h += 0.22 mlp(RMSNorm(h))
    logits = RMSNorm(h_40) E^T / 8       (tied E, logits_scaling)
    mlp:   (g, u) = split(x W_in);  (silu(g) * u) W_out          (no routed part)
    attention (layers 5, 15, 25, 35): 32 query heads over 8 K/V heads of 64,
           causal, no positional encoding, scores * 0.015625, no biases
    Mamba-2 (elsewhere): (z, xBC, dt) = split(u W_in); xBC = silu(causal
           depthwise conv_4(xBC) + b); (x, B, C) = split(xBC); dt =
           softplus(dt + dt_bias); A = -exp(A_log); per head, position by
           position,  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,
           y_t = S_t C_t + D x_t;  out = RMSNorm(y * silu(z)) W_out

The recurrence is the SEQUENTIAL `lax.scan` over positions — the definition —
so the program's two formulations (chunked prefill, one-token step) are each
held against a third.

`init_params` returns the tree under the program's leaf names in bfloat16, the
configuration's storage dtype: the program then holds these very buffers (the
harness keeps the tree alive through the window, and a float32 copy of 3.19 B
parameters beside the program's would leave the chip no cache). `logits`
upcasts one layer at a time. The tied head is ONE buffer under `embed/W` and
`out/W`. The program's extra leaves (`embed/b`, the attention's output bias)
are zeros.

`dtype`: "float32" is the reference. The configuration states bfloat16
products accumulated in float32; the control of the correctness check is the
step below, "float8": both operands of every matrix product (the projections,
the attention scores and mix, the head) rounded to float8_e4m3 under a
per-tensor scale, everything else — the conv, the recurrence, the norms —
float32. "bfloat16" rounds everything to bfloat16, kept for comparison.

`ssm_step_bytes` / `decode_step_bytes` are the bytes the algorithm has to
move, from shapes alone, for the roofline reader (as `forward_macs` is kept
beside the ResNet-50 reference).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

# what `init_params` / `logits` are not handed by the harness; a test ties
# each to configs/granite4_h_micro.json
QUERY_HEADS_PER_KV = 4          # num_attention_heads 32 / num_key_value_heads 8
ATTENTION_LAYERS = (5, 15, 25, 35)
MAMBA_EXPAND = 2
MAMBA_D_HEAD = 64
MAMBA_D_STATE = 128
MAMBA_D_CONV = 4
EMBEDDING_MULTIPLIER = 12.0
ATTENTION_MULTIPLIER = 0.015625
RESIDUAL_MULTIPLIER = 0.22
LOGITS_SCALING = 8.0
RMS_EPS = 1e-5
INIT_STD = 0.02
STORE = jnp.bfloat16


def mamba_dims(d_model):
    """(heads, d_inner, conv channels, in_proj width)."""
    di = MAMBA_EXPAND * d_model
    return (di // MAMBA_D_HEAD, di, di + 2 * MAMBA_D_STATE,
            2 * di + 2 * MAMBA_D_STATE + di // MAMBA_D_HEAD)


@functools.partial(jax.jit, static_argnums=(1,))
def _normal(key, shape):
    return (jax.random.normal(key, shape, jnp.float32)
            * INIT_STD).astype(STORE)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def init_params(key, vocab, d_model, layers, ffn):
    """Weights from a PRNG key: normal(0, 0.02) matrices, A uniform in
    [1, 16], dt_bias the inverse softplus of dt log-uniform in [0.001, 0.1],
    D = 1, unit norms, conv uniform +- 1/sqrt(4), zero biases; bfloat16. Leaf
    by leaf (one small program a shape), the [vocab, d_model] matrix first:
    what a draw needs beside its result is then never large while the tree
    is."""
    H, di, cd, win = mamba_dims(d_model)
    kv = d_model // QUERY_HEADS_PER_KV
    keys = iter(jax.random.split(key, 1 + 8 * layers))
    ones = lambda n: jnp.ones((n,), STORE)
    zeros = lambda n: jnp.zeros((n,), STORE)
    E = _normal(next(keys), (vocab, d_model))
    p = {"embed": {"W": E, "b": zeros(d_model)}}
    for i in range(layers):
        p[f"b{i}_norm1"] = {"gamma": ones(d_model)}
        if i in ATTENTION_LAYERS:
            p[f"b{i}_attn"] = {"Wq": _normal(next(keys), (d_model, d_model)),
                               "Wk": _normal(next(keys), (d_model, kv)),
                               "Wv": _normal(next(keys), (d_model, kv)),
                               "Wo": _normal(next(keys), (d_model, d_model)),
                               "b": zeros(d_model)}
        else:
            dt = jnp.exp(_uniform(next(keys), (H,), math.log(1e-3),
                                  math.log(1e-1)))
            p[f"b{i}_mamba"] = {
                "W_in": _normal(next(keys), (d_model, win)),
                "conv_W": (_uniform(next(keys), (MAMBA_D_CONV, cd), -1.0, 1.0)
                           / math.sqrt(MAMBA_D_CONV)).astype(STORE),
                "conv_b": zeros(cd),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(STORE),
                "A_log": jnp.log(_uniform(next(keys), (H,), 1.0, 16.0))
                .astype(STORE),
                "D": ones(H), "norm": ones(di),
                "W_out": _normal(next(keys), (di, d_model))}
        p[f"b{i}_norm2"] = {"gamma": ones(d_model)}
        p[f"b{i}_mlp"] = {"W_in": _normal(next(keys), (d_model, 2 * ffn)),
                          "W_out": _normal(next(keys), (ffn, d_model))}
    p["norm"] = {"gamma": ones(d_model)}
    p["out"] = {"W": E}
    return p


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _arith(dtype):
    """(the dtype everything is held in, what rounds a product's operand)."""
    if dtype == "float8":
        return jnp.dtype("float32"), _fp8
    return jnp.dtype(dtype), lambda a: a


def _rms(x, gamma):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + RMS_EPS) * gamma


def _up(tree, dt):
    return jax.tree_util.tree_map(lambda a: a.astype(dt), tree)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _embed(E, ids, *, dtype):
    dt, _ = _arith(dtype)
    return EMBEDDING_MULTIPLIER * E.astype(dt)[ids]


@functools.partial(jax.jit, static_argnames=("dtype",))
def _mlp_half(h, norm, mlp, *, dtype):
    dt, q = _arith(dtype)
    norm, mlp = _up(norm, dt), _up(mlp, dt)
    with jax.default_matmul_precision("highest"):
        g, u = jnp.split(q(_rms(h, norm["gamma"])) @ q(mlp["W_in"]), 2,
                         axis=-1)
        return h + RESIDUAL_MULTIPLIER * (q(jax.nn.silu(g) * u)
                                          @ q(mlp["W_out"]))


@functools.partial(jax.jit, static_argnames=("heads", "dtype"))
def _attention_half(h, norm, a, *, heads, dtype):
    dt, q = _arith(dtype)
    norm, a = _up(norm, dt), _up(a, dt)
    T, d = h.shape
    dh, G = d // heads, QUERY_HEADS_PER_KV
    with jax.default_matmul_precision("highest"):
        x = q(_rms(h, norm["gamma"]))
        qh = (x @ q(a["Wq"])).reshape(T, heads // G, G, dh)
        k = (x @ q(a["Wk"])).reshape(T, heads // G, dh)
        v = (x @ q(a["Wv"])).reshape(T, heads // G, dh)
        s = jnp.einsum("qjgd,kjd->jgqk", q(qh), q(k)) * ATTENTION_MULTIPLIER
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
        ctx = jnp.einsum("jgqk,kjd->qjgd", q(w), q(v)).reshape(T, d)
        return h + RESIDUAL_MULTIPLIER * (q(ctx) @ q(a["Wo"]) + a["b"])


@functools.partial(jax.jit, static_argnames=("dtype",))
def _mamba_half(h, norm, m, *, dtype):
    dt_, q = _arith(dtype)
    norm, m = _up(norm, dt_), _up(m, dt_)
    T, d = h.shape
    H, di, cd, _ = mamba_dims(d)
    N, K, P = MAMBA_D_STATE, MAMBA_D_CONV, MAMBA_D_HEAD
    with jax.default_matmul_precision("highest"):
        z, xbc, dt = jnp.split(q(_rms(h, norm["gamma"])) @ q(m["W_in"]),
                               [di, di + cd], axis=-1)
        xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(xp[k:k + T] * m["conv_W"][k] for k in range(K))
                          + m["conv_b"])
        x, B, C = jnp.split(xbc, [di, di + N], axis=-1)
        x = x.reshape(T, H, P)
        dt = jax.nn.softplus(dt + m["dt_bias"])                   # [T, H]
        A = -jnp.exp(m["A_log"])

        def position(S, at):
            x_t, B_t, C_t, dt_t = at
            S = jnp.exp(dt_t * A)[:, None, None] * S \
                + (dt_t[:, None] * x_t)[:, :, None] * B_t
            return S, jnp.sum(S * C_t, axis=-1) + m["D"][:, None] * x_t

        _, y = lax.scan(position, jnp.zeros((H, P, N), dt_), (x, B, C, dt))
        y = _rms(y.reshape(T, di) * jax.nn.silu(z), m["norm"])
        return h + RESIDUAL_MULTIPLIER * (q(y) @ q(m["W_out"]))


@functools.partial(jax.jit, static_argnames=("dtype",))
def _head(h, norm, E, *, dtype):
    dt, q = _arith(dtype)
    with jax.default_matmul_precision("highest"):
        x = q(_rms(h, norm["gamma"].astype(dt)))
        return (x @ q(E.astype(dt)).T / LOGITS_SCALING).astype(jnp.float32)


def logits(params, ids, *, heads, layers, dtype="float32"):
    """[T] token ids -> [T, vocab] float32 logits of the next token at every
    position, one sequence. A jitted program a kind of block, called layer
    after layer, so one layer's float32 copy is alive at a time."""
    h = _embed(params["embed"]["W"], ids, dtype=dtype) \
        + params["embed"]["b"].astype(_arith(dtype)[0])
    for i in range(layers):
        norm = params[f"b{i}_norm1"]
        if i in ATTENTION_LAYERS:
            h = _attention_half(h, norm, params[f"b{i}_attn"], heads=heads,
                                dtype=dtype)
        else:
            h = _mamba_half(h, norm, params[f"b{i}_mamba"], dtype=dtype)
        h = _mlp_half(h, params[f"b{i}_norm2"], params[f"b{i}_mlp"],
                      dtype=dtype)
    return _head(h, params["norm"], params["out"]["W"], dtype=dtype)


def ssm_step_bytes(slots, d_model=2048):
    """Bytes one `ssm_step` call has to move: the float32 state read once and
    written once, and the rows and columns it is updated from and emits."""
    _, di, _, _ = mamba_dims(d_model)
    N = MAMBA_D_STATE
    return 4 * slots * (2 * N * di + 3 * di + 2 * N)


def decode_step_bytes(slots, live_tokens, vocab=100352, d_model=2048,
                      layers=40, ffn=8192):
    """Bytes one decode step has to move, in its parts: every bfloat16
    weight once (the tied matrix twice: the one-hot product and the head),
    each Mamba-2 layer's state read and written and its conv tail, and the
    K/V rows of the `live_tokens` tokens the slots hold (4 attention
    layers)."""
    _, di, cd, win = mamba_dims(d_model)
    kv = d_model // QUERY_HEADS_PER_KV
    n_attn = sum(1 for i in ATTENTION_LAYERS if i < layers)
    n_mamba = layers - n_attn
    mamba_w = d_model * win + (MAMBA_D_CONV + 1) * cd + di * d_model + di
    attn_w = 2 * d_model * d_model + 2 * d_model * kv
    mlp_w = 3 * d_model * ffn
    weights = 2 * (2 * vocab * d_model + n_mamba * mamba_w + n_attn * attn_w
                   + layers * mlp_w)
    return {"weights": weights,
            "ssm_state": n_mamba * ssm_step_bytes(slots, d_model),
            "conv_tail": n_mamba * 2 * slots * (MAMBA_D_CONV - 1) * cd * 2,
            "kv": n_attn * live_tokens * 2 * kv * 2}


def decode_macs_per_token(vocab, d_model, layers, ffn):
    """Multiply-accumulates one generated token needs in the weights'
    products: a Mamba-2 layer's in- and out-projection, an attention layer's
    four projections, every block's gated MLP, and the head. The lookup
    needs none; the recurrence, the conv and attention's scores and mix are
    left out: a share of the peak computed from this reads low, never
    high."""
    _, di, _, win = mamba_dims(d_model)
    kv = d_model // QUERY_HEADS_PER_KV
    n_attn = sum(1 for i in ATTENTION_LAYERS if i < layers)
    return (layers - n_attn) * (d_model * win + di * d_model) \
        + n_attn * (2 * d_model * d_model + 2 * d_model * kv) \
        + layers * 3 * d_model * ffn + d_model * vocab
