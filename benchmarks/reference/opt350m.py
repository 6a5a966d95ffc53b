"""Plain reference for the `opt350m` configuration: a decoder-only
transformer of OPT-350m's shape (Zhang et al. 2022, arXiv:2205.01068;
facebook/opt-350m) in straightforward `jax.numpy`, float32, with
`jax.default_matmul_precision("highest")`: no kernels, no cache, no batching.

Per block, post-norm as OPT-350m is (`do_layer_norm_before=false`):
    a = x + (softmax(q k^T / sqrt(d_head), causal) v) Wo + bo
    h = LayerNorm(a)
    f = h + relu(h W1 + b1) W2 + b2
    x' = LayerNorm(f)
then logits = x W_head + b_head. Departures, shared with the program under
test and written into configs/opt350m.json: no learned position embedding;
tokens enter through a [vocab, d_model] matrix (a row lookup) with a bias,
not a 512-wide embedding with project_in/out; an untied head; no biases on
q, k, v; random weights from the seed.

`dtype` selects the arithmetic: float32 is the reference. The configuration
states float32 storage with the TPU's default matmul precision, which
multiplies in bfloat16; the control of the correctness check is the step
below that, "float8": both operands of every matrix product rounded to
float8_e4m3 under a per-tensor scale, everything else float32. ("bfloat16",
all of it rounded to bfloat16, is kept for comparison: it reads the same as
the program, which is what told us where the program's precision really is.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

LN_EPS = 1e-5
INIT_STD = 0.02            # facebook/opt-350m config.json: init_std


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def init_params(key, vocab, d_model, layers, ffn):
    """Weights from a PRNG key, normal(0, 0.02) matrices, zero biases, unit
    layer norms: one jitted call on the device. Keys are the names the zoo
    gives its vertices."""
    def mat(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * INIT_STD
    keys = iter(jax.random.split(key, 2 + 6 * layers))
    ones, zeros = jnp.ones((d_model,), jnp.float32), \
        jnp.zeros((d_model,), jnp.float32)
    p = {"embed": {"W": mat(next(keys), (vocab, d_model)), "b": zeros}}
    for i in range(layers):
        p[f"b{i}_attn"] = {"Wq": mat(next(keys), (d_model, d_model)),
                           "Wk": mat(next(keys), (d_model, d_model)),
                           "Wv": mat(next(keys), (d_model, d_model)),
                           "Wo": mat(next(keys), (d_model, d_model)),
                           "b": zeros}
        p[f"b{i}_ln1"] = {"gamma": ones, "beta": zeros}
        p[f"b{i}_ffn1"] = {"W": mat(next(keys), (d_model, ffn)),
                           "b": jnp.zeros((ffn,), jnp.float32)}
        p[f"b{i}_ffn2"] = {"W": mat(next(keys), (ffn, d_model)), "b": zeros}
        p[f"b{i}_ln2"] = {"gamma": ones, "beta": zeros}
    p["out"] = {"W": mat(next(keys), (d_model, vocab)),
                "b": jnp.zeros((vocab,), jnp.float32)}
    return p


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _ln(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * p["gamma"] + p["beta"]


@functools.partial(jax.jit, static_argnames=("heads", "layers", "dtype"))
def logits(params, ids, *, heads, layers, dtype="float32"):
    """[T] token ids -> [T, vocab] float32 logits of the next token at every
    position, one sequence, full causal attention."""
    fp8 = dtype == "float8"
    dt = jnp.dtype("float32" if fp8 else dtype)
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dt), t)
    q = _fp8 if fp8 else (lambda a: a)
    mm = lambda a, b: q(a) @ q(b)
    with jax.default_matmul_precision("highest"):
        p = cast(params)
        T = ids.shape[0]
        x = p["embed"]["W"][ids] + p["embed"]["b"]
        d = x.shape[-1]
        dh = d // heads
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(layers):
            a = p[f"b{i}_attn"]
            qh = mm(x, a["Wq"]).reshape(T, heads, dh)
            k = mm(x, a["Wk"]).reshape(T, heads, dh)
            v = mm(x, a["Wv"]).reshape(T, heads, dh)
            s = jnp.einsum("qhd,khd->hqk", q(qh), q(k)) / jnp.sqrt(
                jnp.asarray(dh, dt))
            s = jnp.where(causal[None], s, -jnp.inf)
            w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
            ctx = jnp.einsum("hqk,khd->qhd", q(w), q(v)).reshape(T, d)
            h = _ln(x + mm(ctx, a["Wo"]) + a["b"], p[f"b{i}_ln1"])
            f1, f2 = p[f"b{i}_ffn1"], p[f"b{i}_ffn2"]
            f = mm(jnp.maximum(mm(h, f1["W"]) + f1["b"], 0), f2["W"]) \
                + f2["b"]
            x = _ln(h + f, p[f"b{i}_ln2"])
        return (mm(x, p["out"]["W"]) + p["out"]["b"]).astype(jnp.float32)


def decode_macs_per_token(vocab, d_model, layers, ffn):
    """Multiply-accumulates one generated token needs in the weights'
    products: four [d, d] attention projections and the two feed-forward
    matrices a layer, and the head. The lookup needs none (that the program
    multiplies a one-hot row is its own doing), and attention's scores and
    mix, which grow with the slot's length, are left out: a share of the
    peak computed from this reads low, never high."""
    return layers * (4 * d_model * d_model + 2 * d_model * ffn) \
        + d_model * vocab
