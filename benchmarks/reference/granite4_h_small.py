"""Plain reference for the `granite4_h_small` configuration: ONE CHIP'S SHARE
of the `granitemoehybrid` decoder of ibm-granite/granite-4.0-h-small
(config.json; Mamba-2: Dao & Gu 2024, arXiv:2405.21060) in straightforward
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`: no kernels,
no cache, no batching, no chunking, no sorting of rows by expert. It imports
nothing of the program.

    h_0 = 12 E[ids]                      (embedding_multiplier)
    per block, pre-norm:  h += 0.22 mixer(RMSNorm(h));  h += 0.22 ffn(RMSNorm(h))
    logits = RMSNorm(h_L) E^T / 16       (tied E, logits_scaling)
    ffn(x) = routed(x) + shared(x), both on the same normed x
      shared: (g, u) = split(x W_in);  (silu(g) * u) W_out
      router: r = x W_r in float32 [72]; T(x) = the 10 largest; gates =
              softmax over those ten; 0 for the other 62
      expert e: (a, b) = split(x W1_e);  f_e(x) = (silu(a) * b) W2_e
      routed, the whole layer:  sum over e in T(x) of gate_e f_e(x)
      routed, THIS CHIP: the same sum over e in T(x) that are held
              (`first_expert` .. + W1.shape[0] - 1), the gates as above —
              not renormalised over the held; what the absent experts would
              add is left out, and the partial sum goes on to the next layer
    attention (layer 5 of every 10): 32 query heads over 8 K/V heads of 128,
           causal, no positional encoding, scores * 0.0078125, no biases
    Mamba-2 (elsewhere): as granite4_h_micro.py states it, 128 heads of 64,
           state 128, one group, conv 4, the SEQUENTIAL `lax.scan` over
           positions
    vocabulary: E holds rows 0 .. vocab - 1 of the published 100,352; ids,
           logits and the argmax are over the slice

The expert layer is the plain masked sum: EVERY held expert on EVERY row,
times a gate that is zero where the router did not choose it, one expert
after another (`lax.scan`) so one expert's float32 copy is alive at a time.
Given all 72 experts (`first_expert=0`, 72 matrices) it is the uncut layer;
tests/test_moe.py adds the four shares up to that.

`init_params` returns the tree under the program's leaf names in bfloat16
(the harness keeps these very buffers alive through the window). The tied
head is ONE buffer under `embed/W` and `out/W`.

`dtype`: "float32" is the reference; "float8" the control of the correctness
check — both operands of every matrix product (projections, shared and
routed experts, attention scores and mix, the head) rounded to float8_e4m3
under a per-tensor scale; the router (the configuration states float32 for
it), the conv, the recurrence and the norms stay float32. "bfloat16" rounds
everything to bfloat16.

`expert_layer_bytes`, `ssm_step_bytes`, `decode_step_bytes` are the bytes
the algorithm has to move, from shapes alone, for the roofline readers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

# what `init_params` / `logits` are not handed by the harness; a test ties
# each to configs/granite4_h_small.json
QUERY_HEADS_PER_KV = 4          # num_attention_heads 32 / num_key_value_heads 8
N_EXPERTS = 72                  # the router's width, as published
EXPERTS_PER_TOKEN = 10
EXPERT_HIDDEN = 768
EXPERTS_HELD = 18               # this chip's share: experts 0..17
FIRST_EXPERT = 0
ATTENTION_LAYERS = (5, 15, 25, 35)
MAMBA_EXPAND = 2
MAMBA_D_HEAD = 64
MAMBA_D_STATE = 128
MAMBA_D_CONV = 4
EMBEDDING_MULTIPLIER = 12.0
ATTENTION_MULTIPLIER = 0.0078125
RESIDUAL_MULTIPLIER = 0.22
LOGITS_SCALING = 16.0
RMS_EPS = 1e-5
INIT_STD = 0.02
# The [vocab, d_model] matrix alone is drawn at a quarter of that. With one
# period of four, 12 E[id] is still most of what reaches the tied head at
# std 0.02: a position's own input token leads its logits by 0.9 against a
# spread of 0.08, every served token is its predecessor, and the check
# compares nothing (measured on the chip: 4,142 checked tokens, 32 distinct,
# every gap 0.0). At 0.005 the own token's 0.059 lies inside the others'
# spread of 0.020 (largest of 25,088: 0.081) and the logits answer to every
# layer.
EMBED_STD = 0.005
STORE = jnp.bfloat16


def mamba_dims(d_model):
    """(heads, d_inner, conv channels, in_proj width)."""
    di = MAMBA_EXPAND * d_model
    return (di // MAMBA_D_HEAD, di, di + 2 * MAMBA_D_STATE,
            2 * di + 2 * MAMBA_D_STATE + di // MAMBA_D_HEAD)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std=INIT_STD):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(STORE)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def init_params(key, vocab, d_model, layers, ffn):
    """Weights from a PRNG key: normal(0, 0.02) matrices (router and expert
    matrices too; `ffn` is the shared expert's width; the tied [vocab,
    d_model] matrix normal(0, 0.005): `EMBED_STD`), A uniform in
    [1, 16], dt_bias the inverse softplus of dt log-uniform in [0.001, 0.1],
    D = 1, unit norms, conv uniform +- 1/sqrt(4), zero biases; bfloat16. Leaf
    by leaf (one small program a shape), the [vocab, d_model] matrix first:
    what a draw needs beside its result is then never large while the tree
    is."""
    H, di, cd, win = mamba_dims(d_model)
    kv = d_model // QUERY_HEADS_PER_KV
    keys = iter(jax.random.split(key, 1 + 11 * layers))
    ffn = int(ffn)
    ones = lambda n: jnp.ones((n,), STORE)
    zeros = lambda n: jnp.zeros((n,), STORE)
    E = _normal(next(keys), (vocab, d_model), EMBED_STD)
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
        p[f"b{i}_moe"] = {
            "Wg": _normal(next(keys), (d_model, N_EXPERTS)),
            "W1": _normal(next(keys), (EXPERTS_HELD, d_model,
                                       2 * EXPERT_HIDDEN)),
            "W2": _normal(next(keys), (EXPERTS_HELD, EXPERT_HIDDEN,
                                       d_model))}
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


def gates_of(x, Wg, top_k):
    """[T, d] normed rows -> [T, experts] float32: the softmax over each
    row's `top_k` largest router logits at their experts, 0 elsewhere."""
    with jax.default_matmul_precision("highest"):
        r = x.astype(jnp.float32) @ Wg.astype(jnp.float32)
    top, chosen = lax.top_k(r, top_k)
    hit = chosen[:, :, None] == jnp.arange(r.shape[1])[None, None]
    return jnp.sum(jax.nn.softmax(top, axis=-1)[:, :, None] * hit, axis=1)


def expert_sum(x, gates, W1, W2, dt, q):
    """sum over the experts given of gates[:, e] * f_e(x), every expert on
    every row, one expert after another. gates [T, len(W1)]."""
    def add(acc, e):
        w1, w2, g = e
        a, b = jnp.split(q(x) @ q(w1.astype(dt)), 2, axis=-1)
        return acc + g[:, None].astype(dt) \
            * (q(jax.nn.silu(a) * b) @ q(w2.astype(dt))), None
    with jax.default_matmul_precision("highest"):
        return lax.scan(add, jnp.zeros_like(x), (W1, W2, gates.T))[0]


@functools.partial(jax.jit,
                   static_argnames=("dtype", "first_expert", "top_k"))
def _ffn_half(h, norm, mlp, moe, *, dtype, first_expert, top_k):
    dt, q = _arith(dtype)
    norm, mlp = _up(norm, dt), _up(mlp, dt)
    held = moe["W1"].shape[0]
    x = _rms(h, norm["gamma"])
    gates = gates_of(x, moe["Wg"], top_k)[:, first_expert:first_expert + held]
    with jax.default_matmul_precision("highest"):
        g, u = jnp.split(q(x) @ q(mlp["W_in"]), 2, axis=-1)
        shared = q(jax.nn.silu(g) * u) @ q(mlp["W_out"])
    routed = expert_sum(x, gates, moe["W1"], moe["W2"], dt, q)
    return h + RESIDUAL_MULTIPLIER * (routed + shared)


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


def logits(params, ids, *, heads, layers, dtype="float32",
           first_expert=FIRST_EXPERT, experts_per_token=EXPERTS_PER_TOKEN):
    """[T] token ids -> [T, vocab] float32 logits of the next token at every
    position, one sequence, for the share of the experts `params` holds
    (`first_expert` on). A jitted program a kind of block, called layer
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
        h = _ffn_half(h, params[f"b{i}_norm2"], params[f"b{i}_mlp"],
                      params[f"b{i}_moe"], dtype=dtype,
                      first_expert=first_expert, top_k=experts_per_token)
    return _head(h, params["norm"], params["out"]["W"], dtype=dtype)


def expert_layer_bytes(rows, d_model=4096):
    """Bytes one layer's expert products have to move for `rows` tokens: the
    held experts' matrices once (every held expert gets a row: at 32 rows a
    step an expert of mean load stays empty once in 120 steps, and then the
    kernel reads less than is counted here) and the rows routed to them,
    gathered in and written out: rows * 10 * 18 / 72 of them at the mean."""
    pairs = rows * EXPERTS_PER_TOKEN * EXPERTS_HELD / N_EXPERTS
    return 2 * (EXPERTS_HELD * 3 * d_model * EXPERT_HIDDEN
                + 2 * pairs * d_model)


def ssm_step_bytes(slots, d_model=4096):
    """Bytes one `ssm_step` call has to move: the float32 state read once and
    written once, and the rows and columns it is updated from and emits."""
    _, di, _, _ = mamba_dims(d_model)
    N = MAMBA_D_STATE
    return 4 * slots * (2 * N * di + 3 * di + 2 * N)


def decode_step_bytes(slots, live_tokens, vocab=25088, d_model=4096,
                      layers=10, ffn=1536):
    """Bytes one decode step has to move, in its parts: every bfloat16
    weight once (the tied matrix twice: the one-hot product and the head),
    the held experts with their rows, each Mamba-2 layer's state read and
    written and its conv tail, and the K/V rows of the `live_tokens` tokens
    the slots hold."""
    _, di, cd, win = mamba_dims(d_model)
    kv = d_model // QUERY_HEADS_PER_KV
    n_attn = sum(1 for i in ATTENTION_LAYERS if i < layers)
    n_mamba = layers - n_attn
    mamba_w = d_model * win + (MAMBA_D_CONV + 1) * cd + di * d_model + di
    attn_w = 2 * d_model * d_model + 2 * d_model * kv
    shared_w = 3 * d_model * ffn + d_model * N_EXPERTS
    return {"weights": 2 * (2 * vocab * d_model + n_mamba * mamba_w
                            + n_attn * attn_w + layers * shared_w),
            "experts": layers * expert_layer_bytes(slots, d_model),
            "ssm_state": n_mamba * ssm_step_bytes(slots, d_model),
            "conv_tail": n_mamba * 2 * slots * (MAMBA_D_CONV - 1) * cd * 2,
            "kv": n_attn * live_tokens * 2 * kv * 2}


def decode_macs_per_token(vocab, d_model, layers, ffn):
    """Multiply-accumulates one generated token needs on this chip in the
    weights' products: a Mamba-2 layer's in- and out-projection, the
    attention layer's four projections, every block's router, shared expert
    and the held share of its 10 routed experts (10 * 18 / 72 pairs a token
    at the mean), and this chip's rows of the head. The lookup needs none;
    the recurrence, the conv and attention's scores and mix are left out: a
    share of the peak computed from this reads low, never high."""
    _, di, _, win = mamba_dims(d_model)
    kv = d_model // QUERY_HEADS_PER_KV
    n_attn = sum(1 for i in ATTENTION_LAYERS if i < layers)
    pairs = EXPERTS_PER_TOKEN * EXPERTS_HELD / N_EXPERTS
    return (layers - n_attn) * (d_model * win + di * d_model) \
        + n_attn * (2 * d_model * d_model + 2 * d_model * kv) \
        + layers * (3 * d_model * ffn + d_model * N_EXPERTS
                    + pairs * 3 * d_model * EXPERT_HIDDEN) \
        + d_model * vocab
