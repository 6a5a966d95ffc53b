"""Plain reference for the `kimi_k27_code` configuration: ONE CHIP'S SHARE of
the `kimi_k2` decoder of moonshotai/Kimi-K2.7-Code (config.json; DeepSeek-V3's
block, arXiv:2412.19437) in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`: no kernels, no cache, no batching,
no absorbed products, no sorting of rows by expert. It imports nothing of the
program.

    h_0 = E[ids];  per block, pre-norm, eps 1e-5, no bias anywhere:
        h += attention(RMSNorm(h));  h += ffn(RMSNorm(h))
    logits = RMSNorm(h_L) W_head^T                      (untied)
    layer 0: ffn a dense gated-SiLU of 18,432; layers 1..: routed + shared

Multi-head latent attention in EVERY layer (DeepSeek-V2, arXiv:2405.04434;
64 heads, query latent 1,536, key/value latent 512, nope 128, rotary 64,
value 128), the plain form, x the normed input at positions p = 0, 1, ..:

    c_q = RMSNorm_1536(x W_qa) ;  [q_nope | q_pe]_h = c_q W_qb
    [c | k_pe] = x W_kva ;  c <- RMSNorm_512(c)          one row for all heads
    q_pe, k_pe <- RoPE(., p): adjacent pairs (2i, 2i + 1) by p f_i, with
        e_i = 50000^(-2i/64), low = floor(c(32)) = 8, high = ceil(c(1)) = 20,
        c(r) = 64 ln(4096 / (2 pi r)) / (2 ln 50000); ramp_i = clip((i - 8) /
        12, 0, 1); f_i = e_i / 64 * ramp_i + e_i (1 - ramp_i)   (DeepSeek-V3's
        YaRN); cos and sin times m(mscale 1) / m(mscale_all_dim 1) = 1
    [k_nope | v]_h = c W_kvb
    p_h = causal softmax((q_nope_h . k_nope_h + q_pe_h . k_pe) 192^-1/2 m^2),
        m = 0.1 ln 64 + 1 = 1.41589, float32
    y = concat_h(sum p_h v_h) W_o                        no gate

in query blocks of 128 positions, so that H x T^2 scores never stand whole
(6,144 positions at 64 heads: 201 MB a block).

Routed ffn (`noaux_tc`, one group), router in float32: s = sigmoid(x W_r)
[384]; the 8 largest of s + b; gate_e = 2.827 s_e / sum of the chosen s (no b
in the gates). Expert and shared expert: (SiLU(x W_g) * x W_u) W_d, width
2,048. THIS CHIP sums over the chosen experts it holds (`first_expert` .. +
W1.shape[0] - 1) with the gates above, not renormalised over the held; what
the other 372 would add is left out, and the partial sum goes on to the next
layer. Given all 384 experts it is the uncut layer; tests/test_kimi_k2.py
adds eight shares up to that.

`init_params` returns the tree under the program's leaf names in bfloat16
(the harness keeps these very buffers alive through the window). Every
matrix is normal(0, 0.02) but W_qb (0.03) and W_kvb (0.04): at hidden 7,168
the siblings' 0.02 everywhere leaves a score's spread over the keys near 1.7
and a few hundred of 2,048 keys effectively attended; with these two the
spread is about 2.7 (the nope part 1.7, the rotary part 2.1: numpy at the
published widths), so a position attends to tens of keys, not hundreds, and
the served tokens answer to their context
(`distinct_served_tokens` of a run says whether the check compares
something).

`dtype`: "float32" is the reference; "float8" the control of the correctness
check — both operands of every matrix product (projections, shared and
routed experts, attention scores and mix, the head) rounded to float8_e4m3
under a per-tensor scale; the router, the rotary turn and the norms stay
float32. "bfloat16" rounds everything but the router, the rotary angles and
the softmax to bfloat16. `router_dtype` / `softmax_dtype` compute only the
router, or only attention's softmax, in another dtype; `without` leaves out
one piece of the mathematics ("mscale": the scores' m^2; "yarn_ramp": the
frequencies' blend, plain rotary instead; "q_norm": the query latent's norm;
"k_rope": the turn of k_pe) — the tier-1 tests' proof that the tolerances see
each.

A sequence is padded to a multiple of 1,024 positions inside `logits`
(causal: the padding changes nothing before it), so the check's requests
compile six shapes and not twenty.

`mla_decode_bytes`, `mla_prefill_flops`, `decode_step_bytes` are the bytes
and operations the algorithm has to move and do, from shapes alone, for the
roofline readers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# what `init_params` / `logits` are not handed by the harness; a test ties
# each to configs/kimi_k27_code.json
HIDDEN_PER_HEAD = 112           # hidden_size 7168 / num_attention_heads 64
Q_LORA_RANK = 1536
KV_LORA_RANK = 512
QK_NOPE_HEAD_DIM = 128
QK_ROPE_HEAD_DIM = 64
V_HEAD_DIM = 128
ROPE_THETA = 50000.0
YARN_FACTOR = 64.0
YARN_ORIGINAL = 4096            # original_max_position_embeddings
YARN_BETA_FAST = 32.0
YARN_BETA_SLOW = 1.0
YARN_MSCALE = 1.0
YARN_MSCALE_ALL_DIM = 1.0
FIRST_K_DENSE = 1
N_EXPERTS = 384                 # the router's width, as published
EXPERTS_PER_TOKEN = 8
ROUTED_SCALING = 2.827
EXPERT_HIDDEN = 2048
SHARED_HIDDEN = 2048
EXPERTS_HELD = 12               # this chip's share of 32
FIRST_EXPERT = 0
RMS_EPS = 1e-5
INIT_STD = 0.02
WQB_STD = 0.03                  # see the module docstring
WKVB_STD = 0.04
ROUTE_BIAS_STD = 0.01
QUERY_BLOCK = 128
PAD_TO = 1024
STORE = jnp.bfloat16


def heads_of(d_model):
    return max(1, d_model // HIDDEN_PER_HEAD)


def yarn_mscale(scale):
    return 0.1 * scale * math.log(YARN_FACTOR) + 1.0


def yarn_range():
    """(low, high): the channels between which the frequencies go from
    theta's own to theta's / 64 (DeepSeek-V3's `yarn_find_correction_range`
    at 64 channels)."""
    def c(rotations):
        return QK_ROPE_HEAD_DIM * math.log(
            YARN_ORIGINAL / (2 * math.pi * rotations)) \
            / (2 * math.log(ROPE_THETA))
    return max(math.floor(c(YARN_BETA_FAST)), 0), \
        min(math.ceil(c(YARN_BETA_SLOW)), QK_ROPE_HEAD_DIM - 1)


def rope_table(ramped=True):
    """(the 32 frequencies [32] float32, the factor on cos and sin)."""
    i = np.arange(QK_ROPE_HEAD_DIM // 2, dtype=np.float64)
    e = ROPE_THETA ** (-2 * i / QK_ROPE_HEAD_DIM)
    if not ramped:
        return e.astype(np.float32), 1.0
    low, high = yarn_range()
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return (e / YARN_FACTOR * ramp + e * (1 - ramp)).astype(np.float32), \
        yarn_mscale(YARN_MSCALE) / yarn_mscale(YARN_MSCALE_ALL_DIM)


def rope(x, pos, ramped=True):
    """x [T, .., 64] rotated at positions pos [T]: adjacent pairs (2i, 2i+1)
    by the angle pos * f_i, in float32."""
    half = x.shape[-1] // 2
    f, a = rope_table(ramped)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(f)    # [T, half]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    cos, sin = (jnp.cos(ang) * a).astype(x.dtype), \
        (jnp.sin(ang) * a).astype(x.dtype)
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    u, w = pairs[..., 0], pairs[..., 1]
    return jnp.stack([u * cos - w * sin, u * sin + w * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std=INIT_STD):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(STORE)


def init_params(key, vocab, d_model, layers, ffn, experts_held=None):
    """Weights from a PRNG key: every matrix normal(0, 0.02) — embedding,
    untied head, router and expert matrices too — but W_qb (0.03) and W_kvb
    (0.04), which spread attention's scores (the module docstring has the
    arithmetic); the router's selection bias normal(0, 0.01), unit norms,
    zero bias on the lookup; bfloat16. `ffn` is the dense layer's width.
    Leaf by leaf (one small program a shape), so what a draw needs beside
    its result is never large while the tree is."""
    H = heads_of(d_model)
    held = EXPERTS_HELD if experts_held is None else experts_held
    keys = iter(jax.random.split(key, 2 + 11 * layers))
    ffn = int(round(ffn))
    ones = lambda n: jnp.ones((n,), STORE)
    p = {"embed": {"W": _normal(next(keys), (vocab, d_model)),
                   "b": jnp.zeros((d_model,), STORE)}}
    for i in range(layers):
        p[f"b{i}_norm1"] = {"gamma": ones(d_model)}
        p[f"b{i}_mla"] = {
            "Wq_a": _normal(next(keys), (d_model, Q_LORA_RANK)),
            "q_norm": ones(Q_LORA_RANK),
            "Wq_b": _normal(next(keys), (Q_LORA_RANK, H * (
                QK_NOPE_HEAD_DIM + QK_ROPE_HEAD_DIM)), WQB_STD),
            "Wkv_a": _normal(next(keys), (d_model, KV_LORA_RANK
                                          + QK_ROPE_HEAD_DIM)),
            "kv_norm": ones(KV_LORA_RANK),
            "Wkv_b": _normal(next(keys), (KV_LORA_RANK, H * (
                QK_NOPE_HEAD_DIM + V_HEAD_DIM)), WKVB_STD),
            "Wo": _normal(next(keys), (H * V_HEAD_DIM, d_model))}
        p[f"b{i}_norm2"] = {"gamma": ones(d_model)}
        width = ffn if i < FIRST_K_DENSE else SHARED_HIDDEN
        p[f"b{i}_mlp"] = {"W_in": _normal(next(keys), (d_model, 2 * width)),
                          "W_out": _normal(next(keys), (width, d_model))}
        if i >= FIRST_K_DENSE:
            p[f"b{i}_moe"] = {
                "Wg": _normal(next(keys), (d_model, N_EXPERTS)),
                "route_bias": _normal(next(keys), (N_EXPERTS,),
                                      ROUTE_BIAS_STD),
                "W1": _normal(next(keys), (held, d_model,
                                           2 * EXPERT_HIDDEN)),
                "W2": _normal(next(keys), (held, EXPERT_HIDDEN, d_model))}
    p["norm"] = {"gamma": ones(d_model)}
    p["out"] = {"W": _normal(next(keys), (vocab, d_model))}
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


def gates_of(x, Wg, bias, dtype=jnp.float32):
    """[T, d] normed rows -> [T, 384]: 2.827 s_e / (sum of the chosen s) at
    the 8 experts with the largest s + bias, 0 elsewhere; the router in
    `dtype` (float32 but in the tests' proof)."""
    E = Wg.shape[1]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x.astype(dtype) @ Wg.astype(dtype))
    chosen = lax.top_k(s + bias.astype(dtype), EXPERTS_PER_TOKEN)[1]
    hit = jnp.any(chosen[:, :, None] == jnp.arange(E), axis=1)
    s = jnp.where(hit, s, 0).astype(jnp.float32)
    return ROUTED_SCALING * s / jnp.sum(s, axis=-1, keepdims=True)


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


def _gated(x, mlp, q):
    with jax.default_matmul_precision("highest"):
        g, u = jnp.split(q(x) @ q(mlp["W_in"]), 2, axis=-1)
        return q(jax.nn.silu(g) * u) @ q(mlp["W_out"])


@functools.partial(jax.jit, static_argnames=("dtype",))
def _dense_half(h, norm, mlp, *, dtype):
    dt, q = _arith(dtype)
    norm, mlp = _up(norm, dt), _up(mlp, dt)
    return h + _gated(_rms(h, norm["gamma"]), mlp, q)


@functools.partial(jax.jit,
                   static_argnames=("dtype", "first_expert", "router_dtype"))
def _routed_half(h, norm, mlp, moe, *, dtype, first_expert, router_dtype):
    dt, q = _arith(dtype)
    norm, mlp = _up(norm, dt), _up(mlp, dt)
    held = moe["W1"].shape[0]
    x = _rms(h, norm["gamma"])
    gates = gates_of(x, moe["Wg"], moe["route_bias"], jnp.dtype(
        router_dtype))[:, first_expert:first_expert + held]
    return h + expert_sum(x, gates, moe["W1"], moe["W2"], dt, q) \
        + _gated(x, mlp, q)


@functools.partial(jax.jit,
                   static_argnames=("dtype", "softmax_dtype", "without"))
def _attention_half(h, norm, a, *, dtype, softmax_dtype, without):
    dt, q = _arith(dtype)
    norm, a = _up(norm, dt), _up(a, dt)
    T, d = h.shape
    H, R = heads_of(d), KV_LORA_RANK
    Dn, Dr, Dv = QK_NOPE_HEAD_DIM, QK_ROPE_HEAD_DIM, V_HEAD_DIM
    B = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    pos = jnp.arange(T)
    ramped = "yarn_ramp" not in without
    scale = (Dn + Dr) ** -0.5 * (1.0 if "mscale" in without
                                 else yarn_mscale(YARN_MSCALE_ALL_DIM) ** 2)
    with jax.default_matmul_precision("highest"):
        x = q(_rms(h, norm["gamma"]))
        c_q = x @ q(a["Wq_a"])
        if "q_norm" not in without:
            c_q = _rms(c_q, a["q_norm"])
        qn, q_pe = jnp.split((q(c_q) @ q(a["Wq_b"])).reshape(T, H, Dn + Dr),
                             [Dn], axis=-1)
        q_pe = rope(q_pe, pos, ramped)
        c, k_pe = jnp.split(x @ q(a["Wkv_a"]), [R], axis=-1)
        c = _rms(c, a["kv_norm"])
        if "k_rope" not in without:
            k_pe = rope(k_pe, pos, ramped)
        kn, v = jnp.split((q(c) @ q(a["Wkv_b"])).reshape(T, H, Dn + Dv),
                          [Dn], axis=-1)
        kn, k_pe, v = q(kn), q(k_pe), q(v)

        def block(at):
            start, qn_b, qp_b = at              # [B, H, Dn], [B, H, Dr]
            s = (jnp.einsum("qhd,khd->hqk", q(qn_b), kn)
                 + jnp.einsum("qhr,kr->hqk", q(qp_b), k_pe)) * scale
            seen = jnp.arange(T)[None, :] <= start + jnp.arange(B)[:, None]
            s = jnp.where(seen, s, -jnp.inf)
            w = jax.nn.softmax(s.astype(softmax_dtype), axis=-1).astype(dt)
            return jnp.einsum("hqk,khd->qhd", q(w), v)
        o = lax.map(block, (jnp.arange(0, T, B),
                            qn.reshape(T // B, B, H, Dn),
                            q_pe.reshape(T // B, B, H, Dr)))
        return h + q(o.reshape(T, H * Dv)) @ q(a["Wo"])


@functools.partial(jax.jit, static_argnames=("dtype",))
def _embed(E, b, ids, *, dtype):
    dt, _ = _arith(dtype)
    return E.astype(dt)[ids] + b.astype(dt)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _head(h, norm, W, *, dtype):
    dt, q = _arith(dtype)
    with jax.default_matmul_precision("highest"):
        x = q(_rms(h, norm["gamma"].astype(dt)))
        return (x @ q(W.astype(dt)).T).astype(jnp.float32)


def logits(params, ids, *, heads, layers, dtype="float32",
           first_expert=FIRST_EXPERT, router_dtype="float32",
           softmax_dtype="float32", without=()):
    """[T] token ids -> [T, vocab] float32 logits of the next token at every
    position, one sequence, for the share of the experts `params` holds
    (`first_expert` on). A jitted program a kind of block, called layer
    after layer, so one layer's float32 copy is alive at a time. `heads`
    must be `heads_of(d_model)` (the harness passes the configuration's)."""
    if heads != heads_of(params["embed"]["W"].shape[1]):
        raise ValueError(f"{heads} heads at d_model "
                         f"{params['embed']['W'].shape[1]}")
    T = ids.shape[0]
    if T > PAD_TO and T % PAD_TO:
        ids = jnp.pad(ids, (0, -T % PAD_TO))
    h = _embed(params["embed"]["W"], params["embed"]["b"], ids, dtype=dtype)
    for i in range(layers):
        h = _attention_half(h, params[f"b{i}_norm1"], params[f"b{i}_mla"],
                            dtype=dtype, softmax_dtype=softmax_dtype,
                            without=tuple(without))
        if i < FIRST_K_DENSE:
            h = _dense_half(h, params[f"b{i}_norm2"], params[f"b{i}_mlp"],
                            dtype=dtype)
        else:
            h = _routed_half(h, params[f"b{i}_norm2"], params[f"b{i}_mlp"],
                             params[f"b{i}_moe"], dtype=dtype,
                             first_expert=first_expert,
                             router_dtype=router_dtype)
    return _head(h[:T], params["norm"], params["out"]["W"], dtype=dtype)


def mla_decode_bytes(slots, live_tokens, d_model=7168):
    """Bytes one `mla_decode` call has to move: the bfloat16 latent row
    (512 + 64) of each of the `live_tokens` tokens the slots hold, read
    once for all heads, and a slot's query rows in ([H, 576] bfloat16) and
    latent mixes out ([H, 512] float32)."""
    H, W = heads_of(d_model), KV_LORA_RANK + QK_ROPE_HEAD_DIM
    return 2 * live_tokens * W + slots * H * (2 * W + 4 * KV_LORA_RANK)


def mla_prefill_flops(tokens, d_model=7168):
    """Operations one `mla_prefill` call — a layer's causal attention over a
    prefill bucket of `tokens` positions in the plain form — has to do: 2 a
    multiply-add, a head's 192-wide score and 128-wide mix for every (query,
    key) pair at or under the diagonal. The kernel also computes the rest of
    the diagonal's blocks, so a share of the peak from this reads low."""
    return 2 * heads_of(d_model) * (
        QK_NOPE_HEAD_DIM + QK_ROPE_HEAD_DIM + V_HEAD_DIM) \
        * tokens * (tokens + 1) // 2


def expert_pairs_per_token():
    """(token, expert) pairs a token brings this chip at the mean: 8 of 384
    experts, 12 of them held."""
    return EXPERTS_PER_TOKEN * EXPERTS_HELD / N_EXPERTS


def _attention_weights(d_model):
    H = heads_of(d_model)
    return d_model * Q_LORA_RANK \
        + Q_LORA_RANK * H * (QK_NOPE_HEAD_DIM + QK_ROPE_HEAD_DIM) \
        + d_model * (KV_LORA_RANK + QK_ROPE_HEAD_DIM) \
        + KV_LORA_RANK * H * (QK_NOPE_HEAD_DIM + V_HEAD_DIM) \
        + H * V_HEAD_DIM * d_model


def decode_step_bytes(slots, live_tokens, vocab=20480, d_model=7168,
                      layers=5, ffn=18432):
    """Bytes one decode step has to move, in its parts: every bfloat16
    weight outside the routed experts once, the held experts that get a row
    (an expert of mean load `slots * 8 / 384` rows is empty with
    probability exp(-load): the grouped product skips it) with their rows,
    and each layer's latent rows of the `live_tokens` tokens the slots
    hold."""
    n_moe = max(0, layers - FIRST_K_DENSE)
    pairs = slots * expert_pairs_per_token()
    touched = EXPERTS_HELD * (1 - math.exp(-pairs / EXPERTS_HELD))
    return {"weights": 2 * (2 * vocab * d_model
                            + layers * _attention_weights(d_model)
                            + min(layers, FIRST_K_DENSE) * 3 * d_model
                            * int(round(ffn))
                            + n_moe * (3 * d_model * SHARED_HIDDEN
                                       + d_model * N_EXPERTS)),
            "experts": n_moe * 2 * (touched * 3 * d_model * EXPERT_HIDDEN
                                    + 2 * pairs * d_model),
            "latent": layers * mla_decode_bytes(slots, live_tokens, d_model)}


def decode_macs_per_token(vocab, d_model, layers, ffn):
    """Multiply-accumulates one generated token needs on this chip in the
    weights' products: every layer's five attention projections (the
    absorbed step's two per-head products with W_kvb among them), the dense
    layer's MLP, every routed block's router, shared expert and the held
    share of its 8 routed experts (8 * 12 / 384 = 0.25 pairs a token at the
    mean), and this chip's rows of the head. The lookup needs none;
    attention's scores and mix against the cached rows are left out: a share
    of the peak computed from this reads low, never high."""
    n_moe = max(0, layers - FIRST_K_DENSE)
    return layers * _attention_weights(d_model) \
        + min(layers, FIRST_K_DENSE) * 3 * d_model * int(round(ffn)) \
        + n_moe * (3 * d_model * SHARED_HIDDEN + d_model * N_EXPERTS
                   + expert_pairs_per_token() * 3 * d_model * EXPERT_HIDDEN) \
        + d_model * vocab
