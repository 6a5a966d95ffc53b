"""Plain reference for the `ling3_flash` configuration: ONE CHIP'S SHARE of
the `bailing_hybrid` decoder of inclusionAI/Ling-3.0-flash (config.json) in
straightforward `jax.numpy`, float32, `jax.default_matmul_precision
("highest")`: no kernels, no cache, no batching, no chunking, no absorbed
products, no sorting of rows by expert. It imports nothing of the program.

    h_0 = E[ids];  per block, pre-norm, eps 1e-6:
        h += mixer(RMSNorm(h));  h += ffn(RMSNorm(h))
    logits = RMSNorm(h_L) W_head^T                      (untied)
    layer i (0-based): mixer MLA when (i + 1) % 6 == 0, else KDA;
        ffn dense gated-SiLU of 6,144 for i < 2, else routed + shared

KDA (Kimi Linear, arXiv:2510.26692 section 3; d_k = d_v = 128 a head, every
projection full rank), x the normed input at position t:

    q, k, v = SiLU(conv4(x W_q)), SiLU(conv4(x W_k)), SiLU(conv4(x W_v))
    q <- q / |q|_2 * 128^-1/2 ;  k <- k / |k|_2                  a head
    g_t = -5 sigmoid(exp(A_log_h) (x W_f + dt_bias))   in [-5, 0]^128
    beta_t = sigmoid(x W_beta)                                    a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t ;   y = [RMSNorm_128(o_t) * sigmoid(x W_g)] W_o

as the SEQUENTIAL `lax.scan` over positions, one token at a time:
S' = Diag(exp g) S; u = beta (v - S'^T k); S = S' + k u^T; o = S^T q.
(The five 4,096-wide projections are stored side by side as `W_in` = [W_q |
W_k | W_v | W_f | W_g], the three convs' taps as `conv_W` = [q | k | v].)

MLA (DeepSeek-V2, arXiv:2405.04434; no query compression, latent 512, rotary
64, nope 128, v 128), the plain form:

    [c | k_pe] = x W_kv_a ;  c <- RMSNorm_512(c) ;  k_pe <- RoPE(k_pe)
    [q_nope | q_pe]_h = x W_q ;  q_pe <- RoPE(q_pe)     theta 6e6, adjacent pairs
    [k_nope | v]_h = c W_kv_b
    p_h = causal softmax((q_nope_h . k_nope_h + q_pe_h . k_pe) 192^-1/2)
    y = [(sum p_h v_h) sigmoid(x W_gate)_h] W_o         head-wise gate

Routed ffn (DeepSeek-V3, arXiv:2412.19437, `noaux_tc`), router in float32:
s = sigmoid(x W_r) [512]; chosen by s + b: a group's score is the sum of its
two largest s + b (8 groups of 64), 4 groups stay, then the 8 largest s + b
inside them; gate_e = 2.5 s_e / sum of the chosen s (no b in the gates).
Expert and shared expert: (SiLU(x W_g) * x W_u) W_d, width 768. THIS CHIP
sums over the chosen experts it holds (`first_expert` .. + W1.shape[0] - 1)
with the gates above, not renormalised over the held; what the other 448
would add is left out, and the partial sum goes on to the next layer. Given
all 512 experts it is the uncut layer; tests/test_moe.py adds the eight
shares up to that.

`init_params` returns the tree under the program's leaf names in bfloat16
(the harness keeps these very buffers alive through the window).

`dtype`: "float32" is the reference; "float8" the control of the correctness
check — both operands of every matrix product (projections, shared and
routed experts, attention scores and mix, the head) rounded to float8_e4m3
under a per-tensor scale; the router, the convs, the delta-rule recurrence,
the rotary angles and the norms stay float32. "bfloat16" rounds everything
but the router to bfloat16. `state_dtype` / `router_dtype` compute only the
recurrence's state, or only the router, in another dtype (the tier-1 tests'
proof that the tolerances see those two).

`kda_step_bytes`, `mla_decode_bytes`, `decode_step_bytes` are the bytes the
algorithm has to move, from shapes alone, for the roofline readers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

# what `init_params` / `logits` are not handed by the harness; a test ties
# each to configs/ling3_flash.json
HIDDEN_PER_HEAD = 80            # hidden_size 2560 / num_attention_heads 32
HEAD_DIM = 128                  # KDA's d_k = d_v, MLA's nope and v widths
D_CONV = 4
KDA_LOWER_BOUND = -5.0
KV_LORA_RANK = 512
QK_NOPE_HEAD_DIM = 128
QK_ROPE_HEAD_DIM = 64
V_HEAD_DIM = 128
ROPE_THETA = 6000000.0
LAYER_GROUP_SIZE = 6            # five KDA layers, then one MLA layer
FIRST_K_DENSE = 2
N_EXPERTS = 512                 # the router's width, as published
N_GROUPS = 8
TOPK_GROUPS = 4
EXPERTS_PER_TOKEN = 8
ROUTED_SCALING = 2.5
EXPERT_HIDDEN = 768
SHARED_HIDDEN = 768
EXPERTS_HELD = 64               # this chip's share: routing group 0
FIRST_EXPERT = 0
RMS_EPS = 1e-6
L2_EPS = 1e-6                   # under the root of q's and k's L2 norm
INIT_STD = 0.02
ROUTE_BIAS_STD = 0.01
STORE = jnp.bfloat16


def heads_of(d_model):
    return max(1, d_model // HIDDEN_PER_HEAD)


def is_mla(i):
    return (i + 1) % LAYER_GROUP_SIZE == 0


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std=INIT_STD):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(STORE)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def init_params(key, vocab, d_model, layers, ffn, experts_held=None):
    """Weights from a PRNG key: every matrix normal(0, 0.02) (embedding,
    head, router and expert matrices too: the head is untied and nothing
    multiplies the embedding, so a position's own input token does not lead
    its logits as it did in `granite4_h_small`), the router's selection bias
    normal(0, 0.01), A uniform in [1, 16], dt_bias uniform in [-1, 1] (so
    that the decays spread over all of [-5, 0] and the check sees them),
    unit norms, conv taps uniform +- 1/sqrt(4), zero bias on the lookup;
    bfloat16. `ffn` is the dense layers' width. Leaf by leaf (one small
    program a shape), so what a draw needs beside its result is never large
    while the tree is."""
    H = heads_of(d_model)
    HD = H * HEAD_DIM
    held = EXPERTS_HELD if experts_held is None else experts_held
    keys = iter(jax.random.split(key, 2 + 12 * layers))
    ffn = int(ffn)
    ones = lambda n: jnp.ones((n,), STORE)
    p = {"embed": {"W": _normal(next(keys), (vocab, d_model)),
                   "b": jnp.zeros((d_model,), STORE)}}
    for i in range(layers):
        p[f"b{i}_norm1"] = {"gamma": ones(d_model)}
        if is_mla(i):
            p[f"b{i}_mla"] = {
                "Wq": _normal(next(keys), (d_model, H * (
                    QK_NOPE_HEAD_DIM + QK_ROPE_HEAD_DIM))),
                "Wkv_a": _normal(next(keys), (d_model, KV_LORA_RANK
                                              + QK_ROPE_HEAD_DIM)),
                "kv_norm": ones(KV_LORA_RANK),
                "Wkv_b": _normal(next(keys), (KV_LORA_RANK, H * (
                    QK_NOPE_HEAD_DIM + V_HEAD_DIM))),
                "Wgate": _normal(next(keys), (d_model, H)),
                "Wo": _normal(next(keys), (H * V_HEAD_DIM, d_model))}
        else:
            p[f"b{i}_kda"] = {
                "W_in": _normal(next(keys), (d_model, 5 * HD)),
                "Wb": _normal(next(keys), (d_model, H)),
                "conv_W": (_uniform(next(keys), (D_CONV, 3 * HD), -1.0, 1.0)
                           / math.sqrt(D_CONV)).astype(STORE),
                "dt_bias": _uniform(next(keys), (HD,), -1.0, 1.0)
                .astype(STORE),
                "A_log": jnp.log(_uniform(next(keys), (H,), 1.0, 16.0))
                .astype(STORE),
                "norm": ones(HEAD_DIM),
                "Wo": _normal(next(keys), (HD, d_model))}
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


def _l2(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + L2_EPS)


def _up(tree, dt):
    return jax.tree_util.tree_map(lambda a: a.astype(dt), tree)


def rope(x, pos):
    """x [T, .., 64] rotated at positions pos [T]: adjacent pairs (2i, 2i+1)
    by the angle pos * theta^(-2i/64), in float32."""
    half = x.shape[-1] // 2
    freq = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq             # [T, half]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def gates_of(x, Wg, bias, dtype=jnp.float32):
    """[T, d] normed rows -> [T, 512]: 2.5 s_e / (sum of the chosen s) at the
    8 experts chosen by s + bias among the 4 best of 8 groups, 0 elsewhere;
    the router in `dtype` (float32 but in the tests' proof)."""
    E, G = Wg.shape[1], N_GROUPS
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x.astype(dtype) @ Wg.astype(dtype))
    pick = (s + bias.astype(dtype)).reshape(-1, G, E // G)
    best = jnp.sum(lax.top_k(pick, 2)[0], axis=-1)            # [T, G]
    kept = jnp.any(lax.top_k(best, TOPK_GROUPS)[1][:, :, None]
                   == jnp.arange(G), axis=1)
    pick = jnp.where(kept[:, :, None], pick, -jnp.inf).reshape(-1, E)
    chosen = lax.top_k(pick, EXPERTS_PER_TOKEN)[1]
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


def kda_scan(qh, kh, vh, g, beta, S0):
    """The delta rule, one position after another. qh, kh, vh, g [T, H, 128]
    (q and k normalised, g the decay's log), beta [T, H], S0 [H, 128, 128]
    -> (o [T, H, 128], the state after the last position)."""
    def position(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = jnp.exp(g_t)[:, :, None].astype(S.dtype) * S
        u = b_t[:, None] * (v_t - jnp.sum(S * k_t[:, :, None], axis=1))
        S = S + (k_t[:, :, None] * u[:, None, :]).astype(S.dtype)
        return S, jnp.sum(S * q_t[:, :, None], axis=1).astype(q_t.dtype)
    S, o = lax.scan(position, S0, (qh, kh, vh, g, beta))
    return o, S


@functools.partial(jax.jit, static_argnames=("dtype", "state_dtype"))
def _kda_half(h, norm, m, *, dtype, state_dtype):
    dt, q = _arith(dtype)
    norm, m = _up(norm, dt), _up(m, dt)
    T, d = h.shape
    H, D, K = heads_of(d), HEAD_DIM, D_CONV
    HD = H * D
    with jax.default_matmul_precision("highest"):
        x = q(_rms(h, norm["gamma"]))
        qkv, f, gate = jnp.split(x @ q(m["W_in"]), [3 * HD, 4 * HD], axis=-1)
        beta = jax.nn.sigmoid(x @ q(m["Wb"]))                  # [T, H]
        xp = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(xp[k:k + T] * m["conv_W"][k]
                              for k in range(K)))
        qh, kh, vh = (a.reshape(T, H, D) for a in jnp.split(qkv, 3, axis=-1))
        qh, kh = _l2(qh) * D ** -0.5, _l2(kh)
        g = KDA_LOWER_BOUND * jax.nn.sigmoid(
            jnp.exp(m["A_log"])[None, :, None]
            * (f + m["dt_bias"]).reshape(T, H, D))
        o, _ = kda_scan(qh, kh, vh, g, beta,
                        jnp.zeros((H, D, D), state_dtype or dt))
        y = _rms(o, m["norm"]) * jax.nn.sigmoid(gate).reshape(T, H, D)
        return h + q(y.reshape(T, HD)) @ q(m["Wo"])


@functools.partial(jax.jit, static_argnames=("dtype",))
def _mla_half(h, norm, a, *, dtype):
    dt, q = _arith(dtype)
    norm, a = _up(norm, dt), _up(a, dt)
    T, d = h.shape
    H, R = heads_of(d), KV_LORA_RANK
    Dn, Dr, Dv = QK_NOPE_HEAD_DIM, QK_ROPE_HEAD_DIM, V_HEAD_DIM
    pos = jnp.arange(T)
    with jax.default_matmul_precision("highest"):
        x = q(_rms(h, norm["gamma"]))
        c, k_pe = jnp.split(x @ q(a["Wkv_a"]), [R], axis=-1)
        c, k_pe = _rms(c, a["kv_norm"]), rope(k_pe, pos)
        qn, q_pe = jnp.split((x @ q(a["Wq"])).reshape(T, H, Dn + Dr), [Dn],
                             axis=-1)
        q_pe = rope(q_pe, pos)
        kn, v = jnp.split((q(c) @ q(a["Wkv_b"])).reshape(T, H, Dn + Dv),
                          [Dn], axis=-1)
        s = (jnp.einsum("qhd,khd->hqk", q(qn), q(kn))
             + jnp.einsum("qhr,kr->hqk", q(q_pe), q(k_pe))) \
            * (Dn + Dr) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
        o = jnp.einsum("hqk,khd->qhd", q(w), q(v))
        gate = jax.nn.sigmoid(x @ q(a["Wgate"]))               # [T, H]
        return h + q((o * gate[:, :, None]).reshape(T, H * Dv)) @ q(a["Wo"])


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
           first_expert=FIRST_EXPERT, state_dtype=None,
           router_dtype="float32"):
    """[T] token ids -> [T, vocab] float32 logits of the next token at every
    position, one sequence, for the share of the experts `params` holds
    (`first_expert` on). A jitted program a kind of block, called layer
    after layer, so one layer's float32 copy is alive at a time. `heads`
    must be `heads_of(d_model)` (the harness passes the configuration's)."""
    if heads != heads_of(params["embed"]["W"].shape[1]):
        raise ValueError(f"{heads} heads at d_model "
                         f"{params['embed']['W'].shape[1]}")
    h = _embed(params["embed"]["W"], params["embed"]["b"], ids, dtype=dtype)
    for i in range(layers):
        norm = params[f"b{i}_norm1"]
        if is_mla(i):
            h = _mla_half(h, norm, params[f"b{i}_mla"], dtype=dtype)
        else:
            h = _kda_half(h, norm, params[f"b{i}_kda"], dtype=dtype,
                          state_dtype=state_dtype)
        if i < FIRST_K_DENSE:
            h = _dense_half(h, params[f"b{i}_norm2"], params[f"b{i}_mlp"],
                            dtype=dtype)
        else:
            h = _routed_half(h, params[f"b{i}_norm2"], params[f"b{i}_mlp"],
                             params[f"b{i}_moe"], dtype=dtype,
                             first_expert=first_expert,
                             router_dtype=router_dtype)
    return _head(h, params["norm"], params["out"]["W"], dtype=dtype)


def kda_step_bytes(slots, d_model=2560):
    """Bytes one `kda_step` call has to move: the float32 state [H, 128,
    128] a slot read once and written once, and its row operands: q, k, the
    decay and beta k in (each [H, 128]), beta v in and o out."""
    H, D = heads_of(d_model), HEAD_DIM
    return 4 * slots * H * (2 * D * D + 6 * D)


def mla_decode_bytes(slots, live_tokens, d_model=2560):
    """Bytes one `mla_decode` call has to move: the bfloat16 latent row
    (512 + 64) of each of the `live_tokens` tokens the slots hold, read
    once for all heads, and a slot's query rows in ([H, 576] bfloat16) and
    latent mixes out ([H, 512] float32)."""
    H, W = heads_of(d_model), KV_LORA_RANK + QK_ROPE_HEAD_DIM
    return 2 * live_tokens * W + slots * H * (2 * W + 4 * KV_LORA_RANK)


def expert_pairs_per_token():
    """(token, expert) pairs a token brings this chip at the mean: its 8
    experts lie in 4 of 8 groups and this chip holds one group."""
    return EXPERTS_PER_TOKEN * EXPERTS_HELD / N_EXPERTS


def decode_step_bytes(slots, live_tokens, vocab=19648, d_model=2560,
                      layers=6, ffn=6144):
    """Bytes one decode step has to move, in its parts: every bfloat16
    weight outside the routed experts once, the held experts that get a row
    (an expert of mean load `slots / 64` rows is empty with probability
    exp(-load): the grouped product skips it) with their rows, each KDA
    layer's state read and written and its conv tail, and the latent rows
    of the `live_tokens` tokens the slots hold."""
    H = heads_of(d_model)
    HD = H * HEAD_DIM
    n_mla = sum(1 for i in range(layers) if is_mla(i))
    n_kda, n_moe = layers - n_mla, max(0, layers - FIRST_K_DENSE)
    kda_w = d_model * (5 * HD + H) + D_CONV * 3 * HD + 2 * HD + HD * d_model
    mla_w = d_model * H * (QK_NOPE_HEAD_DIM + QK_ROPE_HEAD_DIM) \
        + d_model * (KV_LORA_RANK + QK_ROPE_HEAD_DIM) \
        + KV_LORA_RANK * H * (QK_NOPE_HEAD_DIM + V_HEAD_DIM) \
        + d_model * H + H * V_HEAD_DIM * d_model
    dense_w = min(layers, FIRST_K_DENSE) * 3 * d_model * ffn
    shared_w = n_moe * (3 * d_model * SHARED_HIDDEN + d_model * N_EXPERTS)
    pairs = slots * expert_pairs_per_token()
    touched = EXPERTS_HELD * (1 - math.exp(-pairs / EXPERTS_HELD))
    return {"weights": 2 * (2 * vocab * d_model + n_kda * kda_w
                            + n_mla * mla_w + dense_w + shared_w),
            "experts": n_moe * 2 * (touched * 3 * d_model * EXPERT_HIDDEN
                                    + 2 * pairs * d_model),
            "kda_state": n_kda * kda_step_bytes(slots, d_model),
            "conv_tail": n_kda * 2 * slots * (D_CONV - 1) * 3 * HD * 2,
            "latent": n_mla * mla_decode_bytes(slots, live_tokens, d_model)}


def decode_macs_per_token(vocab, d_model, layers, ffn):
    """Multiply-accumulates one generated token needs on this chip in the
    weights' products: a KDA layer's six projections, the MLA layer's (the
    absorbed step's two per-head products with W_kv_b among them), the
    dense layers' MLPs, every routed block's router, shared expert and the
    held share of its 8 routed experts (8 * 64 / 512 pairs a token at the
    mean), and this chip's rows of the head. The lookup needs none; the
    delta rule, the convs and attention's scores and mix are left out: a
    share of the peak computed from this reads low, never high."""
    H = heads_of(d_model)
    HD = H * HEAD_DIM
    n_mla = sum(1 for i in range(layers) if is_mla(i))
    n_moe = max(0, layers - FIRST_K_DENSE)
    kda = d_model * (5 * HD + H) + HD * d_model
    mla = d_model * H * (QK_NOPE_HEAD_DIM + QK_ROPE_HEAD_DIM) \
        + d_model * (KV_LORA_RANK + QK_ROPE_HEAD_DIM) \
        + KV_LORA_RANK * H * (QK_NOPE_HEAD_DIM + V_HEAD_DIM) \
        + d_model * H + H * V_HEAD_DIM * d_model
    return (layers - n_mla) * kda + n_mla * mla \
        + min(layers, FIRST_K_DENSE) * 3 * d_model * int(ffn) \
        + n_moe * (3 * d_model * SHARED_HIDDEN + d_model * N_EXPERTS
                   + expert_pairs_per_token() * 3 * d_model * EXPERT_HIDDEN) \
        + d_model * vocab
