"""Plain reference for the `solar_open2` configuration: ONE CHIP'S SHARE of
the `solar_open2` decoder of upstage/Solar-Open2-250B (config.json) in
straightforward `jax.numpy`, float32, `jax.default_matmul_precision
("highest")`: no kernels, no cache, no batching, no chunking of the
recurrence, no sorting of rows by expert. It imports nothing of the program.

    h_0 = E[ids];  per block, pre-norm, eps 1e-5:
        h += mixer(RMSNorm(h));  h += ffn(RMSNorm(h))
    logits = RMSNorm(h_L) W_head^T                      (untied)
    layer i (0-based): mixer attention when i % 4 == 0, else KDA;
        ffn routed + shared in every layer (first_k_dense_replace 0)

Gated grouped-query attention (64 query heads on 8 K/V heads of 128, no
positions of any kind, no bias, no q/k norm; the gate after arXiv:2505.06708,
its SDPA-output form), x the normed input:

    q = x W_q [H x 128] ;  k = x W_k ,  v = x W_v  [H/8 x 128]
    p_h = causal softmax(q_h . k_{h // 8} * 128^-1/2) ;  o_h = sum p_h v_{h // 8}
    y = [o * sigmoid(x W_gate)] W_o                     elementwise gate

in query blocks of 256 positions, so that H x T^2 scores never stand whole.

KDA (Kimi Linear, arXiv:2510.26692 section 3, with its public
implementation's low-rank gates and `allow_neg_eigval`; d_k = d_v = 128 a
head, as many k/v heads as q heads):

    q, k, v = SiLU(conv4(x W_q)), SiLU(conv4(x W_k)), SiLU(conv4(x W_v))
    q <- q / |q|_2 * 128^-1/2 ;  k <- k / |k|_2                  a head
    g_t = -exp(A_log_h) softplus(x W_fa W_fb + dt_bias)   in (-inf, 0]^128
    beta_t = 2 sigmoid(x W_beta)                          a head, in (0, 2)
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t ;   y = [RMSNorm_128(o_t) * sigmoid(x W_ga W_gb)] W_o

as the SEQUENTIAL `lax.scan` over positions, one token at a time:
S' = Diag(exp g) S; u = beta (v - S'^T k); S = S' + k u^T; o = S^T q.
(Stored side by side: `W_in` = [W_q | W_k | W_v | W_fa | W_ga], the three
convs' taps as `conv_W` = [q | k | v].)

Routed ffn (DeepSeek-V3's `noaux_tc` in one group), router in float32:
s = sigmoid(x W_r) [320]; the 8 largest s + b (b a selection-only bias);
gate_e = s_e / sum of the chosen s (`norm_topk_prob`, scaling 1). Expert and
shared expert: (SiLU(x W_g) * x W_u) W_d, width 1,280; the shared one is
added ungated. THIS CHIP sums over the chosen experts it holds
(`first_expert` .. + W1.shape[0] - 1) with the gates above, not renormalised
over the held; what the other 280 would add is left out, and the partial sum
goes on to the next layer. Given all 320 experts it is the uncut layer;
tests/test_moe.py adds the eight shares up to that.

`init_params` returns the tree under the program's leaf names in bfloat16
(the harness keeps these very buffers alive through the window).

`dtype`: "float32" is the reference; "float8" the control of the correctness
check — both operands of every matrix product (projections, shared and
routed experts, attention scores and mix, the head) rounded to float8_e4m3
under a per-tensor scale; the router, the convs, the delta-rule recurrence
and the norms stay float32. "bfloat16" rounds everything but the router to
bfloat16. `state_dtype` / `router_dtype` compute only the recurrence's
state, or only the router, in another dtype (the tier-1 tests' proof that
the tolerances see those two).

`kda_step_bytes`, `expert_layer_bytes`, `flash_decode_bytes`,
`decode_step_bytes` are the bytes the algorithm has to move, from shapes
alone, for the roofline readers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

# what `init_params` / `logits` are not handed by the harness; a test ties
# each to configs/solar_open2.json
HIDDEN_PER_HEAD = 64            # hidden_size 4096 / num_attention_heads 64
HEAD_DIM = 128                  # attention's head_dim, KDA's d_k = d_v
Q_PER_KV = 8                    # 64 query heads on 8 K/V heads
D_CONV = 4
GATE_RANK = 128                 # W_fa, W_ga [d_model, 128]: the head's width
BETA_SCALE = 2.0                # kda_allow_neg_eigval
GQA_PERIOD = 4                  # attention at 0, 4, ..: gqa_interval 3 + 1
N_EXPERTS = 320                 # the router's width, as published
EXPERTS_PER_TOKEN = 8
ROUTED_SCALING = 1.0
EXPERT_HIDDEN = 1280
SHARED_HIDDEN = 1280            # n_shared_experts 1 x moe_intermediate_size
EXPERTS_HELD = 40               # this chip's share of 8
FIRST_EXPERT = 0
RMS_EPS = 1e-5
L2_EPS = 1e-6                   # under the root of q's and k's L2 norm
INIT_STD = 0.02
ROUTE_BIAS_STD = 0.01
QUERY_BLOCK = 256
STORE = jnp.bfloat16


def heads_of(d_model):
    return max(1, d_model // HIDDEN_PER_HEAD)


def kv_heads_of(d_model):
    return max(1, heads_of(d_model) // Q_PER_KV)


def is_attention(i):
    return i % GQA_PERIOD == 0


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std=INIT_STD):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(STORE)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def init_params(key, vocab, d_model, layers, ffn=None, experts_held=None):
    """Weights from a PRNG key: every matrix normal(0, 0.02) (embedding,
    untied head, router and expert matrices too, as in `ling3_flash`: a
    position's own input token does not lead its logits), the router's
    selection bias normal(0, 0.01), A uniform in [1, 16], dt_bias uniform in
    [-6, 1] (softplus of it 0.0025 .. 1.3, so the decays exp(-A softplus)
    spread from 0.9975 a token to nothing left after one), unit norms, conv
    taps uniform +- 1/sqrt(4), zero biases on the lookup and on attention's
    output; bfloat16. `ffn` (the dense layers' width) is taken and unused:
    the model has no dense layer. Leaf by leaf (one small program a shape),
    so what a draw needs beside its result is never large while the tree
    is."""
    H, Hkv = heads_of(d_model), kv_heads_of(d_model)
    HD = H * HEAD_DIM
    held = EXPERTS_HELD if experts_held is None else experts_held
    keys = iter(jax.random.split(key, 2 + 14 * layers))
    ones = lambda n: jnp.ones((n,), STORE)
    p = {"embed": {"W": _normal(next(keys), (vocab, d_model)),
                   "b": jnp.zeros((d_model,), STORE)}}
    for i in range(layers):
        p[f"b{i}_norm1"] = {"gamma": ones(d_model)}
        if is_attention(i):
            p[f"b{i}_attn"] = {
                "Wq": _normal(next(keys), (d_model, HD)),
                "Wk": _normal(next(keys), (d_model, Hkv * HEAD_DIM)),
                "Wv": _normal(next(keys), (d_model, Hkv * HEAD_DIM)),
                "Wgate": _normal(next(keys), (d_model, HD)),
                "Wo": _normal(next(keys), (HD, d_model)),
                "b": jnp.zeros((d_model,), STORE)}
        else:
            p[f"b{i}_kda"] = {
                "W_in": _normal(next(keys), (d_model, 3 * HD + 2 * GATE_RANK)),
                "W_fb": _normal(next(keys), (GATE_RANK, HD)),
                "W_gb": _normal(next(keys), (GATE_RANK, HD)),
                "Wb": _normal(next(keys), (d_model, H)),
                "conv_W": (_uniform(next(keys), (D_CONV, 3 * HD), -1.0, 1.0)
                           / math.sqrt(D_CONV)).astype(STORE),
                "dt_bias": _uniform(next(keys), (HD,), -6.0, 1.0)
                .astype(STORE),
                "A_log": jnp.log(_uniform(next(keys), (H,), 1.0, 16.0))
                .astype(STORE),
                "norm": ones(HEAD_DIM),
                "Wo": _normal(next(keys), (HD, d_model))}
        p[f"b{i}_norm2"] = {"gamma": ones(d_model)}
        p[f"b{i}_mlp"] = {
            "W_in": _normal(next(keys), (d_model, 2 * SHARED_HIDDEN)),
            "W_out": _normal(next(keys), (SHARED_HIDDEN, d_model))}
        p[f"b{i}_moe"] = {
            "Wg": _normal(next(keys), (d_model, N_EXPERTS)),
            "route_bias": _normal(next(keys), (N_EXPERTS,), ROUTE_BIAS_STD),
            "W1": _normal(next(keys), (held, d_model, 2 * EXPERT_HIDDEN)),
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


def gates_of(x, Wg, bias, dtype=jnp.float32):
    """[T, d] normed rows -> [T, 320]: s_e / (sum of the chosen s) at the 8
    experts with the largest s + bias, 0 elsewhere; the router in `dtype`
    (float32 but in the tests' proof)."""
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
    H, D, K, R = heads_of(d), HEAD_DIM, D_CONV, GATE_RANK
    HD = H * D
    with jax.default_matmul_precision("highest"):
        x = q(_rms(h, norm["gamma"]))
        qkv, fa, ga = jnp.split(x @ q(m["W_in"]), [3 * HD, 3 * HD + R],
                                axis=-1)
        f, gate = q(fa) @ q(m["W_fb"]), q(ga) @ q(m["W_gb"])
        beta = BETA_SCALE * jax.nn.sigmoid(x @ q(m["Wb"]))     # [T, H]
        xp = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(xp[k:k + T] * m["conv_W"][k]
                              for k in range(K)))
        qh, kh, vh = (a.reshape(T, H, D) for a in jnp.split(qkv, 3, axis=-1))
        qh, kh = _l2(qh) * D ** -0.5, _l2(kh)
        g = -jnp.exp(m["A_log"])[None, :, None] * jax.nn.softplus(
            (f + m["dt_bias"]).reshape(T, H, D))
        o, _ = kda_scan(qh, kh, vh, g, beta,
                        jnp.zeros((H, D, D), state_dtype or dt))
        y = _rms(o, m["norm"]) * jax.nn.sigmoid(gate).reshape(T, H, D)
        return h + q(y.reshape(T, HD)) @ q(m["Wo"])


@functools.partial(jax.jit, static_argnames=("dtype",))
def _attention_half(h, norm, a, *, dtype):
    dt, q = _arith(dtype)
    norm, a = _up(norm, dt), _up(a, dt)
    T, d = h.shape
    H, Hkv, D = heads_of(d), kv_heads_of(d), HEAD_DIM
    B = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    with jax.default_matmul_precision("highest"):
        x = q(_rms(h, norm["gamma"]))
        qh = q((x @ q(a["Wq"])).reshape(T // B, B, Hkv, H // Hkv, D))
        kh = q((x @ q(a["Wk"])).reshape(T, Hkv, D))
        vh = q((x @ q(a["Wv"])).reshape(T, Hkv, D))

        def block(at):
            start, qb = at                          # qb [B, Hkv, G, D]
            s = jnp.einsum("qhgd,khd->hgqk", qb, kh) * D ** -0.5
            seen = jnp.arange(T)[None, :] <= start + jnp.arange(B)[:, None]
            s = jnp.where(seen, s, -jnp.inf)
            w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
            return jnp.einsum("hgqk,khd->qhgd", q(w), vh)
        o = lax.map(block, (jnp.arange(0, T, B), qh)).reshape(T, H * D)
        gate = jax.nn.sigmoid(x @ q(a["Wgate"]))
        return h + q(o * gate) @ q(a["Wo"]) + a["b"]


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
        if is_attention(i):
            h = _attention_half(h, norm, params[f"b{i}_attn"], dtype=dtype)
        else:
            h = _kda_half(h, norm, params[f"b{i}_kda"], dtype=dtype,
                          state_dtype=state_dtype)
        h = _routed_half(h, params[f"b{i}_norm2"], params[f"b{i}_mlp"],
                         params[f"b{i}_moe"], dtype=dtype,
                         first_expert=first_expert,
                         router_dtype=router_dtype)
    return _head(h, params["norm"], params["out"]["W"], dtype=dtype)


def kda_step_bytes(slots, d_model=4096):
    """Bytes one `kda_step` call has to move: the float32 state [H, 128,
    128] a slot read once and written once, and its row operands: q, k, the
    decay and beta k in (each [H, 128]), beta v in and o out."""
    H, D = heads_of(d_model), HEAD_DIM
    return 4 * slots * H * (2 * D * D + 6 * D)


def expert_pairs_per_token():
    """(token, expert) pairs a token brings this chip at the mean: 8 of 320
    experts, 40 of them held."""
    return EXPERTS_PER_TOKEN * EXPERTS_HELD / N_EXPERTS


def expert_layer_bytes(rows, d_model=4096):
    """Bytes one layer's expert products have to move for `rows` tokens
    when every held expert gets a row: the 40 held experts' matrices once
    and the rows routed to them, gathered in and written out. With even
    loads an expert of mean load 4.8 (192 rows) stays empty with
    probability exp(-4.8), 0.8 %; at the seeded weights the loads are NOT
    even: `gates_of` over steps of 192 rows (192 random sequences, two
    seeds, on the host) touches 39.3 of the 40 in layer 0 and 35.4-38.2 in
    layers 1-3, 37.3-37.9 a layer — so a roofline share from this count
    reads over 100 (102.8 on the chip) and the cell does not list
    `expert_gmm_roofline_pct` (PERF.md sections 5 and 7)."""
    pairs = rows * expert_pairs_per_token()
    return 2 * (EXPERTS_HELD * 3 * d_model * EXPERT_HIDDEN
                + 2 * pairs * d_model)


def flash_decode_bytes(slots, live_tokens, d_model=4096):
    """Bytes one `flash_decode` call of the attention layer has to move: the
    bfloat16 K and V rows (8 heads of 128 each) of the `live_tokens` tokens
    the slots hold, once for all the query heads of a K/V head; a slot's
    token rows in (to the cache) and its query rows in and context rows
    out ([H, 128] bfloat16 each)."""
    H, Hkv, D = heads_of(d_model), kv_heads_of(d_model), HEAD_DIM
    return 2 * (2 * live_tokens * Hkv * D + slots * 2 * (Hkv + H) * D)


def _mixer_weights(d_model):
    """(an attention layer's, a KDA layer's) parameter counts."""
    H, Hkv, D, R = heads_of(d_model), kv_heads_of(d_model), HEAD_DIM, \
        GATE_RANK
    HD = H * D
    attn = d_model * (2 * HD + 2 * Hkv * D) + HD * d_model
    kda = d_model * (3 * HD + 2 * R + H) + 2 * R * HD + HD * d_model \
        + _kda_vectors(d_model)
    return attn, kda


def _kda_vectors(d_model):
    """A KDA layer's parameters that enter no matrix product: the convs'
    taps, dt_bias, A_log and the output norm."""
    H, D = heads_of(d_model), HEAD_DIM
    return D_CONV * 3 * H * D + H * D + H + D


def decode_step_bytes(slots, live_tokens, vocab=24576, d_model=4096,
                      layers=4):
    """Bytes one decode step has to move, in its parts: every bfloat16
    weight outside the routed experts once, the held experts that get a row
    (an expert of mean load `slots * 8 / 320` rows is empty with
    probability exp(-load): the grouped product skips it) with their rows,
    each KDA layer's state read and written and its conv tail, and the K/V
    rows of the `live_tokens` tokens the slots hold in each attention
    layer."""
    HD = heads_of(d_model) * HEAD_DIM
    n_attn = sum(1 for i in range(layers) if is_attention(i))
    n_kda = layers - n_attn
    attn_w, kda_w = _mixer_weights(d_model)
    shared_w = layers * (3 * d_model * SHARED_HIDDEN + d_model * N_EXPERTS)
    pairs = slots * expert_pairs_per_token()
    touched = EXPERTS_HELD * (1 - math.exp(-pairs / EXPERTS_HELD))
    return {"weights": 2 * (2 * vocab * d_model + n_attn * attn_w
                            + n_kda * kda_w + shared_w),
            "experts": layers * 2 * (touched * 3 * d_model * EXPERT_HIDDEN
                                     + 2 * pairs * d_model),
            "kda_state": n_kda * kda_step_bytes(slots, d_model),
            "conv_tail": n_kda * 2 * slots * (D_CONV - 1) * 3 * HD * 2,
            "kv": n_attn * flash_decode_bytes(slots, live_tokens, d_model)}


def decode_macs_per_token(vocab, d_model, layers, ffn=None):
    """Multiply-accumulates one generated token needs on this chip in the
    weights' products: the attention layer's five projections, a KDA
    layer's (its low-rank gates' two factors each), every block's router,
    shared expert and the held share of its 8 routed experts (8 * 40 / 320 =
    1 pair a token at the mean), and this chip's rows of the head. The
    lookup needs none; the delta rule, the convs and attention's scores and
    mix are left out: a share of the peak computed from this reads low,
    never high. `ffn` is taken and unused (no dense layer)."""
    n_attn = sum(1 for i in range(layers) if is_attention(i))
    attn, kda = _mixer_weights(d_model)
    kda -= _kda_vectors(d_model)
    return n_attn * attn + (layers - n_attn) * kda \
        + layers * (3 * d_model * SHARED_HIDDEN + d_model * N_EXPERTS
                    + expert_pairs_per_token() * 3 * d_model * EXPERT_HIDDEN) \
        + d_model * vocab
