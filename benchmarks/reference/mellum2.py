"""Plain reference for the `mellum2` configuration: ONE CHIP'S SHARE of the
`mellum` decoder of JetBrains/Mellum2-12B-A2.5B-Instruct (config.json) in
straightforward `jax.numpy`, float32, `jax.default_matmul_precision
("highest")`: no kernels, no cache, no ring, no batching, no sorting of rows
by expert. It imports nothing of the program.

    h_0 = E[ids];  per block, pre-norm, eps 1e-6:
        h += attention_l(RMSNorm(h));  h += experts(RMSNorm(h))
    logits = RMSNorm(h_L) W_head^T                      (untied, no bias)
    layer l (0-based): FULL attention when l % 4 == 3, else SLIDING

Grouped-query attention (32 query heads on 4 K/V heads of 128, no bias, no
q/k norm), x the normed input, positions p = 0, 1, ..:

    q = x W_q [32 x 128] ;  k = x W_k ,  v = x W_v  [4 x 128]
    q, k <- R_l(q, p), R_l(k, p)
    R(x, p) = x * cos(p f) * a + rotate_half(x) * sin(p f) * a
        rotate_half pairs channel j with j + 64 (HF's), f the 64
        frequencies repeated twice; angles, cos and sin float32
    p_h = softmax(q_h . k_{h // 8} * 128^-1/2) over the positions seen,
        float32 ;  o_h = sum p_h v_{h // 8} ;  y = o W_o
    sliding (l % 4 != 3): f_j = 500000^(-2j/128), a = 1; position i sees j
        with i - 1024 < j <= i (1,024 keys with its own)
    full (l % 4 == 3): YaRN as `transformers` computes it: e_j =
        500000^(-2j/128), n_j = e_j / 16; c(r) = 128 ln(8192 / (2 pi r)) /
        (2 ln 500000); low = floor(c(32)) = 18, high = ceil(c(1)) = 35, both
        clipped to [0, 127]; ramp_j = clip((j - low) / (high - low), 0, 1);
        f_j = n_j ramp_j + e_j (1 - ramp_j); a = 1.2772588722239782 on cos
        and sin alike (a score carries a^2); every j <= i seen

in query blocks of 256 positions, so that H x T^2 scores never stand whole.

Routed experts (no shared expert, no dense layer), router in float32:
p = softmax(x W_r) over all 64; the 8 largest; gate_e = p_e / sum of the
chosen p (`norm_topk_prob`). Expert: (SiLU(x W_g) * x W_u) W_d, width 896.
THIS CHIP sums over the chosen experts it holds (`first_expert` .. +
W1.shape[0] - 1) with the gates above, not renormalised over the held; what
the other 48 would add is left out, and the partial sum goes on to the next
layer. Given all 64 experts it is the uncut layer; tests/test_mellum.py adds
the four shares up to that.

Departures from the published description (configs/mellum2.json
`departures` has the same list):
- the lookup is a row of E here and one-hot x E in the program (ROADMAP
  R-A0): equal exactly; the program's zero bias leaves (`embed.b`, an
  attention layer's `b`) ride along in the tree and are added;
- the routed sum is partial, as above;
- the vocabulary is rows 0 .. vocab - 1 of the published 98,304: ids,
  logits, softmax and argmax are over the slice;
- weights are random, drawn from the seed: every matrix normal(0, 0.02),
  norm weights 1;
- storage only: matrices are [in, out] (the transpose of a torch Linear's),
  an expert's gate and up projections one matrix W1 = [W_g | W_u], expert
  major [held, ...].

`init_params` returns the tree under the program's leaf names in bfloat16
(the harness keeps these very buffers alive through the window).

`dtype`: "float32" is the reference; "float8" the control of the correctness
check — both operands of every matrix product (projections, experts,
attention scores and mix, the head) rounded to float8_e4m3 under a
per-tensor scale; the router, the rotary turn and the norms stay float32.
"bfloat16" rounds everything but the router and the rotary angles to
bfloat16. `router_dtype` / `rope_dtype` compute only the router, or only the
rotary angles, cos and sin, in another dtype (the tier-1 tests' proof that
the tolerances see those two).

`flash_decode_bytes`, `window_decode_bytes`, `expert_layer_bytes`,
`decode_step_bytes` are the bytes the algorithm has to move, from shapes
alone, for the roofline readers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# what `init_params` / `logits` are not handed by the harness; a test ties
# each to configs/mellum2.json
HIDDEN_PER_HEAD = 72            # hidden_size 2304 / num_attention_heads 32
HEAD_DIM = 128
Q_PER_KV = 8                    # 32 query heads on 4 K/V heads
WINDOW = 1024                   # sliding_window: keys seen, the own among them
PERIOD = 4                      # sliding x 3, then full
ROPE_THETA = 500000.0
YARN_FACTOR = 16.0
YARN_ORIGINAL = 8192            # original_max_position_embeddings
YARN_BETA_FAST = 32.0
YARN_BETA_SLOW = 1.0
YARN_ATTENTION_FACTOR = 1.2772588722239782
N_EXPERTS = 64                  # the router's width, as published
EXPERTS_PER_TOKEN = 8
EXPERT_HIDDEN = 896
EXPERTS_HELD = 16               # this chip's share of 4
FIRST_EXPERT = 0
RMS_EPS = 1e-6
INIT_STD = 0.02
QUERY_BLOCK = 256
STORE = jnp.bfloat16


def heads_of(d_model):
    return max(1, d_model // HIDDEN_PER_HEAD)


def kv_heads_of(d_model):
    return max(1, heads_of(d_model) // Q_PER_KV)


def is_full(i):
    return i % PERIOD == PERIOD - 1


def yarn_range():
    """(low, high): the channels between which the full layers' frequencies
    go from theta's own to theta's / 16."""
    def c(rotations):
        return HEAD_DIM * math.log(YARN_ORIGINAL / (2 * math.pi * rotations)) \
            / (2 * math.log(ROPE_THETA))
    return max(math.floor(c(YARN_BETA_FAST)), 0), \
        min(math.ceil(c(YARN_BETA_SLOW)), HEAD_DIM - 1)


def rope_table(full):
    """(the 64 frequencies [64] float32, the factor a on cos and sin) of a
    full layer (YaRN) or a sliding one (plain)."""
    j = np.arange(HEAD_DIM // 2, dtype=np.float64)
    e = ROPE_THETA ** (-2 * j / HEAD_DIM)
    if not full:
        return e.astype(np.float32), 1.0
    low, high = yarn_range()
    ramp = np.clip((j - low) / (high - low), 0, 1)
    return (e / YARN_FACTOR * ramp + e * (1 - ramp)).astype(np.float32), \
        YARN_ATTENTION_FACTOR


def rotate(x, full, dtype=jnp.float32):
    """x [T, heads, 128] at positions 0 .. T - 1: x cos a + rotate_half(x)
    sin a; the angles, cos and sin in `dtype` (float32 but in the tests'
    proof)."""
    f, a = rope_table(full)
    ang = jnp.arange(x.shape[0], dtype=dtype)[:, None] * jnp.asarray(f, dtype)
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]     # [T, 1, 128]
    cos, sin = (jnp.cos(ang) * a).astype(x.dtype), \
        (jnp.sin(ang) * a).astype(x.dtype)
    half = jnp.concatenate([-x[..., HEAD_DIM // 2:], x[..., :HEAD_DIM // 2]],
                           axis=-1)
    return x * cos + half * sin


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std=INIT_STD):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(STORE)


def init_params(key, vocab, d_model, layers, ffn=None, experts_held=None):
    """Weights from a PRNG key: every matrix normal(0, 0.02) (embedding,
    untied head, router and expert matrices too, `ling3_flash`'s
    conventions: a position's own input token does not lead its logits),
    unit norms, zero biases on the lookup and on attention's output;
    bfloat16. `ffn` (a dense layer's width) is taken and unused: the model
    has no dense layer. Leaf by leaf (one small program a shape), so what a
    draw needs beside its result is never large while the tree is."""
    H, Hkv = heads_of(d_model), kv_heads_of(d_model)
    HD = H * HEAD_DIM
    held = EXPERTS_HELD if experts_held is None else experts_held
    keys = iter(jax.random.split(key, 2 + 7 * layers))
    ones = lambda n: jnp.ones((n,), STORE)
    p = {"embed": {"W": _normal(next(keys), (vocab, d_model)),
                   "b": jnp.zeros((d_model,), STORE)}}
    for i in range(layers):
        p[f"b{i}_norm1"] = {"gamma": ones(d_model)}
        p[f"b{i}_attn"] = {
            "Wq": _normal(next(keys), (d_model, HD)),
            "Wk": _normal(next(keys), (d_model, Hkv * HEAD_DIM)),
            "Wv": _normal(next(keys), (d_model, Hkv * HEAD_DIM)),
            "Wo": _normal(next(keys), (HD, d_model)),
            "b": jnp.zeros((d_model,), STORE)}
        p[f"b{i}_norm2"] = {"gamma": ones(d_model)}
        p[f"b{i}_moe"] = {
            "Wg": _normal(next(keys), (d_model, N_EXPERTS)),
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


def _up(tree, dt):
    return jax.tree_util.tree_map(lambda a: a.astype(dt), tree)


def gates_of(x, Wg, dtype=jnp.float32):
    """[T, d] normed rows -> [T, 64]: p_e / (sum of the chosen p) at the 8
    experts with the largest p = softmax(x W_r), 0 elsewhere; the router in
    `dtype` (float32 but in the tests' proof)."""
    E = Wg.shape[1]
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(x.astype(dtype) @ Wg.astype(dtype), axis=-1)
    chosen = lax.top_k(p, EXPERTS_PER_TOKEN)[1]
    hit = jnp.any(chosen[:, :, None] == jnp.arange(E), axis=1)
    p = jnp.where(hit, p, 0).astype(jnp.float32)
    return p / jnp.sum(p, axis=-1, keepdims=True)


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
                   static_argnames=("dtype", "first_expert", "router_dtype"))
def _routed_half(h, norm, moe, *, dtype, first_expert, router_dtype):
    dt, q = _arith(dtype)
    held = moe["W1"].shape[0]
    x = _rms(h, norm["gamma"].astype(dt))
    gates = gates_of(x, moe["Wg"], jnp.dtype(router_dtype))[
        :, first_expert:first_expert + held]
    return h + expert_sum(x, gates, moe["W1"], moe["W2"], dt, q)


@functools.partial(jax.jit, static_argnames=("dtype", "window", "rope_dtype"))
def _attention_half(h, norm, a, *, dtype, window, rope_dtype):
    """`window`: the keys a position sees, its own among them (a sliding
    layer), or None (a full layer: the whole context, YaRN's turn)."""
    full = window is None
    dt, q = _arith(dtype)
    norm, a = _up(norm, dt), _up(a, dt)
    T, d = h.shape
    H, Hkv, D = heads_of(d), kv_heads_of(d), HEAD_DIM
    B = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    rope_dt = jnp.dtype(rope_dtype)
    with jax.default_matmul_precision("highest"):
        x = q(_rms(h, norm["gamma"]))
        qh = rotate((x @ q(a["Wq"])).reshape(T, H, D), full, rope_dt)
        kh = q(rotate((x @ q(a["Wk"])).reshape(T, Hkv, D), full, rope_dt))
        vh = q((x @ q(a["Wv"])).reshape(T, Hkv, D))
        qh = q(qh).reshape(T // B, B, Hkv, H // Hkv, D)

        def block(at):
            start, qb = at                          # qb [B, Hkv, G, D]
            s = jnp.einsum("qhgd,khd->hgqk", qb, kh) * D ** -0.5
            i = start + jnp.arange(B)[:, None]
            j = jnp.arange(T)[None, :]
            seen = j <= i
            if not full:
                seen &= j > i - window
            s = jnp.where(seen, s, -jnp.inf)
            w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
            return jnp.einsum("hgqk,khd->qhgd", q(w), vh)
        o = lax.map(block, (jnp.arange(0, T, B), qh)).reshape(T, H * D)
        return h + q(o) @ q(a["Wo"]) + a["b"]


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
           rope_dtype="float32"):
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
        h = _attention_half(h, params[f"b{i}_norm1"], params[f"b{i}_attn"],
                            dtype=dtype, rope_dtype=rope_dtype,
                            window=None if is_full(i) else WINDOW)
        h = _routed_half(h, params[f"b{i}_norm2"], params[f"b{i}_moe"],
                         dtype=dtype, first_expert=first_expert,
                         router_dtype=router_dtype)
    return _head(h, params["norm"], params["out"]["W"], dtype=dtype)


def expert_pairs_per_token():
    """(token, expert) pairs a token brings this chip at the mean: 8 of 64
    experts, 16 of them held."""
    return EXPERTS_PER_TOKEN * EXPERTS_HELD / N_EXPERTS


def expert_layer_bytes(rows, d_model=2304):
    """Bytes one layer's expert products have to move for `rows` tokens
    when every held expert gets a row: the 16 held experts' matrices once
    (6,193,152 parameters each: 198 MB) and the rows routed to them,
    gathered in and written out. With even loads an expert of mean load 6
    (48 rows) stays empty with probability exp(-6), 0.25 %."""
    pairs = rows * expert_pairs_per_token()
    return 2 * (EXPERTS_HELD * 3 * d_model * EXPERT_HIDDEN
                + 2 * pairs * d_model)


def _attention_call_bytes(slots, cached_tokens, d_model):
    H, Hkv, D = heads_of(d_model), kv_heads_of(d_model), HEAD_DIM
    return 2 * (2 * cached_tokens * Hkv * D + slots * 2 * (Hkv + H) * D)


def flash_decode_bytes(slots, live_tokens, d_model=2304):
    """Bytes one `flash_decode` call — a FULL layer's — has to move: the
    bfloat16 K and V rows (4 heads of 128 each) of the `live_tokens` tokens
    the slots hold, once for all the query heads of a K/V head; a slot's
    token rows in (to the cache) and its query rows in and context rows out
    ([32, 128] bfloat16 each)."""
    return _attention_call_bytes(slots, live_tokens, d_model)


def window_decode_bytes(slots, window_tokens, d_model=2304):
    """Bytes one `flash_decode_window` call — a SLIDING layer's — has to
    move: the K and V rows of the `window_tokens` positions the slots' rings
    hold (slots x 1,024 once every prompt is at least the window), the
    token's rows in and the query rows in and context rows out."""
    return _attention_call_bytes(slots, window_tokens, d_model)


def _attention_weights(d_model):
    H, Hkv, D = heads_of(d_model), kv_heads_of(d_model), HEAD_DIM
    return d_model * (H * D + 2 * Hkv * D) + H * D * d_model


def decode_step_bytes(slots, live_tokens, vocab=24576, d_model=2304,
                      layers=28):
    """Bytes one decode step has to move, in its parts: every bfloat16
    weight outside the experts once, the held experts that get a row (an
    expert of mean load `slots * 8 / 64` rows is empty with probability
    exp(-load): the grouped product skips it) with their rows, the K/V rows
    of the `live_tokens` tokens the slots hold in each full layer, and in
    each sliding layer those of min(live, slots x 1,024) positions (exact
    when no slot is shorter than the window)."""
    n_full = sum(1 for i in range(layers) if is_full(i))
    pairs = slots * expert_pairs_per_token()
    touched = EXPERTS_HELD * (1 - math.exp(-pairs / EXPERTS_HELD))
    return {"weights": 2 * (2 * vocab * d_model + layers * (
                _attention_weights(d_model) + d_model * N_EXPERTS)),
            "experts": layers * 2 * (touched * 3 * d_model * EXPERT_HIDDEN
                                     + 2 * pairs * d_model),
            "kv": n_full * flash_decode_bytes(slots, live_tokens, d_model),
            "window": (layers - n_full) * window_decode_bytes(
                slots, min(live_tokens, slots * WINDOW), d_model)}


def decode_macs_per_token(vocab, d_model, layers, ffn=None):
    """Multiply-accumulates one generated token needs on this chip in the
    weights' products: every layer's four attention projections, its
    router and the held share of its 8 routed experts (8 * 16 / 64 = 2
    pairs a token at the mean), and this chip's rows of the head. The
    lookup needs none; attention's scores and mix are left out: a share of
    the peak computed from this reads low, never high. `ffn` is taken and
    unused (no dense layer)."""
    return layers * (_attention_weights(d_model) + d_model * N_EXPERTS
                     + expert_pairs_per_token() * 3 * d_model
                     * EXPERT_HIDDEN) + d_model * vocab
