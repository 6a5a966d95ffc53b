"""What the `mellum2` configuration brought — rotary positions (plain and
YaRN) and a sliding window in SelfAttentionLayer, a window layer's cache as
a ring, the windowed flash forward, the row-major decode kernel for fewer
K/V heads than a tile has sublanes and for a ring
(kernels/flash_attention.py `_decode_rows_kernel`), softmax routing with a
share of the experts — each against benchmarks/reference/mellum2.py or the
code it replaces, at tiny widths, seeded, on the CPU (float64 rows under
conftest's x64 unless said). The whole model through `DecodeEngine` against
the reference's one-pass logits is tests/test_mellum_decode.py (a file of
its own so that the two run on two workers).

Tolerances, each with its reason:
- TURN (1e-6, float32): the layer's turn and the reference's are the same
  products of the same float32 cos and sin, summed in the same order.
- FLASH (2e-6, float32): the windowed kernel's online softmax over key blocks
  against one masked softmax.
- ROWS (2e-6, float32): the row-major kernel sums a score's 128 products on
  the MXU's order and the masked row down another: the outputs agree to
  float32 rounding, not bit for bit; both slabs ARE equal bit for bit (a
  copy, no arithmetic, the token's tile rewritten as read).
"""
import dataclasses
import importlib
import json
import math
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import mellum2 as ref
from deeplearning4j_tpu.nn.conf.layers import (MixtureOfExpertsLayer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.layers.feedforward import \
    MixtureOfExpertsLayerModule
from deeplearning4j_tpu.nn.layers.recurrent import (SelfAttentionLayerModule,
                                                    rope_frequencies,
                                                    rope_half, yarn_ramp)
from deeplearning4j_tpu.parallel.ring_attention import attention_reference

# the module, not the function of the same name the package re-exports
fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

TURN, FLASH, ROWS = 1e-6, 2e-6, 2e-6
VOCAB, D_MODEL, HEADS, WINDOW = 96, 144, 2, 8
CONFIG = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
                     / "configs" / "mellum2.json").read_text())
YARN = CONFIG["args"]["yarn"]


# ------------------------------------------------------------------- rotary
def test_yarn_ramp_is_the_formulas_18_to_35():
    """low = floor(c(32)), high = ceil(c(1)) with c(r) = 128 ln(8192 / (2 pi
    r)) / (2 ln 500000): worked out here, not copied."""
    c = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) \
        / (2 * math.log(500000))
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    assert 18 < c(32) < 19 and 34 < c(1) < 35
    assert yarn_ramp(128, 500000.0, YARN) == (18, 35) == ref.yarn_range()


@pytest.mark.parametrize("full", [False, True], ids=["plain", "yarn"])
def test_rotary_frequencies_and_turn_are_the_references(full):
    f, a = rope_frequencies(128, CONFIG["args"]["rope_theta"],
                            YARN if full else None)
    want_f, want_a = ref.rope_table(full)
    np.testing.assert_array_equal(f, want_f)
    assert a == want_a == (YARN["attention_factor"] if full else 1.0)
    e = 500000.0 ** (-np.arange(64) / 64)
    if full:    # theta's own up to channel 18, theta's / 16 from 35 on
        np.testing.assert_allclose(f[:19], e[:19], rtol=1e-6)
        np.testing.assert_allclose(f[35:], e[35:] / 16, rtol=1e-6)
        assert np.all(np.diff(f) < 0)
    else:
        np.testing.assert_allclose(f, e, rtol=1e-6)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 50, 3, 128),
                    jnp.float32)
    pos = jnp.arange(50, dtype=jnp.int32)[None]
    got = rope_half(x, pos, f, a)
    np.testing.assert_allclose(got[0], ref.rotate(x[0], full), atol=TURN,
                               rtol=0)
    # a step's turn at a far position is that position's row of a sequence's
    far = jnp.asarray([[5000], [17]], jnp.int32)
    one = rope_half(x[:, :2].reshape(2, 1, 3, 128), far, f, a)
    seq = ref.rotate(jnp.broadcast_to(x[0, 0], (5001, 3, 128)), full)
    np.testing.assert_allclose(one[0, 0], seq[5000], atol=TURN * a, rtol=0)
    # the pairing is channel j with j + 64, and a score carries a^2
    q, k = x[0, 7, 0], x[0, 9, 0]
    turned = lambda t, p: np.asarray(rope_half(
        t.reshape(1, 1, 1, 128), jnp.asarray([[p]]), f, a), np.float64)[
        0, 0, 0]
    s1 = turned(q, 30) @ turned(k, 10)
    s2 = turned(q, 1030) @ turned(k, 1010)          # the distance, not p
    assert abs(s1 - s2) < 5e-3 * a * a
    assert abs(turned(q, 0) @ turned(k, 0) - a * a * float(q @ k)) < 1e-4


# -------------------------------------------------- the windowed flash forward
@pytest.mark.parametrize("window", [5, 37, 64, 100])
def test_windowed_flash_forward_is_the_masked_softmax(window):
    """T = 96 in query blocks of 16 and key blocks of 32: windows inside a
    block, across blocks, a multiple of the block and none of it, and longer
    than the sequence; with a key mask too. The blocks wholly below the
    window are skipped, and the result is the masked softmax's."""
    rng = np.random.RandomState(window)
    q, k, v = (jnp.asarray(rng.randn(2, 96, 2, 16), jnp.float32)
               for _ in range(3))
    i, j = np.arange(96)[:, None], np.arange(96)[None, :]
    seen = (j <= i) & (j > i - window)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    want = np.einsum("bhqk,bkhd->bqhd", p, v)
    got = fa.flash_attention(q, k, v, causal=True, window=window, block_q=16,
                             block_k=32, interpret=True)
    np.testing.assert_allclose(got, want, atol=FLASH, rtol=0)
    np.testing.assert_allclose(
        attention_reference(q, k, v, causal=True, window=window), want,
        atol=FLASH, rtol=0)
    from deeplearning4j_tpu.parallel.ring_attention import \
        blockwise_attention
    np.testing.assert_allclose(
        blockwise_attention(q, k, v, block_size=32, causal=True,
                            window=window), want, atol=FLASH, rtol=0)
    mask = jnp.asarray(np.arange(96) < 70, jnp.float32)[None]
    masked = fa.flash_attention(q, k, v, causal=True, window=window,
                                key_mask=mask, block_q=16, block_k=32,
                                interpret=True)
    np.testing.assert_allclose(masked[:, :70], want[:, :70], atol=FLASH,
                               rtol=0)
    with pytest.raises(AssertionError, match="causal"):
        fa.flash_attention(q, k, v, window=window, interpret=True)


# ------------------------------------------------ the decode kernel: half tiles
def decode_inputs(S, C, Hq, H, D, dtype, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), dtype)
    return (mk(S, 1, Hq, D), mk(S, C, H, D), mk(S, C, H, D), mk(S, 1, H, D),
            mk(S, 1, H, D))


@pytest.mark.parametrize("Hq,H", [(8, 1), (32, 4), (4, 2)],
                         ids=["8_on_1", "32_on_4", "4_on_2"])
def test_half_tile_rows_are_the_two_calls_they_replace(Hq, H):
    """Fewer K/V heads of 128 than a tile's 8 sublanes, the cache declared
    in whole tiles `[S, C H / 8, 8, 128]` (`tiled_rows`): the token's rows
    reach the cache with their tile — every position of the tile, first and
    last, on and off the key block's (64) boundaries — both slabs bit for
    bit `kv_append`'s, the output `flash_decode`'s on them (ROWS)."""
    S, C, D = 8, 256, 128
    q, k, v, kn, vn = decode_inputs(S, C, Hq, H, D, jnp.float32)
    pos = jnp.asarray([0, 1, 2, 7, 63, 64, 201, 255], jnp.int32)
    assert fa.tiled_rows(C, H, D) == C * H // 8
    tiles = lambda t: t.reshape(S, C * H // 8, 8, D)
    out, nk, nv = fa.flash_decode_append(q, tiles(k), tiles(v), kn, vn, pos,
                                         block_k=64, interpret=True)
    assert nk.shape == (S, C * H // 8, 8, D)
    k2, v2 = fa.kv_append(k, v, kn, vn, pos, interpret=True)
    np.testing.assert_array_equal(nk.reshape(k.shape), k2)
    np.testing.assert_array_equal(nv.reshape(v.shape), v2)
    want = fa.flash_decode(q, k2, v2, pos + 1, interpret=True)
    np.testing.assert_allclose(out, want, atol=ROWS, rtol=0)
    # the same kernel on the plain leaf (interpreted, any H): the same rows
    out2, nk2, _ = fa.flash_decode_append(q, k, v, kn, vn, pos, block_k=64,
                                          interpret=True)
    np.testing.assert_array_equal(nk2, k2)
    np.testing.assert_allclose(out2, out, atol=ROWS, rtol=0)


def test_which_leaves_are_declared_in_whole_tiles():
    """4 K/V heads of 128 are two positions a tile; 8 heads fill one, 4
    heads of 64 pack to 2 rows and go four positions a tile (since PR 49;
    16 of them fill tiles packed and stay `packed_rows`'), 3 heads divide
    no tile, a model axis splits heads a tile would mix, and an odd count of
    positions leaves half a tile."""
    assert fa.tiled_rows(1024, 4, 128) == 512
    assert fa.tiled_rows(6144, 4, 128) == 3072
    assert fa.tiled_rows(1024, 1, 256) == 128
    assert fa.tiled_rows(1024, 8, 128) is None
    assert fa.tiled_rows(1024, 4, 64) == 256
    assert fa.tiled_rows(1024, 16, 64) is None
    assert fa.tiled_rows(1024, 3, 128) is None
    assert fa.tiled_rows(1024, 4, 128, shards=2) is None
    assert fa.tiled_rows(1023, 4, 128) is None
    # compiled, the row-major kernel takes rows that fill tiles, and rows
    # that divide one only on a leaf declared in whole tiles
    assert fa._rows_block(6144, 4, 128, 2, 1024, False, tiled=True) == 256
    assert fa._rows_block(1024, 1, 128, 2, 1024, False, tiled=True) == 256
    assert fa._rows_block(6144, 4, 128, 2, 1024, False) is None
    assert fa._rows_block(1024, 16, 128, 2, 1024, False) == 256
    assert fa._rows_block(1024, 3, 128, 2, 1024, False, tiled=True) is None
    assert fa._rows_block(1024, 12, 128, 2, 1024, False) is None


# --------------------------------------------------- the decode kernel: a ring
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, ROWS),
                                        (jnp.bfloat16, 2 ** -6)],
                         ids=["float32", "bfloat16"])
def test_window_step_through_a_ring_that_has_wrapped(dtype, atol):
    """32 query heads on 4 K/V heads of 128, a ring of 1,024 positions in
    blocks of 256, declared in whole tiles: slots whose ring has not filled
    (positions 0, 500, 1,023: the last one fills it), has just wrapped
    (1,024) and has wrapped several times (2,600 and 5,119, the last place
    of the ring) against the masked row over each slot's min(pos + 1, 1,024)
    newest positions; the ring afterwards holds the token at pos % 1,024 and
    every other row as it was. (bfloat16 rows multiply on the MXU in
    bfloat16: one ulp of an output of order 1.)"""
    R, Hq, H, D = 1024, 32, 4, 128
    poses = [0, 500, 1023, 1024, 2600, 5119] if dtype == jnp.float32 \
        else [700, 1024, 5119]
    S = len(poses)
    rng = np.random.RandomState(1)
    hist_k = jnp.asarray(rng.randn(S, 5120, H, D), dtype)   # position p
    hist_v = jnp.asarray(rng.randn(S, 5120, H, D), dtype)
    q = jnp.asarray(rng.randn(S, 1, Hq, D), dtype)
    ring_k, ring_v = np.zeros((2, S, R, H, D), np.float32)
    for s, p in enumerate(poses):           # what the steps before left
        for src, ring in ((hist_k, ring_k), (hist_v, ring_v)):
            old = np.arange(max(0, p - R), p)
            ring[s, old % R] = np.asarray(src[s, old], np.float32)
    ring_k, ring_v = (jnp.asarray(r, dtype) for r in (ring_k, ring_v))
    pos = jnp.asarray(poses, jnp.int32)
    kn = jnp.stack([hist_k[s, p] for s, p in enumerate(poses)])[:, None]
    vn = jnp.stack([hist_v[s, p] for s, p in enumerate(poses)])[:, None]
    tiles = lambda t: t.reshape(S, R * H // 8, 8, D)
    out, nk, nv = fa.flash_decode_append(q, tiles(ring_k), tiles(ring_v), kn,
                                         vn, pos, ring=True, interpret=True)
    f32 = lambda a: np.asarray(a, np.float32)
    for s, p in enumerate(poses):
        seen = np.arange(max(0, p + 1 - R), p + 1)      # the newest 1,024
        want = fa._decode_reference(
            q[s:s + 1], hist_k[s:s + 1, seen], hist_v[s:s + 1, seen],
            jnp.asarray([len(seen)]), D ** -0.5)
        np.testing.assert_allclose(f32(out[s]), f32(want[0]), atol=atol,
                                   rtol=0)
        for new, ring, src in ((nk, ring_k, hist_k), (nv, ring_v, hist_v)):
            rows = f32(new).reshape(S, R, H, D)[s]
            np.testing.assert_array_equal(rows[p % R], f32(src[s, p]))
            rest = np.arange(R) != p % R
            np.testing.assert_array_equal(rows[rest], f32(ring[s])[rest])
    # the two references behind `use_pallas=False` say the same
    out0, k0, _ = fa.flash_decode_append(q, ring_k, ring_v, kn, vn, pos,
                                         ring=True, use_pallas=False)
    np.testing.assert_allclose(f32(out), f32(out0), atol=atol, rtol=0)
    np.testing.assert_array_equal(f32(nk).reshape(k0.shape), f32(k0))


def test_a_ring_no_kernel_reads_gives_way_to_the_two_calls():
    """head_dim 32 is stored positions-minor: a ring of it is `kv_append` at
    pos % ring + `flash_decode` over min(pos + 1, ring), counted."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    counter = get_registry().counter("pallas_fallback_total", "")
    q, k, v, kn, vn = decode_inputs(2, 16, 4, 2, 32, jnp.float32)
    pos = jnp.asarray([3, 40], jnp.int32)
    before = counter.get(kernel="flash_decode",
                         path="kv_append+flash_decode",
                         shape="C=16,D=32,interpret=True")
    out, nk, nv = fa.flash_decode_append(q, k, v, kn, vn, pos, ring=True,
                                         interpret=True)
    assert counter.get(kernel="flash_decode", path="kv_append+flash_decode",
                       shape="C=16,D=32,interpret=True") == before + 1
    k2, v2 = fa._append_reference(k, v, kn, vn, pos % 16)
    np.testing.assert_array_equal(nk, k2)
    np.testing.assert_allclose(out, fa._decode_reference(
        q, k2, v2, jnp.asarray([4, 16]), 32 ** -0.5), atol=ROWS, rtol=0)


# ------------------------------------------------- the attention layer alone
@pytest.mark.parametrize("full", [False, True], ids=["sliding", "full_yarn"])
def test_attention_layer_is_the_references_half(full):
    """SelfAttentionLayer(rope_theta=, rope_yarn=, window=) at d_model 144
    (two query heads on one K/V head of 128) against the reference's
    attention half; without the positions, or the window, another result."""
    conf = SelfAttentionLayer(
        n_in=D_MODEL, n_out=D_MODEL, n_heads=HEADS, n_kv_heads=1,
        head_dim=ref.HEAD_DIM, causal=True, rope_theta=ref.ROPE_THETA,
        rope_yarn=YARN if full else None, window=None if full else WINDOW,
        weight_init="xavier", activation="identity")
    mod = SelfAttentionLayerModule(conf)
    drawn, _, _ = mod.init(jax.random.PRNGKey(0), None, jnp.float32)
    params = ref.init_params(jax.random.PRNGKey(1), VOCAB, D_MODEL, 1)[
        "b0_attn"]
    assert {k: v.shape for k, v in drawn.items()} \
        == {k: v.shape for k, v in params.items()}
    params = {k: v.astype(jnp.float32) * 4 for k, v in params.items()}
    h = jnp.asarray(np.random.RandomState(0).randn(29, D_MODEL), jnp.float32)
    want = ref._attention_half(h, {"gamma": jnp.ones(D_MODEL)}, params,
                               dtype="float32", rope_dtype="float32",
                               window=None if full else WINDOW) - h
    x = ref._rms(h, 1.0)[None]
    got = mod.forward(params, {}, x)[0][0]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert mod.decode_rewindable is full
    for key in ("rope_theta",) + (() if full else ("window",)):
        other = dataclasses.replace(conf, **{key: None})
        moved = SelfAttentionLayerModule(other).forward(params, {}, x)[0][0]
        assert np.abs(np.asarray(moved - want)).max() > 1e-2, key


# ------------------------------------------------------------------ router
def test_router_is_the_references_softmax_top_8_renormalised():
    E, d = ref.N_EXPERTS, 16
    conf = MixtureOfExpertsLayer(
        n_in=d, n_out=d, n_experts=E, top_k=ref.EXPERTS_PER_TOKEN, gated=True,
        n_hidden=8, score_function="softmax", activation="identity")
    mod = MixtureOfExpertsLayerModule(conf)
    rng = np.random.RandomState(0)
    params = {"Wg": jnp.asarray(rng.randn(d, E))}
    x = jnp.asarray(rng.randn(40, d))
    experts, gates = mod.route(params, x)
    dense = np.asarray(jnp.sum(gates[:, :, None] * (
        experts[:, :, None] == jnp.arange(E)), axis=1))
    np.testing.assert_allclose(dense, ref.gates_of(x, params["Wg"], x.dtype),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)


def test_four_shares_and_the_attention_half_once_are_the_uncut_layer():
    """The deployment's four chips: each computes the attention half alike
    and its own 16 experts' part of the routed sum, the gates not
    renormalised over the held. The program's four shares, the attention
    half counted once, add up to the reference's layer with all 64."""
    d = D_MODEL
    whole = ref.init_params(jax.random.PRNGKey(5), VOCAB, d, 1,
                            experts_held=64)
    f32 = lambda t: {k: v.astype(jnp.float32) * 3 for k, v in t.items()}
    attn, moe = f32(whole["b0_attn"]), f32(whole["b0_moe"])
    ones = {"gamma": jnp.ones(d)}
    h = jnp.asarray(np.random.RandomState(2).randn(33, d), jnp.float32)
    mid = ref._attention_half(h, ones, attn, dtype="float32",
                              rope_dtype="float32", window=WINDOW)
    want = ref._routed_half(mid, ones, moe, dtype="float32", first_expert=0,
                            router_dtype="float32")
    x = ref._rms(mid, 1.0)
    total = mid
    for first in (0, 16, 32, 48):
        conf = MixtureOfExpertsLayer(
            n_in=d, n_out=d, n_experts=64, top_k=8, gated=True,
            n_hidden=ref.EXPERT_HIDDEN, experts_held=16, first_expert=first,
            score_function="softmax", activation="identity")
        share = {"Wg": moe["Wg"], "W1": moe["W1"][first:first + 16],
                 "W2": moe["W2"][first:first + 16]}
        part = MixtureOfExpertsLayerModule(conf).forward(share, {}, x)[0]
        np.testing.assert_allclose(
            mid + part, ref._routed_half(
                mid, ones, share, dtype="float32", first_expert=first,
                router_dtype="float32"), atol=2e-5, rtol=0)
        total = total + part
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=0)
    assert np.abs(np.asarray(want - mid)).max() > 1e-2


def test_the_references_constants_are_the_configuration_files():
    a, pub = CONFIG["args"], CONFIG["published"]
    assert ref.heads_of(a["d_model"]) == a["n_heads"] \
        == pub["num_attention_heads"]
    assert ref.kv_heads_of(a["d_model"]) == a["n_kv_heads"] \
        == pub["num_key_value_heads"]
    assert ref.Q_PER_KV * pub["num_key_value_heads"] \
        == pub["num_attention_heads"]
    assert ref.HEAD_DIM == a["head_dim"] == pub["head_dim"]
    assert ref.WINDOW == a["window"] == pub["sliding_window"] == 1024
    assert ref.PERIOD == a["full_interval"] + 1
    assert pub["layer_types"] == [
        "full_attention" if ref.is_full(i) else "sliding_attention"
        for i in range(pub["num_hidden_layers"])]
    assert set(pub["mlp_layer_types"]) == {"sparse"} \
        and len(pub["mlp_layer_types"]) == pub["num_hidden_layers"]
    rope = pub["rope_parameters"]
    assert rope["sliding_attention"] == {"rope_type": "default",
                                         "rope_theta": ref.ROPE_THETA}
    assert a["rope_theta"] == ref.ROPE_THETA
    assert rope["full_attention"] == dict(a["yarn"], rope_type="yarn",
                                          rope_theta=ref.ROPE_THETA)
    assert (ref.YARN_FACTOR, ref.YARN_ORIGINAL, ref.YARN_BETA_FAST,
            ref.YARN_BETA_SLOW, ref.YARN_ATTENTION_FACTOR) == tuple(
        a["yarn"][k] for k in ("factor", "original_max_position_embeddings",
                               "beta_fast", "beta_slow", "attention_factor"))
    assert (ref.N_EXPERTS, ref.EXPERTS_PER_TOKEN, ref.EXPERT_HIDDEN,
            ref.RMS_EPS) == (a["n_experts"], a["experts_per_token"],
                             a["expert_hidden"], a["rms_norm_eps"]) == (
        pub["num_experts"], pub["num_experts_per_tok"],
        pub["moe_intermediate_size"], pub["rms_norm_eps"])
    assert pub["norm_topk_prob"] is True \
        and pub["tie_word_embeddings"] is False \
        and pub["attention_bias"] is False and pub["hidden_act"] == "silu"
    assert (ref.EXPERTS_HELD, ref.FIRST_EXPERT) == (a["experts_held"],
                                                    a["first_expert"])
    assert a["d_model"] == pub["hidden_size"] == 2304
    # the cut is the one stated; every width and the depth as published
    assert {k for k, v in pub.items() if CONFIG[k] != v} \
        == set(CONFIG["reduced"]) == {"num_experts", "vocab_size"}
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (a["n_layers"], a["experts_held"],
                                      a["vocab_size"]) == (28, 16, 24576)
    assert a["vocab_size"] * 4 == pub["vocab_size"] \
        and a["experts_held"] * 4 == pub["num_experts"] \
        and "4 chips share every layer" in CONFIG["deployment"]
    assert CONFIG["control_precision"] == "float8"
    assert set(CONFIG["assumed"]) >= {"window", "qk_norm", "router",
                                      "layer_types", "dtypes",
                                      "initialisation"}
    assert len(CONFIG["departures"]) >= 5
    # P by the program's own tree: the deployment paragraph states it
    per_layer = 2304 * (32 * 128 + 2 * 4 * 128) + 32 * 128 * 2304 + 2304 \
        + 2304 * 64 + 16 * 3 * 2304 * 896 + 2 * 2304
    P = 28 * per_layer + 2 * 24576 * 2304 + 2304 + 2304
    assert P == 3_486_647_808 and f"{P:,}" in CONFIG["deployment"]
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        rows = [json.loads(line) for line in catalog.read_text().splitlines()]
        row = {r["name"]: r for r in rows}["Mellum2-12B-A2.5B-Instruct"]
        assert pub == row["config"] and CONFIG["source"] == row["source_url"]
