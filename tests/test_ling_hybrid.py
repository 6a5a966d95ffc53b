"""The three mechanisms the `ling3_flash` configuration brought — Kimi Delta
Attention (nn/layers/kda.py, kernels/kda_step.py), latent attention with
rotary positions (nn/layers/mla.py, kernels/mla_decode.py) and sigmoid,
bias-corrected, group-limited routing (MixtureOfExpertsLayer) — each against
benchmarks/reference/ling3_flash.py at tiny widths, seeded, on the CPU
(float64 rows under conftest's x64 unless said), and the whole model through
`DecodeEngine` against the reference's one-pass logits.

Tolerances, each with its reason:
- LOGP (2e-5 on log-probabilities, float32 parameters): the program and the
  reference order their float32 sums differently (chunked against
  sequential, absorbed against plain, rows sorted by expert against the
  masked sum); measured 2e-6. The delta-rule state or the router computed in
  bfloat16 moves the same numbers by more than a hundred times that
  (`test_state_or_router_in_bfloat16_fails_the_tolerance`).
- RULE (1e-9, float64 inputs): the chunked form and the scan are the same
  arithmetic in another order; nothing else differs.
"""
import importlib
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import ling3_flash as ref
from deeplearning4j_tpu.decode.engine import DecodeEngine
from deeplearning4j_tpu.nn.conf.layers import (LatentAttentionLayer,
                                               MixtureOfExpertsLayer)
from deeplearning4j_tpu.nn.layers.feedforward import \
    MixtureOfExpertsLayerModule
from deeplearning4j_tpu.nn.layers.kda import kda_chunked
from deeplearning4j_tpu.nn.layers.mla import LatentAttentionLayerModule
from deeplearning4j_tpu.zoo.models import ling_hybrid_lm

# the modules, not the functions of the same names the package re-exports
ks = importlib.import_module("deeplearning4j_tpu.kernels.kda_step")
md = importlib.import_module("deeplearning4j_tpu.kernels.mla_decode")

LOGP, RULE = 2e-5, 1e-9
VOCAB, D_MODEL, LAYERS, HEADS = 96, 160, 6, 2
CONFIG = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
                     / "configs" / "ling3_flash.json").read_text())


# ------------------------------------------------------------ the delta rule
def rule_inputs(T, H=2, D=8, seed=0, g_all=None):
    rng = np.random.RandomState(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.randn(T, H, D)) * D ** -0.5
    k, v = unit(rng.randn(T, H, D)), rng.randn(T, H, D)
    g = -5.0 * rng.rand(T, H, D) if g_all is None \
        else np.full((T, H, D), g_all)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, rng.rand(T, H)))


# (T, chunk): lengths on and off the chunk's boundary at chunk 8 (one block a
# chunk: no sub-block is entered); then chunks that ARE cut into sub-blocks —
# 64 and 32 into blocks of 16, 24 into blocks of 8 — whole, padded, several
LENGTHS = [(T, 8) for T in (1, 7, 8, 9, 16, 23, 64)] \
    + [(64, 64), (100, 64), (192, 64), (100, 32), (100, 24)]


@pytest.mark.parametrize("g_all", [None, -5.0], ids=["drawn", "at_the_bound"])
@pytest.mark.parametrize("T,chunk", LENGTHS)
def test_chunked_kda_is_the_sequential_scan(T, chunk, g_all):
    """With every g at -5, the bounded gate's lower bound, a sub-block of 16
    sums to -80, a chunk of 8 to -40 and one of 64 to -320, where a ratio
    of two exponentials would be 0 / 0 in float32: inside a sub-block and
    across a sub-block boundary every exponent is still a difference."""
    q, k, v, g, beta = rule_inputs(T, g_all=g_all)
    want, S = ref.kda_scan(q, k, v, g, beta, jnp.zeros((2, 8, 8)))
    got, last = kda_chunked(*(a[None] for a in (q, k, v, g, beta)), chunk)
    np.testing.assert_allclose(got[0], want, atol=RULE, rtol=0)
    np.testing.assert_allclose(last[0], S, atol=RULE, rtol=0)
    f32 = lambda a: a.astype(jnp.float32)[None]
    got32, last32 = kda_chunked(*(f32(a) for a in (q, k, v, g, beta)), chunk)
    assert np.isfinite(np.asarray(got32)).all()
    assert np.isfinite(np.asarray(last32)).all()
    np.testing.assert_allclose(got32[0], want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("T,real,chunk", [(16, 11, 8), (128, 70, 64),
                                          (128, 64, 64), (96, 41, 32)])
def test_masked_positions_leave_the_state_alone(T, real, chunk):
    """g = 0 and beta = 0 behind the last real token, whatever k and v hold
    there: the state is the real tokens', bucket or not — the tail inside a
    sub-block, on a chunk's boundary, and over whole sub-blocks."""
    q, k, v, g, beta = rule_inputs(T, seed=1)
    m = (jnp.arange(T) < real).astype(g.dtype)
    _, want = ref.kda_scan(*(a[:real] for a in (q, k, v, g, beta)),
                           jnp.zeros((2, 8, 8)))
    _, last = kda_chunked(q[None], k[None], v[None],
                          (g * m[:, None, None])[None], (beta * m[:, None])[None],
                          chunk)
    np.testing.assert_allclose(last[0], want, atol=RULE, rtol=0)


@pytest.mark.parametrize("T,chunk", [(40, 32), (70, 64), (30, 24), (20, 8)])
def test_chunked_kda_differentiates_to_the_sequential_scans_gradient(T, chunk):
    """Training's backward is autodiff through the chunked form: the
    gradient of a scalar of o and of the last state, in every input, is the
    sequential rule's."""
    inputs = rule_inputs(T, seed=3)
    rng = np.random.RandomState(4)
    wo, ws = jnp.asarray(rng.randn(T, 2, 8)), jnp.asarray(rng.randn(2, 8, 8))

    def scalar(rule):
        def f(*a):
            o, last = rule(*a)
            return jnp.sum(o * wo) + jnp.sum(last * ws)
        return jax.grad(f, argnums=(0, 1, 2, 3, 4))(*inputs)

    want = scalar(lambda *a: ref.kda_scan(*a, jnp.zeros((2, 8, 8))))
    got = scalar(lambda *a: tuple(
        r[0] for r in kda_chunked(*(x[None] for x in a), chunk)))
    for w, g_ in zip(want, got):
        assert float(jnp.abs(w).max()) > 1e-3
        np.testing.assert_allclose(g_, w, atol=1e-8, rtol=0)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernel"])
def test_kda_step_is_one_position_of_the_scan(use_pallas):
    S, H, D = 3, 4, 16
    rng = np.random.RandomState(2)
    state = jnp.asarray(rng.randn(S, H, D, D), jnp.float32)
    q, k, v, g, beta = (jnp.stack(a).astype(jnp.float32) for a in zip(*(
        [x[0] for x in rule_inputs(1, H, D, seed=s)] for s in range(S))))
    new, o = ks.kda_step(state, jnp.exp(g), k, q, beta, v,
                         use_pallas=use_pallas, interpret=True)
    for s in range(S):
        want_o, want_S = ref.kda_scan(q[s:s + 1], k[s:s + 1], v[s:s + 1],
                                      g[s:s + 1], beta[s:s + 1], state[s])
        np.testing.assert_allclose(new[s], want_S, atol=1e-5, rtol=0)
        np.testing.assert_allclose(o[s], want_o[0], atol=1e-5, rtol=0)


def test_kda_step_falls_back_where_a_slot_is_no_tile():
    from deeplearning4j_tpu.telemetry.registry import get_registry
    assert ks._kda_tiles(32, 128, 128, 4, False) == 32   # one group a slot
    assert ks._kda_tiles(64, 128, 128, 4, False) == 32   # 4 MB a slot: two
    assert not ks._kda_tiles(2, 8, 8, 4, False)
    counter = get_registry().counter("pallas_fallback_total", "")
    label = dict(kernel="kda_step", path="jnp",
                 shape="H=2,Dk=8,Dv=8,interpret=False")
    before = counter.get(**label)
    z = jnp.zeros((1, 2, 8), jnp.float32)
    ks.kda_step(jnp.zeros((1, 2, 8, 8), jnp.float32), z, z, z, z[..., 0], z,
                interpret=False)
    assert counter.get(**label) == before + 1


# ------------------------------------------------------- latent attention
def mla_module(**over):
    conf = LatentAttentionLayer(n_in=32, n_out=32, n_heads=2, kv_lora_rank=16,
                                qk_nope_head_dim=8, qk_rope_head_dim=4,
                                v_head_dim=8, rope_theta=100.0, weight_init="xavier",
                                activation="identity", **over)
    mod = LatentAttentionLayerModule(conf)
    params, _, _ = mod.init(jax.random.PRNGKey(0), None, jnp.float64)
    return mod, jax.tree_util.tree_map(lambda a: a * 3, params)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernel"])
def test_absorbed_step_is_the_plain_form_with_rotary_at_ctx_pos(use_pallas):
    """Two slots prefilled to different lengths, then one step each: the
    absorbed step's row is the plain form's last row over the longer
    sequence — so the rotary angle is the slot's own position."""
    from types import SimpleNamespace
    mod, params = mla_module(use_pallas=use_pallas)
    geom = SimpleNamespace(slots=2, capacity=16, dtype=jnp.float64,
                           paged=False)
    entry = {k: jnp.zeros(leaf.shape, leaf.dtype)
             for k, leaf in mod.decode_entry(geom).items()}
    assert entry["latent"].shape == (2, 16, 128)      # 20 -> one lane tile
    x = jnp.asarray(np.random.RandomState(1).randn(2, 9, 32))
    lengths = (5, 8)
    for slot, n in enumerate(lengths):
        ctx = SimpleNamespace(mask=(jnp.arange(8) < n)[None].astype(x.dtype),
                              slot=jnp.int32(slot), length=jnp.int32(n))
        y, entry = mod.decode_prefill(params, {}, x[slot:slot + 1, :8], entry,
                                      ctx)
        np.testing.assert_allclose(
            y[0, :n], mod.forward(params, {}, x[slot:slot + 1, :n])[0][0],
            atol=1e-9)
    pos = jnp.asarray(lengths, jnp.int32)
    step_in = jnp.stack([x[0, 5], x[1, 8]])[:, None]
    y, entry = mod.decode_step(params, {}, step_in, entry,
                               SimpleNamespace(pos=pos, kv_valid=pos + 1))
    for slot, n in enumerate(lengths):
        seq = jnp.concatenate([x[slot, :n], step_in[slot]])[None]
        want = mod.forward(params, {}, seq)[0][0, -1]
        # float32 by the layer's own statement, whatever the rows' dtype:
        # the rotary angles and the scores
        np.testing.assert_allclose(y[slot, 0], want, atol=5e-6)
        other = mod.forward(params, {}, jnp.concatenate(
            [jnp.zeros((1, 1, 32)), seq], axis=1)[:, :-1])[0][0, -1]
        assert np.abs(np.asarray(want - other)).max() > 1e-3
    # a verify window at its start is the plain form too
    win, _ = mod.decode_verify(params, {}, x[:1, 5:8], entry, SimpleNamespace(
        slot=jnp.int32(0), start=jnp.int32(5)))
    np.testing.assert_allclose(win[0], mod.forward(params, {}, x[:1, :8])[0]
                               [0, 5:8], atol=5e-6)


def test_mla_layer_is_the_references():
    """The reference's MLA half at the reference's own widths (latent 512,
    rotary 64, theta 6e6), float32 parameters."""
    a = CONFIG["args"]
    conf = LatentAttentionLayer(
        n_in=D_MODEL, n_out=D_MODEL, n_heads=HEADS,
        kv_lora_rank=a["kv_lora_rank"],
        qk_nope_head_dim=a["qk_nope_head_dim"],
        qk_rope_head_dim=a["qk_rope_head_dim"], v_head_dim=a["v_head_dim"],
        rope_theta=a["rope_theta"], eps=a["rms_norm_eps"],
        activation="identity")
    params = ref.init_params(jax.random.PRNGKey(1), VOCAB, D_MODEL, 6,
                             384)["b5_mla"]
    params = {k: v.astype(jnp.float32) * 4 for k, v in params.items()}
    h = jnp.asarray(np.random.RandomState(0).randn(19, D_MODEL), jnp.float32)
    want = ref._mla_half(h, {"gamma": jnp.ones(D_MODEL)}, params,
                         dtype="float32") - h
    x = ref._rms(h, 1.0)[None]
    got = LatentAttentionLayerModule(conf).forward(params, {}, x)[0][0]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_mla_decode_and_latent_append_interpreted_are_their_plain_forms():
    rng = np.random.RandomState(3)
    S, C, W, H, R = 4, 1024, 24, 4, 16      # four key blocks of 256 a slot
    lat = jnp.asarray(rng.randn(S, C, W), jnp.float32)
    q = jnp.asarray(rng.randn(S, H, W), jnp.float32)
    lengths = jnp.asarray([1, 257, 700, 1024], jnp.int32)
    got = md.mla_decode(q, lat, lengths, rank=R, interpret=True)
    np.testing.assert_allclose(got, md._mla_reference(q, lat, lengths, R),
                               atol=1e-5)
    poisoned = lat.at[1, 257:].set(1e9)         # past the length: not read
    np.testing.assert_allclose(
        md.mla_decode(q, poisoned, lengths, rank=R, interpret=True)[1],
        got[1], atol=1e-5)
    rows = jnp.asarray(rng.randn(S, W), jnp.float32)
    pos = jnp.asarray([0, 17, 699, 1023], jnp.int32)
    np.testing.assert_array_equal(
        md.latent_append(lat, rows, pos, interpret=True),
        md._latent_append_reference(lat, rows, pos))


# (lengths of the slots of one call): the edges of the ring of copies that
# `mla_decode` keeps in flight over the call's (slot, live block) pairs
PIPELINE_EDGES = {
    "one_live_block": [100, 900, 30, 600],
    "fewer_than_depth_in_a_row": [1, 200, 256, 17, 1024, 3, 2, 700],
    "length_0": [0, 300, 0],                  # every block of the slot live
    "block_edges": [256, 512, 257, 511, 255],
    "one_slot": [700],
    "last_slot_longest": [10, 300, 1024],     # nothing follows its blocks
}


@pytest.mark.parametrize("heads", [32, 64])
@pytest.mark.parametrize("lengths", list(PIPELINE_EDGES.values()),
                         ids=list(PIPELINE_EDGES))
def test_mla_decode_ring_of_copies_is_the_one_ahead_scheme_bit_for_bit(
        lengths, heads):
    """At every depth the blocks are consumed in the order of depth 1 — one
    copy ahead, the kernel's scheme before PR 51 — so the output is that
    one's bit for bit, and the reference's within rounding; rows in blocks
    wholly past a slot's length are never waited for nor read (NaN there
    changes nothing)."""
    rng = np.random.RandomState(len(lengths) + heads)
    S, C, W, R, block = len(lengths), 1024, 640, 512, 256
    lat = jnp.asarray(rng.randn(S, C, W) * 0.5, jnp.float32)
    q = jnp.asarray(rng.randn(S, heads, W) * 0.1, jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    one_ahead = md._mla_call(q, lat, lens, R, block, True, 1)
    np.testing.assert_allclose(one_ahead, md._mla_reference(q, lat, lens, R),
                               atol=1e-5)
    live = np.where(np.asarray(lengths) > 0,
                    -(-np.asarray(lengths) // block) * block, C)
    poisoned = jnp.where(jnp.arange(C)[None, :, None] >= live[:, None, None],
                         jnp.nan, lat)
    assert md._DEPTH > 1
    for depth in sorted({2, 3, 4, md._DEPTH}):
        np.testing.assert_array_equal(
            md._mla_call(q, lat, lens, R, block, True, depth), one_ahead)
    np.testing.assert_array_equal(
        md._mla_call(q, poisoned, lens, R, block, True, md._DEPTH), one_ahead)
    np.testing.assert_array_equal(
        md.mla_decode(q, lat, lens, rank=R, interpret=True), one_ahead)
    # which plan ran, beside `pallas_fallback_total`
    from deeplearning4j_tpu.telemetry.registry import get_registry
    assert get_registry().get("mla_decode_block").get(
        C=C, W=W, depth=md._DEPTH) == block


# ------------------------------------------------------------------ router
def router(E=64, k=8, d=16, seed=0, **over):
    conf = MixtureOfExpertsLayer(
        n_in=d, n_out=d, n_experts=E, top_k=k, gated=True, n_hidden=8,
        score_function="sigmoid", n_groups=8, topk_groups=4,
        routed_scaling=2.5, activation="identity", **over)
    mod = MixtureOfExpertsLayerModule(conf)
    rng = np.random.RandomState(seed)
    params = {"Wg": jnp.asarray(rng.randn(d, E)),
              "route_bias": jnp.asarray(rng.randn(E) * 0.01)}
    return mod, params, jnp.asarray(rng.randn(40, d))


def dense_gates(experts, gates, E):
    return np.asarray(jnp.sum(gates[:, :, None] * (
        experts[:, :, None] == jnp.arange(E)), axis=1))


def test_router_is_the_references_and_keeps_to_four_groups():
    mod, params, x = router()
    experts, gates = mod.route(params, x)
    assert gates.dtype == jnp.float32 or gates.dtype == jnp.float64
    np.testing.assert_allclose(
        dense_gates(experts, gates, 64),
        ref.gates_of(x, params["Wg"], params["route_bias"], x.dtype),
        atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, atol=1e-6)
    groups = np.asarray(experts) // 8
    assert max(len(set(row)) for row in groups) <= 4
    assert len({g for row in groups for g in row}) == 8   # over the tokens


def test_bias_moves_the_choice_and_not_the_gates():
    mod, params, x = router()
    experts, gates = mod.route(params, x)
    low = int(np.argmin(np.bincount(np.asarray(experts).ravel(),
                                    minlength=64)))
    pushed = dict(params, route_bias=params["route_bias"].at[low].add(10.0))
    experts2, gates2 = mod.route(pushed, x)
    assert (np.asarray(experts2) == low).any(axis=1).all()
    s = np.asarray(jax.nn.sigmoid(x @ params["Wg"]))
    chosen = np.take_along_axis(s, np.asarray(experts2), axis=1)
    np.testing.assert_allclose(
        gates2, 2.5 * chosen / chosen.sum(-1, keepdims=True), atol=1e-6)


# ---------------------------------------------------- the model, end to end
def place(net, params):
    assert {k: sorted(v) for k, v in params.items()} \
        == {k: sorted(v) for k, v in net.params.items()}
    net.params = {n: {k: jnp.asarray(params[n][k], old.dtype)
                      for k, old in leaves.items()}
                  for n, leaves in net.params.items()}


def log_softmax(z):
    z = np.asarray(z, np.float64)
    return z - np.log(np.sum(np.exp(z), axis=-1, keepdims=True))


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(jax.random.PRNGKey(3), VOCAB, D_MODEL, LAYERS,
                           D_MODEL * CONFIG["args"]["ffn_mult"])


def tiny(weights, **over):
    """One period at d_model 160: two heads of the reference's own 128-wide
    KDA and MLA, 512 routed experts of which group 0 is held."""
    net = ling_hybrid_lm(vocab_size=VOCAB, d_model=D_MODEL, n_layers=LAYERS,
                         n_heads=HEADS, experts_held=ref.EXPERTS_HELD,
                         kda_chunk_size=8, **over).init()
    place(net, weights)
    return net


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernels"])
def test_one_period_output_is_the_references_logits(weights, use_pallas):
    net = tiny(weights, use_pallas=use_pallas)
    ids = np.random.RandomState(0).randint(0, VOCAB, 21)
    want = log_softmax(ref.logits(weights, jnp.asarray(ids), heads=HEADS,
                                  layers=LAYERS))
    probs = np.asarray(net.output(np.eye(VOCAB, dtype=np.float32)[ids][None]))
    np.testing.assert_allclose(np.log(probs[0]), want, atol=LOGP, rtol=0)
    other = log_softmax(ref.logits(weights, jnp.asarray(ids), heads=HEADS,
                                   layers=LAYERS, first_expert=64))
    assert np.abs(other - want).max() > 1e-3      # the share is in them


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernels"])
@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("n_prompt", [5, 16, 19],
                         ids=["padded", "bucket", "longer"])
def test_prefill_then_steps_are_the_references_one_pass(weights, paged,
                                                       n_prompt, use_pallas):
    """KDA, MLA, dense and expert blocks behind `DecodeEngine`: the prefill
    leaves the exact state, conv tails and latent rows of the prompt's last
    real token whatever the padding, and every step's row of probabilities
    is the reference's at that position."""
    net = tiny(weights, use_pallas=use_pallas)
    eng = DecodeEngine(net, slots=2, max_len=32,
                       **({"paged": True, "block_size": 8} if paged else {}))
    assert {k for e in eng._entries.values() for k in e} \
        == {"state", "conv", "latent"}
    ids = list(np.random.RandomState(n_prompt).randint(0, VOCAB, n_prompt + 6))
    want = log_softmax(ref.logits(weights, jnp.asarray(ids), heads=HEADS,
                                  layers=LAYERS))
    cache = eng.init_cache()
    cache, _, _ = eng.prefill(cache, 0, [1, 2, 3])      # a reused slot
    cache, _, probs = eng.prefill(cache, 0, ids[:n_prompt])
    rows = [np.asarray(eng.read_probs(probs))]
    for t in range(n_prompt, n_prompt + 5):
        cache, _, probs = eng.step(cache, np.asarray([ids[t], 0], np.int32))
        rows.append(np.asarray(eng.read_probs(probs[0])))
    np.testing.assert_allclose(np.log(np.stack(rows)),
                               want[n_prompt - 1:n_prompt + 5], atol=LOGP,
                               rtol=0)


def test_state_or_router_in_bfloat16_fails_the_tolerance(weights):
    """What the configuration states as float32: were the delta-rule state
    or the router computed in bfloat16, the logits would move by far more
    than LOGP allows."""
    ids = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, 40))
    run = lambda **how: log_softmax(ref.logits(
        weights, ids, heads=HEADS, layers=LAYERS, **how))
    want = run()
    assert np.abs(run(state_dtype="bfloat16") - want).max() > 100 * LOGP
    assert np.abs(run(router_dtype="bfloat16") - want).max() > 100 * LOGP


def test_cache_entries_are_counted_by_kind(weights):
    from deeplearning4j_tpu.telemetry.registry import get_registry
    eng = DecodeEngine(tiny(weights), slots=2, max_len=32)
    H, D = HEADS, ref.HEAD_DIM
    reg = get_registry()
    assert reg.get("decode_cache_state_bytes").get() == 5 * 2 * (
        H * D * D * 4 + 3 * 3 * H * D * 4)
    assert reg.get("decode_cache_kv_bytes").get() == 2 * 32 * 640 * 4
    assert eng._carries == {f"b{i}_kda" for i in range(5)}


def test_a_non_zero_swiglu_limit_is_refused():
    with pytest.raises(ValueError, match="swiglu limit"):
        ling_hybrid_lm(expert_swiglu_limits=[0, 0, 4])
    with pytest.raises(ValueError, match="swiglu limit"):
        ling_hybrid_lm(shared_swiglu_limits=[5])


def test_the_references_constants_are_the_configuration_files():
    a, pub = CONFIG["args"], CONFIG["published"]
    assert ref.heads_of(a["d_model"]) == a["n_heads"] \
        == pub["num_attention_heads"]
    assert (ref.HEAD_DIM, ref.D_CONV, ref.KDA_LOWER_BOUND) == (
        a["kda_head_dim"], a["kda_d_conv"], a["kda_lower_bound"]) == (
        pub["head_dim"], pub["short_conv_kernel_size"],
        pub["kda_lower_bound"])
    assert (ref.KV_LORA_RANK, ref.QK_NOPE_HEAD_DIM, ref.QK_ROPE_HEAD_DIM,
            ref.V_HEAD_DIM, ref.ROPE_THETA) == (
        a["kv_lora_rank"], a["qk_nope_head_dim"], a["qk_rope_head_dim"],
        a["v_head_dim"], a["rope_theta"]) == (
        pub["kv_lora_rank"], pub["qk_nope_head_dim"],
        pub["qk_rope_head_dim"], pub["v_head_dim"], pub["rope_theta"])
    assert (ref.LAYER_GROUP_SIZE, ref.FIRST_K_DENSE, ref.RMS_EPS) == (
        a["layer_group_size"], a["first_k_dense"], a["rms_norm_eps"]) == (
        pub["layer_group_size"], pub["first_k_dense_replace"],
        pub["rms_norm_eps"])
    assert (ref.N_EXPERTS, ref.N_GROUPS, ref.TOPK_GROUPS,
            ref.EXPERTS_PER_TOKEN, ref.ROUTED_SCALING, ref.EXPERT_HIDDEN,
            ref.SHARED_HIDDEN) == (
        a["n_experts"], a["n_groups"], a["topk_groups"],
        a["experts_per_token"], a["routed_scaling"], a["expert_hidden"],
        a["shared_hidden"]) == (
        pub["num_experts"], pub["n_group"], pub["topk_group"],
        pub["num_experts_per_tok"], pub["routed_scaling_factor"],
        pub["moe_intermediate_size"],
        pub["moe_shared_expert_intermediate_size"])
    assert (ref.EXPERTS_HELD, ref.FIRST_EXPERT) == (a["experts_held"],
                                                    a["first_expert"])
    assert (a["d_model"], int(a["d_model"] * a["ffn_mult"])) == (
        pub["hidden_size"], pub["intermediate_size"])
    # the cut is the one stated; every width as published
    assert {k for k, v in pub.items() if CONFIG[k] != v} \
        == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"], CONFIG["num_nextn_predict_layers"]) == (
        a["n_layers"], a["experts_held"], a["vocab_size"], 0) \
        == (6, 64, 19648, 0)
    assert a["vocab_size"] * 8 == pub["vocab_size"] \
        and a["experts_held"] * 8 == pub["num_experts"] \
        and "8 chips of a stage share every layer" in CONFIG["deployment"]
    assert CONFIG["expert_swiglu_limit_list"] == a["expert_swiglu_limits"] \
        == pub["expert_swiglu_limit_list"][:6] == [0] * 6
    assert CONFIG["share_expert_swiglu_limit_list"] \
        == a["shared_swiglu_limits"] \
        == pub["share_expert_swiglu_limit_list"][:6] == [0] * 6
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        rows = [json.loads(line) for line in catalog.read_text().splitlines()]
        row = {r["name"]: r for r in rows}["Ling-3.0-flash"]
        assert pub == row["config"] and CONFIG["source"] == row["source_url"]
