"""What the `solar_open2` configuration brought — KDA's published variants
(unbounded softplus decay, low-rank gates, beta up to 2: nn/layers/kda.py),
`kda_step` over head groups (kernels/kda_step.py), the row-major decode
kernel for head_dim 128 (kernels/flash_attention.py `_decode_rows_kernel`),
the output gate and `head_dim` of SelfAttentionLayer, and sigmoid routing in
one group — each against benchmarks/reference/solar_open2.py or the code it
replaces, at tiny widths, seeded, on the CPU (float64 rows under conftest's
x64 unless said), and the whole model through `DecodeEngine` against the
reference's one-pass logits.

Tolerances, each with its reason:
- LOGP (2e-5 on log-probabilities, float32 parameters): the program and the
  reference order their float32 sums differently (chunked against
  sequential, flash blocks against one softmax, rows sorted by expert
  against the masked sum); measured 1.3e-6. The delta-rule state or the
  router computed in bfloat16 moves the same numbers by more than a hundred
  times that (`test_state_or_router_in_bfloat16_fails_the_tolerance`).
- RULE (1e-9, float64 inputs): the chunked form and the scan are the same
  arithmetic in another order; with beta up to 2 the triangular system's
  entries double and the bound still holds.
- ROWS (2e-6, float32): the row-major kernel sums a score's 128 products on
  the MXU's order and the kernel it stands in for down the sublanes: the
  outputs agree to float32 rounding, not bit for bit; both slabs ARE equal
  bit for bit (a copy, no arithmetic).
"""
import importlib
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import solar_open2 as ref
from deeplearning4j_tpu.decode.engine import DecodeEngine
from deeplearning4j_tpu.nn.conf.layers import (KimiDeltaAttentionLayer,
                                               MixtureOfExpertsLayer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.layers.feedforward import \
    MixtureOfExpertsLayerModule
from deeplearning4j_tpu.nn.layers.kda import (KimiDeltaAttentionLayerModule,
                                              kda_chunked)
from deeplearning4j_tpu.nn.layers.recurrent import SelfAttentionLayerModule
from deeplearning4j_tpu.zoo.models import solar_hybrid_lm

# the modules, not the functions of the same names the package re-exports
ks = importlib.import_module("deeplearning4j_tpu.kernels.kda_step")
fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

LOGP, RULE, ROWS = 2e-5, 1e-9, 2e-6
VOCAB, D_MODEL, LAYERS, HEADS = 96, 128, 4, 2
CONFIG = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
                     / "configs" / "solar_open2.json").read_text())


# ------------------------------------------------------------ the delta rule
def rule_inputs(T, H=2, D=8, seed=0, steep=False):
    """beta drawn up to 2; g unbounded: drawn down to -8 a token, and with
    `steep` a third of the channels at -30 a token (nothing of the state
    survives a step there, exp(-30 t) underflows within three)."""
    rng = np.random.RandomState(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.randn(T, H, D)) * D ** -0.5
    k, v = unit(rng.randn(T, H, D)), rng.randn(T, H, D)
    g = -8.0 * rng.rand(T, H, D) ** 3
    if steep:
        g = np.where(rng.rand(1, H, D) < 1 / 3, -30.0, g)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, 2.0 * rng.rand(T, H)))


# (T, chunk): chunk 8 is one block a chunk; 64 and 32 are cut into sub-blocks
# of 16 and 24 into sub-blocks of 8 — whole, padded, several chunks
LENGTHS = [(T, 8) for T in (1, 7, 8, 9, 16, 23, 64)] \
    + [(64, 64), (100, 64), (192, 64), (100, 32), (100, 24)]


@pytest.mark.parametrize("steep", [False, True], ids=["drawn", "minus_30"])
@pytest.mark.parametrize("T,chunk", LENGTHS)
def test_chunked_kda_with_beta_to_2_and_unbounded_decay_is_the_scan(T, chunk,
                                                                    steep):
    """beta reaches 2, so I - beta k k^T reflects and the system (I + A) U =
    rhs has entries up to 2, in the sub-blocks' forward substitution too;
    channels at -30 a token sum to -480 inside a sub-block of 16, -240 a
    chunk of 8 and -1,920 over 64 positions, where a ratio of two
    exponentials would be 0 / 0 — inside a sub-block and in both factors
    about a sub-block's boundary."""
    q, k, v, g, beta = rule_inputs(T, steep=steep)
    assert float(beta.max()) > 1.0 or T < 3
    want, S = ref.kda_scan(q, k, v, g, beta, jnp.zeros((2, 8, 8)))
    got, last = kda_chunked(*(a[None] for a in (q, k, v, g, beta)), chunk)
    np.testing.assert_allclose(got[0], want, atol=RULE, rtol=0)
    np.testing.assert_allclose(last[0], S, atol=RULE, rtol=0)
    f32 = lambda a: a.astype(jnp.float32)[None]
    got32, last32 = kda_chunked(*(f32(a) for a in (q, k, v, g, beta)), chunk)
    assert np.isfinite(np.asarray(got32)).all()
    assert np.isfinite(np.asarray(last32)).all()
    np.testing.assert_allclose(got32[0], want, atol=5e-5, rtol=0)


@pytest.mark.parametrize("T,reals,chunk", [(16, (8, 11, 16), 8),
                                           (128, (64, 70, 80, 128), 64),
                                           (72, (24, 29, 48), 24)])
def test_masked_positions_leave_the_state_alone_on_a_bucket_boundary(
        T, reals, chunk):
    """g = 0 and beta = 0 behind the last real token: the state is the real
    tokens', with the prompt ending on a chunk boundary, inside a chunk (and
    there on a sub-block's boundary or inside one) and filling the bucket."""
    q, k, v, g, beta = rule_inputs(T, seed=1, steep=True)
    for real in reals:
        m = (jnp.arange(T) < real).astype(g.dtype)
        _, want = ref.kda_scan(*(a[:real] for a in (q, k, v, g, beta)),
                               jnp.zeros((2, 8, 8)))
        _, last = kda_chunked(q[None], k[None], v[None],
                              (g * m[:, None, None])[None],
                              (beta * m[:, None])[None], chunk)
        np.testing.assert_allclose(last[0], want, atol=RULE, rtol=0)


@pytest.mark.parametrize("steep", [False, True], ids=["drawn", "minus_30"])
@pytest.mark.parametrize("T,chunk", [(40, 32), (70, 64), (30, 24)])
def test_chunked_kda_with_beta_to_2_differentiates_to_the_scans_gradient(
        T, chunk, steep):
    """Training's backward is autodiff through the sub-blocks — the
    boundary's two factors, the -inf the diagonal blocks mask with, the
    block rows' solves: the gradient of a scalar of o and of the last state,
    in every input, is the sequential rule's and finite."""
    inputs = rule_inputs(T, seed=3, steep=steep)
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
        assert np.isfinite(np.asarray(g_)).all()
        np.testing.assert_allclose(g_, w, atol=1e-8, rtol=0)


def kda_module(**over):
    conf = KimiDeltaAttentionLayer(**dict(dict(
        n_in=24, n_out=24, n_heads=2, head_dim=8, chunk_size=4,
        gate_form="softplus", gate_rank=8, beta_scale=2.0, eps=1e-5,
        weight_init="xavier", activation="identity"), **over))
    mod = KimiDeltaAttentionLayerModule(conf)
    params, _, _ = mod.init(jax.random.PRNGKey(0), None, jnp.float64)
    return mod, params


def test_kda_variants_are_in_the_layer():
    """Low-rank gates are two factors each; the softplus gate is unbounded
    below and 0 at most; beta reaches past 1; and the bounded form
    `ling3_flash` uses is still what the defaults give."""
    mod, params = kda_module()
    assert params["W_in"].shape == (24, 3 * 16 + 2 * 8)
    assert params["W_fb"].shape == params["W_gb"].shape == (8, 16)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 9, 24)) * 4
    qkv, f, gate, beta = mod._project(params, x)
    assert f.shape == gate.shape == (1, 9, 16) and beta.shape == (1, 9, 2)
    assert 1.0 < float(beta.max()) < 2.0
    lifted = dict(params, dt_bias=params["dt_bias"] + 9.0,
                  A_log=params["A_log"] + 1.0)
    g = mod._rule_inputs(lifted, qkv[..., :48], f)[3]
    assert float(g.max()) <= 0.0 and float(g.min()) < -30.0
    plain = KimiDeltaAttentionLayerModule(KimiDeltaAttentionLayer(
        n_in=24, n_out=24, n_heads=2, head_dim=8, weight_init="xavier"))
    p2, _, _ = plain.init(jax.random.PRNGKey(0), None, jnp.float64)
    assert p2["W_in"].shape == (24, 5 * 16) and "W_fb" not in p2
    g2 = plain._rule_inputs(p2, *plain._project(p2, x)[:2])[3]
    assert -5.0 <= float(g2.min()) and float(plain._project(p2, x)[3].max()) \
        < 1.0
    with pytest.raises(ValueError, match="gate_form"):
        kda_module(gate_form="tanh")[0]._rule_inputs(params, qkv[..., :48], f)


# ------------------------------------------------- kda_step over head groups
def step_inputs(S, H, D, seed=2):
    rng = np.random.RandomState(seed)
    state = jnp.asarray(rng.randn(S, H, D, D), jnp.float32)
    q, k, v, g, beta = (jnp.stack(a).astype(jnp.float32) for a in zip(*(
        [x[0] for x in rule_inputs(1, H, D, seed=s)] for s in range(S))))
    return state, jnp.exp(g), k, q, beta, v


@pytest.mark.parametrize("H,groups", [(6, 2), (6, 3), (4, 1)],
                         ids=["two_groups", "three_groups", "one_group"])
def test_kda_step_in_head_groups_is_its_plain_form(monkeypatch, H, groups):
    """A slot whose state does not fit one tile is walked in head groups,
    each group's columns in its own lane tile: the plain form to float32
    rounding, whichever the split (beta up to 2)."""
    S, D = 3, 16
    monkeypatch.setattr(ks, "_STATE_TILE_BYTES", H // groups * D * D * 4)
    assert ks._kda_tiles(H, D, D, 4, True) == H // groups
    args = step_inputs(S, H, D)
    new, o = ks.kda_step(*args, interpret=True)
    want_new, want_o = ks._kda_step_reference(*args)
    np.testing.assert_allclose(new, want_new, atol=1e-5, rtol=0)
    np.testing.assert_allclose(o, want_o, atol=1e-5, rtol=0)
    state, decay, k, q, beta, v = args
    for s in range(S):      # and one position of the reference's scan
        ro, rS = ref.kda_scan(q[s:s + 1], k[s:s + 1], v[s:s + 1],
                              jnp.log(decay[s:s + 1]), beta[s:s + 1],
                              state[s])
        np.testing.assert_allclose(new[s], rS, atol=1e-5, rtol=0)
        np.testing.assert_allclose(o[s], ro[0], atol=1e-5, rtol=0)


def test_kda_tiles_at_the_two_cells_shapes():
    """`ling3_flash` (32 heads: 2 MB a slot) stays one group a slot;
    `solar_open2` (64 heads: 4 MB) is two groups of 32; a group that is not
    the whole slot has to be a multiple of 8 heads."""
    assert ks._kda_tiles(32, 128, 128, 4, False) == 32
    assert ks._kda_tiles(64, 128, 128, 4, False) == 32
    assert ks._kda_tiles(48, 128, 128, 4, False) == 24
    assert ks._kda_tiles(34, 128, 128, 4, False) is None   # 17: no tile
    assert ks._kda_tiles(12, 128, 128, 4, False) == 12


# ------------------------------------------- the row-major decode kernel
def decode_inputs(S, C, Hq, H, D, dtype, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), dtype)
    return (mk(S, 1, Hq, D), mk(S, C, H, D), mk(S, C, H, D), mk(S, 1, H, D),
            mk(S, 1, H, D))


@pytest.mark.parametrize("Hq,H", [(8, 1), (16, 2), (8, 2)],
                         ids=["8to1", "8to1_two_kv_heads", "4to1"])
def test_row_major_decode_kernel_is_the_two_calls_it_replaces(Hq, H):
    """head_dim 128, grouped heads 8 : 1 and 4 : 1, append positions on and
    off the key block's (64) boundaries, first and last of the capacity:
    both slabs bit for bit `kv_append`'s, the output `flash_decode`'s on
    them to float32 rounding (ROWS)."""
    S, C, D = 6, 256, 128
    q, k, v, kn, vn = decode_inputs(S, C, Hq, H, D, jnp.float32)
    pos = jnp.asarray([0, 1, 63, 64, 200, 255], jnp.int32)
    assert fa._append_block(C, D, 4, True) is None
    assert fa._rows_block(C, H, D, 4, 64, True) == 64
    out, nk, nv = fa.flash_decode_append(q, k, v, kn, vn, pos, block_k=64,
                                         interpret=True)
    k2, v2 = fa.kv_append(k, v, kn, vn, pos, interpret=True)
    want = fa.flash_decode(q, k2, v2, pos + 1, interpret=True)
    np.testing.assert_array_equal(nk, k2)
    np.testing.assert_array_equal(nv, v2)
    np.testing.assert_allclose(out, want, atol=ROWS, rtol=0)
    np.testing.assert_allclose(
        out, fa._decode_reference(q, k2, v2, pos + 1, D ** -0.5), atol=ROWS,
        rtol=0)
    # what lies past a slot's length is not read: poison it
    poisoned = k.at[2, 64:].set(1e9), v.at[2, 64:].set(1e9)
    out2, _, _ = fa.flash_decode_append(q, *poisoned, kn, vn, pos,
                                        block_k=64, interpret=True)
    np.testing.assert_allclose(out2[2], out[2], atol=ROWS, rtol=0)


def test_row_major_decode_kernel_in_bfloat16_and_head_dim_256():
    """bfloat16 rows multiply on the MXU in bfloat16 and accumulate in
    float32 (one bfloat16 ulp of an output of order 1: 2^-7); head_dim 256
    is two lane tiles."""
    q, k, v, kn, vn = decode_inputs(3, 128, 16, 2, 128, jnp.bfloat16)
    pos = jnp.asarray([5, 64, 127], jnp.int32)
    out, nk, nv = fa.flash_decode_append(q, k, v, kn, vn, pos, interpret=True)
    k2, v2 = fa.kv_append(k, v, kn, vn, pos, interpret=True)
    np.testing.assert_array_equal(nk, k2)
    np.testing.assert_array_equal(nv, v2)
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(
        f32(out), f32(fa.flash_decode(q, k2, v2, pos + 1, interpret=True)),
        atol=2 ** -6, rtol=0)
    q, k, v, kn, vn = decode_inputs(2, 64, 4, 2, 256, jnp.float32, seed=1)
    pos = jnp.asarray([9, 63], jnp.int32)
    out, nk, _ = fa.flash_decode_append(q, k, v, kn, vn, pos, interpret=True)
    k2, v2 = fa.kv_append(k, v, kn, vn, pos, interpret=True)
    np.testing.assert_array_equal(nk, k2)
    np.testing.assert_allclose(out, fa._decode_reference(
        q, k2, v2, pos + 1, 256 ** -0.5), atol=ROWS, rtol=0)


def test_head_dim_64_still_takes_the_positions_minor_kernel(monkeypatch):
    """The shared entry point: head_dim 64 lowers to the kernel it lowered
    to (PR 42's `_decode_append_call`), head_dim 128 to the row-major one,
    and a head_dim the row-major kernel refuses (192) to the two calls,
    counted."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    taken = []
    for name in ("_decode_append_call", "_decode_rows_call"):
        real = getattr(fa, name)
        monkeypatch.setattr(fa, name, lambda *a, _n=name, _r=real:
                            (taken.append(_n), _r(*a))[1])
    counter = get_registry().counter("pallas_fallback_total", "")
    label = dict(kernel="flash_decode", path="kv_append+flash_decode",
                 shape="C=64,D=192,interpret=True")
    before = counter.get(**label)
    for D, want in ((64, "_decode_append_call"), (128, "_decode_rows_call"),
                    (192, None)):
        q, k, v, kn, vn = decode_inputs(2, 64, 4, 2, D, jnp.float32)
        pos = jnp.asarray([3, 40], jnp.int32)
        taken.clear()
        out, nk, nv = fa.flash_decode_append(q, k, v, kn, vn, pos,
                                             interpret=True)
        assert taken == ([want] if want else [])
        k2, v2 = fa._append_reference(k, v, kn, vn, pos)
        np.testing.assert_array_equal(nk, k2)
        np.testing.assert_allclose(out, fa._decode_reference(
            q, k2, v2, pos + 1, D ** -0.5), atol=ROWS, rtol=0)
    assert counter.get(**label) == before + 1
    # compiled, 8 K/V heads fill a tile's sublanes; 4 do not
    assert fa._rows_block(4096, 8, 128, 2, 1024, False) == 256
    assert fa._rows_block(4096, 4, 128, 2, 1024, False) is None
    assert fa._rows_block(4096, 8, 64, 2, 1024, False) is None


# -------------------------------------------------- the gated attention layer
def test_attention_layer_is_the_references_gated_grouped_half():
    """SelfAttentionLayer(head_dim=128, n_kv_heads=1, output_gate=True) at
    d_model 128 (two query heads on one K/V head, H Dh = 256 != n_out)
    against the reference's attention half; without the gate another
    result."""
    conf = SelfAttentionLayer(n_in=D_MODEL, n_out=D_MODEL, n_heads=HEADS,
                              n_kv_heads=1, head_dim=ref.HEAD_DIM,
                              output_gate=True, causal=True,
                              weight_init="xavier", activation="identity")
    mod = SelfAttentionLayerModule(conf)
    drawn, _, _ = mod.init(jax.random.PRNGKey(0), None, jnp.float32)
    params = ref.init_params(jax.random.PRNGKey(1), VOCAB, D_MODEL, 1)[
        "b0_attn"]
    assert {k: v.shape for k, v in drawn.items()} \
        == {k: v.shape for k, v in params.items()}
    assert drawn["Wq"].shape == drawn["Wgate"].shape == (D_MODEL, 256) \
        and drawn["Wo"].shape == (256, D_MODEL) \
        and drawn["Wk"].shape == (D_MODEL, 128)
    params = {k: v.astype(jnp.float32) * 4 for k, v in params.items()}
    h = jnp.asarray(np.random.RandomState(0).randn(19, D_MODEL), jnp.float32)
    want = ref._attention_half(h, {"gamma": jnp.ones(D_MODEL)}, params,
                               dtype="float32") - h
    x = ref._rms(h, 1.0)[None]
    got = mod.forward(params, {}, x)[0][0]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    conf.output_gate = False
    ungated = SelfAttentionLayerModule(conf).forward(params, {}, x)[0][0]
    assert np.abs(np.asarray(ungated - want)).max() > 1e-2


# ------------------------------------------------------------------ router
def test_router_is_the_references_in_one_group():
    E, d = ref.N_EXPERTS, 16
    conf = MixtureOfExpertsLayer(
        n_in=d, n_out=d, n_experts=E, top_k=ref.EXPERTS_PER_TOKEN, gated=True,
        n_hidden=8, score_function="sigmoid", n_groups=1,
        routed_scaling=ref.ROUTED_SCALING, activation="identity")
    mod = MixtureOfExpertsLayerModule(conf)
    rng = np.random.RandomState(0)
    params = {"Wg": jnp.asarray(rng.randn(d, E)),
              "route_bias": jnp.asarray(rng.randn(E) * 0.5)}
    x = jnp.asarray(rng.randn(40, d))
    experts, gates = mod.route(params, x)
    dense = np.asarray(jnp.sum(gates[:, :, None] * (
        experts[:, :, None] == jnp.arange(E)), axis=1))
    np.testing.assert_allclose(
        dense, ref.gates_of(x, params["Wg"], params["route_bias"], x.dtype),
        atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    # the bias chooses and is not in the gates
    s = np.asarray(jax.nn.sigmoid(x @ params["Wg"]))
    chosen = np.take_along_axis(s, np.asarray(experts), axis=1)
    np.testing.assert_allclose(gates, chosen / chosen.sum(-1, keepdims=True),
                               atol=1e-6)
    unbiased = np.argsort(-s, axis=1)[:, :ref.EXPERTS_PER_TOKEN]
    assert any(set(a) != set(b) for a, b in zip(unbiased,
                                                np.asarray(experts)))


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
    return ref.init_params(jax.random.PRNGKey(3), VOCAB, D_MODEL, LAYERS)


def tiny(weights, **over):
    """One period at d_model 128: gated attention of two query heads on one
    K/V head of 128, then three KDA layers of two heads of 128 x 128 with
    rank-128 gates; 320 routed experts of which 0-39 are held, beside a
    shared one, in every block."""
    net = solar_hybrid_lm(vocab_size=VOCAB, d_model=D_MODEL, n_layers=LAYERS,
                          n_heads=HEADS, n_kv_heads=1,
                          experts_held=ref.EXPERTS_HELD, kda_chunk_size=8,
                          **over).init()
    place(net, weights)
    return net


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernels"])
def test_one_period_output_is_the_references_logits(weights, use_pallas):
    net = tiny(weights, use_pallas=use_pallas)
    assert [n for n in net.params if n.endswith(("_attn", "_kda"))] \
        == ["b0_attn", "b1_kda", "b2_kda", "b3_kda"]
    ids = np.random.RandomState(0).randint(0, VOCAB, 21)
    want = log_softmax(ref.logits(weights, jnp.asarray(ids), heads=HEADS,
                                  layers=LAYERS))
    probs = np.asarray(net.output(np.eye(VOCAB, dtype=np.float32)[ids][None]))
    np.testing.assert_allclose(np.log(probs[0]), want, atol=LOGP, rtol=0)
    other = log_softmax(ref.logits(weights, jnp.asarray(ids), heads=HEADS,
                                   layers=LAYERS, first_expert=40))
    assert np.abs(other - want).max() > 1e-3      # the share is in them


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernels"])
@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("n_prompt", [5, 16, 19],
                         ids=["padded", "bucket", "longer"])
def test_prefill_then_steps_are_the_references_one_pass(weights, paged,
                                                       n_prompt, use_pallas):
    """All four kinds of block (gated attention, KDA, routed experts, the
    shared expert) behind `DecodeEngine`: the prefill leaves the exact state,
    conv tails and K/V rows of the prompt's last real token whatever the
    padding, and every step's row of probabilities is the reference's at
    that position — with `use_pallas` through `kda_step`, the row-major
    decode kernel and `expert_gmm`, interpreted."""
    net = tiny(weights, use_pallas=use_pallas)
    eng = DecodeEngine(net, slots=2, max_len=32,
                       **({"paged": True, "block_size": 8} if paged else {}))
    assert {k for e in eng._entries.values() for k in e} \
        == {"state", "conv", "k", "v"}
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
    assert reg.get("decode_cache_state_bytes").get() == 3 * 2 * (
        H * D * D * 4 + 3 * 3 * H * D * 4)
    assert reg.get("decode_cache_kv_bytes").get() == 2 * 2 * 32 * 1 * D * 4
    assert eng._carries == {f"b{i}_kda" for i in (1, 2, 3)}


def test_the_references_constants_are_the_configuration_files():
    a, pub = CONFIG["args"], CONFIG["published"]
    lin = pub["linear_attn_config"]
    assert ref.heads_of(a["d_model"]) == a["n_heads"] \
        == pub["num_attention_heads"] == lin["num_heads"]
    assert ref.kv_heads_of(a["d_model"]) == a["n_kv_heads"] \
        == pub["num_key_value_heads"]
    assert ref.Q_PER_KV * pub["num_key_value_heads"] \
        == pub["num_attention_heads"]
    assert ref.HEAD_DIM == a["head_dim"] == a["kda_head_dim"] \
        == pub["head_dim"] == lin["head_dim"]
    assert ref.D_CONV == a["kda_d_conv"] == lin["short_conv_kernel_size"]
    assert ref.GATE_RANK == a["kda_gate_rank"] == lin["head_dim"] \
        and pub["kda_use_full_proj"] is False
    assert ref.BETA_SCALE == 2.0 and pub["kda_allow_neg_eigval"] is True
    assert lin["num_kv_heads"] is None and pub["use_rope"] is False \
        and pub["use_gqa_gate"] is True
    assert ref.GQA_PERIOD == a["gqa_interval"] + 1 == pub["gqa_interval"] + 1
    assert pub["gqa_layers"] == [i for i in range(pub["num_hidden_layers"])
                                 if ref.is_attention(i)]
    assert CONFIG["gqa_layers"] == [i for i in range(a["n_layers"])
                                    if ref.is_attention(i)] == [0]
    assert (ref.N_EXPERTS, ref.EXPERTS_PER_TOKEN, ref.ROUTED_SCALING,
            ref.EXPERT_HIDDEN, ref.SHARED_HIDDEN, ref.RMS_EPS) == (
        a["n_experts"], a["experts_per_token"], a["routed_scaling"],
        a["expert_hidden"], a["shared_hidden"], a["rms_norm_eps"]) == (
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["routed_scaling_factor"], pub["moe_intermediate_size"],
        pub["n_shared_experts"] * pub["moe_intermediate_size"],
        pub["rms_norm_eps"])
    assert pub["norm_topk_prob"] is True \
        and pub["tie_word_embeddings"] is False
    assert (ref.EXPERTS_HELD, ref.FIRST_EXPERT) == (a["experts_held"],
                                                    a["first_expert"])
    assert "first_k_dense" not in a and pub["first_k_dense_replace"] == 0
    assert (a["d_model"], int(a["d_model"] * a["ffn_mult"])) == (
        pub["hidden_size"], pub["intermediate_size"])
    # the cut is the one stated; every width as published
    assert {k for k, v in pub.items() if CONFIG[k] != v} \
        == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (a["n_layers"], a["experts_held"],
                                      a["vocab_size"]) == (4, 40, 24576)
    assert a["vocab_size"] * 8 == pub["vocab_size"] \
        and a["experts_held"] * 8 == pub["n_routed_experts"] \
        and "8 chips of a stage share every layer" in CONFIG["deployment"]
    assert CONFIG["control_precision"] == "float8"
    assert set(CONFIG["assumed"]) >= {"kda_decay_gate", "kda_use_full_proj",
                                      "kda_allow_neg_eigval", "use_gqa_gate",
                                      "router", "shared_expert",
                                      "initialisation"}
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        rows = [json.loads(line) for line in catalog.read_text().splitlines()]
        row = {r["name"]: r for r in rows}["Solar-Open2-250B"]
        assert pub == row["config"] and CONFIG["source"] == row["source_url"]
