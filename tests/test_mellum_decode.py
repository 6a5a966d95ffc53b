"""The `mellum2` configuration's model end to end, at tiny widths, seeded, on
the CPU: `mellum_lm` (three sliding layers with a window of 8, a full one
under YaRN, 16 of 64 routed experts) through `output()` and through
`DecodeEngine` — prefill into the rings, then steps that wrap them, slab and
paged, jnp and kernels — against benchmarks/reference/mellum2.py's one-pass
logits. The kernels and layers one by one are tests/test_mellum.py (a file
of its own so that the two run on two workers).

Tolerance, with its reason:
- LOGP (2e-5 on log-probabilities, float32 parameters): the program and the
  reference order their float32 sums differently (flash blocks and a ring's
  blocks against one softmax, rows sorted by expert against the masked sum)
  and both take cos and sin of the same float32 angles; measured 7e-7. The
  rotary turn or the router computed in bfloat16 moves the same numbers by
  more than a hundred times that
  (`test_rotary_or_router_in_bfloat16_fails_the_tolerance`).
"""
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import mellum2 as ref
from deeplearning4j_tpu.decode.engine import DecodeEngine, DecodeUnsupported
from deeplearning4j_tpu.zoo.models import mellum_lm

LOGP = 2e-5
VOCAB, D_MODEL, LAYERS, HEADS, WINDOW = 96, 144, 4, 2, 8
YARN = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
                   / "configs" / "mellum2.json").read_text())["args"]["yarn"]


@pytest.fixture
def small_window(monkeypatch):
    """The reference's window at the tests' size (the layers get theirs from
    the builder's `window`)."""
    monkeypatch.setattr(ref, "WINDOW", WINDOW)


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
    """One period at d_model 144: three sliding layers (a window of 8) and a
    full one under YaRN, two query heads on one K/V head of 128; 64 routed
    experts of which 0-15 are held, in every block."""
    net = mellum_lm(vocab_size=VOCAB, d_model=D_MODEL, n_layers=LAYERS,
                    n_heads=HEADS, n_kv_heads=1, window=WINDOW, yarn=YARN,
                    experts_held=ref.EXPERTS_HELD, **over).init()
    place(net, weights)
    return net


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernels"])
def test_one_period_output_is_the_references_logits(weights, use_pallas,
                                                    small_window):
    net = tiny(weights, use_pallas=use_pallas)
    confs = [net.conf.vertices[f"b{i}_attn"].layer_conf for i in range(4)]
    assert [c.window for c in confs] == [8, 8, 8, None]
    assert [c.rope_yarn is not None for c in confs] == [False] * 3 + [True]
    ids = np.random.RandomState(0).randint(0, VOCAB, 37)
    want = log_softmax(ref.logits(weights, jnp.asarray(ids), heads=HEADS,
                                  layers=LAYERS))
    probs = np.asarray(net.output(np.eye(VOCAB, dtype=np.float32)[ids][None]))
    np.testing.assert_allclose(np.log(probs[0]), want, atol=LOGP, rtol=0)
    other = log_softmax(ref.logits(weights, jnp.asarray(ids), heads=HEADS,
                                   layers=LAYERS, first_expert=16))
    assert np.abs(other - want).max() > 1e-3      # the share is in them


_ENGINES = {}


def engine(weights, paged, use_pallas):
    """One engine a (layout, path), shared by the prompts: its executables
    compile once."""
    key = (paged, use_pallas)
    if key not in _ENGINES:
        _ENGINES[key] = DecodeEngine(
            tiny(weights, use_pallas=use_pallas), slots=2, max_len=64,
            **({"paged": True, "block_size": 8} if paged else {}))
    return _ENGINES[key]


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernels"])
@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("n_prompt", [5, 8, 16, 19, 33],
                         ids=["shorter", "the_ring", "bucket", "longer",
                              "wrapped_four_times"])
def test_prefill_then_steps_are_the_references_one_pass(weights, paged,
                                                       n_prompt, use_pallas,
                                                       small_window):
    """A ring of 8 positions in the three sliding layers, 64 in the full
    one, behind `DecodeEngine`: a prompt shorter than the ring (in a bucket
    of 16, longer than it: the pad rows overwrite nothing live), one that
    fills it, one that fills its bucket, longer ones whose last 8 real rows
    land at their remainders; then 11 steps, which wrap the ring again — each
    row of probabilities is the reference's at that position. With
    `use_pallas` through the windowed flash forward, the row-major decode
    kernel on whole-tile leaves (ring and slab) and `expert_gmm`,
    interpreted; paged, the window is a mask over the shared table."""
    eng = engine(weights, paged, use_pallas)
    if not paged:
        leaf = (1, 8, 128) if use_pallas else (8, 1, 128)
        assert [e["k"].shape[1:] for e in eng._entries.values()] \
            == [leaf] * 3 + [(8, 8, 128) if use_pallas else (64, 1, 128)]
    ids = list(np.random.RandomState(n_prompt).randint(0, VOCAB, 48))
    want = log_softmax(ref.logits(weights, jnp.asarray(ids), heads=HEADS,
                                  layers=LAYERS))
    cache = eng.init_cache()
    cache, _, _ = eng.prefill(cache, 0, list(range(1, 24)))   # a reused slot
    cache, _, probs = eng.prefill(cache, 0, ids[:n_prompt])
    rows = [np.asarray(eng.read_probs(probs))]
    for t in range(n_prompt, n_prompt + 11):
        cache, _, probs = eng.step(cache, np.asarray([ids[t], 0], np.int32))
        rows.append(np.asarray(eng.read_probs(probs[0])))
    np.testing.assert_allclose(np.log(np.stack(rows)),
                               want[n_prompt - 1:n_prompt + 11], atol=LOGP,
                               rtol=0)


def test_rotary_or_router_in_bfloat16_fails_the_tolerance(weights,
                                                          small_window):
    """What the configuration states as float32: were the rotary angles, cos
    and sin or the router computed in bfloat16, the logits would move by far
    more than LOGP allows."""
    ids = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, 300))
    run = lambda **how: log_softmax(ref.logits(
        weights, ids, heads=HEADS, layers=LAYERS, **how))
    want = run()
    assert np.abs(run(rope_dtype="bfloat16") - want).max() > 100 * LOGP
    assert np.abs(run(router_dtype="bfloat16") - want).max() > 100 * LOGP


def test_cache_entries_are_counted_by_kind_and_a_ring_is_not_rewound(weights):
    from deeplearning4j_tpu.telemetry.registry import get_registry
    eng = DecodeEngine(tiny(weights), slots=2, max_len=64)
    reg, D = get_registry(), ref.HEAD_DIM
    assert reg.get("decode_cache_window_bytes").get() == 3 * 2 * 2 * 8 * D * 4
    assert reg.get("decode_cache_kv_bytes").get() == 2 * 2 * 64 * D * 4
    assert reg.get("decode_cache_state_bytes").get() == 0
    assert eng._carries == {f"b{i}_attn" for i in (0, 1, 2)}
    with pytest.raises(DecodeUnsupported):
        eng.verify(eng.init_cache(), 0, [1, 2, 3], 0)
    # a slot that cannot outgrow the window keeps a ring of its capacity:
    # one rule (`window is not None`) names the kind, the kernel and
    # `decode_rewindable`
    eng = DecodeEngine(tiny(weights), slots=2, max_len=8)
    assert reg.get("decode_cache_window_bytes").get() == 3 * 2 * 2 * 8 * D * 4
    assert reg.get("decode_cache_kv_bytes").get() == 2 * 2 * 8 * D * 4
    assert eng._carries == {f"b{i}_attn" for i in (0, 1, 2)}
