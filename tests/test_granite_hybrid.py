"""The hybrid state-space / attention decoder (zoo.granite_hybrid_lm: Mamba2Layer,
grouped-query SelfAttentionLayer, RMSNormalization, GatedDenseLayer,
LMHeadLayer) at tiny sizes on the CPU, seeded weights:

- `output()` of a one-period (10-layer) model against the plain reference's
  logits (benchmarks/reference/granite4_h_micro.py: the sequential scan);
- prefill, then token-by-token decode through DecodeEngine, against the same
  full forward — slab and paged, prompts shorter than, equal to and longer
  than the scan's chunk and exactly a bucket long;
- a slot reused after a longer request reads as a fresh one;
- `ssm_step` in interpret mode against its plain form, to the last bits;
- `flash_decode` with 4 query heads a K/V head against `_decode_reference`;
- the new SelfAttentionLayer fields at their defaults leave transformer_lm's
  decode programs as they were;
- the reference's constants are the configuration file's, and that file's
  `published` is the catalog row's config where the catalog is at hand.
"""
import importlib
import json
import pathlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import granite4_h_micro as ref
from deeplearning4j_tpu.decode import DecodeEngine
from deeplearning4j_tpu.kernels import flash_decode, ssm_step
from deeplearning4j_tpu.nn.layers.mamba import ssd_chunked
from deeplearning4j_tpu.zoo.models import granite_hybrid_lm, transformer_lm

fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")
ss = importlib.import_module("deeplearning4j_tpu.kernels.ssm_step")
ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / "granite4_h_micro.json").read_text())

V = 48
CHUNK = 8


def tiny(**over):
    """Three blocks (Mamba-2, attention, Mamba-2) with every multiplier of
    the configuration, 2 K/V heads under 4 query heads, chunk 8."""
    args = dict(vocab_size=V, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
                attention_layers=(1,), mamba_d_head=8, mamba_d_state=16,
                mamba_chunk_size=CHUNK, embedding_multiplier=12,
                attention_multiplier=0.125, residual_multiplier=0.22,
                logits_scaling=8, seed=7)
    args.update(over)
    return granite_hybrid_lm(**args).init()


def one_hot(ids):
    return np.eye(V, dtype=np.float32)[np.asarray(ids)][None]


def full_forward_rows(net, ids, n_prompt):
    """Probability rows of the full forward at positions n_prompt - 1 ..:
    what the engine must emit after the prompt and after each token."""
    return np.asarray(net.output(one_hot(ids)))[0, n_prompt - 1:]


# ------------------------------------------------------------ the reference
def test_one_period_output_matches_the_reference_logits():
    """10 layers, attention at 5, the reference's own sizes at d_model 128
    (Mamba-2: 4 heads of 64, state 128; 8 query heads over 2 K/V heads)."""
    d, layers, heads, vocab = 128, 10, 8, 96
    a = CONFIG["args"]
    net = granite_hybrid_lm(
        vocab_size=vocab, d_model=d, n_layers=layers, n_heads=heads,
        n_kv_heads=heads // ref.QUERY_HEADS_PER_KV, attention_layers=(5,),
        mamba_d_head=a["mamba_d_head"], mamba_d_state=a["mamba_d_state"],
        mamba_d_conv=a["mamba_d_conv"], mamba_chunk_size=CHUNK,
        embedding_multiplier=a["embedding_multiplier"],
        attention_multiplier=a["attention_multiplier"],
        residual_multiplier=a["residual_multiplier"],
        logits_scaling=a["logits_scaling"],
        rms_norm_eps=a["rms_norm_eps"]).init()
    params = ref.init_params(jax.random.PRNGKey(3), vocab, d, layers, 4 * d)
    assert {k: sorted(v) for k, v in params.items()} \
        == {k: sorted(v) for k, v in net.params.items()}
    net.params = {n: {k: jnp.asarray(params[n][k], old.dtype)
                      for k, old in leaves.items()}
                  for n, leaves in net.params.items()}
    ids = np.random.RandomState(0).randint(0, vocab, 21)
    want = np.asarray(ref.logits(params, jnp.asarray(ids), heads=heads,
                                 layers=layers), np.float64)
    want -= np.log(np.sum(np.exp(want), axis=-1, keepdims=True))
    probs = np.asarray(net.output(np.eye(vocab, dtype=np.float32)[ids][None]))
    # float32 both sides, another order of sums (chunked against sequential)
    np.testing.assert_allclose(np.log(probs[0]), want, atol=2e-5, rtol=0)


def test_the_references_constants_are_the_configuration_files():
    a, pub = CONFIG["args"], CONFIG["published"]
    assert ref.QUERY_HEADS_PER_KV == a["n_heads"] // a["n_kv_heads"]
    assert list(ref.ATTENTION_LAYERS) == a["attention_layers"] == [
        i for i, t in enumerate(pub["layer_types"]) if t == "attention"]
    assert ref.mamba_dims(a["d_model"])[:2] == (
        a["mamba_n_heads"], a["mamba_n_heads"] * a["mamba_d_head"])
    assert (ref.MAMBA_EXPAND, ref.MAMBA_D_HEAD, ref.MAMBA_D_STATE,
            ref.MAMBA_D_CONV) == (pub["mamba_expand"], a["mamba_d_head"],
                                  a["mamba_d_state"], a["mamba_d_conv"])
    assert (ref.EMBEDDING_MULTIPLIER, ref.ATTENTION_MULTIPLIER,
            ref.RESIDUAL_MULTIPLIER, ref.LOGITS_SCALING, ref.RMS_EPS) == (
        a["embedding_multiplier"], a["attention_multiplier"],
        a["residual_multiplier"], a["logits_scaling"], a["rms_norm_eps"])
    # uncut: every key of the published config also at the file's top level
    assert CONFIG["reduced"] == [] and a["n_layers"] == 40
    assert all(CONFIG[k] == v for k, v in pub.items())
    assert (a["vocab_size"], a["d_model"], a["d_model"] * a["ffn_mult"]) == (
        pub["vocab_size"], pub["hidden_size"], pub["shared_intermediate_size"])
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        rows = [json.loads(line) for line in catalog.read_text().splitlines()]
        row = {r["name"]: r for r in rows}["granite-4.0-h-micro"]
        assert pub == row["config"] and CONFIG["source"] == row["source_url"]
    # the issue's arithmetic: 36 layers' state 75.5 MB a slot, read and
    # written; 268 MB of state a kernel call at 64 slots (+ 1 % of operands)
    assert 36 * ref.ssm_step_bytes(1) / 2 == pytest.approx(75.5e6, rel=0.02)
    assert ref.ssm_step_bytes(64) == pytest.approx(268.4e6, rel=0.02)
    parts = ref.decode_step_bytes(64, 64 * 256)
    assert 16e9 < sum(parts.values()) < 17.5e9
    assert parts["ssm_state"] / sum(parts.values()) > 0.55


# ------------------------------------------------------- the scan's chunking
@pytest.mark.parametrize("T", [5, 8, 19])
def test_chunked_scan_is_the_sequential_recurrence(T):
    rng = np.random.RandomState(T)
    b, H, P, N = 2, 3, 4, 5
    x = rng.randn(b, T, H, P)
    dt = np.log1p(np.exp(rng.randn(b, T, H)))
    dt[1, T - 2:] = 0.0                     # a masked tail: state passes on
    A = -np.exp(rng.randn(H))
    B, C = rng.randn(b, T, N), rng.randn(b, T, N)
    y, last = ssd_chunked(*map(jnp.asarray, (x, dt, A, B, C)), CHUNK)
    S = np.zeros((b, N, H, P))
    for t in range(T):
        S = np.exp(dt[:, t] * A)[:, None, :, None] * S + np.einsum(
            "bn,bhp->bnhp", B[:, t], dt[:, t, :, None] * x[:, t])
        np.testing.assert_allclose(
            y[:, t], np.einsum("bnhp,bn->bhp", S, C[:, t]), atol=1e-9)
    np.testing.assert_allclose(last, S.reshape(b, N, H * P), atol=1e-9)


# ------------------------------------------------- decode through the engine
@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("n_prompt", [5, CHUNK, 19, 16],
                         ids=["shorter", "one_chunk", "longer", "bucket"])
def test_prefill_then_decode_is_the_full_forward(paged, n_prompt):
    """Prompts against the chunk (8) and the prefill bucket (16 is exactly a
    bucket: no padding; 5 and 19 are padded to 16 and 32)."""
    net = tiny()
    n_new = 6
    eng = DecodeEngine(net, slots=2, max_len=32, paged=paged, block_size=8)
    prompt = list(np.random.RandomState(n_prompt).randint(0, V, n_prompt))
    cache, nid, probs = eng.prefill(eng.init_cache(), 1, prompt)
    got, rows = [nid], [probs]
    ids = np.zeros((2,), np.int32)
    for _ in range(n_new - 1):
        ids[1] = got[-1]
        cache, nxt, probs = eng.step(cache, ids)
        got.append(int(nxt[1]))
        rows.append(probs[1])
    want = full_forward_rows(net, prompt + got[:-1], n_prompt)
    np.testing.assert_allclose(np.stack(rows), want, rtol=2e-4, atol=1e-7)
    assert got == [int(r.argmax()) for r in want]
    assert eng.executable_counts()["decode_step"] == 1


@pytest.mark.parametrize("kv", [2, 4], ids=["1_row", "2_rows"])
def test_heads_of_64_decode_from_a_packed_leaf_in_whole_tiles(kv):
    """The attention block as `granite4_h_micro` has it — heads of 64,
    `use_pallas` — with 2 and 4 K/V heads: 1 and 2 rows of 128 lanes a
    position, so the slab leaf is declared packed in whole tiles
    (`tiled_rows`; the cell's 8 heads are 4 rows: tests/
    test_decode_contract.py) and prefill + steps give the full forward's
    rows and the plain-leaf engine's tokens."""
    over = dict(d_model=256, n_kv_heads=kv, attention_multiplier=0.125)
    net = tiny(use_pallas=True, **over)
    eng = DecodeEngine(net, slots=2, max_len=32)
    assert eng._entries["b1_attn"]["k"].shape == (2, 32 * kv // 16, 8, 128)
    prompt = list(np.random.RandomState(kv).randint(0, V, 11))
    cache, nid, probs = eng.prefill(eng.init_cache(), 1, prompt)
    got, rows = [nid], [probs]
    ids = np.zeros((2,), np.int32)
    for _ in range(5):
        ids[1] = got[-1]
        cache, nxt, probs = eng.step(cache, ids)
        got.append(int(nxt[1]))
        rows.append(probs[1])
    want = full_forward_rows(net, prompt + got[:-1], len(prompt))
    np.testing.assert_allclose(np.stack(rows), want, rtol=2e-4, atol=1e-6)
    plain = DecodeEngine(tiny(**over), slots=2, max_len=32)
    assert plain._entries["b1_attn"]["k"].shape == (2, 32, kv, 64)
    assert got == plain.generate(prompt, 6)


def test_both_kinds_of_entry_live_side_by_side_and_do_not_rewind():
    eng = DecodeEngine(tiny(), slots=2, max_len=32)
    cache = eng.init_cache()
    kinds = {name: sorted(entry) for name, entry in cache["layers"].items()}
    assert kinds == {"b0_mamba": ["conv", "ssm"], "b1_attn": ["k", "v"],
                     "b2_mamba": ["conv", "ssm"]}
    # state: [slots, d_state, heads * head_dim] whatever the capacity; K/V:
    # 2 heads of the 4 query heads, a row a position
    assert cache["layers"]["b0_mamba"]["ssm"].shape == (2, 16, 64)
    assert cache["layers"]["b0_mamba"]["conv"].shape == (2, 3, 64 + 2 * 16)
    assert cache["layers"]["b1_attn"]["k"].shape == (2, 32, 2, 8)
    assert eng.has_recurrent() and eng._carries == {"b0_mamba", "b2_mamba"}
    from deeplearning4j_tpu.telemetry.registry import get_registry
    state = 2 * (2 * 16 * 64 * 4 + 2 * 3 * 96 * 4)
    assert get_registry().get("decode_cache_state_bytes").get() == state
    assert get_registry().get("decode_cache_kv_bytes").get() \
        == eng.cache_bytes() - state - 2 * 4


def test_a_slot_reused_after_a_longer_request_reads_as_a_fresh_one():
    net = tiny()
    eng = DecodeEngine(net, slots=2, max_len=32)
    rng = np.random.RandomState(1)
    long_, short = list(rng.randint(0, V, 21)), list(rng.randint(0, V, 3))
    ids = np.zeros((2,), np.int32)

    def serve(cache, prompt, n):
        cache, nid, probs = eng.prefill(cache, 0, prompt)
        rows = [probs]
        for _ in range(n):
            ids[0] = nid
            cache, nxt, probs = eng.step(cache, ids)
            nid = int(nxt[0])
            rows.append(probs[0])
        return cache, np.stack(rows)

    used, _ = serve(eng.init_cache(), long_, 5)
    _, again = serve(used, short, 4)
    _, fresh = serve(eng.init_cache(), short, 4)
    np.testing.assert_array_equal(again, fresh)


def test_a_discarded_step_then_a_prefill_leave_the_exact_recurrent_state():
    """Through the scheduler, which keeps a step in flight: a request ends
    by a stop id, so its slot's Mamba-2 state and K/V row are stepped once
    more than its answer shows; the request that takes the slot over is
    prefilled over that and serves `generate`'s tokens, and when it is done
    the slot's conv and ssm state are to the last bit those of the same
    request served alone on a fresh cache."""
    from deeplearning4j_tpu.decode import DecodeScheduler
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
    net = tiny()
    registry, mreg = ModelRegistry(), MetricsRegistry()
    registry.register("v1", net)
    registry.deploy("v1")
    sched = DecodeScheduler(registry, mreg, slots=2, max_len=32)
    eng = DecodeEngine(net, slots=2, max_len=32)
    rng = np.random.RandomState(3)
    first, beside, after = (list(rng.randint(0, V, n)) for n in (11, 4, 6))
    full = eng.generate(first, 8)
    cut = next(i for i in range(1, len(full)) if full[i] not in full[:i])

    def drive(futures):
        for _ in range(100):
            if all(f.done() for f in futures) and sched._flight is None:
                return [f.result(timeout=0) for f in futures]
            sched._pass()
        raise AssertionError("the loop never finished")

    f_first = sched.submit(first, max_new_tokens=8, stop_id=full[cut])
    f_beside = sched.submit(beside, max_new_tokens=6)
    (r_first,) = drive([f_first])
    assert r_first["tokens"] == full[:cut + 1]
    slot = sched._free[-1]                      # the one it has just left
    f_after = sched.submit(after, max_new_tokens=5)
    r_beside, r_after = drive([f_beside, f_after])
    assert mreg.get("decode_discarded_slot_steps_total").get() == 1
    assert r_beside["tokens"] == eng.generate(beside, 6)
    assert r_after["tokens"] == eng.generate(after, 5)

    # the same request alone: a prefill and the 4 steps after it
    cache, nid, _ = eng.prefill(eng.init_cache(), slot, after)
    ids = np.zeros((2,), np.int32)
    for _ in range(4):
        ids[slot] = nid
        cache, nxt, _ = eng.step(cache, ids)
        nid = int(nxt[slot])
    for name in sorted(eng._carries):
        for leaf, alone in cache["layers"][name].items():
            np.testing.assert_array_equal(
                np.asarray(sched._cache["layers"][name][leaf])[slot],
                np.asarray(alone)[slot], err_msg=f"{name}/{leaf}")
    assert int(sched._cache["lengths"][slot]) == int(cache["lengths"][slot])


# ---------------------------------------------------------------- the kernels
@pytest.mark.parametrize("shape", [(3, 16, 64), (2, 8, 256)])
def test_ssm_step_kernel_is_its_plain_form_to_the_last_bits(shape):
    """Interpret mode runs the kernel's own arithmetic, tile by tile: the
    same products and the same order of the sum over the state index. Not
    bit for bit: XLA's CPU backend contracts decay * S + b * dtx into a fused
    multiply-add in one of the two programs and not in the other, so the
    state differs in its last bit (4.8e-7 at |S| ~ 1) and y by the sum of
    16 such."""
    S, N, C = shape
    rng = np.random.RandomState(S)
    state = jnp.asarray(rng.randn(S, N, C), jnp.float32)
    decay = jnp.asarray(rng.rand(S, C), jnp.float32)
    dtx, b, c = (jnp.asarray(rng.randn(S, n), jnp.float32)
                 for n in (C, N, N))
    block = ss._ssm_block(N, C, 4, True)
    assert C % block == 0
    want_new, want_y = ss._ssm_step_reference(state, decay, dtx, b, c)
    # the whole row a tile, and half of it so the grid has a second axis
    for got in (ssm_step(state, decay, dtx, b, c, interpret=True),
                ss._ssm_step_call(state, decay, dtx, b, c, C // 2, True)):
        np.testing.assert_allclose(got[0], want_new, rtol=0, atol=1e-6)
        np.testing.assert_allclose(got[1], want_y, rtol=0, atol=1e-5)


def test_ssm_step_block_and_fallback():
    assert ss._ssm_block(128, 4096, 4, False) == 4096   # 2 MB: a slot's state
    assert ss._ssm_block(128, 8192, 4, False) == 4096
    assert ss._ssm_block(128, 96, 4, False) is None     # lanes: 128s
    assert ss._ssm_block(12, 256, 4, False) is None     # sublanes: 8s
    from deeplearning4j_tpu.telemetry.registry import get_registry
    counter = get_registry().counter("pallas_fallback_total")
    before = counter.get(kernel="ssm_step", path="jnp",
                         shape="N=12,C=256,interpret=False")
    z = jnp.zeros((1, 12, 256), jnp.float32)
    ssm_step(z, z[:, 0], z[:, 0], z[:, :, 0], z[:, :, 0], interpret=False)
    assert counter.get(kernel="ssm_step", path="jnp",
                       shape="N=12,C=256,interpret=False") == before + 1


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_decode_reads_a_kv_head_once_for_its_four_query_heads(dtype):
    rng = np.random.RandomState(4)
    S, C, Hq, Hkv, D = 3, 32, 8, 2, 16
    q = jnp.asarray(rng.randn(S, 1, Hq, D), dtype)
    k, v = (jnp.asarray(rng.randn(S, C, Hkv, D), dtype) for _ in range(2))
    lengths = jnp.asarray([1, 19, 32], jnp.int32)
    got = flash_decode(q, k, v, lengths, block_k=16)
    want = fa._decode_reference(q, k, v, lengths, 1.0 / np.sqrt(D))
    assert got.shape == (S, 1, Hq, D)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2 if dtype == "bfloat16" else 1e-5)
    # query head j*4 + g reads K/V head j, not head (j*4 + g) % 2
    alone = fa._decode_reference(q[:, :, 5:6], k[:, :, 1:2], v[:, :, 1:2],
                                 lengths, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(want[:, :, 5:6], np.float32),
                               np.asarray(alone, np.float32), atol=1e-6)


# --------------------------------------- what the new fields leave untouched
def _stripped(lowered):
    return re.sub(r"loc\(.*?\)|#loc\d*( = .*)?", "", lowered.as_text())


def test_attention_fields_at_their_defaults_leave_transformer_lm_programs():
    """A grouping of one and no explicit score scale are the code path that
    was there: the step and a prefill bucket of transformer_lm lower to the
    same text with the fields unset and with n_kv_heads = n_heads. (Against
    the parent commit the same texts were compared once, PERF.md section 6.)"""
    def programs(**attention):
        net = transformer_lm(vocab_size=32, d_model=32, n_layers=2, n_heads=2,
                             seed=5)
        for name, spec in net.conf.vertices.items():
            if name.endswith("_attn"):
                for k, val in attention.items():
                    setattr(spec.layer_conf, k, val)
        net.init()
        eng = DecodeEngine(net, slots=2, max_len=32)
        cache = eng.init_cache()
        step = eng._build_step().lower(
            net.params, net.states, cache, np.zeros((2,), np.int32),
            eng._greedy_step_ops, None)
        prefill = eng._build_prefill(16).lower(
            net.params, net.states, cache, np.int32(0),
            np.zeros((16,), np.int32), np.int32(9), eng._greedy_slot_ops,
            None, np.zeros((2,), np.int32))
        return _stripped(step), _stripped(prefill)

    assert programs() == programs(n_kv_heads=2)
    scaled = programs(score_scale=0.25)
    assert scaled != programs()             # the scale is a multiply on q
