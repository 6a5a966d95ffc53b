"""Decode v2 tests: sampled decoding, paged KV, speculative verify.

- sampling: the traced top-k/top-p filter (keep_mask) against hand-built
  cases and against its numpy mirror (filter_probs_np), seeded streams
  reproducible / seed-sensitive, greedy short-circuit, and compile-flat
  executable counts while every sampling parameter swings per request
  (the GL016 invariant, asserted on XLA cache sizes); `sample_tokens`
  behind its conditional against the unconditional formulation (equal ids
  over greedy, sampled and mixed batches), the distribution left on the
  device, `decode_steps_total{sampler}` and the probabilities-read count.
- paged KV: BlockPool unit behavior (all-or-nothing alloc, double-free,
  defrag, high-water), flash_decode_paged == flash_decode on the gathered
  layout, and paged greedy/sampled decode == slab decode token-for-token
  for both model families.
- speculative: greedy parity with target-only decoding (attention and
  recurrent drafts), stop-id parity, seeded sampled determinism, verify
  probs == sequential step probs, recurrent targets rejected.
- scheduler: 2x-oversubscribed admission with forced preemption stays
  token-stream-invisible, pool accounting drains to zero, and the
  ManualClock fairness regression — deadline-expired and preempted slots
  retire through the SAME path, so the active_slots gauge and the block
  pool never leak (ISSUE 18 satellite).
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.decode import (BlockPool, DecodeEngine,
                                       DecodeScheduler, DecodeUnsupported,
                                       PoolExhausted, SamplerConfig,
                                       SpeculativeEngine, blocks_for)
from deeplearning4j_tpu.decode.sampling import (NEG_INF, batch_operands,
                                                filter_probs_np, keep_mask,
                                                sample_tokens)
from deeplearning4j_tpu.kernels import flash_decode, flash_decode_paged
from deeplearning4j_tpu.serving.registry import ModelRegistry
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
from deeplearning4j_tpu.util.time_source import (ManualClock,
                                                 TimeSourceProvider)
from deeplearning4j_tpu.zoo.models import char_rnn_lstm, transformer_lm

V = 24


def _tlm(seed=1, layers=1):
    net = transformer_lm(vocab_size=V, d_model=32, n_layers=layers,
                         n_heads=2, seed=seed)
    return net.init()


def _rnn(seed=2, layers=1):
    net = char_rnn_lstm(vocab_size=V, hidden=16, layers=layers, seed=seed)
    return net.init()


@pytest.fixture
def manual_clock():
    clock = ManualClock(start_s=1000.0)
    TimeSourceProvider.set_instance(clock)
    try:
        yield clock
    finally:
        TimeSourceProvider.reset()


# ---------------------------------------------------------------- sampling

def test_sampler_config_validation_and_parsing():
    with pytest.raises(ValueError):
        SamplerConfig(temperature=float("nan"))
    with pytest.raises(ValueError):
        SamplerConfig(top_k=-1)
    with pytest.raises(ValueError):
        SamplerConfig(top_p=-0.1)
    assert SamplerConfig().is_greedy
    assert not SamplerConfig(temperature=0.7).is_greedy
    assert SamplerConfig.from_request({"prompt": [1]}) is None
    cfg = SamplerConfig.from_request({"temperature": 0.8, "seed": 9})
    assert cfg.temperature == 0.8 and cfg.seed == 9 and cfg.top_k == 0
    assert cfg.to_dict()["top_p"] == 1.0


def test_keep_mask_matches_numpy_mirror():
    """The traced filter and filter_probs_np keep the SAME support on
    random distributions across the parameter grid — the speculative
    engine's host-side accept math relies on this parity."""
    rng = np.random.default_rng(0)
    probs = rng.dirichlet(np.ones(V), size=6).astype(np.float32)
    for tk, tp in [(0, 1.0), (3, 1.0), (0, 0.5), (5, 0.7), (1, 0.0),
                   (V, 1.0), (0, 0.0)]:
        mask = np.asarray(keep_mask(
            jnp.asarray(probs),
            jnp.full((6,), tk, np.int32),
            jnp.full((6,), tp, np.float32)))
        for b in range(6):
            cfg = SamplerConfig(temperature=1.0, top_k=tk, top_p=tp)
            support = filter_probs_np(probs[b], cfg) > 0
            assert (mask[b] == support).all(), (tk, tp, b)


def test_keep_mask_edges():
    probs = jnp.asarray([[0.5, 0.3, 0.1, 0.06, 0.04]], jnp.float32)

    def km(tk, tp):
        return np.asarray(keep_mask(probs,
                                    jnp.asarray([tk], jnp.int32),
                                    jnp.asarray([tp], jnp.float32)))[0]

    # top_k keeps exactly the k largest; 0 and >=V disable
    assert km(2, 1.0).tolist() == [True, True, False, False, False]
    assert km(0, 1.0).all() and km(5, 1.0).all()
    # top_p=0 still keeps the top-1 token (never an empty support)
    assert km(0, 0.0).tolist() == [True, False, False, False, False]
    # exclusive-cumsum nucleus: p=0.8 keeps {0.5, 0.3} (excl cumsum 0,
    # 0.5) and also 0.1 (excl cumsum 0.8 is NOT < 0.8 -> excluded)
    assert km(0, 0.8).tolist() == [True, True, False, False, False]
    # filters compose: top_k=1 wins over a loose top_p
    assert km(1, 0.99).tolist() == [True, False, False, False, False]


def test_seeded_generate_reproducible_and_seed_sensitive():
    net = _tlm(seed=4)
    s42 = SamplerConfig(temperature=0.9, top_k=8, top_p=0.95, seed=42)
    a = net.generate([3, 1, 4], 12, sampler=s42)
    b = net.generate([3, 1, 4], 12,
                     sampler=SamplerConfig(temperature=0.9, top_k=8,
                                           top_p=0.95, seed=42))
    c = net.generate([3, 1, 4], 12,
                     sampler=SamplerConfig(temperature=0.9, top_k=8,
                                           top_p=0.95, seed=43))
    assert a == b
    assert a != c
    # temperature 0 short-circuits to greedy regardless of other params
    g = net.generate([3, 1, 4], 12,
                     sampler=SamplerConfig(temperature=0.0, seed=42))
    assert g == net.generate([3, 1, 4], 12)


def test_sampling_params_swing_compile_flat():
    """ISSUE acceptance: swinging temperature/top_k/top_p/seed across
    requests leaves every decode executable's XLA cache at exactly 1 —
    sampling params are operands, never keys (GL016)."""
    net = _tlm(seed=5)
    eng = DecodeEngine(net, slots=2, max_len=48)
    rng = np.random.default_rng(0)
    outs = set()
    for i in range(6):
        cfg = SamplerConfig(temperature=0.3 + 0.2 * i,
                            top_k=int(rng.integers(0, V)),
                            top_p=float(rng.uniform(0.5, 1.0)),
                            seed=i)
        outs.add(tuple(eng.generate([2, 7, 1], 6, sampler=cfg)))
    eng.generate([2, 7, 1], 6)                      # greedy co-resident
    counts = eng.executable_counts()
    assert all(v == 1 for v in counts.values()), counts
    assert len(outs) > 1      # the params actually changed the streams


def _sample_tokens_unconditional(probs, operands):
    """`sample_tokens` as it was before the conditional (PR 31's body): the
    filter and the draw run whatever the operands say. The reference the
    conditional form must equal id for id."""
    temperature = operands["temperature"]
    greedy_ids = jnp.argmax(probs, axis=-1).astype(jnp.int32)
    keep = keep_mask(probs, operands["top_k"], operands["top_p"])
    t = jnp.maximum(temperature, 1e-6)[:, None]
    logits = jnp.log(jnp.clip(probs, 1e-30, None)) / t
    logits = jnp.where(keep, logits, NEG_INF)

    def draw(seed, step, row):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return jax.random.categorical(key, row)

    sampled = jax.vmap(draw)(operands["seed"].astype(jnp.uint32),
                             operands["step"], logits).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy_ids)


_SAMPLERS = {
    "temperature": dict(temperature=0.9),
    "top_k": dict(temperature=1.3, top_k=5),
    "top_p": dict(temperature=0.7, top_p=0.6),
    "both": dict(temperature=1.1, top_k=9, top_p=0.85),
}
# which of the 6 slots sample
_BATCHES = {"all_greedy": (), "all_sampled": range(6), "mixed": (1, 4)}


@pytest.mark.parametrize("seed,step", [(0, 0), (7, 3), (2**32 - 1, 511)])
@pytest.mark.parametrize("sampler", _SAMPLERS)
@pytest.mark.parametrize("batch", _BATCHES)
def test_sample_tokens_equals_the_unconditional_formulation(batch, sampler,
                                                            seed, step):
    """Behind the conditional a mixed batch draws exactly the tokens of the
    unconditional code and a greedy batch emits exactly its argmax: equal,
    not close, as jitted programs (how the step runs them)."""
    S = 6
    rng = np.random.default_rng(seed % 1000 + step)
    probs = jnp.asarray(rng.dirichlet(np.full(V, 0.3), size=S), jnp.float32)
    sampled = set(_BATCHES[batch])
    ops = batch_operands(
        S, {s: SamplerConfig(seed=(seed + s) & 0xFFFFFFFF,
                             **(_SAMPLERS[sampler] if s in sampled
                                else {"top_k": 3, "top_p": 0.5}))
            for s in range(S)},
        {s: step + s for s in range(S)})
    got = np.asarray(jax.jit(sample_tokens)(probs, ops))
    want = np.asarray(jax.jit(_sample_tokens_unconditional)(probs, ops))
    assert got.dtype == np.int32 and got.shape == (S,)
    assert got.tolist() == want.tolist()
    greedy = np.argmax(np.asarray(probs), axis=-1)
    for s in range(S):
        if s not in sampled:
            assert got[s] == greedy[s]


def test_step_and_prefill_leave_the_distribution_on_the_device():
    """The ids come back as host values, the distribution as the device
    array the program produced; `read_probs` is the one host read, and its
    histogram counts it."""
    reg = MetricsRegistry()
    eng = DecodeEngine(_tlm(seed=6), slots=3, max_len=32, registry=reg)
    cache, nid, probs = eng.prefill(eng.init_cache(), 1, [4, 2, 9])
    assert isinstance(nid, int)
    assert isinstance(probs, jax.Array) and probs.shape == (V,)
    cache, nxt, probs = eng.step(cache, np.array([0, nid, 0], np.int32))
    assert isinstance(nxt, np.ndarray) and nxt.dtype == np.int32 \
        and nxt.shape == (3,)
    assert isinstance(probs, jax.Array) and probs.shape == (3, V)
    read = reg.get("decode_probs_read_ms")
    assert read.count() == 0
    row = eng.read_probs(probs[1])
    assert isinstance(row, np.ndarray) and row.shape == (V,)
    assert int(np.argmax(row)) == nxt[1]
    assert read.count() == 1


def test_greedy_sampled_greedy_steps_share_one_executable():
    """The conditional's predicate is a traced value of an array operand:
    one engine stepped greedy -> sampled -> greedy compiles nothing new,
    and `decode_steps_total` says which way each step went."""
    reg = MetricsRegistry()
    eng = DecodeEngine(_tlm(seed=5), slots=2, max_len=32, registry=reg)
    cache, nid, _ = eng.prefill(eng.init_cache(), 0, [2, 7, 1])
    ids = np.array([nid, 0], np.int32)
    cache, _, _ = eng.step(cache, ids)
    assert eng.executable_counts() == {"decode_step": 1,
                                       "decode_prefill:16": 1}
    samp = batch_operands(2, {0: SamplerConfig(temperature=5.0, seed=3)},
                          {0: 2})
    cache, _, _ = eng.step(cache, ids, sampling=samp)
    # operands given, no positive temperature: still the greedy way
    cache, _, _ = eng.step(cache, ids, sampling=batch_operands(
        2, {0: SamplerConfig(top_k=3, seed=3)}))
    cache, _, _ = eng.step(cache, ids)
    cache, nid, _ = eng.prefill(cache, 1, [5, 5],
                                sampling=SamplerConfig(temperature=0.8))
    assert eng.executable_counts() == {"decode_step": 1,
                                       "decode_prefill:16": 1}
    steps = reg.get("decode_steps_total")
    assert steps.get(sampler="greedy") == 3
    assert steps.get(sampler="sampled") == 1
    assert steps.get() == reg.get("decode_step_sync_ms").count()


def test_scheduler_counts_sampled_steps_only_while_a_sampled_request_runs():
    """An all-greedy scheduler run never reads probabilities and counts
    every step greedy; a sampled request counts its steps `sampled` while
    it is active, and the greedy run after it is greedy again."""
    registry = ModelRegistry()
    registry.register("v1", _tlm(seed=9))
    registry.deploy("v1")
    mreg = MetricsRegistry()
    sched = DecodeScheduler(registry, mreg, slots=2, max_len=48)
    steps = lambda which: mreg.get("decode_steps_total").get(sampler=which)
    sync = lambda: mreg.get("decode_step_sync_ms").count()
    sched.start()
    try:
        greedy = sched.generate([3, 1, 4], max_new_tokens=8)["tokens"]
        assert steps("sampled") == 0 and steps("greedy") == sync() >= 7
        before = steps("greedy")
        cfg = SamplerConfig(temperature=0.9, top_k=8, seed=11)
        drawn = sched.generate([3, 1, 4], max_new_tokens=8,
                               sampler=cfg)["tokens"]
        assert steps("sampled") == 7 and steps("greedy") == before
        assert sched.generate([3, 1, 4],
                              max_new_tokens=8)["tokens"] == greedy
        assert steps("sampled") == 7
        assert steps("greedy") + steps("sampled") == sync()
    finally:
        sched.stop()
    assert drawn == _tlm(seed=9).generate([3, 1, 4], 8, sampler=cfg)
    assert mreg.get("decode_probs_read_ms").count() == 0


# ---------------------------------------------------------------- paged KV

def test_block_pool_unit():
    pool = BlockPool(8, 16)                 # block 0 is scratch
    assert pool.capacity_blocks == 7 and pool.free_blocks == 7
    a = pool.alloc(3)
    assert len(a) == 3 and 0 not in a
    assert pool.used_blocks == 3
    with pytest.raises(PoolExhausted):
        pool.alloc(5)                       # all-or-nothing: 4 free
    assert pool.used_blocks == 3            # failed alloc took nothing
    b = pool.alloc(4)
    assert pool.free_blocks == 0 and pool.high_water == 7
    assert 0.99 < pool.utilization() <= 1.0
    pool.free(a)
    assert pool.free_blocks == 3
    with pytest.raises(ValueError):
        pool.free(a)                        # double free
    with pytest.raises(ValueError):
        pool.free([0])                      # scratch is not freeable
    pool.free(b)
    pool.defrag()
    assert pool.free_blocks == 7 and pool.used_blocks == 0
    assert pool.high_water == 7             # high-water survives drain
    assert blocks_for(1, 16) == 1 and blocks_for(16, 16) == 1
    assert blocks_for(17, 16) == 2 and blocks_for(0, 16) == 0


def test_flash_decode_paged_matches_slab():
    """Gather+flash on the paged pool == flash_decode on the equivalent
    slab, under jit, for ragged per-slot lengths."""
    S, H, D, bs, nb = 3, 2, 8, 4, 4         # capacity 16 tokens per slot
    rng = np.random.default_rng(1)
    cap = bs * nb
    k_slab = rng.standard_normal((S, cap, H, D)).astype(np.float32)
    v_slab = rng.standard_normal((S, cap, H, D)).astype(np.float32)
    q = rng.standard_normal((S, 1, H, D)).astype(np.float32)
    lengths = np.asarray([5, 16, 1], np.int32)
    # scatter the slabs into a pool via a known table (block 0 = scratch)
    pool_k = np.zeros((1 + S * nb, bs, H, D), np.float32)
    pool_v = np.zeros_like(pool_k)
    table = np.zeros((S, nb), np.int32)
    for s in range(S):
        for j in range(nb):
            blk = 1 + s * nb + j
            table[s, j] = blk
            pool_k[blk] = k_slab[s, j * bs:(j + 1) * bs]
            pool_v[blk] = v_slab[s, j * bs:(j + 1) * bs]
    ref = np.asarray(flash_decode(jnp.asarray(q), jnp.asarray(k_slab),
                                  jnp.asarray(v_slab),
                                  jnp.asarray(lengths), use_pallas=False))
    got = np.asarray(jax.jit(
        lambda *a: flash_decode_paged(*a, use_pallas=False))(
            jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.asarray(lengths)))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("make,label", [(_tlm, "attention"),
                                        (_rnn, "recurrent")])
def test_paged_engine_matches_slab_both_families(make, label):
    net = make(seed=6)
    prompt = [3, 1, 4, 1, 5]
    slab = DecodeEngine(net, slots=2, max_len=48)
    paged = DecodeEngine(net, slots=2, max_len=48, paged=True, block_size=8)
    assert paged.generate(prompt, 10) == slab.generate(prompt, 10), label
    cfg = SamplerConfig(temperature=0.8, top_k=6, seed=7)
    assert paged.generate(prompt, 10, sampler=cfg) == \
        slab.generate(prompt, 10, sampler=cfg), label
    counts = paged.executable_counts()
    assert all(n == 1 for n in counts.values()), counts


# ------------------------------------------------------------- speculative

def test_verify_probs_match_sequential_steps():
    """One batched verify pass returns the same next-token distributions
    the step executable would produce one token at a time."""
    net = _tlm(seed=7, layers=2)
    prompt = [2, 9, 4]
    window = [7, 3, 8, 1]
    eng = DecodeEngine(net, slots=1, max_len=32)
    cache = eng.init_cache()
    cache, _, _ = eng.prefill(cache, 0, prompt)
    # vprobs[i] is the distribution AFTER consuming window[i], so the
    # sequential oracle steps each window token in turn
    seq_rows = []
    ids = np.zeros((1,), np.int32)
    for t in window:
        ids[0] = t
        cache, _, pp = eng.step(cache, ids)
        seq_rows.append(np.asarray(pp[0]))
    cache2 = eng.init_cache()
    cache2, _, _ = eng.prefill(cache2, 0, prompt)
    cache2, vprobs = eng.verify(cache2, 0, window, len(prompt))
    vprobs = np.asarray(vprobs)
    assert vprobs.shape == (len(window), V)
    for i in range(len(window)):
        np.testing.assert_allclose(vprobs[i], seq_rows[i], atol=2e-4)


@pytest.mark.parametrize("mkdraft,label", [(_rnn, "recurrent-draft"),
                                           (lambda **kw: _tlm(**kw),
                                            "attention-draft")])
def test_speculative_greedy_parity(mkdraft, label):
    """ISSUE acceptance: greedy speculative == target-only greedy,
    token-for-token, even with an UNRELATED draft (acceptance ~0 — the
    correction path carries every token)."""
    target = _tlm(seed=8, layers=2)
    draft = mkdraft(seed=15)
    ref = target.generate([5, 2, 6], 14)
    spec = SpeculativeEngine(draft, target, k=3, max_len=64)
    assert spec.generate([5, 2, 6], 14) == ref, label
    # the prefill emits the first token outside the round loop
    assert spec.rounds > 0 and spec.emitted >= 13
    counts = spec.executable_counts()
    assert all(n == 1 for n in counts.values()), counts


def test_speculative_stop_id_and_sampled_determinism():
    target = _tlm(seed=8, layers=2)
    draft = _tlm(seed=16)
    full = target.generate([4, 4, 1], 10)
    stop = full[2]
    spec = SpeculativeEngine(draft, target, k=3, max_len=64)
    assert spec.generate([4, 4, 1], 10, stop_id=stop) == \
        target.generate([4, 4, 1], 10, stop_id=stop)
    # sampled mode: per-seed deterministic (same distribution as target-
    # only sampling, but a different draw — greedy is the parity mode)
    cfg = SamplerConfig(temperature=0.9, top_p=0.9, seed=5)
    s1 = spec.generate([4, 4, 1], 10, sampler=cfg)
    s2 = spec.generate([4, 4, 1], 10, sampler=cfg)
    assert s1 == s2


def test_speculative_draft_reads_one_row_and_only_when_sampling():
    """The draft's distribution stays on the device in greedy mode (the
    ids suffice); sampled mode reads one [vocab] row a proposed token
    through `read_probs`, counted in `decode_probs_read_ms`."""
    reg = MetricsRegistry()
    spec = SpeculativeEngine(_tlm(seed=16), _tlm(seed=8, layers=2), k=3,
                             max_len=64, registry=reg)
    read = reg.get("decode_probs_read_ms")
    spec.generate([4, 4, 1], 10)
    assert read.count() == 0
    proposed = spec.proposed
    spec.generate([4, 4, 1], 10,
                  sampler=SamplerConfig(temperature=0.9, top_p=0.9, seed=5))
    assert read.count() == spec.proposed - proposed > 0


def test_speculative_guards():
    with pytest.raises(DecodeUnsupported):
        SpeculativeEngine(_tlm(seed=1), _rnn(seed=2))   # recurrent target
    net = _tlm(seed=1)
    with pytest.raises(ValueError):
        SpeculativeEngine(net, net)                     # self-draft
    eng = DecodeEngine(_rnn(seed=3), slots=1, max_len=16)
    with pytest.raises(DecodeUnsupported):
        eng.verify(eng.init_cache(), 0, [1, 2], 0)      # recurrent verify


# ---------------------------------------------------------------- scheduler

def _scheduler(net, version="v1", slots=3, max_len=64, **kw):
    registry = ModelRegistry()
    registry.register(version, net)
    registry.deploy(version)
    mreg = MetricsRegistry()
    sched = DecodeScheduler(registry, mreg, slots=slots, max_len=max_len,
                            **kw)
    return sched, registry, mreg


def test_oversubscribed_scheduler_parity_with_forced_preemption():
    """2x-oversubscribed paged admission with budgets long enough to
    force preemptions: every stream equals its slab run (preempt/requeue
    is token-stream-invisible, greedy AND seeded-sampled), the preempt
    counter moved, and the pool drains to zero."""
    net = _tlm(seed=9, layers=2)
    prompts = [[3, 1, 4, 1, 5], [9, 2], [6, 6, 7, 2, 1, 8]]
    # ~45-token contexts x 3 = ~18 blocks of 8 wanted, 9 allocatable:
    # concurrent growth MUST steal from the youngest
    budgets = [40, 40, 40]
    cfgs = [None, SamplerConfig(temperature=0.8, seed=11), None]
    slab, _, _ = _scheduler(net, slots=3, max_len=64)
    slab.start()
    try:
        want = [slab.generate(p, max_new_tokens=n, sampler=c)["tokens"]
                for p, n, c in zip(prompts, budgets, cfgs)]
    finally:
        slab.stop()
    # 9 allocatable blocks of 8 over 3 slots of capacity 64: each slot
    # wants up to 8 blocks, so concurrent growth must steal
    sched, _, mreg = _scheduler(net, slots=3, max_len=64, paged=True,
                                block_size=8, pool_blocks=10)
    sched.start()
    try:
        futs = [sched.submit(p, max_new_tokens=n, sampler=c)
                for p, n, c in zip(prompts, budgets, cfgs)]
        got = [f.result(timeout=300)["tokens"] for f in futs]
        assert got == want
        assert mreg.get("decode_preempted_total").get() >= 1
        snap = sched.snapshot()
        assert snap["paged"]["used_blocks"] == 0
        assert snap["active_slots"] == 0
    finally:
        sched.stop()


@pytest.mark.parametrize("pool_blocks", [None, 10],
                         ids=["slab", "paged_pool_driven_dry"])
def test_seeded_beside_greedy_under_a_step_in_flight_equal_generate(
        pool_blocks):
    """Seeded and greedy requests in one batch, turned pass by pass with a
    step always in flight: each stream is `DecodeEngine.generate`'s for the
    same prompt and sampler alone (the sampler's step index follows the
    count of tokens dispatched, not of tokens read). On a pool of 9 blocks
    of 8 for three contexts of ~45 a slot is preempted while a step that
    carries it is in flight: that token is discarded, the re-prefill from
    prompt + tokens emits it again, and the stream does not show it."""
    net = _tlm(seed=9, layers=2)
    prompts = [[3, 1, 4, 1, 5], [9, 2], [6, 6, 7, 2, 1, 8], [5]]
    budgets = [40, 40, 40, 2]
    cfgs = [None, SamplerConfig(temperature=0.8, seed=11),
            SamplerConfig(temperature=1.1, top_k=6, seed=5), None]
    eng = DecodeEngine(net, slots=3, max_len=64)
    want = [eng.generate(p, n, sampler=c)
            for p, n, c in zip(prompts, budgets, cfgs)]
    paged = pool_blocks is not None
    sched, _, mreg = _scheduler(net, slots=3, max_len=64, paged=paged,
                                block_size=8, pool_blocks=pool_blocks)
    futs = [sched.submit(p, max_new_tokens=n, sampler=c)
            for p, n, c in zip(prompts, budgets, cfgs)]
    for _ in range(400):
        if all(f.done() for f in futs) and sched._flight is None:
            break
        sched._pass()
    got = [f.result(timeout=0) for f in futs]
    assert [r["tokens"] for r in got] == want
    assert mreg.get("decode_tokens_total").get() == sum(budgets)
    preempted = mreg.get("decode_preempted_total").get()
    # a preempted slot was riding the step in flight, unless it was
    # preempted before its first step
    assert (preempted >= 1) == paged
    assert 0 <= mreg.get("decode_discarded_slot_steps_total").get() \
        <= preempted
    assert paged == (mreg.get("decode_discarded_slot_steps_total").get() > 0)
    assert sum(n for _, n in mreg.get("decode_steps_ahead_total").series()) \
        == mreg.get("decode_step_sync_ms").count()
    assert all(v == 1 for v in sched._engine.executable_counts().values())
    if paged:
        assert sched.snapshot()["paged"]["used_blocks"] == 0


def test_fairness_deadline_and_preempt_share_retire_path(manual_clock):
    """ISSUE satellite: a preempted-then-requeued request whose deadline
    expires retires through the SAME path as a mid-generation deadline —
    partial tokens returned with finish_reason='deadline' (not a 504) —
    and neither preempt nor expiry leaks slots, blocks, or the
    active_slots gauge. Driven pass by pass (no loop thread) under
    ManualClock for a deterministic preempt->requeue->expire sequence. The
    slot is preempted with a step in flight: that step's token for it is
    the one discarded slot-step."""
    net = _tlm(seed=10)
    sched, _, mreg = _scheduler(net, slots=2, max_len=32, paged=True,
                                block_size=8, pool_blocks=5)
    # 4 allocatable blocks; two slots of up to 4 blocks each
    f1 = sched.submit([1, 2, 3], max_new_tokens=20)
    f2 = sched.submit([4, 5, 6], max_new_tokens=20, timeout_ms=5000.0)
    sched._pass()               # nothing to step yet: both are admitted
    assert sched.active_count() == 2
    preempted_at = None
    for _ in range(40):
        sched._pass()           # a step dispatched, the last one read, admit
        if mreg.get("decode_preempted_total").get() >= 1 \
                and preempted_at is None:
            preempted_at = True
            # r2 (youngest) lost its slot mid-flight with partial tokens
            # and is re-queued; active gauge reflects the release
            assert sched.active_count() == 1
            assert mreg.get("decode_active_slots").get() == 1
            # its deadline now expires while it waits in the queue
            manual_clock.advance(6.0)
        if f1.done() and f2.done():
            break
    assert preempted_at, "pool never forced a preemption"
    r1 = f1.result(timeout=0)
    r2 = f2.result(timeout=0)
    assert r1["finish_reason"] == "length" and len(r1["tokens"]) == 20
    # partial result, SAME retire path as a mid-generation deadline
    assert r2["finish_reason"] == "deadline"
    assert 0 < len(r2["tokens"]) < 20
    assert sched.active_count() == 0
    assert mreg.get("decode_active_slots").get() == 0
    snap = sched.snapshot()
    assert snap["paged"]["used_blocks"] == 0
    assert set(sched._free) == {0, 1}       # both slot ids back
    assert mreg.get("decode_discarded_slot_steps_total").get() == 1
    assert mreg.get("decode_tokens_total").get() \
        == len(r1["tokens"]) + len(r2["tokens"])


def test_mid_generation_deadline_returns_partial(manual_clock):
    """The budget-spent path (no preemption involved): tokens stop at the
    deadline, partial result, slot released — the baseline the fairness
    test compares against. The deadline is found out when a token is read,
    with the next step already in flight: that step's token is discarded."""
    net = _tlm(seed=10)
    sched, _, mreg = _scheduler(net, slots=1, max_len=32)
    f = sched.submit([1, 2, 3], max_new_tokens=20, timeout_ms=2000.0)
    for _ in range(3):          # admitted; first token read; step 1 read
        sched._pass()
    manual_clock.advance(3.0)
    sched._pass()               # step 3 dispatched, step 2 read: too late
    r = f.result(timeout=0)
    assert r["finish_reason"] == "deadline"
    assert r["tokens"] == DecodeEngine(net, slots=1, max_len=32).generate(
        [1, 2, 3], 3)
    assert sched.active_count() == 0
    assert mreg.get("decode_active_slots").get() == 0
    assert sched._pass() and not sched._pass()      # step 3 read, then idle
    assert mreg.get("decode_discarded_slot_steps_total").get() == 1
    assert mreg.get("decode_tokens_total").get() == 3


# --------------------------------------------------------------- smoke tool

def test_smoke_decode_v2_tool():
    """End-to-end Decode v2 smoke (seeded sampling across hot-swap,
    2x-oversubscribed admission with zero 5xx, speculative greedy
    parity) — fast variant of tools/smoke_decode_v2.py, mirroring the
    smoke_decode wiring."""
    import tools.smoke_decode_v2 as smoke
    out = smoke.run(n_requests=6)
    assert out["sampling"]["steady_state_compiles"] == 0
    assert out["sampling"]["hot_swap_stable"]
    assert out["paged"]["errors_5xx"] == 0 and out["paged"]["parity_ok"]
    assert out["paged"]["pool_drained"]
    assert out["speculative"]["greedy_parity"]
    assert out["speculative"]["acceptance_rate"] > 0
    assert out["donation_warnings"] == 0
