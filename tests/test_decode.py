"""Autoregressive decode subsystem tests (decode/ + the flash decode path):

- flash_decode (the kernel's decode-mode path) matches the masked reference
  softmax, in and out of jit, Pallas-interpret and reference dispatch.
- greedy KV-cache decode == naive full-forward re-run, token-for-token AND
  to f32 tolerance on the probability rows, for transformer_lm (attention
  KV cache) and char_rnn_lstm (recurrent carry cache) — the ISSUE's
  acceptance parity.
- continuous batching: requests of varying prompt/output lengths join and
  leave the in-flight batch per token with the compile counters FLAT after
  warm-up, per-request outputs independent of co-batched neighbors.
- slot lifecycle: shedding, queued-deadline expiry, stop tokens, hot-swap
  (drain -> swap -> warm engine), DecodeUnsupported guards.
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.decode import (DecodeEngine, DecodeScheduler,
                                       DecodeUnsupported)
from deeplearning4j_tpu.kernels import flash_decode
from deeplearning4j_tpu.kernels.flash_attention import (_decode_block,
                                                        _decode_reference)
from deeplearning4j_tpu.serving.admission import (DeadlineExceeded,
                                                  RejectedError)
from deeplearning4j_tpu.serving.registry import ModelRegistry
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
from deeplearning4j_tpu.telemetry.xla import CompileTracker
from deeplearning4j_tpu.zoo.models import char_rnn_lstm, transformer_lm

V = 24  # test vocab


def _tlm(seed=1, layers=1, causal=True, use_pallas=False):
    net = transformer_lm(vocab_size=V, d_model=32, n_layers=layers,
                         n_heads=2, seed=seed, causal=causal,
                         use_pallas=use_pallas)
    return net.init()


def _rnn(seed=2, layers=1):
    net = char_rnn_lstm(vocab_size=V, hidden=16, layers=layers, seed=seed)
    return net.init()


def _naive_greedy(net, prompt, n):
    """The oracle: re-run the FULL forward on the growing sequence each
    token (exactly what the KV cache exists to avoid). Returns (ids,
    last-position probability rows)."""
    ids = list(prompt)
    out, rows = [], []
    for _ in range(n):
        x = np.eye(V, dtype=np.float32)[np.asarray(ids)][None]
        y = np.asarray(net.output(x))
        rows.append(y[0, -1])
        out.append(int(y[0, -1].argmax()))
        ids.append(out[-1])
    return out, np.stack(rows)


def _engine_greedy(eng, cache, slot, prompt, n):
    """Greedy decode through the engine on one slot, collecting probs."""
    cache, nid, probs = eng.prefill(cache, slot, prompt)
    out, rows = [nid], [probs]
    ids = np.zeros((eng.slots,), np.int32)
    while len(out) < n:
        ids[slot] = out[-1]
        cache, nxt, p = eng.step(cache, ids)
        out.append(int(nxt[slot]))
        rows.append(p[slot])
    return cache, out, np.stack(rows)


# ------------------------------------------------------------- flash decode

@pytest.mark.parametrize("use_pallas", [False, True])
def test_flash_decode_matches_reference(use_pallas):
    rng = np.random.default_rng(0)
    S, C, H, D = 3, 16, 2, 8
    q = jnp.asarray(rng.normal(size=(S, 1, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(S, C, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(S, C, H, D)).astype(np.float32))
    lens = jnp.asarray([5, 16, 1], jnp.int32)
    ref = _decode_reference(q, k, v, lens, 1.0 / np.sqrt(D))
    out = flash_decode(q, k, v, lens, use_pallas=use_pallas)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    jit_out = jax.jit(lambda *a: flash_decode(*a, use_pallas=use_pallas))(
        q, k, v, lens)
    np.testing.assert_allclose(np.asarray(jit_out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_only_valid_positions_matter():
    """Entries past the per-slot length must not influence the output —
    the masking contract continuous batching relies on."""
    rng = np.random.default_rng(1)
    S, C, H, D = 2, 8, 1, 8
    q = jnp.asarray(rng.normal(size=(S, 1, H, D)).astype(np.float32))
    k = rng.normal(size=(S, C, H, D)).astype(np.float32)
    v = rng.normal(size=(S, C, H, D)).astype(np.float32)
    lens = jnp.asarray([3, 6], jnp.int32)
    a = flash_decode(q, jnp.asarray(k), jnp.asarray(v), lens)
    k2, v2 = k.copy(), v.copy()
    k2[0, 3:] = 99.0    # garbage beyond each slot's length
    v2[1, 6:] = -99.0
    b = flash_decode(q, jnp.asarray(k2), jnp.asarray(v2), lens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ------------------------------------------------------------ greedy parity

@pytest.mark.parametrize("make,label", [(_tlm, "transformer_lm"),
                                        (_rnn, "char_rnn_lstm")])
def test_greedy_parity_kv_cache_vs_full_forward(make, label):
    """ISSUE acceptance: KV-cache incremental decode == naive full-forward
    re-run, token-for-token under greedy sampling, probs to f32 tolerance."""
    net = make(layers=2)
    prompt = [3, 1, 4, 15, 9]
    want, want_rows = _naive_greedy(net, prompt, 8)
    eng = DecodeEngine(net, slots=2, max_len=64)
    _, got, got_rows = _engine_greedy(eng, eng.init_cache(), 1, prompt, 8)
    assert got == want, label
    np.testing.assert_allclose(got_rows, want_rows, rtol=1e-4, atol=1e-5,
                               err_msg=label)


def test_greedy_parity_with_pallas_decode_path():
    """use_pallas=True routes the decode step through the Pallas kernel
    (interpret mode on CPU) and prefill through the masked flash kernel."""
    net = _tlm(seed=5, use_pallas=True)
    prompt = [2, 7, 7, 1]
    want, _ = _naive_greedy(net, prompt, 6)
    got = DecodeEngine(net, slots=1, max_len=32).generate(prompt, 6)
    assert got == want


def test_network_generate_api_both_types():
    for net in (_tlm(seed=3), _rnn(seed=4)):
        want, _ = _naive_greedy(net, [5, 2, 9], 5)
        assert net.generate([5, 2, 9], 5) == want
        # engine is cached on the model: a second call mints no new engine
        eng = net._decode_engine
        assert net.generate([5, 2, 9], 5) == want
        assert net._decode_engine is eng


def test_generate_stop_id_and_capacity():
    net = _tlm(seed=6)
    full = net.generate([1, 2, 3], 8)
    stop = full[2]
    stopped = net.generate([1, 2, 3], 8, stop_id=stop)
    # greedy decode is deterministic, so the stop cuts at the token's FIRST
    # occurrence (inclusive)
    assert stopped == full[:full.index(stop) + 1]


def test_decode_unsupported_models():
    from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (GravesBidirectionalLSTM,
                                                   RnnOutputLayer)
    from deeplearning4j_tpu.nn.multilayer.network import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(GravesBidirectionalLSTM(n_out=8, activation="tanh"))
            .layer(RnnOutputLayer(n_out=V, activation="softmax",
                                  loss="MCXENT"))
            .input_type(InputType.recurrent(V)).build())
    with pytest.raises(DecodeUnsupported):
        DecodeEngine(MultiLayerNetwork(conf).init(), slots=1, max_len=32)
    with pytest.raises(DecodeUnsupported):
        DecodeEngine(_tlm(seed=7, causal=False), slots=1, max_len=32)


# ----------------------------------------------------- continuous batching

def _scheduler(net, version="v1", slots=3, max_len=64, **kw):
    registry = ModelRegistry()
    registry.register(version, net)
    registry.deploy(version)
    mreg = MetricsRegistry()
    sched = DecodeScheduler(registry, mreg, slots=slots, max_len=max_len,
                            compile_tracker=CompileTracker(mreg), **kw)
    return sched, registry, mreg


def test_continuous_batching_join_leave_compile_flat():
    """ISSUE acceptance: with requests of varying prompt/output lengths
    joining and leaving mid-flight, the decode compile counters are FLAT
    after warm-up, and per-request outputs are independent of co-batched
    neighbors (== the isolated single-request run)."""
    net = _tlm(seed=8, layers=2)
    sched, _, mreg = _scheduler(net, slots=3)
    sched.start()
    try:
        shapes = [([3, 1, 4], 6), ([5, 2], 4), ([7, 7, 7, 7, 2, 1], 8),
                  ([1], 3), ([9, 8, 7, 6], 5)]
        solo = {i: net.generate(p, n) for i, (p, n) in enumerate(shapes)}
        # warm-up round: every prompt bucket + the step compile here
        warm = [sched.submit(p, max_new_tokens=n) for p, n in shapes]
        for f in warm:
            f.result(timeout=120)
        compiles = mreg.get("compiles_total").get()
        jit_compiles = mreg.get("jit_compiles_total")
        jit_before = jit_compiles.get() if jit_compiles is not None else 0
        # steady state: same length mix, staggered arrivals -> requests
        # join slots as earlier ones retire, per token
        futs = {}
        for i, (p, n) in enumerate(shapes):
            futs[i] = sched.submit(p, max_new_tokens=n)
            time.sleep(0.01)
        results = {i: f.result(timeout=120) for i, f in futs.items()}
        for i, (p, n) in enumerate(shapes):
            assert results[i]["tokens"] == solo[i], \
                f"request {i} disturbed by co-batched neighbors"
            assert results[i]["finish_reason"] in ("length", "capacity")
        assert mreg.get("compiles_total").get() == compiles, \
            "steady-state decode recompiled"
        if jit_compiles is not None:
            assert jit_compiles.get() == jit_before
        # the hard assertion: each decode executable traced exactly once
        counts = sched._engine.executable_counts()
        assert counts and all(v == 1 for v in counts.values()), counts
        # telemetry populated: TTFT + ITL saw every request/token
        assert mreg.get("decode_requests_total").get() == 2 * len(shapes)
        assert mreg.get("decode_ttft_ms").percentiles()["p50"] is not None
        assert mreg.get("decode_itl_ms").percentiles()["p50"] is not None
    finally:
        sched.stop()


def test_scheduler_shed_expiry_and_stop_token():
    net = _tlm(seed=9)
    sched, _, mreg = _scheduler(net, slots=1, queue_capacity=2)
    # not started: the queue only fills
    sched.submit([1, 2], max_new_tokens=2)
    sched.submit([1, 2], max_new_tokens=2)
    with pytest.raises(RejectedError):
        sched.submit([1, 2], max_new_tokens=2)
    assert mreg.get("decode_shed_total").get() == 1
    # an already-expired deadline fails at admission with DeadlineExceeded
    sched._queue.clear()
    f = sched.submit([3, 1, 4], max_new_tokens=4, timeout_ms=0.0)
    sched.start()
    try:
        with pytest.raises(DeadlineExceeded):
            f.result(timeout=60)
        assert mreg.get("decode_expired_total").get() == 1
        # stop token retires a slot early, mid-batch
        # (the stop id must FIRST occur past index 0 — a stream that opens
        # with it rightly stops after one token)
        full = net.generate([3, 1, 4], 6)
        cut = next(i for i in range(1, len(full)) if full[i] not in full[:i])
        res = sched.generate([3, 1, 4], max_new_tokens=6, stop_id=full[cut])
        assert res["tokens"] == full[:cut + 1] \
            and res["finish_reason"] == "stop"
        # unservable size: a clear client error, not a shed
        with pytest.raises(ValueError):
            sched.submit(list(range(10)), max_new_tokens=1000)
    finally:
        sched.stop()


def test_kv_live_gauge_and_key_block_record():
    """`decode_kv_live_pct`: the positions the active requests have filled
    (prompt + tokens the host has read: the loop keeps one step in flight,
    so the device is a token ahead of it) over slots x capacity, from the
    host's own bookkeeping; 0 again once they finish.
    `flash_decode_block{C,H,D,itemsize}`: the key block the kernel chose for
    the engine's shapes. Driven pass by pass (no loop thread)."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    net = _tlm(seed=12, use_pallas=True)
    slots, cap = 3, 64
    sched, _, mreg = _scheduler(net, slots=slots, max_len=cap)
    live = mreg.get("decode_kv_live_pct")
    assert live.get() == 0.0
    f1 = sched.submit([3, 1, 4, 1, 5], max_new_tokens=6)
    f2 = sched.submit([2, 7], max_new_tokens=3)
    sched._pass()               # both prefills dispatched, no token read yet
    assert live.get() == pytest.approx(100.0 * (5 + 2) / (slots * cap))
    sched._pass()               # step 1 dispatched, then the first tokens read
    assert live.get() == pytest.approx(100.0 * (6 + 3) / (slots * cap))
    sched._pass()               # step 2 dispatched, step 1 read
    assert live.get() == pytest.approx(100.0 * (7 + 4) / (slots * cap))
    # the second request's budget of 3 is in flight: step 3 leaves it out,
    # and the read of step 2 retires it; its slot is idle
    sched._pass()
    assert f2.done() and live.get() == pytest.approx(100.0 * 8 / (slots * cap))
    while not f1.done():
        sched._pass()
    assert live.get() == 0.0 and sched.active_count() == 0
    assert not sched._pass() and sched._flight is None    # nothing is owed
    assert mreg.get("decode_discarded_slot_steps_total").get() == 0
    # 2 heads of 16, float32, as `_tlm` builds them
    want = _decode_block(cap, 2, 16, 4, 1024, interpret=True)
    assert get_registry().get("flash_decode_block").get(
        C=cap, H=2, D=16, itemsize=4) == want


def test_key_block_record_reads_256_on_a_packed_cache():
    """`opt350m`'s heads, 16 of 64 in float32, at its capacity of 1024: the
    engine declares the packed leaf, the step's layer takes the row-major
    kernel, and `flash_decode_block{C=1024,H=16,D=64,itemsize=4}` — labelled
    by the heads, not by the rows they pack into — reads that kernel's 256
    positions where the positions-minor kernel's block is 512; greedy tokens
    are the full forward's, and nothing is counted as a decode fallback."""
    from deeplearning4j_tpu.kernels.flash_attention import _rows_block
    from deeplearning4j_tpu.telemetry.registry import get_registry
    net = transformer_lm(vocab_size=V, d_model=1024, n_layers=1, n_heads=16,
                         ffn_mult=1, seed=4, use_pallas=True).init()
    eng = DecodeEngine(net, slots=2, max_len=1024)
    cache = eng.init_cache()
    assert cache["layers"]["b0_attn"]["k"].shape == (2, 1024, 8, 128)
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    decode = lambda: sum(v for labels, v in fallbacks.series()
                         if labels["kernel"] in ("flash_decode", "kv_append"))
    before = decode()
    prompt, n = [3, 1, 4, 1, 5], 4
    _, got, _ = _engine_greedy(eng, cache, 1, prompt, n)
    assert got == _naive_greedy(net, prompt, n)[0]
    assert decode() == before
    assert _decode_block(1024, 16, 64, 4, 1024, interpret=False) == 512
    assert _rows_block(1024, 8, 128, 4, 1024, interpret=False) == 256
    assert get_registry().get("flash_decode_block").get(
        C=1024, H=16, D=64, itemsize=4) == 256


# ------------------------------------------------- one step kept in flight
def _drive(sched, futures, passes=200):
    """Turn the loop by hand until every future is answered and nothing is
    owed to the device."""
    for _ in range(passes):
        if all(f.done() for f in futures) and sched._flight is None:
            return
        sched._pass()
    raise AssertionError("the loop never finished")


def _ahead(mreg):
    """`decode_steps_ahead_total` as {"0": n, "1": n}."""
    return {l["ahead"]: n for l, n in
            mreg.get("decode_steps_ahead_total").series()}


def _loop_accounts(mreg, results):
    """What holds after any mix: a token counted is a token answered, and a
    step read is a step counted as ahead or not."""
    assert mreg.get("decode_tokens_total").get() \
        == sum(len(r["tokens"]) for r in results)
    assert sum(_ahead(mreg).values()) \
        == mreg.get("decode_step_sync_ms").count()


def test_requests_joining_freed_slots_under_a_step_in_flight_equal_generate():
    """Nine requests through three slots, budgets of 1 and 2 among them,
    joining as slots free up while a step is in flight: each gets the
    tokens `DecodeEngine.generate` gives its prompt alone. An end by length
    is known before the dispatch: no slot-step is thrown away, and all but
    the first step were dispatched ahead of a read."""
    net = _tlm(seed=8, layers=2)
    sched, _, mreg = _scheduler(net, slots=3)
    shapes = [([3, 1, 4], 6), ([5, 2], 1), ([7, 7, 7, 7, 2, 1], 2),
              ([1], 3), ([9, 8, 7, 6], 5), ([2, 2], 1), ([4], 2),
              ([6, 5, 4, 3, 2, 1, 0], 7), ([8, 3], 4)]
    futs = []
    for i, (p, n) in enumerate(shapes):
        futs.append(sched.submit(p, max_new_tokens=n))
        if i >= 2:              # the rest arrive a pass apart
            sched._pass()
    _drive(sched, futs)
    results = [f.result(timeout=0) for f in futs]
    eng = DecodeEngine(net, slots=3, max_len=64)
    for (p, n), r in zip(shapes, results):
        assert r["tokens"] == eng.generate(p, n)
        assert r["finish_reason"] == "length" and r["ttft_ms"] is not None
    _loop_accounts(mreg, results)
    assert mreg.get("decode_discarded_slot_steps_total").get() == 0
    ahead = _ahead(mreg)
    assert ahead["0"] == 1 and ahead["1"] >= 5
    assert all(v == 1 for v in sched._engine.executable_counts().values())


@pytest.mark.parametrize("end", ["stop", "abandon"])
def test_an_end_found_a_step_late_discards_that_steps_token(end):
    """A stop id ends a request when its token is read, and by then the next
    step is in flight with the slot in it: the answer holds exactly the
    tokens up to and including the stop id, the step's token for the slot
    is counted as discarded and nowhere else. A caller who gives up with a
    step in flight clamps the budget: that step's token is the last, the
    next dispatch leaves the slot out and nothing is discarded. Either way
    the neighbour in the batch and the request that takes the slot over
    get `generate`'s tokens."""
    net = _tlm(seed=12)
    eng = DecodeEngine(net, slots=2, max_len=64)
    full = eng.generate([3, 1, 4], 8)
    cut = next(i for i in range(1, len(full)) if full[i] not in full[:i])
    sched, _, mreg = _scheduler(net, slots=2)
    first = sched.submit([3, 1, 4], max_new_tokens=8,
                         stop_id=full[cut] if end == "stop" else None)
    beside = sched.submit([2, 7], max_new_tokens=8)
    if end == "abandon":
        for _ in range(cut + 2):        # `cut + 1` tokens read, one in flight
            sched._pass()
        assert sched._flight is not None and sched.abandon(first)
    _drive(sched, [first])
    after = sched.submit([9, 8, 7], max_new_tokens=4)   # into the freed slot
    _drive(sched, [beside, after])
    got = first.result(timeout=0)
    assert got["tokens"] == full[:cut + 1 if end == "stop" else cut + 2]
    assert got["finish_reason"] == ("stop" if end == "stop" else "length")
    assert beside.result(timeout=0)["tokens"] == eng.generate([2, 7], 8)
    assert after.result(timeout=0)["tokens"] == eng.generate([9, 8, 7], 4)
    assert mreg.get("decode_discarded_slot_steps_total").get() \
        == (1 if end == "stop" else 0)
    _loop_accounts(mreg, [f.result(timeout=0)
                          for f in (first, beside, after)])


def test_hot_swap_with_a_step_in_flight_reads_it_before_the_cache_goes():
    """The last request of the old version ends by a stop id, so a step of
    the old cache is still unread when admission finds the new version: it
    is read (and its token discarded) before the engine and the cache are
    swapped, and the new version's first step is dispatched into a drained
    loop."""
    net1, net2 = _tlm(seed=10), _tlm(seed=11)
    sched, registry, mreg = _scheduler(net1, slots=2)
    registry.register("v2", net2)
    full = DecodeEngine(net1, slots=2, max_len=64).generate([3, 1, 4], 6)
    cut = next(i for i in range(1, len(full)) if full[i] not in full[:i])
    old = sched.submit([3, 1, 4], max_new_tokens=6, stop_id=full[cut])
    while not old.done():
        sched._pass()
    assert sched._flight is not None            # its rider has just ended
    registry.deploy("v2")
    new = sched.submit([3, 1, 4], max_new_tokens=5)
    sched._pass()       # nothing rides: the flight is read, then the swap
    assert sched._version == "v2" and sched._flight is None
    assert mreg.get("decode_discarded_slot_steps_total").get() == 1
    _drive(sched, [new])
    r_old, r_new = old.result(timeout=0), new.result(timeout=0)
    assert (r_old["version"], r_old["tokens"]) == ("v1", full[:cut + 1])
    assert (r_new["version"], r_new["tokens"]) == (
        "v2", DecodeEngine(net2, slots=2, max_len=64).generate([3, 1, 4], 5))
    _loop_accounts(mreg, [r_old, r_new])
    assert _ahead(mreg)["0"] == 2       # one drained loop a version


def test_hot_swap_drains_then_swaps_and_warm_engine_stays_warm():
    net1, net2 = _tlm(seed=10), _tlm(seed=11)
    sched, registry, mreg = _scheduler(net1, slots=2)
    sched.start()
    try:
        r1 = sched.generate([4, 4, 1], max_new_tokens=4)
        assert r1["version"] == "v1"
        assert r1["tokens"] == net1.generate([4, 4, 1], 4)
        # deploy v2 with the scheduler's warm-up (what ServingServer.deploy
        # wires): step + observed buckets compile BEFORE the swap
        registry.register("v2", net2)
        registry.deploy("v2", warmup=sched.warmup)
        compiles = mreg.get("compiles_total").get()
        r2 = sched.generate([4, 4, 1], max_new_tokens=4)
        assert r2["version"] == "v2"
        assert r2["tokens"] == net2.generate([4, 4, 1], 4)
        assert mreg.get("compiles_total").get() == compiles, \
            "post-warm-up swap recompiled"
        # rollback: the v1 engine is cached -> no recompile either
        registry.rollback(warmup=sched.warmup)
        compiles = mreg.get("compiles_total").get()
        r3 = sched.generate([4, 4, 1], max_new_tokens=4)
        assert r3["version"] == "v1" and r3["tokens"] == r1["tokens"]
        assert mreg.get("compiles_total").get() == compiles
    finally:
        sched.stop()


def test_scheduler_survives_engine_error_and_serves_next():
    net = _tlm(seed=12)
    sched, _, mreg = _scheduler(net, slots=2, max_len=64)
    sched.start()
    try:
        ok = sched.generate([1, 2, 3], max_new_tokens=3)
        assert len(ok["tokens"]) == 3
        # sabotage one wave: an engine whose prefill raises
        class Boom(Exception):
            pass

        orig = sched._engine.dispatch_prefill

        def boom(*a, **k):
            sched._engine.dispatch_prefill = orig
            raise Boom("injected")
        sched._engine.dispatch_prefill = boom
        with pytest.raises(Boom):
            sched.generate([1, 2], max_new_tokens=2)
        assert mreg.get("decode_errors_total").get() >= 1
        # the loop survived and the next request serves fine
        again = sched.generate([1, 2, 3], max_new_tokens=3)
        assert again["tokens"] == ok["tokens"]
    finally:
        sched.stop()


# ------------------------------------------------------------- smoke tool

def test_smoke_decode_tool():
    """End-to-end /generate smoke (deploy zip -> concurrent staggered
    streams -> zero steady-state recompiles, zero donation warnings, TTFT
    populated) — fast variant of tools/smoke_decode.py, mirroring the
    smoke_serving/smoke_ingest wiring."""
    import tools.smoke_decode as smoke
    out = smoke.run(n_requests=6, max_new_tokens=4)
    assert out["steady_state_compiles"] == 0
    assert out["donation_warnings"] == 0
    assert out["ttft_ms_p50"] is not None
    assert out["parity_ok"]


def test_generate_routed_through_fleet_frontend_with_failover():
    """/generate rides the same failover/breaker path as /predict: a dead
    replica's requests fail over transparently, zero client errors."""
    from deeplearning4j_tpu.serving import FleetFrontend, ServingServer
    from deeplearning4j_tpu.util.http import post_json
    net = _tlm(seed=20)
    solo = net.generate([6, 3], 4)
    s1 = ServingServer(net, decode=True, decode_slots=2, decode_max_len=64,
                       alert_interval_s=0).start()
    s2 = ServingServer(net, decode=True, decode_slots=2, decode_max_len=64,
                       alert_interval_s=0).start()
    fe = FleetFrontend([s1.url, s2.url], names=["a", "b"],
                       health_interval_s=1e9, alert_interval_s=0).start()
    try:
        res = post_json(fe.url + "/generate",
                        {"prompt": [6, 3], "max_new_tokens": 4}, timeout=120)
        assert res["tokens"] == solo and res["replica"] in ("a", "b")
        # kill one replica: the next generates all land on the survivor
        s1.stop()
        survivors = set()
        for _ in range(3):
            res = post_json(fe.url + "/generate",
                            {"prompt": [6, 3], "max_new_tokens": 4},
                            timeout=120)
            assert res["tokens"] == solo
            survivors.add(res["replica"])
        assert survivors == {"b"}
    finally:
        fe.stop()
        s2.stop()
        try:
            s1.stop()
        except Exception:
            pass


def test_unsupported_deployed_model_fails_fast_without_spinning():
    """A deployed model with no decode semantics must fail /generate
    requests promptly (DecodeUnsupported) — not leave them queued forever
    while the loop spins on an engine that can never build."""
    from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (GravesBidirectionalLSTM,
                                                   RnnOutputLayer)
    from deeplearning4j_tpu.nn.multilayer.network import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(GravesBidirectionalLSTM(n_out=8, activation="tanh"))
            .layer(RnnOutputLayer(n_out=V, activation="softmax",
                                  loss="MCXENT"))
            .input_type(InputType.recurrent(V)).build())
    sched, _, mreg = _scheduler(MultiLayerNetwork(conf).init(), slots=1)
    sched.start()
    try:
        with pytest.raises(DecodeUnsupported):
            sched.generate([1, 2], max_new_tokens=2, wait_s=30)
        assert sched.depth() == 0                  # nothing left spinning
        assert mreg.get("decode_errors_total").get() >= 1
        assert sched._thread.is_alive()
    finally:
        sched.stop()


def test_abandon_withdraws_queued_and_clamps_active():
    net = _tlm(seed=13)
    sched, _, _ = _scheduler(net, slots=1)
    # not started: the submit stays queued -> abandon withdraws + fails it
    fut = sched.submit([1, 2], max_new_tokens=4)
    assert sched.abandon(fut) and sched.depth() == 0
    with pytest.raises(RejectedError):
        fut.result(timeout=1)
    # unknown future: no-op
    from concurrent.futures import Future
    assert not sched.abandon(Future())
