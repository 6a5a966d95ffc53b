"""The decode loop's own account of the device (PR 52): the program ledger
`decode_program_ms{program}`, the prefill's rows by kind, the stall record,
a request's stages under its trace id, and the two benchmark readers that
read them from a window's two snapshots.

The loop is driven by hand (`sched._turn()`, no thread) on a manual clock
against a stub engine whose programs take known times on a serial stub
device: dispatching enqueues behind what the device already holds, and
reading a result waits — advances the clock — until the device has finished
it. The ledger itself is the real engine's `observe_wall`."""
import gc
import json
import threading
import urllib.request

import numpy as np
import pytest

from benchmarks.run import load_reader
from deeplearning4j_tpu.decode.engine import (STALL_FACTOR, STALL_FLOOR_MS,
                                              DecodeEngine,
                                              _ledger_instruments,
                                              bucket_for_len)
from deeplearning4j_tpu.decode.scheduler import DecodeScheduler
from deeplearning4j_tpu.serving.registry import ModelRegistry
from deeplearning4j_tpu.telemetry import MetricsRegistry, Tracer
from deeplearning4j_tpu.telemetry.logging import StructuredLogger
from deeplearning4j_tpu.util.time_source import (ManualClock,
                                                 TimeSourceProvider)


@pytest.fixture
def clock():
    c = ManualClock(start_s=1000.0)
    TimeSourceProvider.set_instance(c)
    try:
        yield c
    finally:
        TimeSourceProvider.reset()


class _Pending:
    """A stub program's result, still on the stub device."""

    def __init__(self, engine, seconds, ends_at):
        self.engine, self.seconds, self.ends_at = engine, seconds, ends_at

    def wait(self):
        e = self.engine
        e.clock.advance(max(0.0, self.ends_at - e.clock.monotonic()))
        if e.on_read is not None:
            e.on_read(self)

    def __int__(self):                  # a prefill's first token
        self.wait()
        return 7

    def __array__(self, dtype=None, copy=None):     # a step's next ids
        self.wait()
        return np.zeros((self.engine.slots,), np.int32)


class _StubEngine(DecodeEngine):
    """The scheduler's side of a DecodeEngine with no model behind it: a
    step takes `step_s` of the stub device, a prefill `prefill_s[bucket]`
    (a callable may give one step's time by its number). The ledger
    (`observe_wall`, its instruments) and `read_ids` are DecodeEngine's."""

    def __init__(self, clock, registry, slots, step_s, prefill_s):
        self.clock, self.slots, self.capacity = clock, slots, 4096
        self.step_s, self.prefill_s = step_s, prefill_s
        self.model, self.mesh, self.paged = object(), None, False
        self.cost_registry, self._cold = None, set()
        self._m_program, self._m_program_total, self._m_rows = \
            _ledger_instruments(registry)
        self.tracer, self._m_sync = Tracer(enabled=False), None
        self.last_sync_ms = 0.0
        self.device_free_at = 0.0
        self.steps = 0
        self.on_read = None
        self.enqueue_s = 0.0

    def _enqueue(self, seconds):
        start = max(self.clock.monotonic(), self.device_free_at)
        self.device_free_at = start + seconds
        return _Pending(self, seconds, self.device_free_at)

    def init_cache(self):
        return {}

    def dispatch_prefill(self, cache, slot, prompt_ids, sampling=None,
                         step_index=0, table=None, next_ids=None):
        L = self.prefill_bucket(len(prompt_ids))
        pending = self._enqueue(self.prefill_s[L])
        self.clock.advance(self.enqueue_s)      # the host's own call
        return cache, pending, None, next_ids

    def dispatch_step(self, cache, last_ids, sampling=None, table=None):
        self.steps += 1
        s = self.step_s(self.steps) if callable(self.step_s) else self.step_s
        return cache, self._enqueue(s), None


def _stub_scheduler(clock, step_s=0.005, prefill_s=None, slots=4,
                    tracer=None, logger=None):
    models = ModelRegistry()
    models.register("v1", object())
    models.deploy("v1")
    mreg = MetricsRegistry()
    sched = DecodeScheduler(models, mreg, slots=slots, max_len=4096,
                            tracer=tracer or Tracer(enabled=False),
                            logger=logger)
    eng = _StubEngine(clock, mreg, slots, step_s,
                      prefill_s or {16: 0.002, 32: 0.004})
    model = models.active_entry().model
    eng.model = model
    sched._engines[id(model)] = (model, eng)
    return sched, mreg, eng


def _drain(sched, futures=()):
    for _ in range(10_000):
        if all(f.done() for f in futures) and sched._flight is None \
                and not sched._firsts and not sched.active_count():
            return
        sched._turn()
    raise AssertionError("the loop never drained")


def _by_program(mreg, stat):
    return {labels["program"]: st[stat]
            for labels, st in mreg.get("decode_program_ms").series()}


# ------------------------------------------------------------ the ledger
def test_ledger_of_steps_alone_sums_to_the_wall(clock):
    sched, mreg, _ = _stub_scheduler(clock)
    fut = sched.submit(list(range(5)), max_new_tokens=41)
    while not sched._active or not next(iter(
            sched._active.values())).tokens:
        sched._turn()                   # the prefill's token is on the host
    t0, before = clock.monotonic(), _by_program(mreg, "sum")
    _drain(sched, [fut])
    grown = {p: ms - before.get(p, 0.0)
             for p, ms in _by_program(mreg, "sum").items()}
    assert grown == {"prefill:16": 0.0, "step": pytest.approx(40 * 5.0)}
    assert sum(grown.values()) == pytest.approx(
        (clock.monotonic() - t0) * 1e3)
    assert _by_program(mreg, "count")["step"] == 40
    assert len(fut.result(0)["tokens"]) == 41


def test_ledger_splits_steps_and_two_prefill_buckets_and_leaves_idle_out(
        clock):
    """Two waves of requests with an idle gap between them: every program's
    series holds its own executions' times, they add up to the wall the
    loop was busy, and the gap is in no series."""
    sched, mreg, eng = _stub_scheduler(clock)
    t0 = clock.monotonic()
    first = [sched.submit(list(range(n)), max_new_tokens=k)
             for n, k in ((5, 9), (20, 4), (12, 6))]
    _drain(sched, first)
    busy = clock.monotonic() - t0
    clock.advance(5.0)                  # nothing queued, nothing in flight
    t1 = clock.monotonic()
    second = [sched.submit(list(range(n)), max_new_tokens=k)
              for n, k in ((30, 3), (7, 12))]
    _drain(sched, second)
    busy += clock.monotonic() - t1
    sums, counts = _by_program(mreg, "sum"), _by_program(mreg, "count")
    assert counts == {"prefill:16": 3, "prefill:32": 2, "step": eng.steps}
    assert sums["prefill:16"] == pytest.approx(3 * 2.0)
    assert sums["prefill:32"] == pytest.approx(2 * 4.0)
    assert sums["step"] == pytest.approx(eng.steps * 5.0)
    assert sum(sums.values()) == pytest.approx(busy * 1e3)
    # the counter the share's reader takes holds the same sums
    assert dict((labels["program"], pytest.approx(ms)) for labels, ms in
                mreg.get("decode_program_ms_total").series()) == sums
    assert mreg.get("decode_program_ms").sum() == pytest.approx(busy * 1e3)


def test_the_call_that_compiles_is_left_out_and_rows_count_by_kind():
    """A real engine: the first call of a program is the compile's account
    (`jit_compiles_total`), not the ledger's; every later one is an
    observation. `decode_prefill_rows_total` splits a bucket's rows into
    the context's and the padding."""
    from deeplearning4j_tpu.zoo.models import transformer_lm
    net = transformer_lm(vocab_size=24, d_model=32, n_layers=1, n_heads=2,
                         seed=3, causal=True).init()
    mreg = MetricsRegistry()
    eng = DecodeEngine(net, slots=2, max_len=64, registry=mreg)
    cache = eng.init_cache()
    for n in (5, 20, 7):                # buckets 16, 32, 16
        cache, _, _ = eng.prefill(cache, 0, list(range(1, n + 1)))
    ids = np.zeros((2,), np.int32)
    for _ in range(3):
        cache, ids, _ = eng.step(cache, ids)
    assert _by_program(mreg, "count") == {"prefill:16": 1, "step": 2}
    assert mreg.get("jit_compiles_total").get() == 3
    rows = mreg.get("decode_prefill_rows_total")
    assert rows.get(kind="prompt") == 5 + 20 + 7
    assert rows.get(kind="padding") == (16 - 5) + (32 - 20) + (16 - 7)
    assert bucket_for_len(20, 64) == 32
    # the sums by program are scraped as a counter
    assert ('decode_program_ms_total{program="step"}'
            in mreg.to_prometheus())


def test_histogram_max_survives_the_reservoirs_turnover():
    h = MetricsRegistry().histogram("t_ms", "test")
    h.reservoir_cap = 4
    h.observe(900.0, program="step")
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        h.observe(v, program="step")
    h.observe(7.0, program="prefill:16")
    # the percentiles' shape stays the reservoir's (2..5 are left) ...
    assert h.percentiles(program="step")["p50"] == 4.0
    assert h.percentiles(program="step")["max"] == 5.0
    # ... and the life's maximum is read by itself
    assert h.max(program="step") == 900.0 and h.max() == 900.0
    assert h.max(program="prefill:16") == 7.0
    assert h.max(program="verify:4") is None
    (_, step), = [s for s in h.series() if s[0] == {"program": "step"}]
    assert step["max"] == 900.0 and step["count"] == 6


# -------------------------------------------------------------- the stall
def _stall_run(clock, step_s, tracer=None, on_read=None, new_tokens=40):
    records = []
    logger = StructuredLogger(sinks=[records.append],
                              registry=MetricsRegistry())
    sched, mreg, eng = _stub_scheduler(clock, step_s=step_s, tracer=tracer,
                                       logger=logger)
    eng.on_read = on_read
    fut = sched.submit(list(range(5)), max_new_tokens=new_tokens)
    _drain(sched, [fut])
    return mreg, [r for r in records if r["message"] == "decode_stall"]


def test_a_stalled_step_is_counted_once_and_its_record_names_the_suspects(
        clock):
    """Step 20 of 39 takes 2 s where the others take 5 ms, and a collection
    of the oldest generation runs while the host waits for it."""
    def collect(pending):
        if pending.seconds == 2.0:
            collected.append(gc.collect())

    collected = []
    tracer = Tracer()
    mreg, stalls = _stall_run(
        clock, lambda i: 2.0 if i == 20 else 0.005, tracer=tracer,
        on_read=collect)
    assert mreg.get("decode_stalls_total").get(program="step") == 1
    assert mreg.get("decode_stalls_total").get() == 1
    (record,) = stalls
    assert record["level"] == "warning"
    f = record["fields"]
    assert f["program"] == "step"
    assert f["wall_ms"] == pytest.approx(2000.0)
    # the host sat in the read for all of it: the device was slow, not the
    # loop (its own phases take no time on a manual clock)
    assert f["waited_ms"] == pytest.approx(2000.0)
    assert f["host_phases_ms"] == 0.0
    assert f["phases_ms"]["decode_step_sync_ms"] >= 2000.0
    assert collected and f["gc_collections"][2] >= 1
    assert f["compiles"] == 0 and f["queue_depth"] == 0 \
        and f["active_slots"] == 1
    (span,) = [s for s in tracer.finished_spans()
               if s.name == "decode_stall"]
    assert span.attributes["program"] == "step" \
        and span.attributes["wall_ms"] == pytest.approx(2000.0)
    assert mreg.get("decode_program_ms").max(program="step") \
        == pytest.approx(2000.0)


@pytest.mark.parametrize("step_s", [
    lambda i: 0.2 if i == 20 else 0.005,    # 40 x the median, under 250 ms
    lambda i: 0.3 if i == 20 else 0.1,      # over 250 ms, 3 x the median
], ids=["under_the_floor", "under_the_factor"])
def test_no_stall_below_either_threshold(clock, step_s):
    assert 200.0 < STALL_FLOOR_MS and 3.0 < STALL_FACTOR
    mreg, stalls = _stall_run(clock, step_s)
    assert mreg.get("decode_stalls_total").get() == 0 and stalls == []


def test_a_burst_read_late_does_not_make_the_next_wait_a_stall(clock):
    """Eight prompts into a drained loop: the host reads the first token
    once admission has enqueued all eight, so the first reading holds the
    burst's time and the others none; the median falls to nothing, the mean
    stays true, and a later prefill that takes 1.2 x its time is no stall
    (on the chip: `mellum2_code_decode`'s ramp, 48 prompts of 0.2 s)."""
    sched, mreg, eng = _stub_scheduler(clock, prefill_s={16: 0.3}, slots=8)
    eng.enqueue_s = 0.3     # a prompt's enqueue as long as its program
    _drain(sched, [sched.submit([1, 2, 3], max_new_tokens=1)
                   for _ in range(8)])
    ledger = mreg.get("decode_program_ms")
    assert ledger.percentile(0.5, program="prefill:16") == 0.0
    assert ledger.sum(program="prefill:16") == pytest.approx(8 * 300.0)
    eng.enqueue_s, eng.prefill_s = 0.0, {16: 0.36}
    _drain(sched, [sched.submit([1, 2, 3], max_new_tokens=1)])
    assert ledger.max(program="prefill:16") == pytest.approx(8 * 300.0)
    assert mreg.get("decode_stalls_total").get() == 0


def test_a_stall_shows_as_a_profiler_annotation(clock, monkeypatch):
    from deeplearning4j_tpu.telemetry import trace as trace_mod
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(self.name)

        def __exit__(self, *exc):
            pass
    monkeypatch.setattr(trace_mod, "_TraceAnnotation", Annotation)
    _stall_run(clock, lambda i: 2.0 if i == 20 else 0.005)
    assert seen.count("dl4j:decode_stall") == 1


def test_an_alert_rule_on_the_stall_counter_fires(clock):
    """README's operator rule: any stall among the last five minutes'
    requests."""
    from deeplearning4j_tpu.telemetry import AlertEngine, AlertRule
    sched, mreg, _ = _stub_scheduler(
        clock, step_s=lambda i: 2.0 if i == 20 else 0.005)
    alerts = AlertEngine(registry=mreg, rules=[AlertRule(
        "decode_stall", "ratio", numerator="decode_stalls_total",
        denominator="decode_requests_total", threshold=0.0, window_s=300)])
    assert alerts.evaluate() == []
    _drain(sched, [sched.submit(list(range(5)), max_new_tokens=40)])
    (event,) = alerts.evaluate()
    assert (event["rule"], event["state"], event["value"]) \
        == ("decode_stall", "firing", 1.0)


# ------------------------------------------------------ a request's stages
def test_a_requests_stages_share_its_trace_id_and_add_up():
    """generate -> {generate_front, decode_queue_wait, decode_first_token,
    decode_generate}: one trace id; the queue's wait and the first token's
    stage are `ttft_ms` (enqueue to first token) to the clock's precision,
    and with the handler's own time and the generation they account for at
    least 95 % of the handler's wall."""
    from deeplearning4j_tpu.serving import ServingServer
    from deeplearning4j_tpu.zoo.models import transformer_lm
    net = transformer_lm(vocab_size=24, d_model=32, n_layers=2, n_heads=2,
                         seed=3, causal=True).init()
    srv = ServingServer(decode=True, decode_slots=2, decode_max_len=128)
    srv.registry.register("v1", net)
    srv.deploy("v1")
    srv.start()

    def post(n):
        req = urllib.request.Request(
            srv.url + "/generate", method="POST",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_new_tokens": n}).encode(),
            headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req, timeout=120).read())
    try:
        post(4)                                     # compiles
        srv.tracer.clear()
        body = post(60)
        for _ in range(200):    # the handler's own span closes after it
            spans = srv.tracer.finished_spans()     # has written the answer
            if any(s.name == "generate" for s in spans):
                break
            threading.Event().wait(0.01)
        first = srv.metrics.registry.get("decode_first_token_ms")
    finally:
        srv.stop()
    root = next(s for s in spans if s.name == "generate")
    kids = {s.name: s for s in spans if s.parent_id == root.span_id}
    stages = ("generate_front", "decode_queue_wait", "decode_first_token",
              "decode_generate")
    assert set(stages) <= set(kids)
    assert {kids[n].trace_id for n in stages} == {root.trace_id}
    ms = {n: kids[n].duration_ms for n in stages}
    ms["generate_front"] -= kids["generate_front"].attributes["paused_ms"]
    assert ms["decode_queue_wait"] + ms["decode_first_token"] \
        == pytest.approx(body["ttft_ms"], abs=1e-6)
    wall = root.duration_ms
    assert 1.01 * wall >= sum(ms.values()) >= 0.95 * wall
    assert body["server_ms"] <= wall
    assert kids["decode_first_token"].attributes == {
        "slot": kids["decode_queue_wait"].attributes["slot"],
        "bucket": 16, "n_prompt": 3}
    assert kids["decode_generate"].attributes == {
        "n_tokens": 60, "finish_reason": "length"}
    # the first token's stage is also a histogram, one observation a request
    assert first.count() == 2
    assert first.sum() >= ms["decode_first_token"]


# --------------------------------------------------------------- readers
def _obs(before, after, seconds=45.0):
    return {"before": before, "after": after, "window": {"seconds": seconds}}


SHARE, PADDING = "decode_prefill_program_share_pct", \
    "decode_prefill_padding_pct"
STEP, P256, P512 = ('decode_program_ms_total{program="%s"}' % p
                    for p in ("step", "prefill:256", "prefill:512"))
PROMPT, PAD = ('decode_prefill_rows_total{kind="%s"}' % k
               for k in ("prompt", "padding"))


@pytest.mark.parametrize("name,before,after,value", [
    (SHARE, {STEP: 1000.0, P256: 500.0},
     {STEP: 31000.0, P256: 6500.0, P512: 4000.0, "decode_program_ms_total":
      41500.0}, 25.0),
    # a window of steps alone: no prefill ran, which is a reading
    (SHARE, {STEP: 1000.0, P256: 500.0}, {STEP: 31000.0, P256: 500.0}, 0.0),
    # a window in which no program ran at all has no share
    (SHARE, {STEP: 1000.0, P256: 500.0}, {STEP: 1000.0, P256: 500.0}, None),
    (PADDING, {PROMPT: 100, PAD: 100}, {PROMPT: 820, PAD: 380}, 28.0),
    # every prompt filled its bucket
    (PADDING, {PROMPT: 100, PAD: 100}, {PROMPT: 612, PAD: 100}, 0.0),
    (PADDING, {PROMPT: 100, PAD: 100}, {PROMPT: 100, PAD: 100}, None),
])
def test_reader_on_hand_made_snapshots(name, before, after, value):
    reader = load_reader(name)
    assert reader.read(_obs(before, after)) == (
        None if value is None else pytest.approx(value))
    # a program without the instrument (the parent commit): nothing to read
    assert reader.read(_obs({}, {})) is None
    assert reader.SOURCE == "program_counter" \
        and reader.MOVES == "serve_tokens_per_s"


def test_readers_read_what_the_harness_snapshot_holds():
    """`benchmarks.observe.snapshot` of a registry the engine and the
    scheduler have written: the two readers find their series in it, and
    the histograms that wait for a reader (ROADMAP R-B1 (4)(a)) are there
    in total."""
    from benchmarks import observe
    models = ModelRegistry()
    mreg = MetricsRegistry()
    sched = DecodeScheduler(models, mreg, slots=2, max_len=64)
    eng = _StubEngine(ManualClock(), mreg, 2, 0.005, {})
    rows = eng._m_rows
    before = observe.snapshot(mreg)
    eng.observe_wall("decode_step", 6.0)
    eng.observe_wall("decode_step", 8.0)
    eng.observe_wall("decode_prefill:32", 6.0)
    rows.inc(20, kind="prompt")
    rows.inc(12, kind="padding")
    sched.m_first_token.observe(11.0)
    after = observe.snapshot(mreg)
    obs = _obs(before, after, seconds=0.05)
    assert {n: load_reader(n).read(obs) for n in (SHARE, PADDING)} == {
        SHARE: pytest.approx(30.0), PADDING: pytest.approx(37.5)}
    assert after["decode_program_ms"]["sum"] == pytest.approx(20.0)
    assert after["decode_first_token_ms"] == {"count": 1, "sum": 11.0,
                                              "p50": 11.0}
