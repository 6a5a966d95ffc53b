"""Unified telemetry subsystem tests: structured tracing (span nesting,
cross-thread propagation, Chrome-trace export round-trip), the central
MetricsRegistry (counters/gauges/histograms, exact-bucket percentiles),
Prometheus text exposition, XLA compile accounting, the deterministic
time_source clock, listener coverage (PerformanceListener, ProfilerListener
with a mocked profiler, TelemetryListener), and the serving/UI scrape +
trace endpoints (acceptance criteria)."""
import json
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.telemetry import (CompileTracker, MetricsRegistry,
                                          TelemetryListener, Tracer,
                                          get_registry, render_prometheus)
from deeplearning4j_tpu.telemetry.trace import NOOP_SPAN, current_span
from deeplearning4j_tpu.util.time_source import (ManualClock,
                                                 TimeSourceProvider,
                                                 monotonic_s, now_ms)


@pytest.fixture
def manual_clock():
    clock = ManualClock(start_s=1000.0)
    TimeSourceProvider.set_instance(clock)
    try:
        yield clock
    finally:
        TimeSourceProvider.reset()


# ------------------------------------------------------------------ tracing

def test_span_nesting_parent_ids_and_attributes():
    t = Tracer()
    with t.span("root", kind="test") as root:
        assert current_span() is root
        with t.span("child") as child:
            assert child.parent_id == root.span_id
            assert child.trace_id == root.trace_id
            with t.span("grandchild") as g:
                assert g.parent_id == child.span_id
        assert current_span() is root
    assert current_span() is None
    assert root.duration_ms is not None
    assert root.attributes["kind"] == "test"


def test_spans_on_different_threads_do_not_nest_implicitly():
    t = Tracer()
    seen = {}

    def worker():
        seen["span"] = current_span()

    with t.span("root"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    assert seen["span"] is None     # thread-local, not process-global


def test_explicit_parent_propagates_across_threads():
    t = Tracer()
    with t.span("request") as root:
        ctx = t.current()

    def consumer():
        s = t.start_span("dispatch", parent=ctx)
        s.end()
        return s

    th_result = []
    th = threading.Thread(target=lambda: th_result.append(consumer()))
    th.start()
    th.join()
    assert th_result[0].parent_id == root.span_id


def test_record_span_retroactive(manual_clock):
    t = Tracer()
    t0 = monotonic_s()
    manual_clock.advance(0.25)
    s = t.record_span("queued", t0, monotonic_s())
    assert s.duration_ms == pytest.approx(250.0)


def test_chrome_trace_export_round_trip():
    t = Tracer()
    with t.span("a"):
        with t.span("b"):
            with t.span("c"):
                pass
    text = json.dumps(t.to_chrome_trace())
    trace = json.loads(text)                    # valid JSON
    ev = trace["traceEvents"]
    assert len(ev) == 3
    by_id = {e["args"]["span_id"]: e for e in ev}
    c = next(e for e in ev if e["name"] == "c")
    b = by_id[c["args"]["parent_id"]]
    a = by_id[b["args"]["parent_id"]]
    assert (a["name"], b["name"]) == ("a", "b")
    assert a["args"]["parent_id"] is None
    for e in ev:
        assert e["ph"] == "X" and e["dur"] >= 0


def test_tracer_export_to_file(tmp_path):
    t = Tracer()
    with t.span("only"):
        pass
    p = t.export(tmp_path / "trace.json")
    assert json.loads(open(p).read())["traceEvents"][0]["name"] == "only"


def test_disabled_tracer_is_noop_and_cheap():
    t = Tracer(enabled=False)
    s = t.span("x")
    assert s is NOOP_SPAN
    with s:
        assert current_span() is None
    assert t.finished_spans() == []
    assert t.record_span("y", 0, 1) is NOOP_SPAN


def test_tracer_ring_buffer_bounded():
    t = Tracer(max_spans=4)
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    spans = t.finished_spans()
    assert len(spans) == 4
    assert [s.name for s in spans] == ["s6", "s7", "s8", "s9"]
    assert t.dropped == 6


# ----------------------------------------------------------------- registry

def test_counter_labels_and_atomiccounter_compat():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "help")
    c.add(3)                      # AtomicCounter spelling
    c.inc(2, bucket="8")
    assert c.get() == 5           # unlabeled read sums all series
    assert c.get(bucket="8") == 2
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create is idempotent; a kind clash raises
    assert reg.counter("reqs_total") is c
    with pytest.raises(TypeError):
        reg.gauge("reqs_total")


def test_gauge_set_and_callback():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(4)
    assert g.get() == 4
    cb = reg.gauge("cb_depth", fn=lambda: 7.0)
    assert cb.get() == 7.0
    broken = reg.gauge("broken", fn=lambda: 1 / 0)
    assert broken.get() is None
    assert broken.series() == []  # dead callback must not kill a scrape


def test_histogram_exact_percentiles_and_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", buckets=(1, 10, 100))
    for v in (0.5, 5, 50, 500):
        h.observe(v)
    assert h.count() == 4
    assert h.sum() == pytest.approx(555.5)
    assert h.percentile(0.0) == 0.5
    assert h.percentile(1.0) == 500
    ((labels, data),) = h.series()
    assert labels == {}
    assert data["buckets"] == [(1.0, 1), (10.0, 2), (100.0, 3),
                               (float("inf"), 4)]   # cumulative
    p = h.percentiles()
    assert p["count"] == 4 and p["max"] == 500


def test_histogram_reservoir_bounded_most_recent():
    reg = MetricsRegistry()
    h = reg.histogram("r_ms")
    h.reservoir_cap = h.RESERVOIR
    for v in range(h.RESERVOIR + 100):
        h.observe(float(v))
    assert h.count() == h.RESERVOIR + 100      # total count is unbounded
    assert h.percentile(0.0) == 100.0          # oldest 100 evicted


def test_registry_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("a_total").inc(1)
    reg.counter("b_total").inc(2, k="v")
    reg.gauge("g").set(3)
    reg.histogram("h").observe(10)
    snap = reg.snapshot()
    assert snap["a_total"] == 1
    assert snap["b_total"] == {"k=v": 2}
    assert snap["g"] == 3.0
    assert snap["h"]["count"] == 1 and snap["h"]["p50"] == 10.0
    json.dumps(snap)               # JSON-serializable end to end


# --------------------------------------------------------------- prometheus

def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", 'served "ok"\nrequests')
    c.inc(5)
    c.inc(2, route="/predict", code="200")
    reg.gauge("queue_depth", fn=lambda: 3)
    h = reg.histogram("latency_ms", buckets=(10, 100))
    h.observe(7)
    h.observe(70)
    text = render_prometheus(reg)
    lines = text.splitlines()
    # OpenMetrics: counter FAMILY drops the _total suffix, samples keep it
    assert "# TYPE requests counter" in lines
    assert '# HELP requests served "ok"\\nrequests' in lines
    assert "requests_total 5" in lines
    assert 'requests_total{code="200",route="/predict"} 2' in lines
    assert "# TYPE queue_depth gauge" in lines and "queue_depth 3" in lines
    assert 'latency_ms_bucket{le="10"} 1' in lines
    assert 'latency_ms_bucket{le="100"} 2' in lines
    assert 'latency_ms_bucket{le="+Inf"} 2' in lines
    assert "latency_ms_sum 77" in lines
    assert "latency_ms_count 2" in lines
    assert lines[-1] == "# EOF" and text.endswith("\n")


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.counter("x_total").inc(1, path='a"b\\c')
    text = render_prometheus(reg)
    assert 'x_total{path="a\\"b\\\\c"} 1' in text


# -------------------------------------------------------------- time source

def test_manual_clock_drives_wall_and_monotonic(manual_clock):
    t0_wall, t0_mono = now_ms(), monotonic_s()
    manual_clock.advance(2.5)
    assert now_ms() - t0_wall == 2500
    assert monotonic_s() - t0_mono == pytest.approx(2.5)


def test_stats_reports_use_time_source(manual_clock):
    from deeplearning4j_tpu.ui.stats import ServingStatsReport
    r = ServingStatsReport("s", {"requests": 1})
    assert r.data["time"] == pytest.approx(1000.0)


# ------------------------------------------------------- compile accounting

def test_compile_tracker_counts_and_by_bucket():
    reg = MetricsRegistry()
    ct = CompileTracker(reg)
    ct.record(100.0, bucket=4, phase="serve")
    ct.record(50.0, bucket=8, phase="serve")
    ct.record(25.0, bucket=8, phase="warmup")
    assert ct.total() == 3
    assert ct.total_ms() == pytest.approx(175.0)
    text = render_prometheus(reg)
    assert 'compiles_total{bucket="8",phase="serve"} 1' in text
    assert "compile_ms_total 175" in text


def test_timed_first_call_records_once_and_delegates_attrs():
    reg = MetricsRegistry()
    from deeplearning4j_tpu.telemetry.xla import timed_first_call
    calls = []

    def fn(x):
        calls.append(x)
        return x * 2
    fn.custom_attr = "yes"
    wrapped = timed_first_call(fn, "unit", registry=reg)
    assert wrapped(3) == 6 and wrapped(4) == 8
    assert wrapped.custom_attr == "yes"        # attribute pass-through
    assert reg.counter("jit_compiles_total").get() == 1
    assert reg.counter("jit_compiles_total").get(fn="unit") == 1


# ---------------------------------------------------------------- listeners

class _Model:
    score_value = 0.5
    params = None


def test_performance_listener_deterministic_with_manual_clock(manual_clock):
    from deeplearning4j_tpu.optimize.listeners import PerformanceListener
    reg = MetricsRegistry()
    logs = []
    pl = PerformanceListener(frequency=1, log_fn=logs.append, registry=reg)
    m = _Model()
    pl.record_batch_size(32)
    pl.iteration_done(m, 1)                # primes the clock
    pl.record_batch_size(32)
    manual_clock.advance(0.5)
    pl.iteration_done(m, 2)
    assert pl.last_iteration_ms == pytest.approx(500.0)
    assert pl.last_batches_per_sec == pytest.approx(2.0)
    # the priming iteration does not reset _samples_since, so the first
    # measured window covers both recorded batches (64 rows / 0.5 s)
    assert pl.last_samples_per_sec == pytest.approx(128.0)
    assert logs and "500.00 ms/iter" in logs[0]
    assert reg.counter("training_samples_total").get() == 64
    assert reg.histogram("training_iteration_ms").count() == 1
    assert reg.gauge("training_samples_per_sec").get() == pytest.approx(128.0)


class _FakeProfiler:
    def __init__(self):
        self.starts = 0
        self.stops = 0

    def start_trace(self, log_dir):
        self.starts += 1

    def stop_trace(self):
        self.stops += 1


@pytest.fixture
def fake_profiler(monkeypatch):
    import jax
    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    return fake


def test_profiler_listener_normal_window(fake_profiler, tmp_path):
    from deeplearning4j_tpu.ui.stats import ProfilerListener
    pl = ProfilerListener(tmp_path, start_iteration=2, n_iterations=2)
    m = _Model()
    for i in range(1, 6):
        pl.iteration_done(m, i)
    assert fake_profiler.starts == 1 and fake_profiler.stops == 1
    pl.close()                               # idempotent: window already shut
    assert fake_profiler.stops == 1


def test_profiler_listener_no_leak_when_training_ends_early(fake_profiler,
                                                           tmp_path):
    """Regression: training that ends inside the trace window used to leak
    an active jax.profiler trace; epoch end (and close()) must stop it."""
    from deeplearning4j_tpu.ui.stats import ProfilerListener
    pl = ProfilerListener(tmp_path, start_iteration=1, n_iterations=100)
    m = _Model()
    pl.iteration_done(m, 1)                  # trace starts, window never ends
    assert fake_profiler.starts == 1 and fake_profiler.stops == 0
    pl.on_epoch_end(m)                       # last reliable hook
    assert fake_profiler.stops == 1
    assert not pl._active
    pl.close()
    assert fake_profiler.stops == 1          # close() after stop is a no-op


def test_telemetry_listener_flushes_registry_into_router():
    from deeplearning4j_tpu.ui.storage import CollectionStatsStorageRouter
    reg = MetricsRegistry()
    router = CollectionStatsStorageRouter()
    tl = TelemetryListener(router=router, registry=reg, frequency=2,
                           session_id="tele")
    m = _Model()
    for i in range(1, 5):
        tl.iteration_done(m, i)
    assert reg.counter("training_iterations_total").get() == 4
    assert len(router.updates) == 2          # every 2nd iteration
    d = router.updates[-1].data
    assert d["type"] == "telemetry" and d["session_id"] == "tele"
    assert d["metrics"]["training_iterations_total"] == 4


def test_telemetry_listener_tolerates_broken_router():
    class Broken:
        def put_update(self, r):
            raise RuntimeError("down")
    tl = TelemetryListener(router=Broken(), registry=MetricsRegistry(),
                           frequency=1)
    tl.iteration_done(_Model(), 1)           # must not raise


def test_stats_listener_mirrors_into_registry():
    from deeplearning4j_tpu.ui.stats import StatsListener
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
    reg = MetricsRegistry()
    sl = StatsListener(InMemoryStatsStorage(), session_id="s",
                       collect_params=False, collect_gradients=False,
                       collect_memory=False, registry=reg)

    class M(_Model):
        def param_table(self):
            return {}

        def num_params(self):
            return 0
    for i in range(1, 4):
        sl.iteration_done(M(), i)
    assert reg.histogram("training_iteration_ms").count() == 2
    assert reg.gauge("training_score").get() == pytest.approx(0.5)


# --------------------------------------------------- serving metrics compat

def test_serving_metrics_snapshot_backcompat_and_prometheus():
    from deeplearning4j_tpu.serving import ServingMetrics
    sm = ServingMetrics()
    sm.record_batch(4, n_requests=2, n_rows=3)
    sm.record_latency(5.0)
    sm.record_latency(15.0)
    snap = sm.snapshot(queue_depth=1)
    assert snap["requests"] == 2 and snap["rows"] == 3
    assert snap["batches"] == 1
    assert snap["batch_size_histogram"] == {"4": 1}
    assert snap["latency_ms"]["count"] == 2
    assert snap["latency_ms"]["p50"] == 5.0
    text = sm.to_prometheus()
    assert "requests_total 2" in text
    assert 'batch_size_total{bucket="4"} 1' in text
    assert "latency_ms_count 2" in text


# ------------------------------------------------- acceptance: live serving

class StubModel:
    def output(self, x):
        return np.asarray(x) * 2.0


def test_serving_prometheus_scrape_and_span_tree_acceptance():
    """Acceptance: GET /metrics?format=prometheus on a live ServingServer
    returns valid exposition text including requests_total, the latency_ms
    histogram, compiles_total, and the queue-depth gauge; a traced /predict
    yields a predict->admission span tree plus a batch span (own trace)
    LINKED to the request — exported as valid Chrome-trace JSON with
    flow events connecting request and batch lanes."""
    from deeplearning4j_tpu.serving import ServingServer
    server = ServingServer(StubModel(), port=0).start()
    try:
        for rows in (1, 3, 2):
            x = np.ones((rows, 4), dtype=np.float32)
            req = urllib.request.Request(
                server.url + "/predict",
                data=json.dumps({"data": x.tolist()}).encode())
            with urllib.request.urlopen(req, timeout=30) as r:
                json.loads(r.read())

        with urllib.request.urlopen(server.url + "/metrics?format=prometheus",
                                    timeout=30) as r:
            # exemplars ride the exposition, so it must declare (and be)
            # OpenMetrics — the classic text/plain parser rejects them
            assert r.headers["Content-Type"].startswith(
                "application/openmetrics-text")
            text = r.read().decode()
        assert "requests_total 3" in text
        assert "latency_ms_bucket" in text and "latency_ms_count 3" in text
        assert "compiles_total" in text
        assert "queue_depth 0" in text
        # JSON stays the default for back-compat
        with urllib.request.urlopen(server.url + "/metrics", timeout=30) as r:
            snap = json.loads(r.read())
        assert snap["requests"] == 3 and snap["compiles"] >= 2

        with urllib.request.urlopen(server.url + "/trace", timeout=30) as r:
            trace = json.loads(r.read())        # valid JSON
        ev = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        by_id = {e["args"]["span_id"]: e for e in ev}
        chains = 0
        for e in ev:
            if e["name"] != "dispatch":
                continue
            batch = by_id.get(e["args"]["parent_id"])
            assert batch is not None and batch["name"] == "batch"
            # the batch span is the root of its OWN trace: requests attach
            # by span links, not parent edges
            assert batch["args"]["parent_id"] is None
            chains += 1
        assert chains >= 3                      # one dispatch per request
        admissions = [e for e in ev if e["name"] == "admission"]
        assert admissions and all(
            by_id[a["args"]["parent_id"]]["name"] == "predict"
            for a in admissions)
        # every admission span names the batch that served its request, and
        # the link exports as a flow-event pair (request lane <-> batch lane)
        batch_ids = {e["args"]["span_id"] for e in ev if e["name"] == "batch"}
        assert all(a["args"]["batch_span_id"] in batch_ids
                   for a in admissions)
        flows = [e for e in trace["traceEvents"] if e.get("cat") == "link"]
        assert flows and {e["ph"] for e in flows} == {"s", "f"}
    finally:
        server.stop()


def test_batcher_compile_accounting_once_per_bucket():
    """The first dispatch of a new (signature, bucket) is the compile; the
    steady state must add none."""
    from deeplearning4j_tpu.serving import ServingServer
    server = ServingServer(StubModel(), max_latency_ms=1.0)
    server.batcher.start()
    try:
        rng = np.random.default_rng(0)
        for rows in (3, 4):                     # both pad to bucket 4
            server.predict(rng.normal(size=(rows, 5)).astype(np.float32))
        assert server.compile_tracker.total() == 1
        for rows in (3, 4, 3):
            server.predict(rng.normal(size=(rows, 5)).astype(np.float32))
        assert server.compile_tracker.total() == 1
        server.predict(rng.normal(size=(2, 5)).astype(np.float32))
        assert server.compile_tracker.total() == 2
        assert server.compile_tracker.by_bucket() != {}
    finally:
        server.stop()


# --------------------------------------------------------------- UI scrape

def test_ui_server_metrics_endpoint_json_and_prometheus():
    from deeplearning4j_tpu.ui import UIServer
    reg = MetricsRegistry()
    reg.counter("training_iterations_total").inc(7)
    server = UIServer(port=0, registry=reg).start()
    try:
        with urllib.request.urlopen(server.url + "/metrics", timeout=30) as r:
            snap = json.loads(r.read())
        assert snap["training_iterations_total"] == 7
        with urllib.request.urlopen(
                server.url + "/metrics?format=prometheus", timeout=30) as r:
            text = r.read().decode()
        assert "training_iterations_total 7" in text
    finally:
        server.stop()


def test_ui_overview_ignores_telemetry_reports():
    """Telemetry registry flushes must not pollute the training overview."""
    from deeplearning4j_tpu.ui import UIServer, InMemoryStatsStorage
    storage = InMemoryStatsStorage()
    storage.put_update({"type": "telemetry", "session_id": "s",
                        "metrics": {}})
    storage.put_update({"type": "stats", "session_id": "s", "iteration": 1,
                        "score": 0.25})
    server = UIServer(port=0).attach(storage).start()
    try:
        with urllib.request.urlopen(server.url + "/train/overview?sid=s",
                                    timeout=30) as r:
            ov = json.loads(r.read())
        assert ov["scores"] == [0.25]
    finally:
        server.stop()


# -------------------------------------------------------------- smoke tool

def test_smoke_telemetry_tool():
    """Fast variant of tools/smoke_telemetry.py: serve requests, assert a
    non-empty prometheus scrape and a valid, nested Chrome-trace export."""
    import tools.smoke_telemetry as smoke
    out = smoke.run(n_requests=8, concurrency=4)
    assert out["requests"] == 8
    assert out["span_tree_depth"] >= 2
    assert out["span_link_flows"] > 0
    assert out["scrape_bytes"] > 0


def test_serving_dispatches_under_manual_clock(manual_clock):
    """Regression: a frozen ManualClock (the deterministic-test setup) must
    not make the batcher's coalescing window spin forever — the real-time
    condition wait bounds it."""
    from deeplearning4j_tpu.serving import ServingServer
    server = ServingServer(StubModel(), max_latency_ms=5.0)
    server.batcher.start()
    try:
        res = server.predict(np.ones((2, 3), dtype=np.float32), wait_s=30.0)
        assert res["prediction"].shape == (2, 3)
    finally:
        server.stop()


def test_enable_tracing_flips_default_tracer_in_place():
    """Regression: components capture get_tracer() at construction;
    enable_tracing() must enable that same instance, not swap in a new one."""
    from deeplearning4j_tpu.telemetry import enable_tracing, get_tracer
    captured = get_tracer()
    was_enabled = captured.enabled
    try:
        t = enable_tracing()
        assert t is captured and captured.enabled
    finally:
        captured.enabled = was_enabled


def test_batcher_failed_dispatch_span_is_exported():
    """A model error must still finish the dispatch span (tagged error) —
    the failing dispatch is what an operator looks for in /trace."""
    from deeplearning4j_tpu.serving import ServingServer

    class Broken:
        def output(self, x):
            raise ValueError("bad feature count")

    server = ServingServer(Broken(), max_latency_ms=1.0)
    server.batcher.start()
    try:
        with pytest.raises(ValueError):
            server.predict(np.ones((1, 3), dtype=np.float32), wait_s=30.0)
        names = [s.name for s in server.tracer.finished_spans()]
        assert "dispatch" in names
        d = next(s for s in server.tracer.finished_spans()
                 if s.name == "dispatch")
        assert d.attributes.get("error") == "ValueError"
        assert d.end_mono is not None
    finally:
        server.stop()


def test_broker_stop_releases_depth_gauge():
    from deeplearning4j_tpu.streaming.broker import MessageBroker
    reg = MetricsRegistry()
    broker = MessageBroker(port=0, registry=reg).start()
    broker._topic("t")
    assert reg.gauge("streaming_topic_depth").get() == {"t": 0}
    broker.stop()
    assert reg.gauge("streaming_topic_depth").get() == {}


def test_streaming_broker_registers_central_metrics():
    from deeplearning4j_tpu.streaming.broker import BrokerClient, MessageBroker
    reg = MetricsRegistry()
    broker = MessageBroker(port=0, registry=reg).start()
    try:
        client = BrokerClient(port=broker.port)
        client.publish("t1", {"v": 1})
        client.publish("t1", {"v": 2})
        assert client.poll("t1")["v"] == 1
        assert reg.counter("streaming_published_total").get(topic="t1") == 2
        assert reg.counter("streaming_polled_total").get(topic="t1") == 1
        depths = reg.gauge("streaming_topic_depth").get()
        assert depths == {"t1": 1}
        client.close()
    finally:
        broker.stop()


# ------------------------------------------- phases: one site, three sinks

class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records enter/exit."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture
def fake_annotation(monkeypatch):
    from deeplearning4j_tpu.telemetry import trace as trace_mod
    _FakeAnnotation.log = []
    monkeypatch.setattr(trace_mod, "_TraceAnnotation", _FakeAnnotation)
    return _FakeAnnotation.log


def test_phase_lands_in_three_sinks_with_one_duration(manual_clock,
                                                      fake_annotation):
    """Tracer.phase: the profiler annotation "dl4j:<name>", the histogram
    and the ring span all come from ONE pair of clock reads."""
    t = Tracer()
    h = MetricsRegistry().histogram("work_ms", "test")
    with t.span("request") as root:
        with t.phase("work", histogram=h, parent=root, slot=3) as ph:
            assert current_span() is root       # a phase is never current
            manual_clock.advance(0.25)
    assert fake_annotation == [("enter", "dl4j:work"), ("exit", "dl4j:work")]
    assert h.count() == 1 and h.sum() == pytest.approx(250.0)
    assert ph.duration_ms == pytest.approx(250.0)
    span = next(s for s in t.finished_spans() if s.name == "work")
    assert span.duration_ms == pytest.approx(250.0)
    assert span.parent_id == root.span_id and span.trace_id == root.trace_id
    assert span.attributes == {"slot": 3}


def test_phase_disabled_tracer_observes_histogram_allocates_no_span(
        manual_clock, fake_annotation, monkeypatch):
    from deeplearning4j_tpu.telemetry import trace as trace_mod

    def no_span(*a, **k):
        raise AssertionError("a disabled tracer allocated a Span")
    monkeypatch.setattr(trace_mod, "Span", no_span)
    t = Tracer(enabled=False)
    h = MetricsRegistry().histogram("work_ms", "test")
    with t.phase("pass") as outer:
        with t.phase("work", histogram=h, fold=True):
            manual_clock.advance(0.002)
    t.record_span("waited", 1.0, 1.5, histogram=h)
    assert h.count() == 2 and h.sum() == pytest.approx(502.0)
    # the parts fold into their pass either way (its owner may read them:
    # the decode loop's stall record); only the ring depends on `enabled`
    assert outer.attributes == {"work_ms": pytest.approx(2.0)}
    assert t.finished_spans() == []
    assert ("enter", "dl4j:work") in fake_annotation    # still annotated


@pytest.mark.parametrize("case", ["fold", "cancel", "paused"])
def test_phase_fold_cancel_and_pause(case, manual_clock, fake_annotation):
    t = Tracer()
    h = MetricsRegistry().histogram("part_ms", "test")
    if case == "fold":
        # parts of a hot loop: one ring span a pass, parts as attributes
        with t.phase("pass"):
            for _ in range(2):
                with t.phase("part", histogram=h, fold=True):
                    manual_clock.advance(0.01)
        (span,) = t.finished_spans()
        assert span.name == "pass"
        assert span.attributes["part_ms"] == pytest.approx(20.0)
        assert h.count() == 2
    elif case == "cancel":
        with t.phase("part", histogram=h) as ph:
            manual_clock.advance(0.01)
            ph.cancel()
        assert h.count() == 0 and t.finished_spans() == []
        assert ph.duration_ms == pytest.approx(10.0)    # still measured
    else:
        with t.phase("part", histogram=h) as ph:
            manual_clock.advance(0.01)
            with ph.paused():
                manual_clock.advance(5.0)
            manual_clock.advance(0.02)
        assert h.sum() == pytest.approx(30.0)
        (span,) = t.finished_spans()
        assert span.duration_ms == pytest.approx(5030.0)
        assert span.attributes["paused_ms"] == pytest.approx(5000.0)
        # the annotation leaves the pause out: two segments
        assert fake_annotation == [("enter", "dl4j:part"),
                                   ("exit", "dl4j:part")] * 2


def _tiny_lm(use_pallas=False, d_model=32, vocab=24):
    from deeplearning4j_tpu.zoo.models import transformer_lm
    return transformer_lm(vocab_size=vocab, d_model=d_model, n_layers=2,
                          n_heads=2, seed=3, causal=True,
                          use_pallas=use_pallas).init()


def _tiny_scheduler(net, tracer, slots=2, max_len=64):
    from deeplearning4j_tpu.decode.scheduler import DecodeScheduler
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    registry = ModelRegistry()
    registry.register("v1", net)
    registry.deploy("v1")
    mreg = MetricsRegistry()
    return DecodeScheduler(registry, mreg, slots=slots, max_len=max_len,
                           tracer=tracer), mreg


def test_profiler_session_shows_decode_phases_on_a_host_line(tmp_path):
    """Under a real jax.profiler session the scheduler's phases are events
    of the profiler's own trace: dl4j:decode_wave encloses
    dl4j:decode_step_sync on a host line."""
    import glob
    import jax
    from jax.profiler import ProfileData
    sched, _ = _tiny_scheduler(_tiny_lm(), Tracer(enabled=False))
    sched.start()
    try:
        sched.generate([1, 2, 3], max_new_tokens=3)     # compiles
        jax.profiler.start_trace(str(tmp_path))
        try:
            sched.generate([1, 2, 3], max_new_tokens=6)
        finally:
            jax.profiler.stop_trace()
    finally:
        sched.stop()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    enclosed = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("dl4j:")]
            waves = [e for e in evs if e[0] == "dl4j:decode_wave"]
            for name, a, b in evs:
                if name == "dl4j:decode_step_sync":
                    enclosed += any(w[1] <= a and b <= w[2] for w in waves)
    # 5 steps; the last one's wave may still be open when the trace stops
    assert enclosed >= 3


def test_decode_phase_sums_add_up_to_the_wave():
    """Over N warm steps the parts cover the pass: their sum is at most the
    waves' and at least 90 % of it; decode_itl_ms has one observation a
    step of the one active slot, each from the previous result's arrival to
    this one's, so together they cover the waits for the ids and fit inside
    the passes; no pass of a greedy run reads probabilities to the host,
    and every step counts as greedy."""
    # a step of a few ms, so the parts' share is the loop's, not the
    # bookkeeping's: what a pass spends outside its parts is ~0.2 ms here
    # whatever the step (a 4 ms step reads 94-95 %, the 2 ms one this test
    # used to run 89-93 % — on either side of its 90 % from run to run)
    sched, mreg = _tiny_scheduler(_tiny_lm(d_model=512, vocab=4096),
                                  Tracer(enabled=False), slots=8)
    parts = ["decode_admit_ms", "decode_step_build_ms",
             "decode_step_dispatch_ms", "decode_step_sync_ms",
             "decode_prefill_sync_ms", "decode_emit_ms"]
    names = parts + ["decode_wave_ms", "decode_itl_ms",
                     "decode_probs_read_ms"]

    def sums():
        # generate() returns from inside the pass that emitted the last
        # token: read once that pass has closed and the loop stands idle
        read = lambda: {n: (mreg.get(n).sum(), mreg.get(n).count())
                        for n in names}
        seen = read()
        for _ in range(100):
            threading.Event().wait(0.05)
            again = read()
            if again == seen:
                return seen
            seen = again
        raise AssertionError("the scheduler's histograms never settled")
    sched.start()
    try:
        sched.generate(list(range(1, 9)), max_new_tokens=3)     # compiles
        before = sums()
        sched.generate(list(range(1, 9)), max_new_tokens=40)
        after = sums()
    finally:
        sched.stop()
    d = {n: after[n][0] - before[n][0] for n in names}
    steps = after["decode_step_sync_ms"][1] - before["decode_step_sync_ms"][1]
    assert steps == 39          # the first token comes from the prefill
    covered = sum(d[n] for n in parts)
    assert covered <= d["decode_wave_ms"]
    assert covered >= 0.9 * d["decode_wave_ms"], (covered, d)
    assert after["decode_itl_ms"][1] - before["decode_itl_ms"][1] == steps
    assert d["decode_step_sync_ms"] <= d["decode_itl_ms"] \
        <= d["decode_wave_ms"]
    assert after["decode_probs_read_ms"] == (0, 0)
    assert mreg.get("decode_steps_total").series() == [
        ({"sampler": "greedy"}, after["decode_step_sync_ms"][1])]
    for n in ("decode_queue_wait_ms", "decode_prefill_ms",
              "decode_prefill_sync_ms"):
        assert mreg.get(n).count() == 2


def test_counters_of_the_loop_that_keeps_a_step_in_flight():
    """`decode_steps_ahead_total{ahead}` sums to the count of
    `decode_step_sync_ms`, and of a busy loop's steps only the first was
    dispatched into a drained loop; `decode_itl_ms` has one observation an
    active slot a step; `decode_discarded_slot_steps_total` stays 0 through
    ends by length and is 1 after one stop id; `decode_tokens_total` is the
    tokens in the answers; and none of it compiles anything after the
    warm-up: one executable a label."""
    net = _tiny_lm()
    sched, mreg = _tiny_scheduler(net, Tracer(enabled=False), slots=2)
    count = lambda name, **l: mreg.get(name).get(**l)
    ahead = lambda a: count("decode_steps_ahead_total", ahead=a)
    sync = lambda: mreg.get("decode_step_sync_ms").count()
    itl = lambda: mreg.get("decode_itl_ms").count()

    def settled():
        # an answer leaves from inside a pass, and a step dispatched ahead
        # of an end by value is read a pass later: wait until nothing is owed
        for _ in range(200):
            if sched._flight is None and not sched.active_count():
                return
            threading.Event().wait(0.01)
        raise AssertionError("the loop never drained")

    answers = []

    def serve(*requests):
        futs = [sched.submit(p, max_new_tokens=n, stop_id=stop)
                for p, n, stop in requests]
        answers.extend(f.result(timeout=120) for f in futs)
        settled()
        return answers[-len(futs):]
    sched.start()
    try:
        serve(([1, 2, 3], 3, None))                     # compiles
        compiled = count("jit_compiles_total")
        before = ahead("0"), ahead("1"), itl()
        (alone,) = serve(([1, 2, 3], 12, None))
        # 11 steps after the prefill's token: the first into a drained loop
        assert (ahead("0"), ahead("1"), itl()) \
            == (before[0] + 1, before[1] + 10, before[2] + 11)
        before = itl(), sync()
        serve(([4, 5], 5, None), ([6], 7, None), ([7, 8, 9], 1, None))
        assert itl() - before[0] == 4 + 6       # a slot a step, no more
        assert sync() - before[1] == 6          # the two rode together
        assert count("decode_discarded_slot_steps_total") == 0
        cut = next(i for i in range(1, 12)
                   if alone["tokens"][i] not in alone["tokens"][:i])
        (stopped,) = serve(([1, 2, 3], 12, alone["tokens"][cut]))
        assert stopped["tokens"] == alone["tokens"][:cut + 1]
        assert count("decode_discarded_slot_steps_total") == 1
    finally:
        sched.stop()
    assert ahead("0") + ahead("1") == sync()
    assert count("decode_tokens_total") \
        == sum(len(a["tokens"]) for a in answers)
    assert count("jit_compiles_total") == compiled
    assert set(sched._engine.executable_counts().values()) == {1}


def test_generate_response_times_and_request_spans():
    """/generate answers queue_wait_ms <= ttft_ms <= server_ms; the
    request's trace holds generate -> {decode_queue_wait, decode_prefill,
    generate_front}; the front's histogram leaves the wait on the scheduler
    out."""
    from deeplearning4j_tpu.serving import ServingServer
    srv = ServingServer(decode=True, decode_slots=2, decode_max_len=64)
    srv.registry.register("v1", _tiny_lm())
    srv.deploy("v1")
    srv.start()
    try:
        req = urllib.request.Request(
            srv.url + "/generate", method="POST",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_new_tokens": 6}).encode(),
            headers={"Content-Type": "application/json"})
        body = json.loads(urllib.request.urlopen(req, timeout=120).read())
        front = srv.metrics.registry.get("generate_front_ms")
        # the answer leaves from inside the pass that emitted the last
        # token: that pass's span is recorded when the pass closes, and the
        # handler's own once it has written the answer
        for _ in range(200):
            spans = srv.tracer.finished_spans()
            if sum(s.name == "decode_wave" for s in spans) >= 7 \
                    and any(s.name == "generate" for s in spans):
                break
            threading.Event().wait(0.01)
    finally:
        srv.stop()
    assert len(body["tokens"]) == 6
    assert 0.0 <= body["queue_wait_ms"] <= body["ttft_ms"] \
        <= body["server_ms"]
    assert front.count() == 1 and front.sum() < body["server_ms"]
    root = next(s for s in spans if s.name == "generate")
    kids = {s.name: s for s in spans if s.parent_id == root.span_id}
    assert {"decode_queue_wait", "decode_prefill",
            "generate_front"} <= set(kids)
    assert kids["generate_front"].attributes["paused_ms"] > 0
    # one span a pass, its parts folded into it: the pass that admitted,
    # the one that read the first token, one for each of the 5 steps' ids
    waves = [s for s in spans if s.name == "decode_wave"]
    assert ["decode_admit_ms" in s.attributes for s in waves] \
        == [True] + [False] * 6
    assert ["decode_prefill_sync_ms" in s.attributes for s in waves] \
        == [False, True] + [False] * 5
    assert ["decode_step_sync_ms" in s.attributes for s in waves] \
        == [False] * 2 + [True] * 5
    assert not any(s.name == "decode_step_sync" for s in spans)


def _fit_probe_net(width=16):
    from deeplearning4j_tpu import (Adam, ComputationGraph, DenseLayer,
                                    InputType, NeuralNetConfiguration,
                                    OutputLayer)
    conf = (NeuralNetConfiguration.builder().seed(9).updater(Adam(1e-2))
            .graph_builder().add_inputs("in")
            .add_layer("d", DenseLayer(n_out=width, activation="relu"), "in")
            .add_layer("d2", DenseLayer(n_out=width, activation="relu"), "d")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="MCXENT"), "d2")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(8)).build())
    return ComputationGraph(conf).init()


def _fit_probe_sets(n, batch=16):
    from deeplearning4j_tpu import DataSet
    rng = np.random.default_rng(0)
    return [DataSet(rng.normal(size=(batch, 8)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])
            for _ in range(n)]


FIT_PARTS = ("fit_next_batch_ms", "fit_prepare_ms", "fit_dispatch_ms",
             "fit_listeners_ms")


def _fit_counts():
    reg = get_registry()
    ahead = reg.get("fit_executions_ahead_total")
    return {**{n: reg.get(n).count() if reg.get(n) else 0
               for n in FIT_PARTS},
            "0": ahead.get(ahead="0") if ahead else 0,
            "1": ahead.get(ahead="1") if ahead else 0}


def _traced_fit(net, it, K):
    """fit(it, steps_per_execution=K) with the default tracer on: the
    fit_execution spans and what each counter grew by."""
    from deeplearning4j_tpu.telemetry import enable_tracing, get_tracer
    tracer = get_tracer()
    was = tracer.enabled
    enable_tracing()
    try:
        tracer.clear()
        before = _fit_counts()
        net.fit(it, steps_per_execution=K)
        spans = [s for s in tracer.finished_spans()
                 if s.name == "fit_execution"]
    finally:
        tracer.enabled = was
    after = _fit_counts()
    return spans, {k: after[k] - before[k] for k in after}


def test_fit_steps_per_execution_counts_one_phase_each_per_execution():
    from deeplearning4j_tpu import ListDataSetIterator
    # 7 batches: 3 executions of 2 + a ragged tail
    spans, grew = _traced_fit(_fit_probe_net(),
                              ListDataSetIterator(_fit_probe_sets(7)), 2)
    # the first execution compiles: its dispatch stays with the compile
    # accounting (jit_compiles_total), not in fit_dispatch_ms, and like
    # every epoch's first it runs outside the fit_execution span
    assert [grew[n] for n in ("fit_prepare_ms", "fit_dispatch_ms",
                              "fit_listeners_ms")] == [3, 2, 3]
    assert len(spans) == 2 and spans[0].attributes["steps"] == 2
    assert all(set(FIT_PARTS) <= set(s.attributes) for s in spans)


@pytest.mark.parametrize("read,want", [
    (lambda h: h.count(), 3),
    (lambda h: h.sum(), 112.0),
    (lambda h: h.percentile(1.0), 100.0),
    (lambda h: h.percentiles()["count"], 3),
    (lambda h: [e["value"] for e in h.exemplars()], [5.0, 7.0, 100.0]),
], ids=["count", "sum", "percentile", "percentiles", "exemplars"])
def test_histogram_bare_read_totals_its_series(read, want):
    """A histogram read without labels covers every label-set, as
    Counter.get() does: a reader that names none (the benchmark's snapshot,
    an alert rule) must not read 0 beside `pipeline=<name>` observations."""
    h = MetricsRegistry().histogram("wait_ms")
    h.observe(5.0, trace_id="a", pipeline="prefetch")
    h.observe(7.0, trace_id="b", pipeline="prefetch")
    h.observe(100.0, trace_id="c")
    assert read(h) == want
    assert h.count(pipeline="prefetch") == 2 \
        and h.sum(pipeline="prefetch") == 12.0 \
        and h.percentile(1.0, pipeline="prefetch") == 7.0 \
        and h.count(pipeline="other") == 0 \
        and h.percentile(0.5, pipeline="other") is None


def test_registry_snapshot_totals_a_labeled_histogram():
    reg = MetricsRegistry()
    reg.histogram("wait_ms").observe(4.0, pipeline="a")
    reg.histogram("wait_ms").observe(6.0, pipeline="b")
    snap = reg.snapshot()["wait_ms"]
    assert snap["count"] == 2 and snap["sum"] == 10.0 and snap["max"] == 6.0


def test_phase_labels_name_the_histogram_series(manual_clock):
    """Tracer.phase(labels=) observes the series its labels name, through
    the one call site; folded into an enclosing phase as any other."""
    reg = MetricsRegistry()
    tracer = Tracer()
    h = reg.histogram("etl_consumer_wait_ms")
    with tracer.phase("outer"):
        with tracer.phase("etl_consumer_wait", histogram=h, fold=True,
                          labels={"pipeline": "p1"}):
            manual_clock.advance(0.004)
    with tracer.phase("plain", histogram=h):
        manual_clock.advance(0.001)
    assert h.sum(pipeline="p1") == pytest.approx(4.0)
    assert [ls for ls, _ in h.series()] == [{}, {"pipeline": "p1"}]
    assert h.sum() == pytest.approx(5.0) and h.count() == 2
    outer = [sp for sp in tracer.finished_spans() if sp.name == "outer"]
    assert outer[0].attributes["etl_consumer_wait_ms"] == pytest.approx(4.0)


def test_fit_execution_account_closes_over_a_slow_iterator():
    """An execution runs from the first pull of its group to the end of its
    listeners and its four folded parts account for it; an iterator slower
    than the step leaves the device drained at every dispatch, and the
    counter says so without a trace."""
    import time
    from deeplearning4j_tpu import ListDataSetIterator

    class Slow(ListDataSetIterator):
        def next(self):
            time.sleep(0.1)         # the host makes a batch: 100 ms
            return super().next()

    spans, grew = _traced_fit(_fit_probe_net(), Slow(_fit_probe_sets(8)), 2)
    assert len(spans) == 3          # four groups, the epoch's first outside
    for s in spans:
        parts = sum(s.attributes[n] for n in FIT_PARTS)
        assert s.attributes["fit_next_batch_ms"] >= 190.0
        assert 0.95 * s.duration_ms <= parts <= s.duration_ms
    assert grew["0"] == 3 and grew["1"] == 0
    assert grew["fit_next_batch_ms"] == 4 and grew["fit_dispatch_ms"] == 3


def test_fit_executions_ahead_counts_a_fed_device():
    """With the batches ready and an execution that outlasts the host's
    work between two dispatches, the next one is dispatched while it still
    runs: ahead="1"."""
    import jax
    from deeplearning4j_tpu import ListDataSetIterator
    net = _fit_probe_net(width=1024)
    sets = _fit_probe_sets(20, batch=512)
    net.fit(ListDataSetIterator(sets[:4]), steps_per_execution=4)  # compiles
    jax.block_until_ready(net.params)
    spans, grew = _traced_fit(net, ListDataSetIterator(sets), 4)
    jax.block_until_ready(net.params)
    assert len(spans) == 4 and grew["fit_dispatch_ms"] == 5
    # the first dispatch finds the device drained; of the four behind it
    # at least three find the one before still running (a loaded test
    # machine may stall the loop once)
    assert grew["0"] + grew["1"] == 5 and grew["1"] >= 3


class _StubExecution:
    """What `run` leaves in `last_scores` for an execution a test finishes
    by hand: ready only once released."""

    def __init__(self):
        self._done = threading.Event()

    def is_ready(self):
        return self._done.is_set()

    def block_until_ready(self):
        assert self._done.wait(30), "the test never released this execution"
        return self

    def release(self):
        self._done.set()


def _stub_fit(net, n_groups, K, on_run=None):
    """`net._fit_grouped` over n_groups * K batches on its own thread, its
    prepare / run hooks stubs: a plan is its group's number, an execution a
    _StubExecution. Returns (thread, log, executions): the log holds
    ("pull" | "prepare" | "run", group) in the order the loop did them."""
    log, executions = [], []

    def batches():
        for i in range(n_groups * K):
            if i % K == 0:
                log.append(("pull", i // K))
            yield i

    def prepare(group):
        log.append(("prepare", group[0] // K))
        return ("plan", group[0] // K)

    def run(prepared, group):
        ex = _StubExecution()
        executions.append(ex)
        net.last_scores = ex
        log.append(("run", prepared[1]))
        if on_run is not None:
            on_run(ex)

    t = threading.Thread(target=net._fit_grouped, daemon=True, args=(
        batches(), K), kwargs=dict(prepare=prepare, run=run,
                                   fallback=lambda ds: None))
    t.start()
    return t, log, executions


def _settled(log, want, timeout=20.0):
    """True once the log equals `want` and stays so for a moment."""
    import time
    deadline = time.monotonic() + timeout
    while list(log) != want and time.monotonic() < deadline:
        time.sleep(0.005)
    time.sleep(0.15)                # anything further would show by now
    return list(log) == want


def test_fit_keeps_exactly_one_execution_queued_behind_the_running_one():
    """The run-ahead rule of fit(steps_per_execution=K): N + 1 is dispatched
    while N is unfinished; N + 2 may be pulled, but is neither stacked nor
    dispatched until N has finished; never more than two plans alive."""
    net = _fit_probe_net()
    assert net._in_flight == ()
    t, log, executions = _stub_fit(net, 5, 2)
    step = lambda n: [("pull", n), ("prepare", n), ("run", n)]
    want = step(0) + step(1) + [("pull", 2)]
    # 0 runs, 1 is queued behind it, 2 is pulled and waits for 0
    assert _settled(log, want), log
    for n in range(3):
        # plans alive: stacked and their execution not finished
        assert len(executions) == n + 2
        assert [e.is_ready() for e in executions] == [True] * n + [False] * 2
        executions[n].release()
        want += step(n + 2)[1:] + ([("pull", n + 3)] if n + 3 < 5 else [])
        assert _settled(log, want), (n, log)
    # the iterator has ended: the loop returns with 3 and 4 still out (its
    # caller drains), and the next epoch's first execution waits for 3
    t.join(20)
    assert not t.is_alive()
    assert net._in_flight == (executions[3], executions[4])
    assert not executions[3].is_ready()


def test_fit_execution_wait_is_a_folded_part_of_the_execution():
    """The wait for execution N - 1 is a phase of fit_execution like the
    loop's others: folded into the span, with its histogram, and with it
    the parts still account for at least 95 % of the span."""
    import queue
    import time
    from deeplearning4j_tpu.telemetry import enable_tracing, get_tracer
    tracer, reg = get_tracer(), get_registry()
    was = tracer.enabled
    enable_tracing()
    count = lambda: (reg.get("fit_execution_wait_ms").count()
                     if reg.get("fit_execution_wait_ms") else 0)
    try:
        tracer.clear()
        before = count()
        # a "device" that takes 200 ms an execution, one after the other
        queued = queue.Queue()

        def device():
            for ex in iter(queued.get, None):
                time.sleep(0.2)
                ex.release()
        threading.Thread(target=device, daemon=True).start()
        t, log, executions = _stub_fit(_fit_probe_net(), 5, 2,
                                       on_run=queued.put)
        t.join(30)
        queued.put(None)
        assert not t.is_alive()
        spans = [s for s in tracer.finished_spans()
                 if s.name == "fit_execution"]
    finally:
        tracer.enabled = was
    assert len(spans) == 4 and count() - before == 3
    parts = FIT_PARTS + ("fit_execution_wait_ms",)
    # the epoch's first execution has no span; the second finds one
    # execution out and does not wait; 2, 3 and 4 each wait for the one
    # before the last: about one execution's length, the host being fast
    assert ["fit_execution_wait_ms" in s.attributes for s in spans] \
        == [False, True, True, True]
    for s in spans[1:]:
        assert s.attributes["fit_execution_wait_ms"] >= 100.0
        total = sum(s.attributes.get(n, 0.0) for n in parts)
        assert 0.95 * s.duration_ms <= total <= s.duration_ms
    assert not any(s.name == "fit_execution_wait"
                   for s in tracer.finished_spans())


def test_fit_compiling_call_is_in_no_phase():
    """The call that compiles the multi-step program is in no fit_execution
    span and no fit_dispatch phase, and the ahead counter leaves it out: a
    `with` block around that lowering cost seconds on the chip's host
    (PR 24). Its group's pulls, plan and listeners are counted like any."""
    from deeplearning4j_tpu import ListDataSetIterator
    spans, grew = _traced_fit(_fit_probe_net(),
                              ListDataSetIterator(_fit_probe_sets(2)), 2)
    assert spans == []
    assert grew == {"fit_next_batch_ms": 1, "fit_prepare_ms": 1,
                    "fit_dispatch_ms": 0, "fit_listeners_ms": 1,
                    "0": 0, "1": 0}


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


@pytest.mark.parametrize("what,expect", [
    ("attention_fwd", ["flash_fwd"]),
    ("attention_fwd_bwd", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    ("decode_step", ["flash_decode"] * 2),    # a layer: it appends too
    ("decode_step_paged", ["flash_decode_paged"] * 2),
])
def test_pallas_calls_carry_their_names(what, expect):
    """make_jaxpr of both attention passes and of the decode step: every
    pallas_call says which kernel it is (what a device trace shows)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.kernels import flash_attention
    if what.startswith("attention"):
        q = jnp.ones((1, 128, 2, 64), jnp.float32)

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).sum()
        fn = loss if what == "attention_fwd" \
            else jax.grad(loss, argnums=(0, 1, 2))
        jaxpr = jax.make_jaxpr(fn)(q, q, q)
    else:
        from deeplearning4j_tpu.decode import DecodeEngine
        paged = what.endswith("paged")
        net = _tiny_lm(use_pallas=True)
        eng = DecodeEngine(net, slots=2, max_len=32, paged=paged)
        jaxpr = jax.make_jaxpr(eng._build_step())(
            net.params, net.states, eng.init_cache(),
            np.zeros((2,), np.int32), eng._greedy_step_ops,
            eng.full_table() if paged else None)
    assert _pallas_names(jaxpr.jaxpr, []) == expect


@pytest.mark.parametrize("kind", ["multilayer", "graph", "multi_step"])
def test_train_step_lowering_names_layers_and_optimizer(kind):
    """The lowered text of a two-layer net's train step carries its layer
    names (forward jvp(<layer>), backward transpose(jvp(<layer>))) and
    `optimizer` as scopes."""
    import jax.numpy as jnp
    from deeplearning4j_tpu import (Adam, ComputationGraph, DenseLayer,
                                    InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration, OutputLayer)
    b = NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
    hidden = DenseLayer(n_out=16, activation="tanh", name="hidden")
    out = OutputLayer(n_out=3, activation="softmax", loss="MCXENT")
    x, y = jnp.ones((4, 8)), jnp.ones((4, 3))
    if kind == "graph":
        conf = (b.graph_builder().add_inputs("in")
                .add_layer("hidden", hidden, "in")
                .add_layer("head", out, "hidden").set_outputs("head")
                .set_input_types(InputType.feed_forward(8)).build())
        net = ComputationGraph(conf).init()
        lowered = net._make_train_step().lower(
            net.params, net.opt_state, net.states, net._rng, [x], [y],
            None, None, None)
        layers = ["hidden", "head"]
    else:
        conf = b.list().layer(hidden).layer(out) \
            .input_type(InputType.feed_forward(8)).build()
        net = MultiLayerNetwork(conf).init()
        layers = ["hidden", "layer1"]       # an unnamed layer: layer<i>
        if kind == "multilayer":
            lowered = net._make_train_step().lower(
                net.params, net.opt_state, net.states, net._rng, x, y,
                None, None, None)
        else:
            stacked = (jnp.stack([x, x]), jnp.stack([y, y]), None, None)
            lowered = net._make_multi_step().lower(
                net.params, net.opt_state, net.states, net._rng, stacked)
    text = lowered.as_text(debug_info=True)
    for name in layers:
        assert f'jvp({name})/' in text, name
        assert f'transpose(jvp({name}))/' in text, name
    assert '"optimizer/' in text or '/optimizer/' in text
