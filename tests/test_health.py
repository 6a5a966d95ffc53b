"""Health & alerting layer tests: structured logging (ring buffer, trace
correlation, sinks, level counter), HealthMonitor aggregation + deep
/healthz on both servers, the AlertEngine rule lifecycle under ManualClock
(pending -> firing -> resolved, webhook exactly once per transition),
TrainingHealthListener watchdog (NaN/divergence/step-time) with
FaultTolerantTrainer checkpoint-and-halt, and the satellite regressions
(send_json NaN sanitization, PerformanceListener None-until-measured,
raising gauge callbacks surviving the scrape)."""
import io
import json
import math
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.telemetry import (AlertEngine, AlertRule,
                                          HealthMonitor, LogBuffer,
                                          MetricsRegistry, StderrJsonSink,
                                          StructuredLogger, Tracer,
                                          WebhookAlertSink,
                                          default_serving_rules,
                                          default_training_rules,
                                          render_prometheus)
from deeplearning4j_tpu.telemetry.alerts import RouterAlertSink
from deeplearning4j_tpu.util.http import (BackgroundHttpServer, QuietHandler,
                                          dumps_safe)
from deeplearning4j_tpu.util.time_source import (ManualClock,
                                                 TimeSourceProvider)


@pytest.fixture
def manual_clock():
    clock = ManualClock(start_s=1000.0)
    TimeSourceProvider.set_instance(clock)
    try:
        yield clock
    finally:
        TimeSourceProvider.reset()


def _http_get(url, timeout=30):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# ------------------------------------------------------------------ logging

def test_structured_log_record_shape_and_counter(manual_clock):
    reg = MetricsRegistry()
    log = StructuredLogger(name="test", registry=reg)
    rec = log.info("hello", a=1)
    assert rec["time"] == pytest.approx(1000.0)
    assert rec["level"] == "info" and rec["logger"] == "test"
    assert rec["fields"] == {"a": 1}
    assert "trace_id" not in rec            # no active span
    log.error("boom")
    assert reg.get("log_events_total").get(level="info") == 1
    assert reg.get("log_events_total").get(level="error") == 1
    assert reg.get("log_events_total").get() == 2


def test_log_trace_correlation_from_current_span():
    log = StructuredLogger(name="t", registry=MetricsRegistry())
    tracer = Tracer()
    with tracer.span("request") as root:
        with tracer.span("inner") as inner:
            rec = log.warning("within")
    assert rec["trace_id"] == root.trace_id
    assert rec["span_id"] == inner.span_id
    # filtering the buffer by that trace id finds exactly this record
    assert log.buffer.records(trace_id=root.trace_id) == [rec]


def test_log_buffer_ring_bound_and_level_filter():
    buf = LogBuffer(capacity=4)
    log = StructuredLogger(name="t", buffer=buf, registry=MetricsRegistry())
    for i in range(6):
        log.log("debug" if i % 2 else "error", f"m{i}")
    assert buf.total == 6 and buf.dropped == 2
    msgs = [r["message"] for r in buf.records()]
    assert msgs == ["m2", "m3", "m4", "m5"]
    errors = [r["message"] for r in buf.records(level="error")]
    assert errors == ["m2", "m4"]
    assert [r["message"] for r in buf.records(n=1)] == ["m5"]
    assert buf.records(n=0) == []       # n=0 means zero, not "everything"
    assert buf.records(n=-3) == []


def test_log_sinks_stderr_file_and_dead_sink(tmp_path):
    stream = io.StringIO()
    from deeplearning4j_tpu.telemetry import FileJsonSink
    path = tmp_path / "log.jsonl"
    fsink = FileJsonSink(path)

    def dead_sink(record):
        raise RuntimeError("sink down")

    log = StructuredLogger(name="t", registry=MetricsRegistry(),
                           sinks=[StderrJsonSink(stream), fsink, dead_sink])
    log.info("one", loss=float("nan"))     # non-finite field -> null in JSON
    log.info("two")
    fsink.close()
    assert log.sink_errors == 2            # dead sink never broke the caller
    lines = [json.loads(l) for l in stream.getvalue().splitlines()]
    assert [l["message"] for l in lines] == ["one", "two"]
    assert lines[0]["fields"]["loss"] is None
    disk = [json.loads(l) for l in path.read_text().splitlines()]
    assert [l["message"] for l in disk] == ["one", "two"]


def test_logger_level_floor_and_child():
    log = StructuredLogger(name="root", registry=MetricsRegistry(),
                           level="warning")
    assert log.debug("nope") is None and log.info("nope") is None
    assert log.warning("yes")["level"] == "warning"
    child = log.child("sub")
    child.error("from child")
    names = [(r["logger"], r["message"]) for r in log.buffer.records()]
    assert names == [("root", "yes"), ("root.sub", "from child")]


# ------------------------------------------------------------------- health

def test_health_monitor_aggregates_worst_status():
    m = HealthMonitor()
    assert m.check()["status"] == "healthy"      # vacuous
    m.register("a", lambda: "healthy")
    m.register("b", lambda: ("degraded", {"queue": 9}))
    rep = m.check()
    assert rep["status"] == "degraded"
    assert rep["components"]["b"] == {"status": "degraded", "queue": 9}
    assert HealthMonitor.http_status(rep) == 200  # degraded still serves
    m.set_status("c", "unhealthy", reason="down")
    rep = m.check()
    assert rep["status"] == "unhealthy"
    assert HealthMonitor.http_status(rep) == 503
    m.set_status("c", "healthy")                  # push-style update in place
    m.unregister("b")
    assert m.check()["status"] == "healthy"


def test_health_probe_exception_is_unhealthy_not_a_crash():
    m = HealthMonitor()
    m.register("broken", lambda: 1 / 0)
    rep = m.check()
    assert rep["components"]["broken"]["status"] == "unhealthy"
    assert "ZeroDivisionError" in rep["components"]["broken"]["error"]


def test_health_transitions_logged():
    log = StructuredLogger(name="t", registry=MetricsRegistry())
    m = HealthMonitor(logger=log)
    state = {"status": "healthy"}
    m.register("comp", lambda: state["status"])
    m.check()
    state["status"] = "unhealthy"
    m.check()
    m.check()                                   # steady state: no new record
    recs = [r for r in log.buffer.records()
            if r["message"] == "health_transition"]
    assert [r["fields"]["status"] for r in recs] == ["healthy", "unhealthy"]
    assert recs[-1]["level"] == "error"


# ------------------------------------------------------------------- alerts

def test_alert_threshold_lifecycle_under_manual_clock(manual_clock):
    reg = MetricsRegistry()
    depth = reg.gauge("queue_depth")
    events = []
    eng = AlertEngine(registry=reg, interval_s=0, sinks=[events.append])
    eng.add_rule(AlertRule("deep_queue", metric="queue_depth", threshold=100,
                           for_duration_s=30, severity="page"))
    depth.set(10)
    eng.evaluate()
    assert eng.state()["rules"][0]["state"] == "inactive"
    depth.set(500)
    eng.evaluate()                              # condition true -> pending
    assert eng.state()["rules"][0]["state"] == "pending"
    manual_clock.advance(10)
    eng.evaluate()                              # held 10s < 30s: still pending
    assert eng.state()["rules"][0]["state"] == "pending"
    assert events == []                         # pending never notifies
    manual_clock.advance(25)
    eng.evaluate()                              # held 35s >= 30s: fires
    st = eng.state()
    assert st["rules"][0]["state"] == "firing" and st["firing"] == 1
    assert [e["state"] for e in events] == ["firing"]
    eng.evaluate()                              # still firing: no re-notify
    assert len(events) == 1
    depth.set(5)
    eng.evaluate()                              # recovery -> resolved
    assert eng.state()["rules"][0]["state"] == "inactive"
    assert [e["state"] for e in events] == ["firing", "resolved"]
    assert events[0]["rule"] == "deep_queue"
    assert events[0]["value"] == 500.0


def test_alert_pending_that_recovers_never_notifies(manual_clock):
    reg = MetricsRegistry()
    g = reg.gauge("g")
    events = []
    eng = AlertEngine(registry=reg, interval_s=0, sinks=[events.append])
    eng.add_rule(AlertRule("flap", metric="g", threshold=1,
                           for_duration_s=60))
    g.set(5)
    eng.evaluate()
    manual_clock.advance(10)
    g.set(0)
    eng.evaluate()                              # recovered inside for_duration
    assert events == []
    assert eng.state()["rules"][0]["state"] == "inactive"


def test_alert_ratio_rule_windows_counter_deltas(manual_clock):
    reg = MetricsRegistry()
    errs, reqs = reg.counter("errors_total"), reg.counter("requests_total")
    eng = AlertEngine(registry=reg, interval_s=0)
    eng.add_rule(AlertRule("err", "ratio", numerator="errors_total",
                           denominator="requests_total", threshold=0.1,
                           window_s=60))
    reqs.inc(1000)                   # pre-engine history must not alert
    eng.evaluate()
    assert eng.state()["rules"][0]["state"] == "inactive"
    manual_clock.advance(10)
    reqs.inc(100)
    errs.inc(50)                     # 50% of the last window's traffic
    eng.evaluate()
    row = eng.state()["rules"][0]
    assert row["state"] == "firing" and row["value"] == pytest.approx(0.5)
    # window slides past the burst: clean traffic resolves it
    manual_clock.advance(120)
    reqs.inc(400)
    eng.evaluate()
    assert eng.state()["rules"][0]["state"] == "inactive"


def test_alert_burn_rate_rule(manual_clock):
    reg = MetricsRegistry()
    errs, reqs = reg.counter("errors_total"), reg.counter("requests_total")
    eng = AlertEngine(registry=reg, interval_s=0)
    eng.add_rule(AlertRule("burn", "burn_rate", numerator="errors_total",
                           denominator="requests_total", slo=0.999,
                           threshold=14.4, window_s=300))
    eng.evaluate()
    manual_clock.advance(30)
    reqs.inc(1000)
    errs.inc(2)                      # 0.2% errors / 0.1% budget = 2x: ok
    eng.evaluate()
    assert eng.state()["rules"][0]["state"] == "inactive"
    manual_clock.advance(30)
    reqs.inc(1000)
    errs.inc(50)                     # ~1.7% over window / 0.1% budget = 17x
    eng.evaluate()
    row = eng.state()["rules"][0]
    assert row["state"] == "firing" and row["value"] > 14.4


def test_alert_histogram_rule_aggregates_across_label_sets(manual_clock):
    """A labels-free threshold rule must see labeled observations too: the
    ETL pipelines record etl_consumer_wait_ms under pipeline=<name>, and
    default_training_rules' starvation rule names no labels."""
    reg = MetricsRegistry()
    h = reg.histogram("etl_consumer_wait_ms")
    for _ in range(20):
        h.observe(10_000.0, pipeline="train")
    eng = AlertEngine(registry=reg, interval_s=0)
    eng.add_rule(default_training_rules()[2])      # etl_consumer_starvation
    eng.evaluate()
    row = next(r for r in eng.state()["rules"]
               if r["name"] == "etl_consumer_starvation")
    assert row["state"] == "firing" and row["value"] == 10_000.0


def test_alert_histogram_rule_with_labels_reads_one_series(manual_clock):
    """The registry's bare read is the merged one (no percentile_merged
    beside it); a rule that names labels still reads that series alone."""
    from deeplearning4j_tpu.telemetry.registry import Histogram
    assert not hasattr(Histogram, "percentile_merged")
    reg = MetricsRegistry()
    h = reg.histogram("etl_consumer_wait_ms")
    for _ in range(20):
        h.observe(10_000.0, pipeline="starved")
        h.observe(1.0, pipeline="fed")
    eng = AlertEngine(registry=reg, interval_s=0)
    for name, labels in (("any", None), ("fed", {"pipeline": "fed"})):
        eng.add_rule(AlertRule(name, metric="etl_consumer_wait_ms",
                               percentile=0.99, threshold=5000.0, op=">",
                               labels=labels))
    eng.evaluate()
    rows = {r["name"]: r for r in eng.state()["rules"]}
    assert rows["any"]["state"] == "firing" \
        and rows["any"]["value"] == 10_000.0
    assert rows["fed"]["state"] == "inactive" and rows["fed"]["value"] == 1.0


def test_alert_rule_json_round_trip_and_validation():
    rules = default_serving_rules() + default_training_rules()
    for r in rules:
        clone = AlertRule.from_dict(json.loads(json.dumps(r.to_dict())))
        assert clone.to_dict() == r.to_dict()
    with pytest.raises(ValueError):
        AlertRule("bad", "ratio", numerator="a", threshold=1)  # no denominator
    with pytest.raises(ValueError):
        AlertRule("bad", "burn_rate", numerator="a", denominator="b",
                  threshold=1, slo=2.0)
    with pytest.raises(ValueError):
        AlertRule("bad", metric="m", threshold=1, op="~")


def test_alert_missing_metric_is_no_data_not_firing(manual_clock):
    eng = AlertEngine(registry=MetricsRegistry(), interval_s=0)
    eng.add_rule(AlertRule("ghost", metric="does_not_exist", threshold=0,
                           op=">="))
    eng.evaluate()
    row = eng.state()["rules"][0]
    assert row["state"] == "inactive" and row["value"] is None


class _WebhookReceiver(BackgroundHttpServer):
    def __init__(self):
        super().__init__()
        self.events = []

    def start(self):
        recv = self

        class Handler(QuietHandler):
            def do_POST(self):
                recv.events.append(json.loads(self.body()))
                self.send_json(200, {"ok": True})

        return self.start_with(Handler)


def test_webhook_sink_fires_exactly_once_per_transition(manual_clock):
    reg = MetricsRegistry()
    g = reg.gauge("pressure")
    receiver = _WebhookReceiver().start()
    try:
        sink = WebhookAlertSink(receiver.url + "/alert")
        eng = AlertEngine(registry=reg, interval_s=0, sinks=[sink])
        eng.add_rule(AlertRule("pressure_high", metric="pressure",
                               threshold=10, for_duration_s=5))
        g.set(99)
        eng.evaluate()                          # pending: no webhook
        assert receiver.events == []
        manual_clock.advance(5)
        eng.evaluate()                          # firing: one POST
        eng.evaluate()                          # steady firing: none
        g.set(0)
        eng.evaluate()                          # resolved: one POST
        eng.evaluate()                          # steady inactive: none
        assert [e["state"] for e in receiver.events] == ["firing", "resolved"]
        assert all(e["rule"] == "pressure_high" for e in receiver.events)
        assert sink.delivered == 2
    finally:
        receiver.stop()


def test_replacing_or_removing_a_firing_rule_resolves_it(manual_clock):
    """The receiver of a firing event holds an open incident: replacing or
    removing that rule must still deliver the closing resolved event."""
    reg = MetricsRegistry()
    g = reg.gauge("g")
    events = []
    eng = AlertEngine(registry=reg, interval_s=0, sinks=[events.append])
    eng.add_rule(AlertRule("r", metric="g", threshold=1))
    g.set(5)
    eng.evaluate()
    assert [e["state"] for e in events] == ["firing"]
    eng.add_rule(AlertRule("r", metric="g", threshold=100))  # raise threshold
    assert [e["state"] for e in events] == ["firing", "resolved"]
    g.set(500)
    eng.evaluate()
    assert [e["state"] for e in events][-1] == "firing"
    eng.remove_rule("r")
    assert [e["state"] for e in events] == ["firing", "resolved",
                                            "firing", "resolved"]


def test_post_json_tolerates_non_json_ack():
    """A webhook answering 200 with a plain-text body ("ok", Slack-style)
    is a delivered alert, not a sink error."""
    class TextReceiver(BackgroundHttpServer):
        def start(self):
            class Handler(QuietHandler):
                def do_POST(self):
                    self.send_text(200, "ok")
            return self.start_with(Handler)

    from deeplearning4j_tpu.util.http import post_json
    r = TextReceiver().start()
    try:
        assert post_json(r.url + "/hook", {"a": 1}) == "ok"
    finally:
        r.stop()


def test_router_alert_sink_posts_telemetry_reports(manual_clock):
    from deeplearning4j_tpu.ui.storage import CollectionStatsStorageRouter
    reg = MetricsRegistry()
    g = reg.gauge("g")
    router = CollectionStatsStorageRouter()
    eng = AlertEngine(registry=reg, interval_s=0,
                      sinks=[RouterAlertSink(router, session_id="s1")])
    eng.add_rule(AlertRule("r", metric="g", threshold=1))
    g.set(2)
    eng.evaluate()
    assert len(router.updates) == 1
    d = router.updates[0]
    assert d["type"] == "telemetry" and d["session_id"] == "s1"
    assert d["alert"]["rule"] == "r" and d["alert"]["state"] == "firing"


# ------------------------------------------------- satellite regressions

def test_send_json_sanitizes_non_finite_floats():
    assert json.loads(dumps_safe({"a": float("nan")})) == {"a": None}
    out = json.loads(dumps_safe(
        {"v": [1.5, float("inf"), float("-inf")], "ok": "s"}))
    assert out == {"v": [1.5, None, None], "ok": "s"}
    # strict decoders (JSON.parse semantics) accept the emitted text
    assert "NaN" not in dumps_safe({"a": float("nan")})


def test_performance_listener_reports_none_until_first_measurement(
        manual_clock):
    from deeplearning4j_tpu.optimize.listeners import PerformanceListener
    pl = PerformanceListener(log_fn=lambda *a: None)
    # a snapshot before any measured interval must serialize cleanly
    snap = {"samples_per_sec": pl.last_samples_per_sec,
            "iteration_ms": pl.last_iteration_ms,
            "batches_per_sec": pl.last_batches_per_sec}
    assert json.loads(dumps_safe(snap)) == {
        "samples_per_sec": None, "iteration_ms": None,
        "batches_per_sec": None}
    model = types.SimpleNamespace(score_value=0.5)
    pl.iteration_done(model, 1)
    manual_clock.advance(0.5)
    pl.record_batch_size(64)
    pl.iteration_done(model, 2)
    assert pl.last_iteration_ms == pytest.approx(500.0)
    assert pl.last_samples_per_sec == pytest.approx(128.0)


def test_raising_gauge_callback_survives_scrape_and_logs():
    reg = MetricsRegistry()
    reg.counter("good_total").inc(3)
    reg.gauge("bad_gauge", fn=lambda: 1 / 0)
    reg.gauge("good_gauge").set(7)
    text = render_prometheus(reg)               # must not raise
    assert "good_total 3" in text
    assert "good_gauge 7" in text
    sample_lines = [l for l in text.splitlines() if not l.startswith("#")]
    assert not any(l.startswith("bad_gauge") for l in sample_lines)
    assert reg.get("bad_gauge").get() is None   # point read degrades too
    from deeplearning4j_tpu.telemetry import get_logger
    recs = [r for r in get_logger().buffer.records()
            if r["message"] == "gauge_callback_error"
            and r["fields"]["metric"] == "bad_gauge"]
    assert recs and "ZeroDivisionError" in recs[-1]["fields"]["error"]


def test_raising_gauge_logs_to_the_owning_registrys_logger():
    """A registry wired with its own logger (a ServingServer does this)
    keeps gauge-callback errors on ITS /logs, not the process buffer."""
    reg = MetricsRegistry()
    log = StructuredLogger(name="srv", registry=reg)
    reg.logger = log
    reg.gauge("local_bad", fn=lambda: 1 / 0)
    assert render_prometheus(reg)       # scrape survives
    recs = [r for r in log.buffer.records()
            if r["message"] == "gauge_callback_error"]
    assert recs and recs[-1]["fields"]["metric"] == "local_bad"
    assert reg.get("log_events_total").get(level="warning") >= 1


# ---------------------------------------------- training watchdog

def _fake_model(loss):
    return types.SimpleNamespace(score_value=loss, last_gradients=None)


def test_training_health_listener_nan_loss(manual_clock):
    reg = MetricsRegistry()
    m = HealthMonitor()
    log = StructuredLogger(name="t", registry=reg)
    from deeplearning4j_tpu.optimize.listeners import TrainingHealthListener
    w = TrainingHealthListener(health=m, registry=reg, logger=log)
    w.iteration_done(_fake_model(0.7), 1)
    assert m.check()["status"] == "healthy"
    assert not w.should_halt
    w.iteration_done(_fake_model(float("nan")), 2)
    assert w.should_halt and w.trip_reason == "nan_loss"
    assert reg.get("training_nan_total").get() == 1
    # a PERSISTENT NaN (nothing halts under plain model.fit) is one
    # detection: no per-iteration counter inflation or /logs ring eviction
    for i in range(3, 20):
        w.iteration_done(_fake_model(float("nan")), i)
    assert reg.get("training_nan_total").get() == 1
    assert len([r for r in log.buffer.records()
                if r["message"] == "training_nan_loss"]) == 1
    rep = m.check()
    assert rep["components"]["trainer"]["status"] == "unhealthy"
    assert rep["components"]["trainer"]["reason"] == "nan_loss"
    recs = [r for r in log.buffer.records()
            if r["message"] == "training_nan_loss"]
    assert recs and recs[0]["level"] == "error"


def test_training_health_listener_divergence(manual_clock):
    reg = MetricsRegistry()
    from deeplearning4j_tpu.optimize.listeners import TrainingHealthListener
    w = TrainingHealthListener(registry=reg,
                               logger=StructuredLogger(registry=reg),
                               divergence_factor=10.0, divergence_margin=0.5,
                               divergence_patience=3)
    it = 0
    for loss in (1.0, 0.5, 0.4):
        it += 1
        w.iteration_done(_fake_model(loss), it)
    assert w.best_loss == pytest.approx(0.4)
    for loss in (50.0, 60.0):            # two diverged iterations: patience
        it += 1
        w.iteration_done(_fake_model(loss), it)
    assert not w.should_halt
    it += 1
    w.iteration_done(_fake_model(70.0), it)   # third in a row trips
    assert w.should_halt and w.trip_reason == "divergence"
    assert reg.get("training_divergence_total").get() == 1


def test_training_health_listener_divergence_streak_resets(manual_clock):
    reg = MetricsRegistry()
    from deeplearning4j_tpu.optimize.listeners import TrainingHealthListener
    w = TrainingHealthListener(registry=reg,
                               logger=StructuredLogger(registry=reg),
                               divergence_patience=3)
    losses = [1.0, 50.0, 60.0, 1.2, 50.0, 55.0]   # never 3 in a row
    for i, loss in enumerate(losses, 1):
        w.iteration_done(_fake_model(loss), i)
    assert not w.should_halt


def test_training_health_listener_nan_gradient(manual_clock):
    reg = MetricsRegistry()
    from deeplearning4j_tpu.optimize.listeners import TrainingHealthListener
    w = TrainingHealthListener(registry=reg,
                               logger=StructuredLogger(registry=reg),
                               check_gradients=True)
    assert w.wants_gradients            # keeps grads alive on the model
    model = _fake_model(0.5)
    model.last_gradients = {"w": np.array([0.1, 0.2])}
    w.iteration_done(model, 1)
    assert not w.should_halt
    model.last_gradients = {"w": np.array([0.1, np.nan])}
    w.iteration_done(model, 2)
    assert w.should_halt and w.trip_reason == "nan_gradient"


def test_training_health_listener_step_time_regression(manual_clock):
    reg = MetricsRegistry()
    from deeplearning4j_tpu.optimize.listeners import TrainingHealthListener
    w = TrainingHealthListener(registry=reg,
                               logger=StructuredLogger(registry=reg),
                               step_time_factor=3.0, step_time_window=4)
    m = _fake_model(0.5)
    it = 0
    for _ in range(5):                  # 1 warm-up + 4 baseline @100ms
        it += 1
        w.iteration_done(m, it)
        manual_clock.advance(0.1)
    for _ in range(4):                  # 4 recent @500ms -> 5x baseline
        it += 1
        w.iteration_done(m, it)
        manual_clock.advance(0.5)
    it += 1
    w.iteration_done(m, it)
    assert w.step_time_regressed
    assert reg.get("training_step_time_regressions_total").get() == 1
    assert not w.should_halt            # regression degrades, never halts
    assert w._probe()[0] == "degraded"


def test_fault_tolerant_trainer_checkpoints_and_halts_on_nan(tmp_path,
                                                            manual_clock):
    from tools.smoke_telemetry import _tiny_net
    from deeplearning4j_tpu import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.optimize.listeners import (TrainingHalted,
                                                       TrainingHealthListener)
    from deeplearning4j_tpu.train import CheckpointConfig, FaultTolerantTrainer
    reg = MetricsRegistry()
    monitor = HealthMonitor()
    w = TrainingHealthListener(health=monitor, registry=reg,
                               logger=StructuredLogger(registry=reg))
    X = np.random.default_rng(0).normal(size=(24, 6)).astype(np.float32)
    X[10, 0] = np.nan                   # second batch of 8 is poisoned
    Y = np.eye(3, dtype=np.float32)[np.arange(24) % 3]
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    trainer = FaultTolerantTrainer(lambda: _tiny_net(),
                                   CheckpointConfig(tmp_path, frequency=1),
                                   health=w)
    with pytest.raises(TrainingHalted) as exc:
        trainer.fit(it, epochs=1)
    assert exc.value.reason == "nan_loss" and exc.value.iteration == 2
    # checkpoint-and-halt: the blown-up state is QUARANTINED under halt-*
    # (forensics), never part of the resumable ckpt-* chain
    assert (tmp_path / "halt-000000002").is_dir()
    assert exc.value.checkpoint_path == str(tmp_path / "halt-000000002")
    assert monitor.check()["components"]["trainer"]["status"] == "unhealthy"
    # restart resumes from the last PRE-blow-up periodic checkpoint, so a
    # fixed run never restores NaN params
    resumed = FaultTolerantTrainer(lambda: _tiny_net(),
                                   CheckpointConfig(tmp_path, frequency=1))
    assert resumed.resumed and resumed.state["iteration"] == 1
    assert np.all(np.isfinite(np.asarray(resumed.model.get_flat_params())))


# ---------------------------------------------- endpoints (UI server)

def test_ui_server_health_alerts_logs_endpoints(manual_clock):
    from deeplearning4j_tpu.ui.server import UIServer
    reg = MetricsRegistry()
    monitor = HealthMonitor()
    logger = StructuredLogger(name="ui-test", registry=reg)
    engine = AlertEngine(registry=reg, interval_s=0)
    engine.add_rule(AlertRule("g_high", metric="g", threshold=1))
    server = UIServer(port=0, health=monitor, alerts=engine, logger=logger)
    server.start()
    try:
        status, h = _http_get(server.url + "/healthz")
        assert status == 200 and h["status"] == "healthy"
        monitor.register("etl:bad", lambda: ("unhealthy", {"reason": "x"}))
        status, h = _http_get(server.url + "/healthz")
        assert status == 503
        assert h["components"]["etl:bad"]["reason"] == "x"
        reg.gauge("g").set(5)
        engine.evaluate()
        status, a = _http_get(server.url + "/alerts")
        assert status == 200
        assert a["rules"][0]["name"] == "g_high"
        assert a["rules"][0]["state"] == "firing" and a["firing"] == 1
        logger.info("hello", nan=float("nan"))
        status, l = _http_get(server.url + "/logs?n=10")
        assert status == 200
        assert any(r["message"] == "hello" for r in l["records"])
        status, err = _http_get(server.url + "/logs?n=all")
        assert status == 400 and "bad query" in err["error"]
        # free-form fields may hold non-JSON-native objects (numpy scalars,
        # exceptions): /logs stringifies instead of dropping the connection
        logger.info("odd", version=np.int64(3), err=ValueError("boom"))
        status, l = _http_get(server.url + "/logs?n=1")
        assert status == 200
        assert l["records"][0]["fields"] == {"version": "3", "err": "boom"}
    finally:
        server.stop()


def test_etl_pipeline_registers_health_probe(manual_clock):
    from deeplearning4j_tpu.etl import ParallelPipelineExecutor
    monitor = HealthMonitor()

    class _Reader:
        def __init__(self, n=8):
            self.n, self.i = n, 0

        def has_next(self):
            return self.i < self.n

        def next_record(self):
            self.i += 1
            if self.i == 5:
                raise ValueError("corrupt record")
            return [float(self.i)]

        def reset(self):
            self.i = 0

    pipe = ParallelPipelineExecutor(_Reader(), batch_size=2, workers=1,
                                    name="probe-test", health=monitor,
                                    registry=MetricsRegistry(),
                                    tracer=Tracer(enabled=False))
    assert "etl:probe-test" in monitor.components()
    with pytest.raises(ValueError):
        while pipe.has_next():          # reader blows up mid-stream
            pipe.next()
    pipe.close()                        # error already surfaced: clean close
    assert "etl:probe-test" not in monitor.components()
    # a pipeline whose consumer STOPPED pulling: the monitor sees the parked
    # error through the probe before anyone claims it
    pipe2 = ParallelPipelineExecutor(_Reader(), batch_size=2, workers=1,
                                     name="probe-test", health=monitor,
                                     registry=MetricsRegistry(),
                                     tracer=Tracer(enabled=False))
    import time
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        rep = monitor.check()["components"]["etl:probe-test"]
        if rep["status"] == "unhealthy":
            break
    assert rep["status"] == "unhealthy", rep
    with pytest.raises(ValueError):
        pipe2.close()                   # close surfaces the parked error...
    assert "etl:probe-test" not in monitor.components()  # ...and unregisters


def test_etl_pipelines_sharing_a_name_get_distinct_probes(manual_clock):
    from deeplearning4j_tpu.etl import ParallelPipelineExecutor
    monitor = HealthMonitor()

    class _Reader:
        def __init__(self):
            self.i = 0

        def has_next(self):
            return self.i < 4

        def next_record(self):
            self.i += 1
            return [1.0]

        def reset(self):
            self.i = 0

    kw = dict(batch_size=2, workers=1, health=monitor,
              registry=MetricsRegistry(), tracer=Tracer(enabled=False))
    a = ParallelPipelineExecutor(_Reader(), name="etl", **kw)
    b = ParallelPipelineExecutor(_Reader(), name="etl", **kw)
    assert monitor.components() == ["etl:etl", "etl:etl-2"]
    a.close()
    assert monitor.components() == ["etl:etl-2"]   # b's probe survives
    # close -> reset re-registers a's coverage under a FRESH unique key
    # (never adopting b's), and a's next close leaves b's probe alone
    a.reset()
    assert sorted(monitor.components()) == ["etl:etl", "etl:etl-2"]
    a.close()
    assert monitor.components() == ["etl:etl-2"]
    b.close()
    assert monitor.components() == []


# ---------------------------------------------- acceptance + smoke tool

def test_acceptance_nan_run_alerts_healthz_logs_trace_correlated(
        tmp_path, manual_clock):
    """ISSUE 4 acceptance: a NaN-loss training run fires an alert at
    GET /alerts, flips deep /healthz to 503 with the trainer unhealthy, and
    the structured /logs records carry trace ids matching the training
    iteration spans — all under ManualClock, zero wall-clock sleeps."""
    from tools.smoke_telemetry import _tiny_net
    from deeplearning4j_tpu import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.optimize.listeners import (TrainingHalted,
                                                       TrainingHealthListener)
    from deeplearning4j_tpu.serving import ServingServer
    from deeplearning4j_tpu.telemetry import get_tracer
    from deeplearning4j_tpu.train import CheckpointConfig, FaultTolerantTrainer

    tracer = get_tracer()
    was_enabled, tracer.enabled = tracer.enabled, True
    server = ServingServer(_tiny_net(), max_batch_size=8,
                           alert_interval_s=0).start()
    try:
        for rule in default_training_rules():
            server.alerts.add_rule(rule)
        watchdog = TrainingHealthListener(health=server.health,
                                          registry=server.metrics.registry,
                                          logger=server.logger)
        X = np.random.default_rng(1).normal(size=(16, 6)).astype(np.float32)
        X[0, 0] = np.nan
        Y = np.eye(3, dtype=np.float32)[np.arange(16) % 3]
        trainer = FaultTolerantTrainer(
            lambda: _tiny_net(), CheckpointConfig(tmp_path, frequency=0),
            health=watchdog)
        with pytest.raises(TrainingHalted):
            trainer.fit(ListDataSetIterator(DataSet(X, Y), batch_size=8),
                        epochs=1)
        server.alerts.evaluate()

        status, alerts = _http_get(server.url + "/alerts")
        firing = {r["name"] for r in alerts["rules"]
                  if r["state"] == "firing"}
        assert "training_nan" in firing, alerts

        status, h = _http_get(server.url + "/healthz")
        assert status == 503, h
        assert h["health"] == "unhealthy"
        assert h["components"]["trainer"]["status"] == "unhealthy"
        assert h["components"]["trainer"]["reason"] == "nan_loss"

        status, logs = _http_get(server.url + "/logs?level=error")
        nan_recs = [r for r in logs["records"]
                    if r["message"] == "training_nan_loss"]
        assert nan_recs
        iteration_traces = {s.trace_id for s in tracer.finished_spans()
                            if s.name == "iteration"}
        assert all(r["trace_id"] in iteration_traces for r in nan_recs)
    finally:
        server.stop()
        tracer.enabled = was_enabled
        tracer.clear()


def test_smoke_health_tool():
    """tools/smoke_health.py end to end (fast, like the other smoke
    harnesses): healthy baseline, injected-probe 503, NaN halt, firing
    alert, trace-correlated logs."""
    import tools.smoke_health as smoke
    out = smoke.run()
    assert out["firing"] == ["training_nan"]
    assert out["halt_reason"] == "nan_loss"
    assert out["nan_log_records"] >= 1
