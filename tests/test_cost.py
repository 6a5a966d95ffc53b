"""Live cost attribution tests (telemetry/cost.py, ISSUE 19):

- `compiled_costs` / `classify` are the ONE implementation of the cost
  extraction + roofline arithmetic.
- `ExecutableCostRegistry.capture` attributes every executable family —
  serve (batcher buckets, with pow2-padding-aware per-sample
  normalization), decode (step/prefill), train (the `timed_first_call`
  seam behind the process-default opt-in) — with zero ADDED recompiles
  (AOT lowering never touches jax's dispatch cache).
- Sampled dispatch histograms stay exact under concurrent dispatch, with
  zero sleeps.
- `/profile/cost` + `/profile/trace` HTTP contract on ServingServer and
  UIServer: 400 on bad params, bounded capture always stops.
- The deploy bytes-regression gauge + default alert rule: a
  quantized→f32 fallback deploy fires `deploy_bytes_regression`, a
  rollback resolves it.
- Donation failures are live metrics: a seeded unusable donation counts
  into `donation_warnings_total{site}`; the char-RNN TBPTT scan path
  (whose carries once could not be donated) stays at ZERO.
"""
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (NeuralNetConfiguration, InputType, DenseLayer,
                                OutputLayer, MultiLayerNetwork, Sgd)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.decode import DecodeEngine
from deeplearning4j_tpu.serving import ModelRegistry, ServingServer
from deeplearning4j_tpu.telemetry.alerts import (AlertEngine, FIRING,
                                                 default_serving_rules)
from deeplearning4j_tpu.telemetry.cost import (MAX_TRACE_STEPS,
                                               ExecutableCostRegistry,
                                               abstractify, capture_trace,
                                               classify, compiled_costs,
                                               get_cost_registry,
                                               install_donation_watch,
                                               set_cost_registry)
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
from deeplearning4j_tpu.telemetry.trace import Tracer
from deeplearning4j_tpu.telemetry.xla import timed_first_call
from deeplearning4j_tpu.ui.server import UIServer
from deeplearning4j_tpu.zoo.models import transformer_lm


def _net(nin=6, nout=3, seed=0):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=nout, activation="softmax",
                               loss="MCXENT"))
            .input_type(InputType.feed_forward(nin))
            .build())
    return MultiLayerNetwork(conf).init()


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, json.loads(r.read().decode())


def _get_status(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status
    except urllib.error.HTTPError as e:
        return e.code


class StubCompiled:
    """Duck-typed jax Compiled: fixed cost/memory analysis, so deploy-ratio
    and table logic test without paying real XLA compiles."""

    def __init__(self, flops, nbytes, temp=0.0):
        self._flops, self._nbytes, self._temp = flops, nbytes, temp

    def cost_analysis(self):
        return {"flops": self._flops, "bytes accessed": self._nbytes}

    def memory_analysis(self):
        class M:
            pass
        m = M()
        m.temp_size_in_bytes = self._temp
        m.argument_size_in_bytes = 0.0
        m.output_size_in_bytes = 0.0
        m.generated_code_size_in_bytes = 0.0
        return m


# ----------------------------------------------------- extraction helpers

def test_compiled_costs_of_real_executable_nonzero_and_flat_cache():
    """The cost plane's read of a compiled executable: nonzero flops/bytes
    from a real compiled matmul, and lowering does NOT grow the jitted
    fn's dispatch cache (the zero-added-recompiles invariant)."""
    fn = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((8, 16), jnp.float32)
    fn(a, a.T)                                       # compile once
    before = fn._cache_size()
    comp = fn.lower(*abstractify((a, a.T))).compile()
    costs = compiled_costs(comp)
    assert costs["flops"] > 0 and costs["hbm_bytes"] > 0
    assert fn._cache_size() == before
    # degraded object: never raises, reports zeros
    assert compiled_costs(object())["flops"] == 0.0


def test_classify_matches_bench_roofline_arithmetic():
    flops, nbytes = 5.71e12, 85.07e9
    tf_ceiling, bw = 174.9e12, 820e9
    cls = classify(flops, nbytes, tflops_ceiling=tf_ceiling,
                   hbm_bps_ceiling=bw, measured_ms=103.13)
    assert cls["roofline_compute_ms"] == pytest.approx(flops / tf_ceiling
                                                       * 1e3)
    assert cls["roofline_hbm_ms"] == pytest.approx(nbytes / bw * 1e3)
    assert cls["roofline_binding"] == "hbm"
    assert cls["roofline_util"] == pytest.approx(
        (nbytes / bw * 1e3) / 103.13)
    # flip the legs: tiny byte count on the same flops is matmul-bound
    assert classify(flops, 1.0, tflops_ceiling=tf_ceiling,
                    hbm_bps_ceiling=bw)["roofline_binding"] == "matmul"
    assert classify(1.0, 1.0)["roofline_util"] is None


def test_capture_normalizes_per_sample_and_labels_gauges():
    reg = MetricsRegistry()
    cost = ExecutableCostRegistry(reg)
    row = cost.capture_compiled("serve:b8", StubCompiled(800.0, 1600.0),
                                samples=8, version="v1")
    assert row["family"] == "serve"
    assert row["flops_per_sample"] == pytest.approx(100.0)
    assert row["hbm_bytes_per_sample"] == pytest.approx(200.0)
    assert reg.get("executable_flops_per_sample").get(
        executable="serve:b8") == pytest.approx(100.0)
    assert cost.to_dict()["executables"][0]["executable"] == "serve:b8"
    # the CPU has no published peaks: no legs, no binding, no util — never
    # another chip's numbers
    assert row["roofline_binding"] is None and row["roofline_util"] is None
    assert reg.get("roofline_binding").get(executable="serve:b8") is None
    assert cost.to_dict()["ceilings"] == {"matmul_tflops_ceiling": None,
                                          "hbm_gbps_ceiling": None}
    # with ceilings given the row is classified
    given = ExecutableCostRegistry(reg, matmul_tflops_ceiling=100.0,
                                   hbm_gbps_ceiling=800.0)
    row = given.capture_compiled("serve:b8", StubCompiled(800.0, 1600.0),
                                 samples=8, version="v1")
    assert row["roofline_binding"] == "hbm"
    assert reg.get("roofline_binding").get(executable="serve:b8") == 1.0


def test_device_peaks_table_is_keyed_by_device_kind():
    from deeplearning4j_tpu.telemetry.cost import DEVICE_PEAKS, device_peaks
    assert device_peaks() is None                    # tests run on the CPU
    v5e = device_peaks("TPU v5 lite")
    assert v5e is DEVICE_PEAKS["TPU v5 lite"]
    assert v5e["flops"] == 197e12 and v5e["hbm_bps"] == 819e9
    assert "source" in v5e


def test_capture_error_counts_not_raises():
    reg = MetricsRegistry()
    cost = ExecutableCostRegistry(reg)
    assert cost.capture("bad", object(), (1, 2)) is None
    assert reg.get("cost_capture_errors_total").get(executable="bad") == 1


# ---------------------------------------------------------- train family

def test_train_family_captured_via_timed_first_call_opt_in():
    """The process-default registry is opt-in: with it set, the first call
    of a timed_first_call-wrapped train step lands a cost row; with it
    None (the unit-test default), nothing is captured."""
    reg = MetricsRegistry()
    cost = ExecutableCostRegistry(reg)
    assert get_cost_registry() is None
    set_cost_registry(cost)
    try:
        net = _net()
        x = np.random.default_rng(0).normal(size=(4, 6)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[[0, 1, 2, 0]]
        net.fit_batch(DataSet(x, y))
        labels = cost.labels()
        train = [l for l in labels if l.startswith("train_step")]
        assert train, labels
        row = cost.get(train[0])
        assert row["flops"] > 0 and row["hbm_bytes"] > 0
        # steady state: more steps, same executable, no new capture
        n = reg.get("cost_captures_total").get(executable=train[0],
                                               family="train_step")
        net.fit_batch(DataSet(x, y))
        assert reg.get("cost_captures_total").get(
            executable=train[0], family="train_step") == n
    finally:
        set_cost_registry(None)
    net2 = _net(seed=3)
    net2.fit_batch(DataSet(np.ones((2, 6), np.float32),
                           np.eye(3, dtype=np.float32)[[0, 1]]))
    assert cost.labels() == sorted(labels)      # nothing new after opt-out


# ---------------------------------------------------------- serve family

def test_serve_family_capture_normalizes_by_padded_bucket():
    """3 logical rows pad to the pow2 bucket of 4: the cost row's samples
    is the PADDED bucket (what the executable actually serves), so
    per-sample numbers divide by 4, and dispatches count."""
    registry = ModelRegistry()
    registry.register("v1", _net())
    registry.deploy("v1")
    server = ServingServer(None, registry=registry, max_latency_ms=1.0)
    server.batcher.start()
    try:
        x = np.random.default_rng(1).normal(size=(3, 6)).astype(np.float32)
        server.predict(x, wait_s=30)
        row = server.cost.get("serve:b4")
        assert row is not None, server.cost.labels()
        assert row["samples"] == 4
        assert row["flops"] > 0 and row["hbm_bytes"] > 0
        assert row["flops_per_sample"] == pytest.approx(row["flops"] / 4)
        assert row["version"] == "v1"
        assert row["dispatches"] >= 1
        # steady state: same bucket re-dispatches without re-capturing
        n = server.metrics.registry.get("cost_captures_total").get(
            executable="serve:b4", family="serve")
        server.predict(x, wait_s=30)
        assert server.metrics.registry.get("cost_captures_total").get(
            executable="serve:b4", family="serve") == n
        assert server.cost.dispatches("serve:b4") >= 2
    finally:
        server.stop()


# --------------------------------------------------------- decode family

def test_decode_family_capture_step_and_prefill():
    net = transformer_lm(vocab_size=24, d_model=32, n_layers=1, n_heads=2,
                         seed=1).init()
    reg = MetricsRegistry()
    cost = ExecutableCostRegistry(reg, sample_every=1,
                                  matmul_tflops_ceiling=1.0,
                                  hbm_gbps_ceiling=10.0)
    eng = DecodeEngine(net, slots=2, max_len=32, cost_registry=cost)
    eng.generate([1, 2, 3], 4)
    labels = cost.labels()
    assert "decode_step" in labels, labels
    assert any(l.startswith("decode_prefill") for l in labels), labels
    step = cost.get("decode_step")
    assert step["family"] == "decode"
    assert step["samples"] == 2                  # slots = tokens per dispatch
    assert step["flops"] > 0
    # sample_every=1 -> every dispatch sampled, util estimated live
    # (prefill yields the first token, so 4 new tokens = 3 step dispatches)
    assert step["dispatches"] >= 3
    assert cost.get("decode_step")["roofline_util"] is not None
    assert reg.get("dispatch_ms").count(executable="decode_step") >= 3


# --------------------------------------------------- dispatch sampling

def test_sampled_dispatch_histogram_exact_under_concurrency():
    """96 dispatches from 4 threads at sample_every=16: the dispatch count
    is exact and exactly ceil(96/16)=6 land in the histogram — one lock +
    int increment per unsampled dispatch, zero sleeps anywhere."""
    cost = ExecutableCostRegistry(MetricsRegistry(), sample_every=16)

    def worker():
        for _ in range(24):
            cost.record_dispatch("mesh_dispatch", 1.25)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert cost.dispatches("mesh_dispatch") == 96
    assert cost.dispatch_hist.count(executable="mesh_dispatch") == 6
    # sample_every=1 degenerates to every-dispatch observation
    every = ExecutableCostRegistry(MetricsRegistry(), sample_every=1)
    for _ in range(5):
        every.record_dispatch("d", 2.0)
    assert every.dispatch_hist.count(executable="d") == 5


# --------------------------------------------- deploy bytes regression

def test_deploy_bytes_regression_alert_fires_and_resolves():
    """A hot-swap that doubles hbm_bytes_per_sample (the f32-fallback
    shape) sets the ratio gauge past 1.2 and fires the default
    `deploy_bytes_regression` rule; rolling back re-captures the lean
    version and the rule resolves."""
    mreg = MetricsRegistry()
    cost = ExecutableCostRegistry(mreg)
    engine = AlertEngine(registry=mreg, rules=default_serving_rules(),
                         interval_s=3600.0)
    cost.capture_compiled("serve:b4", StubCompiled(100.0, 1000.0),
                          samples=4, version="v1")
    engine.evaluate()
    rule = next(r for r in engine.rules
                if r.name == "deploy_bytes_regression")
    assert rule.state != FIRING                 # no transition yet
    cost.capture_compiled("serve:b4", StubCompiled(100.0, 2000.0),
                          samples=4, version="v2")
    assert mreg.get("deploy_hbm_bytes_per_sample_ratio").get() \
        == pytest.approx(2.0)
    assert mreg.get("deploy_hbm_bytes_per_sample_ratio").get(
        family="serve") == pytest.approx(2.0)
    engine.evaluate()
    assert rule.state == FIRING, rule.status()
    # rollback: same label re-captured at the lean version's bytes
    cost.capture_compiled("serve:b4", StubCompiled(100.0, 1000.0),
                          samples=4, version="v1")
    assert mreg.get("deploy_hbm_bytes_per_sample_ratio").get() \
        == pytest.approx(0.5)
    engine.evaluate()
    assert rule.state != FIRING, rule.status()
    # a same-version re-capture (warmup replay) is NOT a deploy: ratio holds
    cost.capture_compiled("serve:b4", StubCompiled(100.0, 999.0),
                          samples=4, version="v1")
    assert mreg.get("deploy_hbm_bytes_per_sample_ratio").get() \
        == pytest.approx(0.5)


# ------------------------------------------------------- HTTP contract

def test_profile_cost_and_trace_http_contract_serving():
    server = ServingServer(_net(), port=0).start()
    try:
        x = np.ones((2, 6), np.float32)
        server.predict(x, wait_s=30)
        status, body = _get(server.url + "/profile/cost")
        assert status == 200
        assert body["ceilings"]["hbm_gbps_ceiling"] is None   # CPU: no peaks
        rows = body["executables"]
        assert any(r["executable"].startswith("serve:") for r in rows)
        for r in rows:
            assert r["flops"] > 0 and r["roofline_binding"] is None
        # unknown sort / family filters degrade, never 500
        assert _get_status(server.url + "/profile/cost?sort=bogus") == 200
        status, body = _get(server.url + "/profile/cost?family=nope")
        assert status == 200 and body["executables"] == []
        # trace: bad params are 400s, good one returns a bounded capture
        for bad in ("", "?steps=0", "?steps=-3", "?steps=abc",
                    f"?steps={MAX_TRACE_STEPS + 1}"):
            assert _get_status(server.url + "/profile/trace" + bad) == 400, bad
        server.predict(x, wait_s=30)
        status, body = _get(server.url + "/profile/trace?steps=2&timeout_s=0.2")
        assert status == 200
        assert body["otherData"]["requested_steps"] == 2
        assert body["otherData"]["captured_spans"] <= 2
    finally:
        server.stop()


def test_profile_routes_on_ui_server():
    cost = ExecutableCostRegistry(MetricsRegistry())
    cost.capture_compiled("serve:b2", StubCompiled(10.0, 20.0), samples=2)
    server = UIServer(port=0, cost=cost).start()
    try:
        status, body = _get(server.url + "/profile/cost")
        assert status == 200
        assert body["executables"][0]["executable"] == "serve:b2"
        assert _get_status(server.url + "/profile/trace?steps=0") == 400
    finally:
        server.stop()


def test_capture_trace_always_stops_when_idle():
    """The bounded capture returns even with zero traffic: the poll loop is
    iteration-capped, and the tracer's prior enabled state is restored."""
    tracer = Tracer(enabled=False)
    out = capture_trace(4, tracer=tracer, timeout_s=0.05, poll_s=0.01)
    assert out["otherData"]["captured_spans"] == 0
    assert tracer.enabled is False
    with pytest.raises(ValueError):
        capture_trace(0, tracer=tracer)
    with pytest.raises(ValueError):
        capture_trace(MAX_TRACE_STEPS + 1, tracer=tracer)


# ------------------------------------------------------- donation watch

def _unusable_donation():
    """Deterministic XLA 'donated buffers were not usable': every output is
    f16/smaller than the donated f32 input, so the donation can't stick."""
    fn = jax.jit(lambda x: jnp.float16(0) + x[:1].astype(jnp.float16),
                 donate_argnums=(0,))
    fn(jnp.ones((8,), jnp.float32))


def test_donation_watch_counts_with_site_label():
    reg = MetricsRegistry()
    uninstall = install_donation_watch(reg)
    try:
        _unusable_donation()
        series = reg.get("donation_warnings_total").series()
        counted = {k.get("site"): v for k, v in series if v > 0}
        assert counted, series
        assert any("test_cost.py" in site for site in counted), counted
    finally:
        uninstall()
    # after uninstall this subscriber's counter stays put
    before = reg.get("donation_warnings_total").get()
    _unusable_donation()
    assert reg.get("donation_warnings_total").get() == before


def test_char_rnn_tbptt_scan_has_zero_donation_warnings():
    """Regression pin for the warning the TBPTT carries once drew
    (`float32[64,256] x4`): the scanned TBPTT window path compiles with every
    donation usable on this backend — the counter stays at ZERO through
    prepare/fit. If a carry change re-breaks donation, this counts it."""
    from deeplearning4j_tpu.zoo.models import char_rnn_lstm
    reg = MetricsRegistry()
    uninstall = install_donation_watch(reg)
    try:
        net = char_rnn_lstm(vocab_size=12, hidden=8, layers=2, tbptt=4)
        net.init()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 12, size=(4, 9))
        x = np.eye(12, dtype=np.float32)[ids[:, :-1]]
        y = np.eye(12, dtype=np.float32)[ids[:, 1:]]
        ds = DataSet(jnp.asarray(x), jnp.asarray(y))
        plan = net.prepare_steps([ds] * 2)
        assert plan is not None and plan[0] == "tbptt"
        net.fit_prepared(plan)
        assert reg.get("donation_warnings_total").get() == 0, \
            reg.get("donation_warnings_total").series()
    finally:
        uninstall()


# -------------------------------------------------------------- smoke tool

def test_smoke_profile_tool():
    """Fast variant of tools/smoke_profile.py: deploy, push traffic, scrape
    /profile/cost, and hold the full attribution contract — every active
    executable attributed (no roofline binding on the CPU), zero steady-state
    recompiles/re-captures, and sampled-histogram overhead < 1% of
    steady-state dispatch time."""
    import tools.smoke_profile as smoke
    out = smoke.run(n_requests=12, concurrency=4)
    assert out["executables"] >= 1
    assert out["captures"] == out["executables"]
    assert out["dispatches"] > out["executables"]
    assert out["binding"] is None                    # CPU: no peaks
    assert out["sampling_overhead_pct"] < 1.0


# ------------------------------------------------------------ fleet merge

def test_fleet_profile_merges_cost_tables_across_instances():
    """GET /fleet/profile: one live server with a warm cost table plus one
    dead peer — the merged view tags every row with its instance, sorts by
    bytes-per-sample, and reports the dead peer as an error entry instead
    of failing the merge."""
    from deeplearning4j_tpu.telemetry import FleetCollector
    server = ServingServer(_net(), max_batch_size=8,
                           max_latency_ms=1.0).start()
    try:
        x = np.random.default_rng(5).normal(size=(3, 6)).astype(np.float32)
        server.predict(x, wait_s=30)
        fc = FleetCollector([server.url, "http://127.0.0.1:9"],
                            names=["a", "dead"], interval_s=30.0,
                            timeout_s=2.0)
        assert fc.maybe_poll() is True
        p = fc.profile()
        assert set(p["instances"]) == {"a", "dead"}
        assert "error" in p["instances"]["dead"]
        assert p["instances"]["a"]["executables"], "live peer table empty"
        rows = p["executables"]
        assert rows and all(r["instance"] == "a" for r in rows)
        assert any(r["executable"].startswith("serve:") for r in rows)
        keys = [-float(r.get("hbm_bytes_per_sample") or 0.0) for r in rows]
        assert keys == sorted(keys), "rows not ranked by bytes/sample"
    finally:
        server.stop()


# ------------------------------------------------------- compile cache

def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """`enable_compile_cache`: where JAX_COMPILATION_CACHE_DIR is set JAX
    has read it and nothing is set in code; where it is not, the fixed
    `<checkout>/.jax_cache`. (jax.config.update is stubbed: the tests never
    turn the cache on.)"""
    from pathlib import Path
    from deeplearning4j_tpu.util import compile_cache as cc
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert calls == []
    monkeypatch.delenv(cc.ENV_VAR)
    root = Path(__file__).resolve().parents[1]
    assert cc.enable_compile_cache() == str(root / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", str(root / ".jax_cache"))]
