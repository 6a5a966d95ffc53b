"""Production serving subsystem tests: dynamic micro-batching (padded
power-of-two buckets, zero steady-state recompiles), versioned registry
hot-swap, admission control (deadlines, 429 shedding, graceful drain), and
metrics routing into the ui/storage stats tier."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (NeuralNetConfiguration, InputType, DenseLayer,
                                OutputLayer, MultiLayerNetwork, Sgd,
                                ModelSerializer)
from deeplearning4j_tpu.serving import (AdmissionQueue, DeadlineExceeded,
                                        DynamicBatcher, ModelRegistry,
                                        RejectedError, ServingMetrics,
                                        ServingServer, bucket_for)
from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage


def _net(nin=6, nout=3, seed=0):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=nout, activation="softmax", loss="MCXENT"))
            .input_type(InputType.feed_forward(nin))
            .build())
    return MultiLayerNetwork(conf).init()


class StubModel:
    """Duck-typed model: deterministic affine output + optional dispatch
    delay, to exercise batching/swap/deadline logic without XLA compiles."""

    def __init__(self, scale, delay_s=0.0):
        self.scale = float(scale)
        self.delay_s = float(delay_s)

    def output(self, x):
        if self.delay_s:
            time.sleep(self.delay_s)
        return np.asarray(x) * self.scale


def _component_server(model, **kw):
    """ServingServer with only the batcher running (no HTTP socket)."""
    server = ServingServer(model, **kw)
    server.batcher.start()
    return server


def _wait_queue_empty(server, timeout=5.0):
    deadline = time.monotonic() + timeout
    while server.queue.depth() > 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert server.queue.depth() == 0


# --------------------------------------------------------------- batching

def test_bucket_for_powers_of_two():
    assert [bucket_for(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]


def test_batched_predict_bitwise_identical_to_direct_output():
    """Acceptance: batched /predict == direct model.output — bitwise against
    the executable the batcher ran (the rows padded to their power-of-two
    bucket), and to f32 rounding against the unpadded call, which is another
    executable: XLA vectorises a 3-row and a 4-row batch differently, so the
    last bit may differ there."""
    from deeplearning4j_tpu.serving.batcher import bucket_for
    net = _net()
    server = ServingServer(net, port=0).start()
    rng = np.random.default_rng(0)
    try:
        for rows in (4, 3, 1, 2):
            x = rng.normal(size=(rows, 6)).astype(np.float32)
            req = urllib.request.Request(
                server.url + "/predict",
                data=json.dumps({"data": x.tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                out = json.loads(r.read())
            pad = np.zeros((bucket_for(rows) - rows, 6), np.float32)
            direct = np.asarray(net.output(np.concatenate([x, pad])))[:rows]
            got = np.asarray(out["prediction"], dtype=direct.dtype)
            np.testing.assert_array_equal(got, direct)
            np.testing.assert_allclose(got, np.asarray(net.output(x)),
                                       rtol=1e-6, atol=1e-7)
            assert out["shape"] == [rows, 3]
            assert out["version"] == "v1"
    finally:
        server.stop()


def test_legacy_1d_body_served_as_single_example():
    """A flat-vector body (legacy single example) must be lifted to a 1-row
    batch — not padded/chunked along the feature axis — and answered with
    the un-batched shape, as the old InferenceServer did."""
    net = _net()
    server = ServingServer(net, port=0).start()
    rng = np.random.default_rng(7)
    x1d = rng.normal(size=(6,)).astype(np.float32)
    try:
        req = urllib.request.Request(
            server.url + "/predict",
            data=json.dumps({"data": x1d.tolist()}).encode())
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out["shape"] == [3]
        np.testing.assert_allclose(out["prediction"],
                                   np.asarray(net.output(x1d)),
                                   rtol=1e-6, atol=1e-7)
    finally:
        server.stop()


def test_legacy_wrapper_public_attributes():
    """The compat wrapper keeps the old public surface: `.model` and an
    assignable `.served` counter."""
    from deeplearning4j_tpu.streaming import InferenceServer
    net = _net()
    server = InferenceServer(net, port=0).start()
    try:
        assert server.model is net
        server.predict(np.ones((2, 6), dtype=np.float32))
        assert server.served == 2
        server.served = 0                      # legacy reset still works
        assert server.served == 0
        server.predict(np.ones((3, 6), dtype=np.float32))
        assert server.served == 3
        # legacy hot-swap idiom: assigning .model must change what serves
        net2 = _net(seed=1)
        server.model = net2
        assert server.model is net2
        x = np.ones((2, 6), dtype=np.float32)
        np.testing.assert_array_equal(
            server.predict(x)["prediction"], np.asarray(net2.output(x)))
        # ...without leaking old versions (repeated assignment = one model)
        for _ in range(3):
            server.model = _net(seed=2)
        assert len(server.registry.versions()) == 1
    finally:
        server.stop()


def test_stop_start_cycle_resumes_serving():
    """stop()/start() (maintenance pause) must come back actually serving,
    not shedding everything with 429 off a permanently closed queue."""
    net = _net()
    server = ServingServer(net, port=0).start()
    x = np.ones((2, 6), dtype=np.float32)
    first = server.predict(x)
    observed_before = set(server.batcher.observed)
    server.stop()
    server.start()
    try:
        # observed buckets survive the restart so deploy warm-up still
        # covers pre-restart traffic shapes
        assert server.batcher.observed == observed_before != set()
        again = server.predict(x)
        np.testing.assert_array_equal(again["prediction"],
                                      first["prediction"])
    finally:
        server.stop()


def test_abandon_cancels_lifted_and_chunked_work():
    """_abandon (the 503 path) must free queue capacity for 1-D lifted and
    chunked requests, not just cancel the outer wrapper future."""
    server = _component_server(StubModel(2.0, delay_s=0.3),
                               queue_capacity=1, max_latency_ms=1.0)
    try:
        x = np.ones((1, 4), dtype=np.float32)
        busy = server.submit(x)
        _wait_queue_empty(server)
        time.sleep(0.05)
        lifted = server.submit(np.ones(4, dtype=np.float32))  # fills queue
        server._abandon(lifted)
        live = server.submit(x)         # capacity freed: admitted, not 429
        busy.result(timeout=10)
        np.testing.assert_array_equal(live.result(timeout=10)["prediction"],
                                      x * 2.0)
        time.sleep(0.1)
        assert server.metrics.rows.get() == 2   # abandoned row never served
    finally:
        server.stop()


def test_transform_applied_exactly_once_for_1d_input():
    """The 1-D lift must not re-apply the transform (legacy semantics:
    transform runs once on the raw input)."""
    server = _component_server(StubModel(1.0), max_latency_ms=1.0,
                               transform=lambda x: x + 1.0)
    try:
        res = server.submit(np.zeros(4, dtype=np.float32)).result(timeout=10)
        np.testing.assert_array_equal(res["prediction"],
                                      np.ones(4, dtype=np.float32))
    finally:
        server.stop()


def test_zero_recompiles_mixed_sizes_within_bucket():
    """Acceptance: a steady-state mixed-size workload compiles at most one
    executable per shape bucket (counted via the jit cache)."""
    net = _net()
    server = _component_server(net, max_latency_ms=1.0)
    rng = np.random.default_rng(1)
    try:
        # warm one bucket: sizes 3 and 4 both pad to bucket 4
        for rows in (3, 4):
            server.predict(rng.normal(size=(rows, 6)).astype(np.float32))
        jitted = net._jit_cache[("output", False, False)]
        assert jitted._cache_size() == 1      # ONE executable for the bucket
        for _ in range(20):                    # steady state: zero recompiles
            rows = int(rng.integers(3, 5))
            server.predict(rng.normal(size=(rows, 6)).astype(np.float32))
        assert jitted._cache_size() == 1
        # new bucket sizes compile exactly one executable each
        for rows in (1, 2):
            server.predict(rng.normal(size=(rows, 6)).astype(np.float32))
        assert jitted._cache_size() == 3      # buckets {1, 2, 4}
        for _ in range(20):
            rows = int(rng.integers(1, 5))
            server.predict(rng.normal(size=(rows, 6)).astype(np.float32))
        assert jitted._cache_size() == 3
        hist = server.metrics.snapshot()["batch_size_histogram"]
        assert set(hist) <= {"1", "2", "4"}
    finally:
        server.stop()


def test_concurrent_requests_coalesce_into_batches():
    """Concurrent submits within the latency window share a dispatch."""
    server = _component_server(StubModel(2.0, delay_s=0.05),
                               max_batch_size=8, max_latency_ms=100.0)
    try:
        xs = [np.full((2, 4), float(i + 1), dtype=np.float32)
              for i in range(4)]
        futs = [server.submit(x) for x in xs]
        for x, fut in zip(xs, futs):
            res = fut.result(timeout=10)
            np.testing.assert_array_equal(res["prediction"], x * 2.0)
        snap = server.metrics.snapshot()
        assert snap["requests"] == 4 and snap["rows"] == 8
        assert snap["batches"] < 4            # at least one coalesced batch
    finally:
        server.stop()


# ----------------------------------------------------- admission control

def test_deadline_expiry_fails_exactly_the_expired_caller():
    server = _component_server(StubModel(2.0, delay_s=0.3),
                               max_latency_ms=1.0)
    try:
        x = np.ones((1, 4), dtype=np.float32)
        f1 = server.submit(x)                  # occupies the batcher ~300ms
        _wait_queue_empty(server)
        time.sleep(0.05)                       # f1's coalescing window closed
        f2 = server.submit(x, timeout_ms=50)   # expires while queued
        f3 = server.submit(x * 3)              # no deadline: must survive
        with pytest.raises(DeadlineExceeded):
            f2.result(timeout=10)
        np.testing.assert_array_equal(f1.result(timeout=10)["prediction"],
                                      x * 2.0)
        np.testing.assert_array_equal(f3.result(timeout=10)["prediction"],
                                      x * 6.0)
        assert server.metrics.expired.get() == 1
        assert server.metrics.requests.get() == 2
    finally:
        server.stop()


def test_full_queue_sheds_immediately():
    server = _component_server(StubModel(1.0, delay_s=0.5),
                               queue_capacity=2, max_latency_ms=1.0)
    try:
        x = np.ones((1, 4), dtype=np.float32)
        first = server.submit(x)               # taken by the batcher
        _wait_queue_empty(server)
        time.sleep(0.05)                       # its coalescing window closed
        queued = [server.submit(x) for _ in range(2)]   # fills the queue
        t0 = time.monotonic()
        with pytest.raises(RejectedError) as exc:
            server.submit(x)
        assert time.monotonic() - t0 < 0.1     # shed decision, not a hang
        assert exc.value.retry_after_s >= 1
        assert server.metrics.shed.get() == 1
        for f in [first] + queued:             # admitted work still completes
            f.result(timeout=10)
    finally:
        server.stop()


def test_expired_queue_entries_dont_cause_false_429():
    """Requests that expired while queued are dead weight: they must not
    count against capacity and shed live traffic off an idle queue."""
    server = _component_server(StubModel(2.0, delay_s=0.4),
                               queue_capacity=2, max_latency_ms=1.0)
    try:
        x = np.ones((1, 4), dtype=np.float32)
        busy = server.submit(x)                # occupy the batcher ~400ms
        _wait_queue_empty(server)
        time.sleep(0.05)
        dead = [server.submit(x, timeout_ms=10) for _ in range(2)]  # fills it
        time.sleep(0.05)                       # both now expired in queue
        live = server.submit(x)                # must purge + admit, not 429
        for f in dead:
            with pytest.raises(DeadlineExceeded):
                f.result(timeout=10)
        np.testing.assert_array_equal(busy.result(timeout=10)["prediction"],
                                      x * 2.0)
        np.testing.assert_array_equal(live.result(timeout=10)["prediction"],
                                      x * 2.0)
        assert server.metrics.shed.get() == 0
    finally:
        server.stop()


def test_http_429_with_retry_after_not_a_hang():
    """Acceptance: a full queue yields HTTP 429 (not a hang)."""
    # max_batch_size=1: every dispatch is a 0.2s single-request batch, so
    # with capacity 1 the later concurrent posts must shed deterministically
    server = ServingServer(StubModel(2.0, delay_s=0.2), port=0,
                           queue_capacity=1, max_batch_size=1,
                           max_latency_ms=1.0).start()
    try:
        body = json.dumps({"data": [[1.0, 2.0]]}).encode()

        def fire(results, i):
            req = urllib.request.Request(server.url + "/predict", data=body)
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    results[i] = (r.status, None)
            except urllib.error.HTTPError as e:
                results[i] = (e.code, e.headers.get("Retry-After"))

        results = {}
        threads = [threading.Thread(target=fire, args=(results, i))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        codes = [c for c, _ in results.values()]
        assert len(codes) == 6                 # nothing hung
        assert codes.count(200) >= 1
        assert codes.count(429) >= 1
        retry_after = [ra for c, ra in results.values() if c == 429]
        assert all(ra is not None for ra in retry_after)
    finally:
        server.stop()


def test_client_cancelled_future_does_not_kill_batcher():
    """A caller may cancel() the future from submit(); completing a cancelled
    future raises InvalidStateError, which must be swallowed — not kill the
    batcher thread or fail innocent same-batch requests."""
    server = _component_server(StubModel(2.0, delay_s=0.1),
                               max_batch_size=8, max_latency_ms=50.0)
    try:
        x = np.ones((1, 4), dtype=np.float32)
        f1 = server.submit(x)
        assert f1.cancel()                     # cancelled while queued
        f2 = server.submit(x)                  # coalesces with cancelled f1
        np.testing.assert_array_equal(f2.result(timeout=10)["prediction"],
                                      x * 2.0)
        # cancelled + expired path must not kill the batcher either
        f3 = server.submit(x, timeout_ms=1)
        f3.cancel()
        time.sleep(0.05)
        f4 = server.submit(x)
        np.testing.assert_array_equal(f4.result(timeout=10)["prediction"],
                                      x * 2.0)
    finally:
        server.stop()


def test_graceful_drain_on_stop():
    server = _component_server(StubModel(2.0, delay_s=0.05),
                               max_latency_ms=1.0)
    x = np.ones((1, 4), dtype=np.float32)
    futs = [server.submit(x) for _ in range(4)]
    server.stop(drain=True)
    for f in futs:                             # nothing dropped on shutdown
        np.testing.assert_array_equal(f.result(timeout=1)["prediction"],
                                      x * 2.0)
    with pytest.raises(RejectedError, match="draining"):
        server.submit(x)


def test_oversized_request_chunked_into_bounded_buckets():
    """A request larger than max_batch_size is served by transparent
    server-side chunking (legacy clients may send any batch size) WITHOUT
    minting buckets past the log2(max_batch_size)+1 bound."""
    server = _component_server(StubModel(2.0), max_batch_size=8,
                               max_latency_ms=1.0)
    try:
        x = np.arange(100 * 4, dtype=np.float32).reshape(100, 4)
        res = server.submit(x).result(timeout=10)
        np.testing.assert_array_equal(res["prediction"], x * 2.0)  # in order
        assert all(bucket <= 8 for _, bucket in server.batcher.observed)
        assert server.metrics.rows.get() == 100
        assert server.metrics.requests.get() == 1  # one client call, not 13
    finally:
        server.stop()


def test_predict_before_any_deploy_fails_batch_not_batcher():
    """No model deployed: the request's future fails, the batcher thread
    survives, and serving recovers after a deploy."""
    registry = ModelRegistry()
    server = _component_server(None, registry=registry, max_latency_ms=1.0)
    try:
        fut = server.submit(np.ones((1, 4), dtype=np.float32))
        with pytest.raises(RuntimeError, match="no model deployed"):
            fut.result(timeout=10)
        assert server.metrics.errors.get() == 1
        registry.register("v1", StubModel(2.0))
        server.deploy("v1")                        # batcher must still be alive
        res = server.predict(np.ones((1, 4), dtype=np.float32), wait_s=10)
        np.testing.assert_array_equal(res["prediction"], [[2.0, 2.0, 2.0, 2.0]])
    finally:
        server.stop()


def test_short_deadline_not_held_for_full_coalescing_window():
    """timeout_ms shorter than max_latency_ms: the coalescing window is cut
    to the request's deadline, so it dispatches on time instead of being
    held the full window (let alone expiring)."""
    server = _component_server(StubModel(2.0), max_latency_ms=2000.0)
    try:
        x = np.ones((1, 4), dtype=np.float32)
        t0 = time.monotonic()
        res = server.submit(x, timeout_ms=100).result(timeout=10)
        elapsed = time.monotonic() - t0
        np.testing.assert_array_equal(res["prediction"], x * 2.0)
        assert elapsed < 1.0, f"held {elapsed:.2f}s by the 2s window"
        assert server.metrics.expired.get() == 0
    finally:
        server.stop()


def test_malformed_request_does_not_poison_deploy_warmup():
    """A wrong-feature-count request fails its own caller (400 path) but must
    not enter the observed-bucket set, or every later deploy/rollback would
    replay it and fail."""
    net1, net2 = _net(seed=0), _net(seed=1)
    registry = ModelRegistry()
    registry.register("v1", net1)
    registry.register("v2", net2)
    registry.deploy("v1")
    server = _component_server(None, registry=registry, max_latency_ms=1.0)
    rng = np.random.default_rng(5)
    try:
        good = rng.normal(size=(2, 6)).astype(np.float32)
        server.predict(good)
        bad = server.submit(rng.normal(size=(1, 999)).astype(np.float32))
        with pytest.raises(Exception):
            bad.result(timeout=10)
        assert all(sig != ((999,), "float32")
                   for sig, _ in server.batcher.observed)
        server.deploy("v2")                     # must not replay the bad shape
        assert server.predict(good)["version"] == "v2"
    finally:
        server.stop()


# ------------------------------------------------------ registry hot-swap

def test_hot_swap_mid_traffic_never_drops_or_mixes_versions():
    """Acceptance: hot-swap serves the new version without dropping in-flight
    requests, and no response mixes versions (v1 => x*2, v2 => x*3)."""
    registry = ModelRegistry()
    registry.register("v1", StubModel(2.0, delay_s=0.01))
    registry.register("v2", StubModel(3.0, delay_s=0.01))
    registry.deploy("v1")
    server = _component_server(None, registry=registry, max_batch_size=8,
                               max_latency_ms=2.0)
    results, errors = [], []
    lock = threading.Lock()

    def client(seed):
        rng = np.random.default_rng(seed)
        for _ in range(15):
            x = rng.normal(size=(int(rng.integers(1, 4)), 4)) \
                   .astype(np.float32)
            try:
                res = server.submit(x).result(timeout=10)
                with lock:
                    results.append((x, res))
            except Exception as e:
                with lock:
                    errors.append(e)

    try:
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.1)
        prev = server.deploy("v2")             # atomic swap mid-traffic
        assert prev == "v1"
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        assert len(results) == 60              # zero drops
        scale = {"v1": 2.0, "v2": 3.0}
        seen = set()
        for x, res in results:
            seen.add(res["version"])
            np.testing.assert_array_equal(res["prediction"],
                                          x * scale[res["version"]])
        assert seen == {"v1", "v2"}            # traffic straddled the swap
        counts = {v["version"]: v["serve_count"]
                  for v in registry.versions()}
        assert counts["v1"] > 0 and counts["v2"] > 0
        assert sum(counts.values()) == sum(x.shape[0] for x, _ in results)
    finally:
        server.stop()


def test_deploy_warmup_precompiles_observed_buckets():
    """The incoming version is warm-compiled on every observed bucket BEFORE
    the swap, so steady state on the new version triggers zero recompiles."""
    net1, net2 = _net(seed=0), _net(seed=1)
    registry = ModelRegistry()
    registry.register("v1", net1)
    registry.register("v2", net2)
    registry.deploy("v1")
    server = _component_server(None, registry=registry, max_latency_ms=1.0)
    rng = np.random.default_rng(3)
    try:
        for rows in (3, 4, 2):
            server.predict(rng.normal(size=(rows, 6)).astype(np.float32))
        server.deploy("v2")                    # warms buckets {2, 4} on net2
        jitted2 = net2._jit_cache[("output", False, False)]
        warmed = jitted2._cache_size()
        assert warmed == 2
        for _ in range(10):
            rows = int(rng.integers(2, 5))
            res = server.predict(
                rng.normal(size=(rows, 6)).astype(np.float32))
            assert res["version"] == "v2"
        assert jitted2._cache_size() == warmed  # zero post-swap recompiles
    finally:
        server.stop()


def test_registry_zip_load_deploy_rollback_over_http(tmp_path):
    net1, net2 = _net(seed=0), _net(seed=1)
    zip_path = str(tmp_path / "v2.zip")
    ModelSerializer.write_model(net2, zip_path)
    server = ServingServer(net1, port=0).start()
    rng = np.random.default_rng(4)
    x = rng.normal(size=(2, 6)).astype(np.float32)

    def predict():
        req = urllib.request.Request(
            server.url + "/predict",
            data=json.dumps({"data": x.tolist()}).encode())
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    def post(path, body):
        req = urllib.request.Request(server.url + path,
                                     data=json.dumps(body).encode())
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    try:
        out1 = predict()
        assert out1["version"] == "v1"
        d = post("/deploy", {"version": "v2", "path": zip_path})
        assert d == {"active": "v2", "previous": "v1"}
        out2 = predict()
        assert out2["version"] == "v2"
        np.testing.assert_allclose(out2["prediction"],
                                   np.asarray(net2.output(x)),
                                   rtol=1e-6, atol=1e-7)
        with urllib.request.urlopen(server.url + "/models", timeout=10) as r:
            models = json.loads(r.read())
        assert models["active"] == "v2"
        by_v = {m["version"]: m for m in models["models"]}
        assert set(by_v) == {"v1", "v2"}
        assert by_v["v2"]["active"] and not by_v["v1"]["active"]
        assert by_v["v2"]["path"] == zip_path
        assert by_v["v2"]["format"]["model_class"] == "MultiLayerNetwork"
        assert by_v["v1"]["serve_count"] == 2
        r = post("/rollback", {})
        assert r == {"active": "v1"}
        out3 = predict()
        assert out3["version"] == "v1"
        np.testing.assert_array_equal(out3["prediction"], out1["prediction"])
    finally:
        server.stop()


def test_failed_rollback_warmup_keeps_target_retryable():
    """A warm-up failure during rollback must leave BOTH the active version
    and the rollback target intact, so the rollback can be retried."""
    registry = ModelRegistry()
    registry.register("v1", StubModel(2.0))
    registry.register("v2", StubModel(3.0))
    registry.deploy("v1")
    registry.deploy("v2")

    def bad_warmup(model):
        raise RuntimeError("transient warmup failure")

    with pytest.raises(RuntimeError, match="transient"):
        registry.rollback(warmup=bad_warmup)
    assert registry.active_version == "v2"     # unchanged
    assert registry.rollback() == "v1"         # retry succeeds
    assert registry.active_version == "v1"


def test_metrics_scrape_is_rate_limited_to_router():
    """GET /metrics must not append one routed report per scrape."""
    router = InMemoryStatsStorage()
    server = ServingServer(StubModel(2.0), port=0, stats_router=router,
                           session_id="scrape", router_interval_s=60.0).start()
    try:
        for _ in range(5):
            with urllib.request.urlopen(server.url + "/metrics",
                                        timeout=10) as r:
                r.read()
        assert len(router.get_all_updates("scrape")) == 1   # gated
    finally:
        server.stop()
    # final flush on stop() is unconditional
    assert len(router.get_all_updates("scrape")) == 2


def test_chunked_request_admission_is_all_or_nothing():
    """An oversized request whose chunks don't currently fit the queue sheds
    cleanly (no partial chunks dispatched for a caller that got 429), and one
    that can NEVER fit is a permanent client error, not an eternal 429."""
    server = _component_server(StubModel(2.0, delay_s=0.3),
                               queue_capacity=3, max_batch_size=2,
                               max_latency_ms=1.0)
    try:
        x = np.ones((1, 4), dtype=np.float32)
        busy = server.submit(x)                # occupy the batcher
        _wait_queue_empty(server)
        time.sleep(0.05)
        queued = [server.submit(x) for _ in range(2)]     # depth 2 of 3
        with pytest.raises(RejectedError):     # 6 rows = 3 chunks; 2+3 > 3
            server.submit(np.ones((6, 4), dtype=np.float32))
        assert server.queue.depth() == 2       # nothing partially admitted
        # more chunks than capacity can never fit: permanent client error,
        # not a retryable 429 against an (eventually) empty queue
        with pytest.raises(ValueError, match="capacity"):
            server.submit(np.ones((8, 4), dtype=np.float32))
        for f in [busy] + queued:
            f.result(timeout=10)
        assert server.metrics.rows.get() == 3  # no orphan chunk dispatches
    finally:
        server.stop()


def test_expired_chunked_request_does_not_deadlock_batcher():
    """Expiring a chunked request's sibling runs its done-callback (which
    withdraws the other chunks) from inside the admission queue — this must
    not deadlock the batcher thread."""
    server = _component_server(StubModel(2.0, delay_s=0.3),
                               max_batch_size=2, max_latency_ms=1.0)
    try:
        x = np.ones((1, 4), dtype=np.float32)
        busy = server.submit(x)                # occupy the batcher ~300ms
        _wait_queue_empty(server)
        time.sleep(0.05)
        big = server.submit(np.ones((6, 4), dtype=np.float32),
                            timeout_ms=50)     # 3 chunks, expire while queued
        with pytest.raises(DeadlineExceeded):
            big.result(timeout=10)
        busy.result(timeout=10)
        ok = server.submit(x).result(timeout=10)   # batcher still alive
        np.testing.assert_array_equal(ok["prediction"], x * 2.0)
    finally:
        server.stop()


def test_failed_path_deploy_is_retryable(tmp_path):
    """/deploy {version, path} whose warm-up fails must roll the registration
    back so the identical request can be retried (not 'already registered')."""
    wide = _net(nin=6)
    narrow = _net(nin=4)                       # wrong width for the traffic
    bad_zip, good_zip = str(tmp_path / "bad.zip"), str(tmp_path / "good.zip")
    ModelSerializer.write_model(narrow, bad_zip)
    ModelSerializer.write_model(_net(nin=6, seed=1), good_zip)
    server = _component_server(wide, max_latency_ms=1.0)
    rng = np.random.default_rng(6)
    try:
        x = rng.normal(size=(2, 6)).astype(np.float32)
        server.predict(x)                      # observe bucket (2, (6,))
        with pytest.raises(Exception):         # warm-up on (2, 6) must fail
            server.deploy("v2", path=bad_zip)
        assert server.registry.active_version == "v1"
        server.deploy("v2", path=good_zip)     # same version id, retried OK
        assert server.predict(x)["version"] == "v2"
    finally:
        server.stop()


def test_deploy_unknown_version_is_400_and_keeps_serving():
    server = ServingServer(StubModel(2.0), port=0).start()
    try:
        req = urllib.request.Request(
            server.url + "/deploy",
            data=json.dumps({"version": "nope"}).encode())
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 400
        req = urllib.request.Request(
            server.url + "/predict",
            data=json.dumps({"data": [[1.0]]}).encode())
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["prediction"] == [[2.0]]
    finally:
        server.stop()


# ------------------------------------------------------------- metrics/ui

def test_no_model_deployed_is_503_over_http():
    """A deploy gap is a server condition: /predict must answer 503 (load
    balancers retry 5xx), not blame the client with a 400."""
    server = ServingServer(None, registry=ModelRegistry(), port=0,
                           max_latency_ms=1.0).start()
    try:
        req = urllib.request.Request(
            server.url + "/predict",
            data=json.dumps({"data": [[1.0, 2.0]]}).encode())
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=30)
        assert exc.value.code == 503
    finally:
        server.stop()


def test_metrics_endpoint_and_stats_router():
    router = InMemoryStatsStorage()
    server = ServingServer(StubModel(2.0), port=0, stats_router=router,
                           session_id="serve-test").start()
    try:
        req = urllib.request.Request(
            server.url + "/predict",
            data=json.dumps({"data": [[1.0, 2.0]]}).encode())
        with urllib.request.urlopen(req, timeout=10) as r:
            r.read()
        with urllib.request.urlopen(server.url + "/metrics", timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["requests"] == 1 and snap["rows"] == 1
        assert snap["latency_ms"]["p50"] is not None
        assert snap["latency_ms"]["p99"] >= snap["latency_ms"]["p50"]
        assert snap["batch_size_histogram"] == {"1": 1}
        assert snap["version_rows"] == {"v1": 1}   # from the registry counts
        updates = router.get_all_updates("serve-test")
        assert updates and updates[-1]["type"] == "serving"
        assert updates[-1]["requests"] == 1
    finally:
        server.stop()
    # stop() flushes a final snapshot too
    assert router.get_all_updates("serve-test")[-1]["requests"] == 1


def test_legacy_model_swap_to_different_input_width():
    """The legacy plain-attribute swap allowed replacing the model with one
    of a different input width; the wrapper must deploy it (cold, with stale
    buckets forgotten) instead of failing the assignment on warm-up."""
    from deeplearning4j_tpu.streaming import InferenceServer
    server = InferenceServer(_net(nin=6), port=0).start()
    try:
        server.predict(np.ones((2, 6), dtype=np.float32))   # observe (6,)
        narrow = _net(nin=4, seed=1)
        server.model = narrow                               # width change
        x4 = np.ones((2, 4), dtype=np.float32)
        np.testing.assert_array_equal(
            server.predict(x4)["prediction"], np.asarray(narrow.output(x4)))
        assert len(server.registry.versions()) == 1         # still no leak
    finally:
        server.stop()


def test_file_storage_write_after_close_is_counted_not_raised():
    from deeplearning4j_tpu.ui.storage import FileStatsStorage
    import tempfile, warnings
    store = FileStatsStorage(tempfile.mktemp(suffix=".jsonl"))
    store.put_update({"session_id": "s", "type": "stats", "score": 1.0})
    store.close()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        store.put_update({"session_id": "s", "type": "stats", "score": 2.0})
    assert store.dropped_writes == 1          # divergence surfaced
    assert len(store.get_all_updates("s")) == 2   # memory still consistent


# ------------------------------------------------------------ smoke tests

def test_smoke_serving_light():
    import tools.smoke_serving as smoke
    summary = smoke.run(n_requests=30, concurrency=8, p99_budget_ms=30000.0)
    assert summary["errors"] == [] and summary["shed"] == 0


@pytest.mark.slow
def test_smoke_serving_heavy():
    """Heavy variant of tools/smoke_serving.py: 200 concurrent requests,
    p99 latency budget, zero errors."""
    import tools.smoke_serving as smoke
    summary = smoke.run(n_requests=200, concurrency=16,
                        p99_budget_ms=10000.0)
    assert summary["errors"] == [] and summary["shed"] == 0


# ------------------------------------------------ persistent registry (ETL)

def test_registry_scan_dir_loads_zips_and_deploys_by_name(tmp_path):
    """ModelRegistry(scan_dir=...) loads every ModelSerializer zip at
    startup (version = file stem), and deploy() falls back to
    <scan_dir>/<name>.zip for names registered after startup — the
    persistent-registry ROADMAP item."""
    net_a, net_b = _net(seed=0), _net(seed=1)
    ModelSerializer.write_model(net_a, str(tmp_path / "alpha.zip"))
    ModelSerializer.write_model(net_b, str(tmp_path / "beta.zip"))
    registry = ModelRegistry(scan_dir=str(tmp_path))
    assert {v["version"] for v in registry.versions()} == {"alpha", "beta"}

    registry.deploy("alpha")
    assert registry.active_version == "alpha"
    # a zip dropped into the directory AFTER startup deploys by bare name
    net_c = _net(seed=2)
    ModelSerializer.write_model(net_c, str(tmp_path / "gamma.zip"))
    registry.deploy("gamma")
    assert registry.active_version == "gamma"
    x = np.random.default_rng(0).normal(size=(2, 6)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(registry.active()[1].output(x)),
        np.asarray(net_c.output(x)), rtol=1e-6)
    # unknown names (no zip either) still fail loudly
    with pytest.raises(KeyError):
        registry.deploy("missing")
    # rescan registers without deploying
    ModelSerializer.write_model(_net(seed=3), str(tmp_path / "delta.zip"))
    assert registry.scan() == ["delta"]
    assert registry.active_version == "gamma"


def test_serving_server_scan_dir_deploy_by_name_over_http(tmp_path):
    ModelSerializer.write_model(_net(seed=5), str(tmp_path / "m1.zip"))
    server = ServingServer(scan_dir=str(tmp_path), port=0).start()
    try:
        req = urllib.request.Request(
            server.url + "/deploy",
            data=json.dumps({"version": "m1"}).encode())
        with urllib.request.urlopen(req, timeout=60) as r:
            assert json.loads(r.read())["active"] == "m1"
        x = np.zeros((1, 6), np.float32)
        req = urllib.request.Request(
            server.url + "/predict",
            data=json.dumps({"data": x.tolist()}).encode())
        with urllib.request.urlopen(req, timeout=60) as r:
            assert json.loads(r.read())["version"] == "m1"
    finally:
        server.stop()


def test_zip_normalizer_auto_applied_on_predict(tmp_path):
    """Acceptance (ETL): a normalizer saved in the model zip is auto-applied
    by ServingServer /predict — raw client features, normalized model
    inputs, identical preprocessing to training."""
    from deeplearning4j_tpu import NormalizerStandardize
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(7)
    raw = rng.normal(50.0, 20.0, size=(64, 6)).astype(np.float32)
    nz = NormalizerStandardize().fit(DataSet(raw, raw))
    net = _net(seed=0)
    zip_path = str(tmp_path / "norm.zip")
    ModelSerializer.write_model(net, zip_path, normalizer=nz)

    registry = ModelRegistry()
    registry.load("v1", zip_path)
    assert registry.get("v1").info()["normalizer"] == "NormalizerStandardize"
    server = ServingServer(registry=registry, port=0).start()
    try:
        server.deploy("v1")
        x = raw[:3]
        res = server.predict(x)
        expected = np.asarray(net.output(nz.transform_features(x)))
        np.testing.assert_allclose(res["prediction"], expected,
                                   rtol=1e-5, atol=1e-6)
        # and NOT the un-normalized forward
        assert not np.allclose(res["prediction"], np.asarray(net.output(x)),
                               atol=1e-3)
        # HTTP path agrees with the programmatic path
        req = urllib.request.Request(
            server.url + "/predict",
            data=json.dumps({"data": x.tolist()}).encode())
        with urllib.request.urlopen(req, timeout=60) as r:
            http_out = json.loads(r.read())["prediction"]
        np.testing.assert_allclose(http_out, expected, rtol=1e-4, atol=1e-5)
    finally:
        server.stop()


def test_hot_swap_cannot_mix_model_and_normalizer(tmp_path):
    """The batcher dispatches against ONE ModelVersion snapshot: version A's
    model can never run with version B's normalizer mid-swap."""
    from deeplearning4j_tpu import NormalizerMinMaxScaler
    from deeplearning4j_tpu.datasets.dataset import DataSet
    x = np.linspace(0.0, 10.0, 60, dtype=np.float32).reshape(10, 6)
    nz = NormalizerMinMaxScaler().fit(DataSet(x, x))
    net = _net(seed=0)
    p1 = str(tmp_path / "n1.zip")
    ModelSerializer.write_model(net, p1, normalizer=nz)
    registry = ModelRegistry()
    registry.load("v1", p1)
    registry.register("v2", StubModel(1.0))       # no normalizer at all
    server = _component_server(None, registry=registry)
    try:
        registry.deploy("v1")
        out1 = server.predict(x[:2])["prediction"]
        np.testing.assert_allclose(
            out1, np.asarray(net.output(nz.transform_features(x[:2]))),
            rtol=1e-5, atol=1e-6)
        registry.deploy("v2")
        out2 = server.predict(x[:2])["prediction"]
        np.testing.assert_allclose(out2, x[:2], rtol=1e-6)  # raw passthrough
    finally:
        server.stop()


def test_normalizer_applied_to_integer_typed_request(tmp_path):
    """Regression: the batcher must not cast the normalized (float) batch
    back to an integer request dtype — z-scores truncated to int are
    garbage. Programmatic submits can carry int arrays."""
    from deeplearning4j_tpu import NormalizerStandardize
    from deeplearning4j_tpu.datasets.dataset import DataSet
    raw = np.arange(60, dtype=np.float32).reshape(10, 6) * 7 + 3
    nz = NormalizerStandardize().fit(DataSet(raw, raw))
    net = _net(seed=0)
    p = str(tmp_path / "n.zip")
    ModelSerializer.write_model(net, p, normalizer=nz)
    registry = ModelRegistry()
    registry.load("v1", p)
    server = _component_server(None, registry=registry)
    try:
        registry.deploy("v1")
        x_int = np.asarray(raw[:2], np.int64)    # integer-typed request
        out = server.predict(x_int)["prediction"]
        expected = np.asarray(net.output(
            nz.transform_features(x_int.astype(np.float32))))
        np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)
    finally:
        server.stop()


def test_fit_labels_normalizer_reverts_served_predictions(tmp_path):
    """Regression: a regression model trained against NORMALIZED labels
    (fit_labels=True) predicts in z-score label space; serving must revert
    its outputs to real units."""
    from deeplearning4j_tpu import NormalizerStandardize
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 6)).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) * 100.0 + 500.0).astype(np.float32)
    nz = NormalizerStandardize(fit_labels=True).fit(DataSet(x, y))
    # a stub "perfect model" that predicts the NORMALIZED label exactly
    norm_y = nz.transform(DataSet(x, y)).labels

    class Oracle:
        def output(self, xx):
            # match rows of the padded batch back to known inputs; pad rows
            # (zeros) predict 0 in normalized space
            out = np.zeros((xx.shape[0], 1), np.float32)
            for i in range(xx.shape[0]):
                hit = np.where((np.abs(
                    nz.transform_features(x) - xx[i]).sum(axis=1)) < 1e-4)[0]
                if hit.size:
                    out[i] = norm_y[hit[0]]
            return out

    registry = ModelRegistry()
    registry.register("v1", Oracle(), transform=nz)
    server = _component_server(None, registry=registry)
    try:
        registry.deploy("v1")
        out = server.predict(x[:3])["prediction"]
        np.testing.assert_allclose(out, y[:3], rtol=1e-3, atol=1e-2)
    finally:
        server.stop()


def test_scan_dir_skips_unreadable_zip(tmp_path):
    """Regression: one truncated/foreign zip in scan_dir must not prevent
    the registry (and thus the server) from starting with healthy models."""
    ModelSerializer.write_model(_net(seed=0), str(tmp_path / "good.zip"))
    (tmp_path / "broken.zip").write_bytes(b"this is not a zip")
    registry = ModelRegistry(scan_dir=str(tmp_path))
    assert {v["version"] for v in registry.versions()} == {"good"}
    assert "broken.zip" in registry.scan_errors
    registry.deploy("good")
    assert registry.active_version == "good"


# ------------------------------------------- sequence-length bucketing

def _lstm_net(vocab=12, hidden=8, seed=0):
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(0.1)).list()
            .layer(GravesLSTM(n_out=hidden, activation="tanh"))
            .layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                  loss="MCXENT"))
            .input_type(InputType.recurrent(vocab))
            .build())
    return MultiLayerNetwork(conf).init()


def _seq_x(vocab, *lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, t)][None] for t in lengths]


def test_seq_len_bucketing_coalesces_different_lengths():
    """Requests of DIFFERENT sequence lengths share one padded+masked batch
    and each caller's rows match the direct unpadded model.output — the
    prefill-leg satellite's core contract."""
    net = _lstm_net()
    server = _component_server(net, max_latency_ms=100.0)
    try:
        xs = _seq_x(12, 3, 5, 4)
        futs = [server.submit(x) for x in xs]     # one coalescing window
        results = [f.result(timeout=60) for f in futs]
        for x, res in zip(xs, results):
            pred = np.asarray(res["prediction"])
            assert pred.shape[1] == x.shape[1], "padding leaked to caller"
            np.testing.assert_allclose(pred, np.asarray(net.output(x)),
                                       rtol=1e-5, atol=1e-6)
        # all three lengths (3, 5, 4) coalesced into ONE bucket-8 dispatch
        assert server.metrics.batches.get() == 1
        hist = {ls["len_bucket"]: v
                for ls, v in server.metrics.seq_bucket.series() if ls}
        assert hist == {"8": 1}
        # the observed key carries the (batch, length) bucket pair
        assert any(len(k) == 3 and k[2] == 8 for k in server.batcher.observed)
    finally:
        server.stop()


def test_seq_len_bucketing_zero_steady_state_recompiles_and_warm_swap():
    """Steady state over mixed lengths within one (batch, length) bucket
    pair never recompiles, and a hot-swap warm-up replays the seq keys (so
    the new version serves mixed lengths cold-free)."""
    net = _lstm_net(seed=1)
    server = _component_server(net, max_latency_ms=1.0)
    try:
        for t in (3, 5, 6, 2):
            server.predict(_seq_x(12, t, seed=t)[0])
        compiles = server.compile_tracker.total()
        for t in (4, 7, 5, 3):                 # same bucket-8 executable
            server.predict(_seq_x(12, t, seed=10 + t)[0])
        assert server.compile_tracker.total() == compiles, \
            "seq steady state recompiled"
        # swap to a new version: warm-up replays the seq (bucket, length)
        # keys with masks; serving after the swap stays recompile-free
        net2 = _lstm_net(seed=2)
        server.registry.register("v2", net2)
        server.deploy("v2")
        compiles = server.compile_tracker.total()
        x = _seq_x(12, 5, seed=99)[0]
        res = server.predict(x)
        assert res["version"] == "v2"
        np.testing.assert_allclose(np.asarray(res["prediction"]),
                                   np.asarray(net2.output(x)),
                                   rtol=1e-5, atol=1e-6)
        assert server.compile_tracker.total() == compiles, \
            "post-warm-up swap recompiled on a seq bucket"
    finally:
        server.stop()


def test_seq_len_bucketing_opt_out_keeps_legacy_signatures():
    net = _lstm_net(seed=3)
    server = _component_server(net, seq_len_bucketing=False)
    try:
        x = _seq_x(12, 5, seed=5)[0]
        res = server.predict(x)
        np.testing.assert_allclose(np.asarray(res["prediction"]),
                                   np.asarray(net.output(x)),
                                   rtol=1e-6, atol=1e-7)
        # legacy full-shape key: no length bucket dimension
        assert all(len(k) == 2 for k in server.batcher.observed)
    finally:
        server.stop()


def test_seq_requests_to_maskless_duck_typed_model_demote_to_legacy():
    """A custom model whose output() takes no mask must keep serving 3-D
    requests: the batcher demotes the seq batch to per-length legacy
    dispatches instead of TypeErroring the whole batch."""
    registry = ModelRegistry()
    registry.register("v1", StubModel(2.0))
    server = _component_server(None, registry=registry, max_latency_ms=100.0)
    try:
        registry.deploy("v1")
        xs = _seq_x(12, 3, 5)
        futs = [server.submit(x) for x in xs]
        for x, f in zip(xs, futs):
            res = f.result(timeout=60)
            np.testing.assert_allclose(np.asarray(res["prediction"]),
                                       x * 2.0, rtol=1e-6)
        # demoted dispatches record LEGACY (2-tuple) keys, no seq keys
        assert server.batcher.observed
        assert all(len(k) == 2 for k in server.batcher.observed)
    finally:
        server.stop()
