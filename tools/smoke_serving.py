"""Serving smoke test: ServingServer on a tiny zoo model under concurrent
HTTP load.

Starts a ServingServer on `zoo.mlp_mnist` (narrow hidden layer), fires
`n_requests` concurrent `/predict` calls of mixed batch sizes from a thread
pool, and asserts zero errors plus a p99 latency budget. The default run
(200 requests) is the heavy variant invoked by the `slow`-marked test;
tier-1 runs a lighter request count through `run()`.

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_serving.py [-n 200] [-c 16]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# graftlint: disable-file=GL001 — this benchmark measures REAL wall-clock
# latency of live HTTP calls; reading an injectable time source here would
# zero every measurement under a test-installed ManualClock
# graftlint: disable-file=GL008 — the hot loop times pre-encoded payload
# bytes through a raw urllib request on purpose: util.http.post_json would
# re-serialize the body inside the timed region and skew every latency
# number; nothing here needs trace propagation


def run(n_requests=200, concurrency=16, max_rows=4, p99_budget_ms=10000.0,
        hidden=16, seed=0):
    import numpy as np
    from deeplearning4j_tpu.serving import ServingServer
    from deeplearning4j_tpu.zoo.models import mlp_mnist

    model = mlp_mnist(hidden=hidden)
    # every lock the serving stack creates below runs sanitized: the arc
    # fails if concurrent load reveals a lock-order inversion at runtime
    from deeplearning4j_tpu.util.concurrency import lock_sanitizer
    lock_sanitizer.reset()
    lock_sanitizer.install()
    try:
        server = ServingServer(model, max_batch_size=16, max_latency_ms=5.0,
                               queue_capacity=max(64, n_requests)).start()
        rng = np.random.default_rng(seed)
        # one request per worker up front so every bucket compiles before
        # timing
        for rows in range(1, max_rows + 1):
            server.predict(rng.normal(size=(rows, 784)).astype(np.float32))

        bodies = []
        for _ in range(n_requests):
            rows = int(rng.integers(1, max_rows + 1))
            x = rng.normal(size=(rows, 784)).astype(np.float32)
            bodies.append((rows, json.dumps({"data": x.tolist()}).encode()))

        def fire(body):
            rows, payload = body
            t0 = time.monotonic()
            req = urllib.request.Request(
                server.url + "/predict", data=payload,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                out = json.loads(r.read())
            ms = (time.monotonic() - t0) * 1000.0
            assert len(out["prediction"]) == rows, out["shape"]
            return ms

        t_start = time.monotonic()
        errors = []
        latencies = []
        with ThreadPoolExecutor(max_workers=concurrency) as pool:
            for fut in [pool.submit(fire, b) for b in bodies]:
                try:
                    latencies.append(fut.result())
                except Exception as e:
                    errors.append(f"{type(e).__name__}: {e}")
        wall_s = time.monotonic() - t_start

        latencies.sort()
        from deeplearning4j_tpu.serving import ServingMetrics
        p50 = ServingMetrics._percentile(latencies, 0.50)
        p99 = ServingMetrics._percentile(latencies, 0.99)
        snap = server._metrics_snapshot()
        server.stop()
    finally:
        lock_report = lock_sanitizer.report()
        lock_sanitizer.uninstall()

    summary = {
        "n_requests": n_requests,
        "concurrency": concurrency,
        "errors": errors,
        "wall_s": round(wall_s, 3),
        "throughput_rps": round(n_requests / wall_s, 1),
        # percentiles are None when every request failed: the errors assert
        # below must fire with its diagnostic, not a round(None) TypeError
        "p50_ms": None if p50 is None else round(p50, 2),
        "p99_ms": None if p99 is None else round(p99, 2),
        "batch_size_histogram": snap["batch_size_histogram"],
        "shed": snap["shed"],
        "server_latency_ms": snap["latency_ms"],
        "lock_sanitizer": lock_report,
    }
    assert not errors, f"{len(errors)} failed requests: {errors[:3]}"
    assert snap["shed"] == 0, f"unexpected shedding: {snap['shed']}"
    assert p99 <= p99_budget_ms, f"p99 {p99:.1f}ms > budget {p99_budget_ms}ms"
    assert lock_report["violations"] == 0, \
        f"lock sanitizer: {lock_sanitizer.table()['violations']}"
    return summary


def main(argv=None):
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--n-requests", type=int, default=200)
    ap.add_argument("-c", "--concurrency", type=int, default=16)
    ap.add_argument("--p99-budget-ms", type=float, default=10000.0)
    args = ap.parse_args(argv)
    summary = run(n_requests=args.n_requests, concurrency=args.concurrency,
                  p99_budget_ms=args.p99_budget_ms)
    print(json.dumps(summary, indent=2))


if __name__ == "__main__":
    main()
