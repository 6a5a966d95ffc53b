"""Telemetry smoke test: serve N requests through a live ServingServer,
then assert (a) a non-empty Prometheus scrape with the core serving series
and (b) a valid Chrome-trace JSON export containing the
predict -> admission span trees plus batch spans LINKED (flow events) to
the requests they coalesced.

This drives the whole observability path end to end: client traceparent
injected by util.http.post_json -> handler server span -> trace context
propagated through the admission queue -> batcher batch/dispatch spans +
span links -> compile accounting -> registry -> exposition.

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_telemetry.py [-n 32] [-c 8]
"""
from __future__ import annotations

import argparse
import json
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from deeplearning4j_tpu.util.http import get_json, post_json  # noqa: E402

REQUIRED_SERIES = ("requests_total", "latency_ms_bucket", "latency_ms_count",
                   "compiles_total", "queue_depth", "batches_total")


def _tiny_net(nin=6, nout=3, seed=0):
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    DenseLayer, OutputLayer,
                                    MultiLayerNetwork, Sgd)
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=nout, activation="softmax",
                               loss="MCXENT"))
            .input_type(InputType.feed_forward(nin))
            .build())
    return MultiLayerNetwork(conf).init()


def span_tree_depth(trace):
    """Longest parent chain among the exported spans (1 = flat). Only the
    complete ("X") span events count — flow events carry no parent chain."""
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    by_id = {e["args"]["span_id"]: e for e in spans}
    best = 0
    for e in spans:
        depth, cur = 1, e
        while cur["args"].get("parent_id") in by_id:
            cur = by_id[cur["args"]["parent_id"]]
            depth += 1
        best = max(best, depth)
    return best


def run(n_requests=32, concurrency=8, nin=6, seed=0):
    import numpy as np
    from deeplearning4j_tpu.serving import ServingServer

    server = ServingServer(_tiny_net(nin=nin, seed=seed), max_batch_size=8,
                           max_latency_ms=2.0,
                           queue_capacity=max(64, n_requests)).start()
    rng = np.random.default_rng(seed)
    try:
        def fire(i):
            rows = int(rng.integers(1, 5))
            x = rng.normal(size=(rows, nin)).astype(np.float32)
            out = post_json(server.url + "/predict",
                            {"data": x.tolist()}, timeout=60)
            assert len(out["prediction"]) == rows, out["shape"]

        with ThreadPoolExecutor(max_workers=concurrency) as pool:
            list(pool.map(fire, range(n_requests)))

        # ---- Prometheus scrape ------------------------------------------
        text = get_json(server.url + "/metrics?format=prometheus",
                        timeout=30)
        assert isinstance(text, str) and text.strip(), \
            "empty prometheus scrape"
        missing = [s for s in REQUIRED_SERIES if s not in text]
        assert not missing, f"missing series: {missing}"
        req_line = next(l for l in text.splitlines()
                        if l.startswith("requests_total "))
        assert float(req_line.split()[-1]) == n_requests, req_line

        # ---- Chrome-trace export ----------------------------------------
        trace = get_json(server.url + "/trace", timeout=30)
        names = {e["name"] for e in trace["traceEvents"]}
        for want in ("predict", "admission", "batch", "dispatch"):
            assert want in names, f"missing span {want!r} in {sorted(names)}"
        depth = span_tree_depth(trace)
        assert depth >= 2, f"span tree depth {depth} < 2"
        flows = [e for e in trace["traceEvents"] if e.get("cat") == "link"]
        assert flows, "no request<->batch span-link flow events exported"

        snapshot = get_json(server.url + "/metrics", timeout=30)
        return {"requests": snapshot["requests"],
                "compiles": snapshot.get("compiles", 0),
                "p99_ms": snapshot["latency_ms"]["p99"],
                "spans": len(trace["traceEvents"]),
                "span_tree_depth": depth,
                "span_link_flows": len(flows),
                "scrape_bytes": len(text)}
    finally:
        server.stop()


def main(argv=None):
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--n-requests", type=int, default=32)
    ap.add_argument("-c", "--concurrency", type=int, default=8)
    args = ap.parse_args(argv)
    out = run(n_requests=args.n_requests, concurrency=args.concurrency)
    print("telemetry smoke OK:", json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
