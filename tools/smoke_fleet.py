"""Fleet observability smoke test: the cross-process trace loop, live.

Boots TWO ServingServers plus a FleetServer over both, then:

1. fires traced client requests (util.http.post_json injects the W3C
   `traceparent` header) and asserts the client and server spans share ONE
   trace id, with the request's admission span naming the batch span that
   served it (span links, exported as Chrome-trace flow events);
2. asserts the Prometheus exposition carries OpenMetrics exemplars whose
   trace_id joins back to `/trace` and `/logs`;
3. scrapes the fleet plane: `/fleet/metrics` (per-instance + merged
   totals), `/fleet/healthz` (worst-status aggregation), and `/fleet/trace`
   (one pid lane per host, process_name metadata).

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_fleet.py [-n 8]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from deeplearning4j_tpu.util.http import get_json, post_json  # noqa: E402


def run(n_requests=8, nin=6, seed=0):
    import numpy as np
    from tools.smoke_telemetry import _tiny_net
    from deeplearning4j_tpu.serving import ServingServer
    from deeplearning4j_tpu.telemetry import FleetServer, Tracer

    s1 = ServingServer(_tiny_net(nin=nin, seed=seed), max_batch_size=8).start()
    s2 = ServingServer(_tiny_net(nin=nin, seed=seed + 1),
                       max_batch_size=8).start()
    fleet = FleetServer([s1.url, s2.url], names=["host-a", "host-b"],
                        interval_s=0.0).start()
    client = Tracer(enabled=True)
    rng = np.random.default_rng(seed)
    try:
        client_traces = []
        for i in range(n_requests):
            target = s1 if i % 2 == 0 else s2
            x = rng.normal(size=(1 + i % 3, nin)).astype(np.float32)
            with client.span("client_call", request=i) as cs:
                out = post_json(target.url + "/predict",
                                {"data": x.tolist()}, timeout=60)
                client_traces.append(cs.trace_id)
            assert len(out["prediction"]) == x.shape[0], out["shape"]

        # 1. one trace across client and server, request linked to batch
        trace = get_json(s1.url + "/trace", timeout=30)
        spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        mine = [e for e in spans
                if e["args"].get("trace_id") == client_traces[0]]
        names = {e["name"] for e in mine}
        assert {"http /predict", "predict", "admission"} <= names, names
        batch_ids = {e["args"]["span_id"] for e in spans
                     if e["name"] == "batch"}
        adm = next(e for e in mine if e["name"] == "admission")
        assert adm["args"]["batch_span_id"] in batch_ids
        flows = [e for e in trace["traceEvents"] if e.get("cat") == "link"]
        assert flows, "no span-link flow events"

        # 2. exemplar -> /trace -> /logs join
        text = get_json(s1.url + "/metrics?format=prometheus", timeout=30)
        assert 'trace_id="' in text, "no OpenMetrics exemplars in scrape"
        ex_trace = text.split('trace_id="', 1)[1].split('"', 1)[0]
        assert any(e["args"].get("trace_id") == ex_trace for e in spans)
        logs = get_json(s1.url + f"/logs?trace_id={ex_trace}", timeout=30)
        assert logs["records"], "exemplar trace has no /logs records"

        # 3. the fleet plane
        fm = get_json(fleet.url + "/fleet/metrics", timeout=30)
        assert fm["instances_up"] == 2, fm
        assert fm["totals"]["requests"] == n_requests, fm["totals"]
        status, fh = get_json(fleet.url + "/fleet/healthz", timeout=30,
                              with_status=True)
        assert status == 200 and fh["status"] == "healthy", (status, fh)
        ftrace = get_json(fleet.url + "/fleet/trace", timeout=30)
        lanes = {e["pid"] for e in ftrace["traceEvents"]}
        assert lanes == {0, 1}, lanes
        ftext = get_json(fleet.url + "/fleet/metrics?format=prometheus",
                         timeout=30)
        assert 'instance="host-a"' in ftext and 'instance="host-b"' in ftext

        return {"requests": n_requests,
                "client_traces": len(set(client_traces)),
                "span_link_flows": len(flows),
                "exemplar_trace": ex_trace,
                "exemplar_log_records": len(logs["records"]),
                "fleet_instances_up": fm["instances_up"],
                "fleet_lanes": sorted(lanes)}
    finally:
        fleet.stop()
        s1.stop()
        s2.stop()


def main(argv=None):
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--n-requests", type=int, default=8)
    args = ap.parse_args(argv)
    out = run(n_requests=args.n_requests)
    print("fleet smoke OK:", json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
