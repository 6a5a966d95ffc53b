"""Chaos smoke test: the resilience react loop, live and deterministic.

Boots TWO replica ServingServers behind a FleetFrontend under a ManualClock
(every sleep-shaped wait — breaker cool-off, canary bake, alert windows —
is clock-advanced, zero real sleeps), then scripts the two ISSUE-8
degradation paths with a FaultPlan installed into util.http:

1. kill/recover: replica b dies mid-traffic (injected connection resets) ->
   every client /predict still answers 200 via single-failover retry, b's
   circuit breaker opens; the fault lifts, the cool-off elapses on the
   clock, and the half-open probe restores two-replica routing;
2. bad canary: v2 deploys on b at a 50% traffic fraction, its injected
   error ratio breaches the canary SLO rule, and the AlertEngine gate
   auto-rolls b back to v1 — with zero 5xx reaching front-end clients
   (each failed canary attempt failed over to the stable cohort).

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_chaos.py [-n 8]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from deeplearning4j_tpu.util.http import get_json, post_json  # noqa: E402


def run(n_requests=6, nin=6, seed=0):
    from tools.smoke_telemetry import _tiny_net
    from deeplearning4j_tpu.resilience import FaultPlan, FaultRule
    from deeplearning4j_tpu.serving import FleetFrontend, ServingServer
    from deeplearning4j_tpu.util.time_source import (ManualClock,
                                                     TimeSourceProvider)

    clock = ManualClock(start_s=1000.0)
    TimeSourceProvider.set_instance(clock)
    s1 = ServingServer(_tiny_net(nin=nin, seed=seed), version="v1",
                       max_batch_size=8, alert_interval_s=0).start()
    s2 = ServingServer(_tiny_net(nin=nin, seed=seed), version="v1",
                       max_batch_size=8, alert_interval_s=0).start()
    s2.registry.register("v2", _tiny_net(nin=nin, seed=seed + 1))
    fe = FleetFrontend([s1.url, s2.url], names=["a", "b"],
                       health_interval_s=1e9, breaker_min_calls=2,
                       breaker_window=10, breaker_open_for_s=30.0,
                       alert_interval_s=0,
                       canary_opts={"bake_s": 120.0, "min_requests": 2,
                                    "error_ratio": 0.25,
                                    "window_s": 300.0}).start()
    body = {"data": [[0.1] * nin]}

    def predict():
        return post_json(fe.url + "/predict", body, timeout=60)

    try:
        # warm: both replicas take traffic
        warm = {predict()["replica"] for _ in range(max(4, n_requests))}
        assert warm == {"a", "b"}, warm

        # ---- 1. kill -> failover -> breaker -> recover -------------------
        plan = FaultPlan([FaultRule("reset", match=s2.url + "/predict",
                                    name="kill-b")])
        with plan:
            kill = [predict() for _ in range(n_requests)]
            kill_errors = sum(1 for r in kill if "prediction" not in r)
            snap = get_json(fe.url + "/metrics", timeout=30)
            breaker_opened = \
                snap["replicas"]["b"]["breaker"]["state"] == "open"
            failovers = snap["frontend_failovers_total"]
            plan.set_active("kill-b", False)         # b "recovers"
            clock.advance(31.0)                      # breaker cool-off
            recovered = sorted({predict()["replica"]
                                for _ in range(max(6, n_requests))})

        # ---- 2. bad canary -> alert gate -> auto-rollback ----------------
        post_json(fe.url + "/deploy", {"version": "v2", "canary": 0.5},
                  timeout=60)
        assert s2.registry.active_version == "v2"
        fe.alerts.evaluate()                         # baseline window sample
        bad = FaultPlan([FaultRule("error", match=s2.url + "/predict",
                                   status=500, name="bad-canary")])
        with bad:
            canary_phase = [predict() for _ in range(n_requests)]
            clock.advance(5.0)
            fe.alerts.evaluate()                     # ratio fires -> rollback
        canary_errors = sum(1 for r in canary_phase
                            if "prediction" not in r)
        outcome = fe.canary.history[-1]["outcome"]
        assert s2.registry.active_version == "v1", "rollback did not land"

        snap = get_json(fe.url + "/metrics", timeout=30)
        codes = snap["frontend_requests_total"]
        if isinstance(codes, dict):
            client_5xx = sum(v for k, v in codes.items()
                             if k.startswith("code=5"))
        else:
            client_5xx = 0 if kill_errors + canary_errors == 0 else -1
        return {"requests": int(sum(codes.values())
                                if isinstance(codes, dict) else codes),
                "kill_phase_errors": kill_errors + canary_errors,
                "breaker_opened": breaker_opened,
                "failovers": failovers,
                "recovered_replicas": recovered,
                "canary_outcome": outcome,
                "canary_rollbacks": int(snap["canary_rollbacks_total"]),
                "client_5xx": int(client_5xx)}
    finally:
        fe.stop()
        s1.stop()
        s2.stop()
        TimeSourceProvider.reset()


def main(argv=None):
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--n-requests", type=int, default=6)
    args = ap.parse_args(argv)
    out = run(n_requests=args.n_requests)
    print("chaos smoke OK:", json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
