"""Elastic serving smoke: ramp -> autoscale 1->3 -> preempt -> failover ->
drain back to 1, on a ManualClock with zero real sleeps.

One scan_dir of model zips, an InProcessLauncher (bounded replica spawn),
a FleetFrontend pool that starts at one replica, and an
AutoscaleController with a declarative JSON policy (shed-ratio scale-up
through the AlertEngine ratio machinery, queue-depth scale-down, cooldown
flap damping). The script:

1. offers an open-loop burst (tools/loadgen.py) to a pool whose every
   replica is busy — its batcher inside a dispatch, its admission queue
   full behind it, held there by the script until the burst is over, so
   the overload is a construction and not a race with the host's speed:
   every arrival overflows a queue and clients see honest 429
   backpressure, never a 5xx (the frontend forwards a pool-wide shed AS
   429);
2. the controller's shed-ratio rule fires -> scale-up to 2, then (after
   the cooldown elapses on the clock) to 3; every new replica comes up
   warm via the launcher's RegistrySubscriber deploy replay;
3. a chaos FaultPlan `preempt` rule (JSON-round-tripped) kills one
   launched replica; client traffic keeps answering 200 via
   single-failover — zero 5xx — and the controller reaps the dead replica;
4. load drops; the queue-depth scale-down rule drains the pool back to
   the policy minimum, one cooldown window at a time.

Every transition lands in the frontend registry
(autoscale_transitions_total{action}, autoscale_replicas), the
trace-correlated structured logs, and — scraped over a FleetServer —
/fleet/metrics //fleet/healthz.

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_elastic.py
"""
from __future__ import annotations

import json
import sys
import tempfile
import threading
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from deeplearning4j_tpu.util.http import get_json  # noqa: E402

POLICY = {
    "min_replicas": 1, "max_replicas": 3, "step": 1,
    "cooldown_s": 10.0, "for_duration_s": 0.0, "window_s": 5.0,
    "down_grace_s": 0.0,
    "scale_up": {"shed_ratio": 0.02},
    "scale_down": {"queue_depth": 0.5},
}


def run(burst_rate=2000.0, burst_s=0.05, nin=6, seed=0, scan_dir=None):
    import numpy as np
    from tools.loadgen import predict_body, run_loadgen
    from tools.smoke_telemetry import _tiny_net
    from deeplearning4j_tpu.elastic import (AutoscaleController,
                                            AutoscalePolicy,
                                            InProcessLauncher)
    from deeplearning4j_tpu.resilience import FaultPlan, FaultRule
    from deeplearning4j_tpu.serving import FleetFrontend
    from deeplearning4j_tpu.serving.admission import Request
    from deeplearning4j_tpu.telemetry.fleet import FleetServer
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer
    from deeplearning4j_tpu.util.time_source import (ManualClock,
                                                     TimeSourceProvider)

    clock = ManualClock(start_s=1000.0)
    TimeSourceProvider.set_instance(clock)
    ModelSerializer.write_model(_tiny_net(nin=nin, seed=seed),
                                str(Path(scan_dir) / "v1.zip"))

    # sanitized locks for the whole elastic stack (frontend, launcher
    # replicas, autoscaler, fleet poller) — the arc asserts zero runtime
    # lock-order violations under burst load + preemption
    from deeplearning4j_tpu.util.concurrency import lock_sanitizer
    lock_sanitizer.reset()
    lock_sanitizer.install()

    # an admission queue longer than the failover burst: a ramp's overload
    # does not depend on the length (`occupy` fills whatever there is), and
    # the failover burst cannot overflow a live replica however slowly it
    # dispatches — a shed there, with the dead replica tried last, would
    # reach the client as a 502
    launcher = InProcessLauncher(
        scan_dir=str(scan_dir), max_replicas=POLICY["max_replicas"],
        server_opts=dict(max_batch_size=4, queue_capacity=32,
                         alert_interval_s=0),
        deploy_event={"kind": "deploy", "version": "v1"})
    fe = None
    fleet = None
    body = predict_body(nin=nin)
    rows = np.asarray(body["data"], np.float32)
    reports = []

    def occupy(server, release):
        """`server` as a replica that is busy until `release`: one request
        of the script's own is dispatched and the batcher thread held where
        it completes that request's future (a done-callback runs on the
        thread that completes), then the admission queue is filled to its
        capacity behind it. Nothing drains until `release`, so whatever
        arrives meanwhile is shed — on a fast host as on a slow one."""
        parked = threading.Event()
        first = Request(rows)
        first.future.add_done_callback(
            lambda _f: (parked.set(), release.wait(60.0)))
        server.queue.offer(first)
        assert parked.wait(60.0), "the replica dispatched nothing"
        return [first.future] + [server.submit(rows)
                                 for _ in range(server.queue.capacity)]

    def burst(tag, rate=None, duration=None, busy=False):
        release = threading.Event()
        held = []
        try:
            if busy:
                for r in fe.replicas:
                    held += occupy(launcher.server(r.name), release)
            rep = run_loadgen(fe.url, body, rate=rate or burst_rate,
                              duration_s=duration or burst_s, seed=seed,
                              timeout_s=60.0, max_inflight=64)
        finally:
            release.set()
        for fut in held:                   # the pool is idle again
            fut.result(timeout=60.0)
        rep["phase"] = tag
        reports.append(rep)
        return rep

    try:
        url0 = launcher.launch("r0")      # comes up warm on v1
        fe = FleetFrontend([url0], names=["r0"], health_interval_s=1e9,
                           alert_interval_s=0, breaker_min_calls=5,
                           breaker_window=20, breaker_open_for_s=30.0,
                           max_attempts=3).start()
        fleet = FleetServer([fe.url], names=["frontend"],
                            interval_s=0.0).start()
        # policy JSON round-trip is part of the contract under test
        policy = AutoscalePolicy.from_dict(
            json.loads(json.dumps(POLICY)))
        ctl = AutoscaleController(fe, launcher, policy, interval_s=0)
        plan = FaultPlan.from_json(json.loads(json.dumps(FaultPlan([
            FaultRule("preempt", target="as1", at_step=4,
                      name="preempt-as1")]).to_json())))

        pool_sizes = [len(fe.replicas)]
        ctl.evaluate()                     # tick 1: counter baselines
        # ---- ramp: overload -> shed-ratio fires -> 1 -> 2 -> 3 ----------
        burst("ramp1", busy=True)
        clock.advance(1.0)
        r = ctl.evaluate()                 # tick 2: scale_up -> 2
        pool_sizes.append(len(fe.replicas))
        up1 = r["action"]
        burst("ramp2", busy=True)
        clock.advance(policy.cooldown_s + 1.0)
        r = ctl.evaluate()                 # tick 3: scale_up -> 3
        pool_sizes.append(len(fe.replicas))
        up2 = r["action"]

        # ---- preemption: chaos kills a launched replica ------------------
        for ev in plan.poll_preemptions(step=4):
            if ev["action"] == "kill":
                launcher.kill(ev["target"])
        failover = burst("failover", rate=200.0, duration=0.05)
        assert failover["arrivals"] <= launcher.server_opts["queue_capacity"]
        clock.advance(1.0)
        r = ctl.evaluate()                 # tick 4: reap the dead replica
        pool_sizes.append(len(fe.replicas))
        reap = r["action"]

        # ---- drain: load drops -> queue-depth rule -> back to 1 ---------
        drains = 0
        for _ in range(4):
            clock.advance(policy.cooldown_s + 1.0)
            r = ctl.evaluate()
            pool_sizes.append(len(fe.replicas))
            if r["action"] == "scale_down":
                drains += 1
            if len(fe.replicas) <= policy.min_replicas:
                break

        # ---- observability: transitions on /fleet/* and traced logs -----
        fleet_metrics = get_json(fleet.url + "/fleet/metrics", timeout=30)
        fleet_health = get_json(fleet.url + "/fleet/healthz", timeout=30)
        logs = get_json(fe.url + "/logs?n=512", timeout=30)
        scale_logs = [rec for rec in logs["records"]
                      if rec["message"].startswith(("autoscale_",
                                                    "replica_"))]
        totals = fleet_metrics.get("totals", fleet_metrics)
        transitions = totals.get("autoscale_transitions_total")

        client_5xx = sum(r["errors_5xx"] + r["transport_errors"]
                         for r in reports)
        out = {
            "pool_sizes": pool_sizes,
            "scale_ups": [up1, up2],
            "reap_action": reap,
            "drains": drains,
            "final_pool": [r.name for r in fe.replicas],
            "client_5xx": int(client_5xx),
            "ramp_shed": sum(r["shed"] for r in reports
                             if r["phase"].startswith("ramp")),
            "failover_ok": failover["ok"],
            "transitions": transitions,
            "fleet_sees_autoscale": "autoscale_replicas" in totals,
            "fleet_health": fleet_health.get("status"),
            "scale_log_records": len(scale_logs),
            "scale_logs_traced": all(rec.get("trace_id")
                                     for rec in scale_logs),
            "preemptions": plan.injected(),
            "lock_sanitizer": lock_sanitizer.report(),
        }
        assert out["lock_sanitizer"]["violations"] == 0, \
            f"lock sanitizer: {lock_sanitizer.table()['violations']}"
        assert out["client_5xx"] == 0, out
        assert max(pool_sizes) == 3 and pool_sizes[-1] == 1, out
        assert up1 == "scale_up" and up2 == "scale_up", out
        assert reap == "replace_dead", out
        assert out["failover_ok"] > 0 and failover["errors_5xx"] == 0, out
        assert out["fleet_sees_autoscale"], out
        assert out["scale_log_records"] >= 4 and out["scale_logs_traced"], out
        return out
    finally:
        lock_sanitizer.uninstall()
        if fleet is not None:
            fleet.stop()
        if fe is not None:
            fe.stop()
        launcher.close()
        TimeSourceProvider.reset()


def main(argv=None):
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    with tempfile.TemporaryDirectory() as d:
        out = run(scan_dir=d)
    print("elastic smoke OK:", json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
