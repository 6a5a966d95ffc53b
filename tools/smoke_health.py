"""Health & alerting smoke test: the full observe -> detect -> react loop.

Boots a ServingServer with a registered model, then:

1. asserts the deep `/healthz` starts healthy (admission/batcher/registry
   component probes all green);
2. injects a failing probe and asserts `/healthz` flips to HTTP 503 with
   that component marked unhealthy;
3. runs a NaN-loss training run (NaN features) under FaultTolerantTrainer
   with a TrainingHealthListener wired into the server's health monitor,
   registry, and logger — asserts the run checkpoint-and-halts
   (TrainingHalted), the `training_nan` alert rule fires at `GET /alerts`,
   `/healthz` shows the trainer component unhealthy, and the structured
   records at `GET /logs` carry trace ids matching the training iteration
   spans (the /logs <-> /trace join).

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_health.py
"""
from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _get(url, timeout=30):
    """(status, decoded-JSON body) — 4xx/5xx answers return, not raise."""
    from deeplearning4j_tpu.util.http import get_json
    return get_json(url, timeout=timeout, with_status=True)


def run(nin=6, n_batches=4, seed=0):
    import numpy as np
    from tools.smoke_telemetry import _tiny_net
    from deeplearning4j_tpu import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.optimize.listeners import (TrainingHalted,
                                                       TrainingHealthListener)
    from deeplearning4j_tpu.serving import ServingServer
    from deeplearning4j_tpu.telemetry import get_tracer
    from deeplearning4j_tpu.telemetry.alerts import default_training_rules
    from deeplearning4j_tpu.train import CheckpointConfig, FaultTolerantTrainer

    tracer = get_tracer()
    was_enabled = tracer.enabled
    tracer.enabled = True              # training spans for /logs correlation
    server = ServingServer(_tiny_net(nin=nin, seed=seed), max_batch_size=8,
                           alert_interval_s=0).start()
    for rule in default_training_rules():
        server.alerts.add_rule(rule)
    try:
        # 1. healthy baseline ---------------------------------------------
        status, h = _get(server.url + "/healthz")
        assert status == 200 and h["health"] == "healthy", (status, h)
        for comp in ("admission", "batcher", "registry"):
            assert h["components"][comp]["status"] == "healthy", h

        # 2. injected failing probe -> 503 --------------------------------
        server.health.register(
            "injected", lambda: ("unhealthy", {"reason": "smoke-injected"}))
        status, h = _get(server.url + "/healthz")
        assert status == 503 and h["health"] == "unhealthy", (status, h)
        assert h["components"]["injected"]["reason"] == "smoke-injected", h
        server.health.unregister("injected")

        # 3. NaN-loss training run: watchdog -> checkpoint-and-halt -------
        watchdog = TrainingHealthListener(health=server.health,
                                          registry=server.metrics.registry,
                                          logger=server.logger)
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(8 * n_batches, nin)).astype(np.float32)
        X[0, 0] = np.nan                     # poisoned batch -> NaN loss
        Y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, len(X))]
        it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
        with tempfile.TemporaryDirectory() as ckdir:
            trainer = FaultTolerantTrainer(
                lambda: _tiny_net(nin=nin, seed=seed),
                CheckpointConfig(ckdir, frequency=0), health=watchdog)
            halted = None
            try:
                trainer.fit(it, epochs=1)
            except TrainingHalted as e:
                halted = e
            assert halted is not None, "NaN run was not halted"
            assert halted.reason == "nan_loss", halted.reason
            assert Path(halted.checkpoint_path).is_dir(), halted

        # the alert engine sees training_nan_total in the server registry
        server.alerts.evaluate()
        status, alerts = _get(server.url + "/alerts")
        firing = {r["name"]: r for r in alerts["rules"]
                  if r["state"] == "firing"}
        assert "training_nan" in firing, alerts
        assert firing["training_nan"]["severity"] == "page", firing

        # deep health: trainer component unhealthy -> 503
        status, h = _get(server.url + "/healthz")
        assert status == 503, (status, h)
        trainer_comp = h["components"]["trainer"]
        assert trainer_comp["status"] == "unhealthy", h
        assert trainer_comp["reason"] == "nan_loss", h

        # /logs records carry the originating iteration span's trace id
        status, logs = _get(server.url + "/logs?level=error")
        nan_recs = [r for r in logs["records"]
                    if r["message"] == "training_nan_loss"]
        assert nan_recs, logs
        iteration_traces = {s.trace_id for s in tracer.finished_spans()
                            if s.name == "iteration"}
        assert all(r.get("trace_id") in iteration_traces for r in nan_recs), \
            (nan_recs, iteration_traces)

        return {"components": sorted(h["components"]),
                "firing": sorted(firing),
                "halt_reason": halted.reason,
                "halt_iteration": halted.iteration,
                "nan_log_records": len(nan_recs),
                "log_events": logs["count"]}
    finally:
        server.health.unregister("trainer")
        server.stop()
        tracer.enabled = was_enabled


def main(argv=None):
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = run()
    print("health smoke OK:", json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
