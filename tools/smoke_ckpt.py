#!/usr/bin/env python
"""Durable-checkpoint smoke: train with async checkpoints under a seeded
disk-fault plan, "kill" the process, restore with fallback past the corrupt
newest checkpoint, finish, and prove final-param parity with an
uninterrupted run. ManualClock throughout — the slow_disk rule advances the
injected clock instead of sleeping, so the whole arc runs with ZERO real
sleeps.

Three phases, one summary dict (`run()`; wired as a fast tier-1 test in
tests/test_fault_tolerance.py):

  tear — slow_disk + torn_write corrupt the NEWEST checkpoint's model.zip
         at the util.fs write seam; restore quarantines it
         (corrupt-ckpt-*), falls back to the previous verified checkpoint,
         reports a degraded probe until the next good publish, and the
         resumed run matches the uninterrupted reference bit-for-bit in
         replayed batch order.
  flip — same arc with a single bit flipped (size-preserving, only the
         manifest sha256 catches it).
  full — ENOSPC mid-checkpoint: the async writer absorbs it as checkpoint
         debt (counter + log), training keeps running, the previously
         published checkpoint stays intact, and the final checkpoint
         publishes clean.
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _factory(seed=11):
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    DenseLayer, OutputLayer,
                                    MultiLayerNetwork, Sgd)

    def make():
        conf = (NeuralNetConfiguration.builder()
                .seed(seed).updater(Sgd(0.1)).list()
                .layer(DenseLayer(n_out=16, activation="tanh"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="MCXENT"))
                .input_type(InputType.feed_forward(8))
                .build())
        return MultiLayerNetwork(conf)
    return make


def _data(n=96, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    w = rng.normal(size=(8, 3))
    Y = np.eye(3, dtype=np.float32)[np.argmax(X @ w, axis=1)]
    return X, Y


def _counter(name):
    from deeplearning4j_tpu.telemetry.registry import get_registry
    return get_registry().counter(name).get()


def run(root):
    from deeplearning4j_tpu import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.resilience.chaos import FaultPlan, FaultRule
    from deeplearning4j_tpu.telemetry.health import HealthMonitor
    from deeplearning4j_tpu.telemetry.registry import get_registry
    from deeplearning4j_tpu.train import CheckpointConfig, FaultTolerantTrainer
    from deeplearning4j_tpu.util.time_source import (ManualClock,
                                                     TimeSourceProvider,
                                                     monotonic_s)

    root = str(root)
    X, Y = _data()
    out = {}
    clock = ManualClock()
    TimeSourceProvider.set_instance(clock)
    try:
        it = ListDataSetIterator(DataSet(X, Y), batch_size=8)  # 12 batches
        ref = FaultTolerantTrainer(
            _factory(), CheckpointConfig(os.path.join(root, "ref"),
                                         frequency=0), monitor=False)
        ref.fit(it, epochs=2)
        ref_params = np.asarray(ref.model.get_flat_params())

        # -- tear + flip: corrupt the newest checkpoint, restore falls back
        for phase, kind in (("tear", "torn_write"), ("flip", "bitflip")):
            d = os.path.join(root, phase)
            plan = FaultPlan([
                # every model.zip write costs 50 injected-clock ms
                FaultRule("slow_disk", match="model.zip", latency_s=0.05,
                          name="nfs-stall"),
                # 3rd model.zip write = the NEWEST checkpoint (ckpt at 5,
                # 10, then the fit-end 12)
                FaultRule(kind, match="model.zip", after=2, count=1,
                          name=f"{phase}-newest"),
            ], seed=7)
            t_mono = monotonic_s()
            v0 = _counter("ckpt_verify_failures_total")
            f0 = _counter("ckpt_restore_fallbacks_total")
            with plan:
                t1 = FaultTolerantTrainer(
                    _factory(), CheckpointConfig(d, frequency=5),
                    monitor=False)
                t1.fit(it, epochs=1)        # ckpts 5, 10, 12(corrupt)
            out[f"{phase}_injected"] = plan.injected()[f"{phase}-newest"]
            out[f"{phase}_clock_advance_s"] = round(monotonic_s() - t_mono, 3)
            # "kill" -> restart: a fresh trainer over the same directory
            mon = HealthMonitor()
            t2 = FaultTolerantTrainer(
                _factory(), CheckpointConfig(d, frequency=5), monitor=mon)
            assert t2.resumed and t2.state["iteration"] == 10, t2.state
            assert any(n.startswith("corrupt-ckpt-") for n in os.listdir(d))
            comp = mon.check()["components"][t2.health_key]
            assert comp["status"] == "degraded", comp
            assert comp["checkpoint_debt"]["restore_fallback"] is True
            out[f"{phase}_verify_failures"] = \
                _counter("ckpt_verify_failures_total") - v0
            out[f"{phase}_fallbacks"] = \
                _counter("ckpt_restore_fallbacks_total") - f0
            t2.fit(it, epochs=2)            # replays 10..12, then epoch 2
            # a fresh verified publish clears the checkpoint debt
            comp = mon.check()["components"][t2.health_key]
            assert comp["status"] == "healthy", comp
            t2.unregister_probe()
            got = np.asarray(t2.model.get_flat_params())
            np.testing.assert_allclose(ref_params, got, rtol=1e-6, atol=1e-7)
            out[f"{phase}_parity"] = True

        # -- full: ENOSPC mid-checkpoint leaves training running ------------
        d = os.path.join(root, "full")
        w0 = _counter("ckpt_write_failures_total")
        plan = FaultPlan([
            # 2nd model.zip write = ckpt-10; ckpt-5 and the final 12 succeed
            FaultRule("enospc", match="model.zip", after=1, count=1,
                      name="disk-full"),
        ], seed=7)
        with plan:
            t3 = FaultTolerantTrainer(
                _factory(), CheckpointConfig(d, frequency=5), monitor=False)
            t3.fit(it, epochs=1)            # must NOT raise
        names = sorted(n for n in os.listdir(d) if n.startswith("ckpt-"))
        assert names == ["ckpt-000000005", "ckpt-000000012"], names
        from deeplearning4j_tpu.util import fs
        for n in names:                     # survivors verify, incl. the
            ok, errors = fs.verify_manifest(os.path.join(d, n))
            assert ok, (n, errors)          # one written BEFORE the fault
        out["enospc_write_failures"] = \
            _counter("ckpt_write_failures_total") - w0
        out["enospc_survivors"] = names

        hist = get_registry().get("ckpt_write_ms")
        out["ckpt_write_ms_count"] = hist.count() if hist else 0
        out["ckpt_blocking_ms_count"] = \
            get_registry().get("ckpt_blocking_ms").count()
        assert out["ckpt_write_ms_count"] > 0
        assert out["tear_injected"] == 1 and out["flip_injected"] == 1
        assert out["tear_fallbacks"] == 1 and out["flip_fallbacks"] == 1
        assert out["enospc_write_failures"] == 1
        # slow_disk advanced the injected clock (3 model.zip writes x 50 ms
        # per phase), proving the stall was simulated, not slept
        assert out["tear_clock_advance_s"] >= 0.15
    finally:
        TimeSourceProvider.set_instance(None)
    return out


def main():
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    import json
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        out = run(d)
    print(json.dumps(out, indent=1, sort_keys=True))
    print("SMOKE CKPT OK")


if __name__ == "__main__":
    main()
