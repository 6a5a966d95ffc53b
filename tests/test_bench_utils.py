"""Unit tests for bench.py's measurement self-defense (pure logic only —
no device): the interleaved min-difference timer must cancel a bimodal
per-call floor and survive stalls via its resample self-check, and
the regression detector must compare against the best prior BENCH_r*.json
with the renamed-metric mapping applied."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest


@pytest.fixture(scope="module")
def bench():
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(root, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_diff_time_cancels_bimodal_floor(bench):
    """Per-call cost = signal*depth + floor, floor drawn from {60ms, 105ms}
    at random per CALL (a harsher model than the rig, whose phases persist
    across calls): min(t_2K) − min(t_K) over interleaved samples recovers
    the pure K-step signal once both groups sample the low mode."""
    rng = np.random.default_rng(0)
    sig = 0.020                       # 20 ms of true K-step signal

    def runner(depth_factor):
        def run():
            floor = 0.060 if rng.random() < 0.5 else 0.105
            return sig * depth_factor + floor + rng.normal(0, 1e-4)
        return run

    for _ in range(5):
        est = bench._diff_time(runner(1), runner(2), trials=9)
        assert abs(est - sig) < 0.004, est


def test_diff_time_raises_when_all_rounds_invert(bench):
    # a 2K-deep run can never legitimately be faster than a K-deep one;
    # persistent inversion means stalls corrupted every round
    with pytest.raises(RuntimeError, match="stalls"):
        bench._diff_time(lambda: 0.5, lambda: 0.4, trials=3)


def test_regressions_vs_prior(bench, tmp_path, monkeypatch):
    """>30% drops against the BEST prior value surface; improvements and
    small dips don't; the ucidigits rename maps old files forward; prior
    headline values only compare when the metric name matches."""
    priors = {
        "BENCH_r01.json": {"metric": "resnet50_train_samples_per_sec_per_chip",
                           "value": 2000.0, "lenet_samples_per_sec": 50000.0,
                           "mnist_real_test_acc": 0.95},
        "BENCH_r02.json": {"metric": "lenet_mnist_train_samples_per_sec_per_chip",
                           "value": 99999.0, "flash_speedup": 2.0},
    }
    for name, d in priors.items():
        (tmp_path / name).write_text(json.dumps(d))
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))

    current = {"metric": "resnet50_train_samples_per_sec_per_chip",
               "value": 1900.0,              # small dip: not flagged
               "lenet_samples_per_sec": 20000.0,   # 60% drop: flagged
               "ucidigits_test_acc": 0.5,          # vs renamed 0.95: flagged
               "flash_speedup": 2.5}               # improvement: not flagged
    regs = {r["metric"]: r for r in bench._regressions_vs_prior(current)}
    assert set(regs) == {"lenet_samples_per_sec", "ucidigits_test_acc"}
    assert regs["lenet_samples_per_sec"]["best_prior"] == 50000.0
    # r02's headline (99999 under a DIFFERENT metric) must not poison the
    # resnet "value" comparison
    assert "value" not in regs


def test_regressions_empty_without_priors(bench, tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    assert bench._regressions_vs_prior({"metric": "m", "value": 1.0}) == []


def test_diff_time_resamples_through_a_stall(bench):
    """A multi-second stall covering one sample group makes the round
    violate the diff <= 0.55*min(t_2K) invariant — the estimator must
    detect it and resample instead of publishing a 27x-off number (the
    observed failure this guard exists for)."""
    sig, floor = 0.020, 0.060
    state = {"i": 0}

    def run_k():
        state["i"] += 1
        # round 1: fine for K-runs
        return sig + floor

    def run_2k():
        state["i"] += 1
        if state["i"] <= 10:          # every 2K-sample of round 1: stalled
            return 2 * sig + floor + 11.0
        return 2 * sig + floor

    est = bench._diff_time(run_k, run_2k, trials=5)
    assert abs(est - sig) < 1e-6
