"""The benchmark's reduction from a profiler trace to device time and the
owners of its idle gaps (benchmarks/trace_reduce.py), under tier-1: the
driver's command runs `tests/` only, and `benchmarks/tests/` — which holds
the reduction's full cases — runs in no tier-1 command (ROADMAP D8). Here:
the recorded slice of the train cell, and the rule that names a gap's owner
across threads, which the input path's phases lean on since PR 39 (a
worker-thread `dl4j:etl_h2d` under the main thread's longer
`dl4j:etl_consumer_wait`)."""
import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce

RECORDED = Path(__file__).parent.parent / "benchmarks" / "tests" \
    / "recorded_trace.json"


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.reduce(json.loads(RECORDED.read_text()))


def test_recorded_slice_device_time(recorded):
    r = recorded
    assert r["devices"] == 1
    assert r["programs"][0][:2] == ["jit_multi_step", 6.0]
    assert r["program_p50_s"]["jit_multi_step"] == pytest.approx(0.50193,
                                                                abs=1e-5)
    assert r["busy_s"] == pytest.approx(3.047, abs=2e-3)
    assert sum(s for _, s in r["program_busy"]) == pytest.approx(r["busy_s"])


def test_recorded_slice_idle_gaps_have_owners_and_add_up(recorded):
    gaps = dict(recorded["idle_gaps"])
    assert set(gaps) == {"fit", "fit_execution", "fit_prepare",
                         "fit_dispatch"}
    assert sum(gaps.values()) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"], rel=1e-6)


def test_recorded_slice_kernels_are_self_times(recorded):
    # operations of one line nest: the self times add up to the union of
    # their intervals, program by program
    for prog, events, union, self_s in recorded["kernels_cover"]:
        assert events >= 1 and self_s == pytest.approx(union, rel=1e-9), prog
    rows = {(p, k): t for p, k, _, t in recorded["kernels"]}
    assert rows[("jit_step_fn", "flash_decode")] > 0
    assert trace_reduce.top_kernels(recorded, top=3)[0][0] == "while"


def _slice(worker_events, main_events):
    """Two 100 ms programs 40 ms apart on one device, a `bench:fit` mark
    over all of it, and `dl4j:` phases on two host lines (two threads)."""
    ms = 1e6
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_multi_step(1)", 0.0, 100 * ms],
                ["jit_concatenate(2)", 138 * ms, 1 * ms],
                ["jit_multi_step(1)", 140 * ms, 100 * ms]]},
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = f32[] fusion()", 0.0, 100 * ms],
                ["%fusion.1 = f32[] fusion()", 140 * ms, 100 * ms]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["bench:fit", 0.0, 240 * ms]] + main_events},
            {"name": "python", "events": worker_events}]}]}


@pytest.mark.parametrize("worker,main,owners", [
    # the worker's transfer lies inside the loop's longer wait for it and
    # covers the gap's middle: the shortest covering span owns the gap,
    # whichever thread it ran on
    ([["dl4j:etl_h2d", 101e6, 36e6]],
     [["dl4j:fit_next_batch", 100.5e6, 39e6],
      ["dl4j:etl_consumer_wait", 100.6e6, 38e6]],
     {"etl_h2d": 0.038, "fit": 0.001}),
    # no worker span: the consumer's wait, nested in the pull, owns it
    ([],
     [["dl4j:fit_next_batch", 100.5e6, 39e6],
      ["dl4j:etl_consumer_wait", 100.6e6, 38e6]],
     {"etl_consumer_wait": 0.038, "fit": 0.001}),
    # the program says nothing there (the parent of PR 39): the harness's
    # own mark is all that is left
    ([], [], {"fit": 0.039}),
], ids=["worker_thread_h2d", "consumer_wait", "no_phase"])
def test_gap_owner_is_the_shortest_covering_span_of_any_thread(
        worker, main, owners):
    r = trace_reduce.reduce(_slice(worker, main))
    assert r["busy_s"] == pytest.approx(0.201)
    assert dict(r["idle_gaps"]) == {k: pytest.approx(v)
                                    for k, v in owners.items()}
