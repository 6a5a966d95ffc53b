"""Checks of the benchmark's own arithmetic and wiring. Run by hand and in
the CPU rehearsal (benchmarks/README.md); not part of the repo's tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(ROOT))

from benchmarks import flops, trace_reduce, traffic  # noqa: E402
from benchmarks.run import load_reader  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))
READERS = sorted(p.name[:-3] for p in (BENCH / "layer_metrics").glob("*.py"))


# --------------------------------------------------------------------- flops
def test_resnet50_macs_against_hand_counts():
    from benchmarks.reference import resnet50
    stem = 112 * 112 * 7 * 7 * 3 * 64
    assert flops.conv_macs(112, 112, 7, 7, 3, 64) == stem == 118013952
    total = resnet50.forward_macs({"image_size": 224, "num_classes": 1000})
    # the paper's table 1: "3.8 x 10^9" multiply-adds for the 50-layer net
    assert 3.8e9 < total < 3.9e9
    # by hand: stage 2's first block at 56x56
    s2b1 = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    assert s2b1 == 231211008
    assert flops.train_flops_per_sample(total) == 6 * total


# ------------------------------------------------------------------- traffic
MIX = {"cycle": 32, "prompt_tokens": {"kind": "log_uniform", "min": 32,
                                      "max": 256},
       "new_tokens": {"kind": "log_normal", "min": 32, "max": 512,
                      "median": 128, "sigma": 0.8}}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_traffic_other_seed_same_sizes(seed):
    a = traffic.requests(seed, MIX, 1000, 64)
    b = traffic.requests(seed, MIX, 1000, 64)
    c = traffic.requests(seed + 1, MIX, 1000, 64)
    assert a == b and a != c
    sizes = lambda rs: (sorted(len(p) for p, _ in rs),
                        sorted(n for _, n in rs))
    assert sizes(a) == sizes(c)
    assert min(len(p) for p, _ in a) >= 32 and max(n for _, n in a) <= 512
    t1, t2 = traffic.arrivals(seed, 5.0, 512), traffic.arrivals(seed, 5.0,
                                                                512)
    t3 = traffic.arrivals(seed + 1, 5.0, 512)
    assert t1 == t2 and t1 != t3
    assert abs(t1[-1] - t3[-1]) < 1e-6      # the same gaps, another order
    assert 0.9 < 512 / t1[-1] / 5.0 < 1.1   # the rate asked for


def test_timed_groups_hands_out_whole_groups():
    g = traffic.TimedGroups(list(range(4)), 3, max_groups=2)
    assert list(g) == [0, 1, 2, 3, 0, 1]
    g = traffic.TimedGroups(list(range(4)), 3, seconds=0.0)
    assert list(g) == []


def test_open_loop_sends_on_schedule_and_times_from_due():
    import threading
    import time
    sent = []

    def send(prompt, new):
        sent.append(time.perf_counter())
        time.sleep(0.05)            # slower than the gaps: a closed loop
        return 200, {"tokens": [0] * new}      # would fall behind, this not

    reqs = [([1, 2], 3)] * 10
    times = [0.01 * i for i in range(10)]
    stop = threading.Event()
    t0 = time.perf_counter()
    threads, outcomes = traffic.open_loop(send, reqs, times, t0, stop,
                                          workers=10)
    for t in threads:
        t.join()
    assert len(outcomes) == 10 and all(o.status == 200 for o in outcomes)
    assert max(o.sent - o.due for o in outcomes) < 0.04
    assert sorted(o.due - t0 for o in outcomes) == pytest.approx(times)
    assert all(o.asked == 3 for o in outcomes)


def test_image_pool_rows_all_differ():
    pool = traffic.image_pool(2**31 + 5, 2, 4, 8, 10)
    rows = {x[i].tobytes() for x, _ in pool for i in range(4)}
    assert len(rows) == 8 and pool[0][1].dtype.name == "int32"


# ------------------------------------------------------------- trace_reduce
def synthetic_trace():
    ops = [["%fusion.1 = f32[8] fusion(...)", 100.0, 50.0],
           ["fusion.2", 150.0, 50.0], ["conv.3", 500.0, 100.0],
           ["fusion.1", 900.0, 50.0]]
    marks = [["bench:window", 100.0, 900.0], ["bench:fit", 0.0, 2000.0],
             ["bench:inner", 290.0, 150.0]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_step", 100.0, 200.0],
                                               ["jit_step", 450.0, 500.0]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": marks}]}]}


def test_reduce_synthetic_trace():
    r = trace_reduce.reduce(synthetic_trace())
    # the window mark runs 100..1000, the programs' record ends at 950
    assert r["window_s"] == pytest.approx(850e-9)
    assert r["busy_s"] == pytest.approx(700e-9)     # 100..300 and 450..950
    assert r["programs"] == [["jit_step", 2.0, pytest.approx(700e-9)]]
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(100e-9)] \
        or r["device_ops"][0][0] == "conv.3"
    assert dict(r["idle_gaps"]) == {"inner": pytest.approx(150e-9)}
    assert trace_reduce.reduce({"planes": []}) is None


def test_reduce_recorded_trace():
    """A thinned recording of a traced slice of the train cell on the chip:
    six 5-step executions launched with the device drained, the input path
    slowed by the profiler (a batch per 0.5 s), so mostly idle."""
    path = Path(__file__).parent / "recorded_trace.json"
    r = trace_reduce.reduce(json.loads(path.read_text()))
    assert r["devices"] == 1
    assert r["programs"][0][:2] == ["jit_multi_step", 6.0]
    assert r["programs"][0][2] / 6 == pytest.approx(0.5019, abs=2e-4)
    assert r["busy_s"] == pytest.approx(3.046, abs=2e-3)
    assert 0.2 < r["busy_s"] / r["window_s"] < 0.3
    assert len(r["device_ops"]) == 10 and r["device_ops"][0][1] > 0
    assert dict(r["idle_gaps"])["fit"] == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    # the readers that use it: 3.046 s busy over 30 optimizer steps
    obs = {"trace": r, "cell": {"train": {"steps_per_execution": 5}},
           "window": {"seconds": 45.6, "steps": 420}}
    assert load_reader("train_step_device_ms").read(obs) \
        == pytest.approx(101.5, abs=0.1)
    assert load_reader("device_idle_pct.train").read(obs) \
        == pytest.approx(6.5, abs=0.2)


# -------------------------------------------------------------------- wiring
@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_wired(cell):
    w = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
    assert w["name"] == cell
    cfg = json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    assert (BENCH / "reference" / f"{cfg['reference']}.py").exists()
    assert (BENCH / "kinds" / f"{w['kind']}.py").exists()
    assert "setup_s" in w["end_to_end"] and len(w["end_to_end"]) >= 2
    for name in w["per_layer"]:
        assert name in READERS, f"{cell}: no reader for {name}"
    entry = {e["name"]: e for e in SPEC["workloads"]}[cell]
    assert entry["config"] == w["config"] and entry["chips"] == w["chips"]
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for name, unit in w["end_to_end"].items():
        assert e2e[name]["unit"] == unit
        assert cell in e2e[name].get("workloads", [cell])
    per = {m["name"]: m for m in SPEC["per_layer"]}
    for name in w["per_layer"]:
        assert cell in per[name]["workloads"]
        assert per[name]["moves"] in w["end_to_end"]


@pytest.mark.parametrize("name", READERS)
def test_reader_agrees_with_benchmark_json(name):
    reader = load_reader(name)
    entry = {m["name"]: m for m in SPEC["per_layer"]}[name]
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    # a reader that finds nothing to read returns nothing
    empty = {"before": {}, "after": {}, "trace": None, "polled": {},
             "window": {}, "cell": {"serve": {"slots": 1}},
             "config": {"args": {}}, "peak": {}}
    assert reader.read(empty) is None
