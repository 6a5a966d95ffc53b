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
    slowed by the profiler (a batch per 0.5 s), so mostly idle; every program
    event, the first 300 operation events of one execution (all but the
    first inside `while.4`, the scanned program). Placed by hand among them
    (PR 38): deeper in the `while`, two iterations of two call sites of one
    kind of fusion and a `conditional` with a fusion in its branch; in an
    idle stretch a second program, two events of `jit_step_fn` with two call
    sites each of `kv_append` and `flash_decode`; on the host line the
    program's `dl4j:fit_*` phases and one event of neither kept family."""
    path = Path(__file__).parent / "recorded_trace.json"
    r = trace_reduce.reduce(json.loads(path.read_text()))
    assert r["devices"] == 1
    assert r["programs"][0][:2] == ["jit_multi_step", 6.0]
    assert r["programs"][0][2] / 6 == pytest.approx(0.5019, abs=2e-4)
    assert r["program_p50_s"]["jit_multi_step"] == pytest.approx(0.50193,
                                                                abs=1e-5)
    assert r["busy_s"] == pytest.approx(3.047, abs=2e-3)
    assert sum(s for _, s in r["program_busy"]) == pytest.approx(r["busy_s"])
    assert 0.2 < r["busy_s"] / r["window_s"] < 0.3
    assert len(r["device_ops"]) == 10 and r["device_ops"][0][1] > 0
    # the idle time under the harness's `fit` splits by the program's phases
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"fit", "fit_execution", "fit_prepare",
                         "fit_dispatch"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    assert gaps["fit_prepare"] == pytest.approx(0.2707, abs=1e-4)
    # kernels: the scanned program's `while` is what it spends outside its
    # body (0.5019 s as a call site), its children are counted once, and
    # call sites of one name are one row
    assert r["device_ops"][0] == ["while.4", pytest.approx(0.50191, abs=1e-5)]
    rows = {(p, k): (c, t) for p, k, c, t in r["kernels"]}
    step = {k: v for (p, k), v in rows.items() if p == "jit_multi_step"}
    assert step["convolution_bitcast_fusion"] == (4.0, pytest.approx(0.010))
    assert step["conditional"] == (2.0, pytest.approx(0.0012))
    assert step["while"][0] == 1.0 and step["while"][1] == pytest.approx(
        0.50191 - sum(t for k, (_, t) in step.items() if k != "while"),
        abs=1e-5)
    assert rows[("jit_step_fn", "flash_decode")] == (4.0,
                                                     pytest.approx(4e-4))
    assert rows[("jit_step_fn", "kv_append")] == (4.0, pytest.approx(2e-4))
    assert not any("." in k for _, k in rows)
    # self times add up to the union of the operations' intervals: within 2 %
    for prog, events, union, self_sum in r["kernels_cover"]:
        assert self_sum == pytest.approx(union, rel=0.02)
    assert r["kernels_cover"][0][:2] == ["jit_multi_step", 6.0]
    assert r["kernels_cover"][0][0] == "jit_multi_step"
    top = trace_reduce.top_kernels(r)
    assert len(top) == 10 and top[0][0] == "while" \
        and [k for k, _ in top].count("fusion") == 1
    # the readers that use it: 3.046 s busy over 30 optimizer steps
    obs = {"trace": r, "cell": {"train": {"steps_per_execution": 5}},
           "window": {"seconds": 45.6, "steps": 420}}
    assert load_reader("train_step_device_ms").read(obs) \
        == pytest.approx(101.5, abs=0.1)
    assert load_reader("device_idle_pct.train").read(obs) \
        == pytest.approx(6.5, abs=0.2)


def serve_slice():
    """A slice of a decode loop, in ns: the window mark runs 1000..11000;
    step programs at 500 (cut by the window's start), 2000 and 3010 (whole)
    and 10500 (cut by its end), a prefill program at 5000. A whole step holds
    two `kv_append`, a `flash_decode` and a `while` around two fusions and a
    `conditional` with a `sort` in its branch."""
    def step_ops(at):
        return [["%kv_append.1 = f32[8] custom-call(...)", at, 100.0],
                ["%flash_decode.1 = f32[8] custom-call(...)", at + 100, 300.0],
                ["%while.2 = (s32[]) while(...)", at + 400, 500.0],
                ["%fusion.7 = f32[8] fusion(...)", at + 450, 100.0],
                ["%fusion.8 = f32[8] fusion(...)", at + 600, 100.0],
                ["%conditional.1 = (f32[8]) conditional(...)", at + 700, 150.0],
                ["%sort.3 = f32[8] sort(...)", at + 720, 80.0],
                ["%kv_append.2 = f32[8] custom-call(...)", at + 900, 50.0]]
    programs = [["jit_step_fn(9)", 500.0, 1000.0],
                ["jit_step_fn(9)", 2000.0, 1000.0],
                ["jit_step_fn(9)", 3010.0, 1000.0],
                ["jit_prefill_fn(3)", 5000.0, 400.0],
                ["jit_step_fn(9)", 10500.0, 1000.0]]
    ops = [["%flash_decode.1 = f32[8] custom-call(...)", 900.0, 300.0]] \
        + step_ops(2000.0) + step_ops(3010.0) \
        + [["%fusion.9 = f32[8] fusion(...)", 5000.0, 400.0]] \
        + step_ops(10500.0)
    marks = [["bench:window", 1000.0, 10000.0],
             ["bench:generate_request", 0.0, 12000.0],
             ["dl4j:decode_wave", 4000.0, 1000.0],
             ["dl4j:decode_step_sync", 4100.0, 800.0],
             ["dl4j:decode_admit", 7000.0, 2000.0]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": programs},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": marks}]}]}


def test_idle_gap_is_named_after_the_shortest_span_of_either_family():
    r = trace_reduce.reduce(serve_slice())
    # 1500..2000 under the request alone, 3000..3010 too; 4010..5000 under
    # wave and step_sync; 5400..10500 under admit; the window mark owns none
    assert dict(r["idle_gaps"]) == {
        "generate_request": pytest.approx(510e-9),
        "decode_step_sync": pytest.approx(990e-9),
        "decode_admit": pytest.approx(5100e-9)}
    no_marks = serve_slice()
    no_marks["planes"][1]["lines"][0]["events"] = [["bench:window", 1000.0,
                                                    10000.0]]
    assert [o for o, _ in trace_reduce.reduce(no_marks)["idle_gaps"]] \
        == ["unattributed"]


def test_kernels_count_whole_program_events_only():
    r = trace_reduce.reduce(serve_slice())
    assert r["programs"] == [["jit_step_fn", 2.0, pytest.approx(2000e-9)],
                             ["jit_prefill_fn", 1.0, pytest.approx(400e-9)]]
    assert r["program_p50_s"]["jit_step_fn"] == pytest.approx(1000e-9)
    # clipped to the window the cut events count, and all sum to busy_s
    assert dict(r["program_busy"]) == {
        "jit_step_fn": pytest.approx(3000e-9),
        "jit_prefill_fn": pytest.approx(400e-9)}
    assert r["busy_s"] == pytest.approx(3400e-9)
    rows = {(p, k): (c, t) for p, k, c, t in r["kernels"]}
    # 2 events, not 4: the cut events' operations are left out of `kernels`
    # and kept, clipped, in `device_ops`
    assert rows[("jit_step_fn", "flash_decode")] == (2.0,
                                                     pytest.approx(600e-9))
    assert dict(r["device_ops"])["flash_decode.1"] == pytest.approx(
        (200 + 300 + 300 + 300) * 1e-9)
    assert rows[("jit_prefill_fn", "fusion")] == (1.0, pytest.approx(400e-9))


def test_kernels_are_self_time_merged_by_name():
    r = trace_reduce.reduce(serve_slice())
    step = {k: (c, t * 1e9) for p, k, c, t in r["kernels"]
            if p == "jit_step_fn"}
    assert step == {"flash_decode": (2.0, pytest.approx(600.0)),
                    "fusion": (4.0, pytest.approx(400.0)),
                    "kv_append": (4.0, pytest.approx(300.0)),
                    # 500 less two fusions and the conditional
                    "while": (2.0, pytest.approx(300.0)),
                    "sort": (2.0, pytest.approx(160.0)),
                    # 150 less the sort in its branch
                    "conditional": (2.0, pytest.approx(140.0))}
    assert [k for p, k, _, _ in r["kernels"]][:2] == ["flash_decode",
                                                      "fusion"]
    cover = {p: (c, u, t) for p, c, u, t in r["kernels_cover"]}
    assert cover["jit_step_fn"] == (2.0, pytest.approx(1900e-9),
                                    pytest.approx(1900e-9))
    assert trace_reduce.top_kernels(r, top=3) == [
        ["flash_decode", pytest.approx(600e-9)],
        ["fusion", pytest.approx(400e-9)], ["kv_append", pytest.approx(300e-9)]]
    assert trace_reduce.kernel_name("%copy-done.12 = f32[] copy-done()") \
        == "copy-done"
    assert trace_reduce.kernel_name("expert_gmm_32x1.11") == "expert_gmm_32x1"


def test_an_operation_that_straddles_its_predecessor_is_counted_once():
    ops = [["a.1", 0.0, 100.0], ["b.1", 50.0, 100.0], ["c.1", 150.0, 10.0]]
    assert trace_reduce._self_times(ops) == [["a.1", 50.0], ["b.1", 50.0],
                                             ["c.1", 10.0]]


def test_snapshot_keeps_labeled_counter_series():
    from benchmarks.observe import snapshot
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
    reg = MetricsRegistry()
    c = reg.counter("decode_steps_ahead_total", "steps")
    c.inc(7, ahead="1")
    c.inc(1, ahead="0")
    reg.histogram("decode_step_sync_ms", "ms").observe(2.0)
    snap = snapshot(reg)
    assert snap["decode_steps_ahead_total"] == 8
    assert snap['decode_steps_ahead_total{ahead="1"}'] == 7
    assert snap['decode_steps_ahead_total{ahead="0"}'] == 1
    assert snap["decode_step_sync_ms"]["count"] == 1


# ------------------------------------------------------------------ readers
def hist(count, total, p50=None):
    return {"count": count, "sum": total, "p50": p50}


# the window of each case: 10 decode steps (or 20 training steps) in 2 s,
# 1,000 tokens; the slice is serve_slice()
OBS = {
    "before": {"decode_step_sync_ms": hist(5, 450.0),
               'decode_steps_ahead_total{ahead="1"}': 4,
               'decode_steps_ahead_total{ahead="0"}': 1,
               "fit_prepare_ms": hist(2, 8.0), "fit_dispatch_ms": hist(1, 1.0)},
    "after": {"decode_step_sync_ms": hist(15, 1400.0),
              'decode_steps_ahead_total{ahead="1"}': 13,
              'decode_steps_ahead_total{ahead="0"}': 2,
              "decode_queue_wait_ms": hist(4, 200.0, 48.5),
              "generate_front_ms": hist(4, 6.0, 1.25),
              "fit_prepare_ms": hist(6, 48.0),
              "fit_dispatch_ms": hist(5, 1601.0)},
    "polled": {}, "cell": {"serve": {"slots": 8}},
    "config": {"reference": "opt350m",
               "args": {"vocab_size": 1000, "d_model": 64, "n_layers": 2,
                        "n_heads": 4}},
    "peak": {"flops_per_s": {"bfloat16": 1e9}},
    "window": {"seconds": 2.0, "steps": 20, "tokens": 1000.0, "chips": 1},
}
WANT = {
    "decode_host_room_ms_per_step": 95.0,           # 950 / 10
    "decode_steps_ahead_pct": 90.0,                 # 9 of 10
    "decode_queue_wait_ms_p50": 48.5,
    "generate_front_ms_p50": 1.25,
    "fit_prepare_ms_per_step": 2.0,                 # 40 / 20
    "fit_dispatch_block_ms_per_step": 80.0,         # 1600 / 20
    "decode_step_program_ms": 1e-3,                 # 1000 ns
    "decode_prefill_device_share_pct": 100 * 400 / 3400,
    "decode_attention_kernels_ms_per_step": 450e-6,     # (600 + 300) / 2 ns
    # 2 layers of 12 d^2 + the head = 162,304 MACs a token, 500 tokens/s
    "decode_mfu_pct": 100 * 2 * (2 * 12 * 64 * 64 + 64 * 1000) * 500 / 1e9,
}


def test_every_reader_taken_up_or_written_by_pr_38_has_a_case():
    assert set(WANT) <= set(READERS)
    assert not (BENCH / "pending").exists()


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_value_and_nothing_to_read(name):
    reader = load_reader(name)
    obs = dict(OBS, trace=trace_reduce.reduce(serve_slice()))
    assert reader.read(obs) == pytest.approx(WANT[name])
    # a program without the instrument, a slice without the program: nothing
    empty = dict(OBS, before={}, after={}, trace=None,
                 window={"seconds": 2.0, "steps": 20, "chips": 1})
    assert reader.read(empty) is None
    # an instrument that appeared inside the window counts from zero
    assert reader.read(dict(obs, before={})) is not None


def test_a_slice_without_prefills_or_attention_kernels():
    trace = serve_slice()
    dev = trace["planes"][0]["lines"]
    dev[0]["events"] = [e for e in dev[0]["events"] if "prefill" not in e[0]]
    dev[1]["events"] = [e for e in dev[1]["events"]
                        if "flash" not in e[0] and "kv_append" not in e[0]]
    obs = dict(OBS, trace=trace_reduce.reduce(trace))
    # the loop is there and held no prefill: 0, which is what it took
    assert load_reader("decode_prefill_device_share_pct").read(obs) == 0.0
    assert load_reader("decode_attention_kernels_ms_per_step").read(obs) \
        is None
    assert load_reader("decode_step_program_ms").read(obs) \
        == pytest.approx(1e-3)


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
