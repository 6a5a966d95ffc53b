#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

One process, the default platform. It fails (non-zero exit, no result line)
on anything but a TPU that peaks.json lists, or when JAX reports another
number of devices than the cell's `chips`. The cell is found by name:
workloads/<name>.json names its configuration (configs/<config>.json), its
kind (kinds/<kind>.py drives it) and the metrics it reports; each per-layer
metric has a reader of its own (layer_metrics/<metric>.py). Adding a cell, a
configuration or a metric is adding files.

The last line of stdout is the result: correct, attempted, failed, metrics,
device (and breakdown when traced), then `compared`: every number `correct`
was decided on beside its limit, which are also the last lines of stderr.
With --trace 0 the metrics are the cell's end-to-end metrics; with --trace 1
its per-layer metrics.

`--rehearsal <file>` (CPU only, see README.md) overrides sizes so the whole
path can be driven at a tiny size without a chip; a rehearsal prints no
metrics and no result line of the contract's shape.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # process start, as near as Python gets

import argparse
import importlib
import importlib.util
import json
import os
import resource
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))

from benchmarks import trace_reduce  # noqa: E402
from benchmarks.observe import note  # noqa: E402


def load_json(path):
    with open(path) as f:
        return json.load(f)


def merge(base, over):
    """`over` laid onto `base`, dict by dict."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_reader(name):
    path = HERE / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Check:
    """The numbers compared and their limits; `correct` is their conjunction."""

    def __init__(self):
        self.rows = []

    def at_most(self, name, value, limit):
        ok = value is not None and value == value and value <= limit
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "ok": bool(ok)})
        return ok

    def within(self, name, value, lo, hi):
        ok = value is not None and lo < value < hi
        self.rows.append({"check": name, "value": value, "limit": [lo, hi],
                          "ok": bool(ok)})
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)


class Run:
    """What a kind driver gets: the cell, its configuration, the arguments,
    the device's peaks, and the clock that set-up is measured on."""

    def __init__(self, args, cell, config, peak, device, rehearsal):
        self.seed, self.seconds, self.trace = args.seed, args.seconds, \
            bool(args.trace)
        self.cell, self.config = cell, config
        self.peak, self.device, self.rehearsal = peak, device, rehearsal
        self.control = bool(getattr(args, "control", 0))
        self.check = Check()
        self.control_rows = []      # the control's numbers, same names
        self.trace_dir = str(ROOT / ".bench_trace" / cell["name"])

    def setup_seconds(self):
        return time.perf_counter() - T_START

    def mark(self, phase):
        """An earlier line: seconds since process start at a set-up phase."""
        note(phase=phase, at_s=round(self.setup_seconds(), 2))

    def reference(self):
        return importlib.import_module(
            "benchmarks.reference." + self.config["reference"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", default=None,
                    help="JSON of size overrides; CPU only, prints no metrics")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: also compute the lower-precision control's "
                         "numbers (control.py; never in a benchmark run)")
    args = ap.parse_args(argv)
    run, out = run_cell(args)
    return report(run, out)


def run_cell(args):
    """Everything of a run but the printing of its result."""

    cell = load_json(HERE / "workloads" / f"{args.workload}.json")
    config = load_json(HERE / "configs" / f"{cell['config']}.json")
    if not (ROOT / "deeplearning4j_tpu").is_dir():
        sys.exit("run.py: the program (deeplearning4j_tpu/) is not in this "
                 "checkout")
    rehearsal = None
    if args.rehearsal:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            sys.exit("run.py: --rehearsal is for JAX_PLATFORMS=cpu only")
        rehearsal = load_json(args.rehearsal)
        cell = merge(cell, rehearsal.get("cell", {}))
        config = merge(config, rehearsal.get("config", {}))

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = load_json(HERE / "peaks.json")
    if rehearsal is None:
        if device["platform"] != "tpu" or device["kind"] not in peaks:
            sys.exit(f"run.py: needs a TPU listed in peaks.json, JAX reports "
                     f"{device}; there is no fallback")
        if device["count"] != cell["chips"]:
            sys.exit(f"run.py: cell {cell['name']} needs {cell['chips']} "
                     f"chip(s), JAX reports {device['count']}")
    peak = peaks.get(device["kind"])

    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # every program of the cell goes to the cache, the small ones too: the
    # second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    note(cell=cell["name"], seed=args.seed, device=device,
         compile_cache_dir=cache_dir, rehearsal=bool(rehearsal))

    run = Run(args, cell, config, peak, device, rehearsal)
    kind = importlib.import_module("benchmarks.kinds." + cell["kind"])
    return run, kind.run(run)      # end_to_end, obs, attempted, failed


def report(run, out):
    cell, device, rehearsal = run.cell, run.device, run.rehearsal
    note(host_peak_rss_gb=resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss / 1e6)
    for row in run.check.rows:
        note(**row)
    for row in run.control_rows:
        note(control=True, **row)
    if rehearsal is not None:
        note(rehearsal=True, correct=run.check.correct,
             attempted=out["attempted"], failed=out["failed"],
             counts=out.get("counts"))
        print(json.dumps({"rehearsal": True, "correct": run.check.correct}),
              flush=True)
        return 0 if run.check.correct else 1

    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": run.check.correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if not run.trace:
        units = cell["end_to_end"]
        for name, value in out["end_to_end"].items():
            result["metrics"][name] = {"value": value, "unit": units[name]}
    else:
        reduced = out["obs"].get("trace")
        if reduced is None or not reduced["busy_s"] > 0:
            sys.exit("run.py: the traced window shows no operation on the "
                     "device")
        # the kind's own busy time and window where the slice is not the
        # loop it times (kinds/train.py), else the slice's
        device.update(out.get("device") or {
            "busy_s": reduced["busy_s"], "window_s": reduced["window_s"]})
        note(programs=reduced["programs"],
             program_busy=reduced["program_busy"],
             kernels_cover=reduced["kernels_cover"],
             kernels=reduced["kernels"][:60])
        result["breakdown"] = {
            "device_ops": trace_reduce.top_kernels(reduced),
            "idle_gaps": reduced["idle_gaps"]}
        for name in cell["per_layer"]:
            reader = load_reader(name)
            value = reader.read(out["obs"])
            if value is not None:
                result["metrics"][name] = {"value": value,
                                           "unit": reader.UNIT}
    # every number compared beside its limit: the last lines of stderr, and
    # the result's last key
    result["compared"] = {r["check"]: {"value": r["value"],
                                       "limit": r["limit"]}
                          for r in run.check.rows}
    for r in run.check.rows:
        print(f"compared {r['check']}: {r['value']} limit {r['limit']} "
              f"{'ok' if r['ok'] else 'NOT OK'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
