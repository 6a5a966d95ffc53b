#!/usr/bin/env python3
"""One run of one cell through the harness (benchmarks/run.py, untouched),
which also reads the per-layer metrics that wait under pending/.

    python3 benchmarks/pending/run_pending.py --workload <name> --seed <n> \\
        --seconds <s> [--trace <0|1>] [--rehearsal <file>]

The readers under pending/layer_metrics/ read the histograms of the program's
phases (PERF.md section 3). The harness reports a cell's metrics from the
list in workloads/<cell>.json, a file no PR but a `benchmark` one may edit, so
until one takes them up (README.md here) this is how they are read: an earlier
line `pending_per_layer`, then the harness's own lines and result, unchanged.
A rehearsal prints which of them found something to read, never a value.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from benchmarks import run as harness  # noqa: E402
from benchmarks.observe import note  # noqa: E402


def load_pending(name):
    spec = importlib.util.spec_from_file_location(
        "pending_metric_" + name, HERE / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pending_for(cell):
    entries = json.loads((HERE / "per_layer.json").read_text())["per_layer"]
    return [e["name"] for e in entries if cell in e["workloads"]]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearsal", default=None)
    args = ap.parse_args(argv)
    args.control = 0
    run, out = harness.run_cell(args)
    values = {name: load_pending(name).read(out["obs"])
              for name in pending_for(args.workload)}
    if run.rehearsal is not None:
        values = {name: v is not None for name, v in values.items()}
    note(pending_per_layer=values)
    return harness.report(run, out)


if __name__ == "__main__":
    sys.exit(main())
