#!/usr/bin/env python3
"""The readings the limits of `correct` are set from:

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it starts run.py with `--control 1` (a process each, one after
the other: this parent never touches JAX, so the chip is the child's): the
cell's own set-up, a short window at the cell's own load, and beside every
number `correct` compares, the number the control gives — the plain reference
computed in the precision below the one the configuration states
(`control_precision` in its file), put in the program's place. The last line
sums up: the largest sound reading and the smallest control reading of each
number. A benchmark run never calls this.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearsal", default=None)
    args = ap.parse_args(argv)
    sound, control, end = {}, {}, []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, str(RUN), "--workload", args.workload,
               "--seed", seed, "--seconds", str(args.seconds), "--trace", "0",
               "--control", "1"]
        if args.rehearsal:
            cmd += ["--rehearsal", args.rehearsal]
        out = subprocess.run(cmd, stdout=subprocess.PIPE, text=True).stdout
        for line in out.splitlines():
            try:
                row = json.loads(line)
            except ValueError:
                continue
            print(json.dumps(dict(row, seed=int(seed))), flush=True)
            if "check" in row and row.get("control"):
                control.setdefault(row["check"], []).append(row["value"])
            elif "check" in row and isinstance(row["limit"], float) \
                    and row["limit"] > 0:
                sound.setdefault(row["check"], []).append(row["value"])
    print(json.dumps({"sound_largest": {k: max(v) for k, v in sound.items()},
                      "control_smallest": {k: min(v)
                                           for k, v in control.items()},
                      "sound": sound, "control": control}), flush=True)


if __name__ == "__main__":
    main()
