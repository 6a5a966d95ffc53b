"""The seam between the package and the one benchmark (`BENCHMARK.json`,
`benchmarks/`), under tier-1: the driver's command runs `tests/` only, so a
rename in `zoo/models.py` that breaks a cell's `builder` + `args`, a cell
without its workload file or a metric without its reader would otherwise be
found on the chip, as a cell that gives no result. Every case is read from
`BENCHMARK.json`, so a new configuration, cell or metric is a new case here
with no edit; no model is built. Last, that no source file, README.md or the
verify skill points at the benchmark program and records deleted at PR 46."""
import importlib
import inspect
import json
import re
from pathlib import Path

import pytest

from benchmarks.run import load_reader

ROOT = Path(__file__).resolve().parent.parent
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in BENCHMARK["configs"]}
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}
METRICS = {m["name"]: m for m in BENCHMARK["per_layer"]}
# what building, testing and running leave behind (`.gitignore`), and git
LEFT_BEHIND = {".git", ".jax_cache", ".bench_trace", ".pytest_cache",
               ".hypothesis", "_checkout", "_scratch", "chiprun_out",
               "__pycache__"}


def _resolve(dotted):
    mod, name = dotted.rsplit(".", 1)
    return getattr(importlib.import_module(mod), name)


@pytest.mark.parametrize("name", CONFIGS)
def test_config_builder_takes_its_args(name):
    """What `benchmarks.kinds.train.build_net` will call, bound not run."""
    config = json.loads((ROOT / CONFIGS[name]["file"]).read_text())
    assert config["name"] == name
    args = dict(config["args"])
    up = config.get("updater")
    if up:
        cls = _resolve("deeplearning4j_tpu.nn.updaters." + up["class"])
        inspect.signature(cls).bind(**up["args"])
        args["updater"] = None
    inspect.signature(_resolve(config["builder"])).bind(**args)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_has_its_workload_config_and_reference(cell):
    workload = json.loads(
        (ROOT / "benchmarks" / "workloads" / f"{cell}.json").read_text())
    assert workload["name"] == cell
    assert workload["config"] == CELLS[cell]["config"]
    assert workload["chips"] == CELLS[cell]["chips"]
    listed = CONFIGS[workload["config"]]
    config = json.loads((ROOT / listed["file"]).read_text())
    importlib.import_module("benchmarks.reference." + config["reference"])


@pytest.mark.parametrize("metric", METRICS)
def test_per_layer_metric_has_a_reader_and_its_cells(metric):
    entry = METRICS[metric]
    assert callable(load_reader(metric).read)
    assert entry["workloads"] and set(entry["workloads"]) <= set(CELLS)
    assert entry["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}


def test_no_file_points_at_the_deleted_benchmark():
    """The pattern is put together from pieces, so this file does not hold
    it. The files are those git would track: every `*.py`, README.md and
    `.claude/`."""
    gone = re.compile("|".join(a + b for a, b in (
        ("bench", r"\.py"), ("BENCH", "_r05"), ("MULTICHIP", "_r0"))))
    files = [ROOT / "README.md", *(ROOT / ".claude").rglob("*"),
             *ROOT.rglob("*.py")]
    found = [f"{f.relative_to(ROOT)}:{i}: {line.strip()}"
             for f in files
             if f.is_file()
             and not LEFT_BEHIND & set(f.relative_to(ROOT).parts)
             for i, line in enumerate(
                 f.read_text(errors="replace").splitlines(), 1)
             if gone.search(line)]
    assert not found, "\n".join(found)
