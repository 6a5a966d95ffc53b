"""Checks of the per-layer readers that wait under benchmarks/pending/ (see
its README.md): each on a hand-made `obs`, and the wiring a `benchmark` PR
will need. Not part of the repo's tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
PENDING = ROOT / "benchmarks" / "pending"
sys.path.insert(0, str(ROOT))

from benchmarks.pending.run_pending import load_pending, pending_for  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
ENTRIES = {e["name"]: e for e in
           json.loads((PENDING / "per_layer.json").read_text())["per_layer"]}
READERS = sorted(p.name[:-3] for p in (PENDING / "layer_metrics").glob("*.py"))


def hist(count, total, p50=None):
    return {"count": count, "sum": total, "p50": p50}


# the window of each case: 10 decode steps (or 20 training steps) in 2 s
OBS = {
    "before": {"decode_wave_ms": hist(5, 500.0), "decode_step_sync_ms":
               hist(5, 450.0), "decode_prefill_ms": hist(1, 20.0),
               "decode_probs_read_ms": hist(5, 10.0),
               "fit_prepare_ms": hist(2, 8.0), "fit_dispatch_ms": hist(1, 1.0)},
    "after": {"decode_wave_ms": hist(15, 1600.0), "decode_step_sync_ms":
              hist(15, 1400.0), "decode_prefill_ms": hist(4, 80.0),
              "decode_probs_read_ms": hist(15, 35.0),
              "decode_queue_wait_ms": hist(4, 200.0, 48.5),
              "generate_front_ms": hist(4, 6.0, 1.25),
              "fit_prepare_ms": hist(6, 48.0),
              "fit_dispatch_ms": hist(5, 1601.0)},
    "trace": None, "polled": {},
    "window": {"seconds": 2.0, "steps": 20},
}
WANT = {
    # (1100 wave - 950 sync - 60 prefill) / 10 steps
    "decode_host_gap_ms_per_step": 9.0,
    "decode_probs_read_ms_per_step": 2.5,           # 25 / 10
    "decode_prefill_share_pct": 3.0,                # 60 ms of 2000
    "decode_queue_wait_ms_p50": 48.5,
    "generate_front_ms_p50": 1.25,
    "fit_prepare_ms_per_step": 2.0,                 # 40 / 20
    "fit_dispatch_block_ms_per_step": 80.0,         # 1600 / 20
}


def test_every_pending_reader_has_a_case_and_an_entry():
    assert READERS == sorted(WANT) == sorted(ENTRIES)


@pytest.mark.parametrize("name", sorted(WANT))
def test_pending_reader_value_and_nothing_to_read(name):
    reader = load_pending(name)
    assert reader.read(OBS) == pytest.approx(WANT[name])
    # a program without the instrument (the parent commit): nothing, no raise
    empty = {"before": {}, "after": {}, "trace": None, "polled": {},
             "window": {"seconds": 2.0, "steps": 20}}
    assert reader.read(empty) is None
    # an instrument that appeared inside the window counts from zero
    fresh = dict(OBS, before={})
    assert reader.read(fresh) is not None


@pytest.mark.parametrize("name", sorted(WANT))
def test_pending_entry_is_wired_for_its_cell(name):
    reader, entry = load_pending(name), ENTRIES[name]
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    taken = {m["name"] for m in SPEC["per_layer"] + SPEC["end_to_end"]}
    assert name not in taken
    layers = {m["layer"] for m in SPEC["per_layer"]} | {"serving front"}
    assert entry["layer"] in layers
    for cell in entry["workloads"]:
        w = json.loads((ROOT / "benchmarks" / "workloads"
                        / f"{cell}.json").read_text())
        assert entry["moves"] in w["end_to_end"]
        assert name in pending_for(cell)
