"""The open loop against the server (`opt350m_chat_steady`, PR 52): a whole
run at rehearsal size through `run_cell`, as `test_correct.py` holds for the
closed loop — `correct` true with the generator's lateness reported, and
false when a served token is altered where it is produced.

Run by hand:  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks.tests.test_correct import drive, rows  # noqa: E402

CELL = "opt350m_chat_steady"


def test_sound_open_loop_run_is_correct_and_reports_its_lateness(capsys):
    run, out = drive(CELL, 2**31 + 52, seconds=3.0)
    assert run.cell["serve"]["loop"] == "open"
    assert run.check.correct, run.check.rows
    assert out["failed"] == 0 and out["attempted"] > 0
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    (window,) = [n for n in notes if "generator_late_ms_p50" in n]
    # requests are sent on the schedule, not when a worker comes free
    assert 0.0 <= window["generator_late_ms_p50"] < 50.0
    assert window["completed"] == out["attempted"]
    # the window's two snapshots hold what the cell's new readers read
    after = out["obs"]["after"]
    assert after['decode_program_ms_total{program="step"}'] > 0
    assert after['decode_prefill_rows_total{kind="prompt"}'] > 0
    assert after["decode_first_token_ms"]["count"] > 0


def test_served_token_altered_in_the_open_loop_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    real = DecodeEngine.read_ids     # where the loop reads a step's tokens
    monkeypatch.setattr(DecodeEngine, "read_ids", lambda self, ids:
                        (real(self, ids) + 1) % self.vocab)
    run, _ = drive(CELL, 52, seconds=3.0)
    assert not run.check.correct
    assert not rows(run)["served_token_logit_gap_max"]["ok"]
