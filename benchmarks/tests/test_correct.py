"""`correct` has to come out false when the timed path is broken underneath,
and when the plain reference, computed in the precision below the one the
configuration states, is put in the program's place.

These drive a whole run at rehearsal size on the CPU, past the harness's look
for a chip. Run by hand:  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import argparse
import os
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import run as harness  # noqa: E402

REHEARSAL = ROOT / "benchmarks" / "rehearsal"


def drive(cell, seed, control=0, seconds=1.0):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0, control=control,
                              rehearsal=str(REHEARSAL / f"{cell}.json"))
    run, out = harness.run_cell(args)
    return run, out


def rows(run):
    return {r["check"]: r for r in run.check.rows}


def test_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    from deeplearning4j_tpu.nn.multistep import MultiStepTrainable
    real = MultiStepTrainable.fit_prepared

    def frozen(self, prepared):
        params = self.params
        import jax
        keep = jax.tree_util.tree_map(lambda a: a + 0, params)
        real(self, prepared)
        self.params = keep              # the step's update is thrown away
        return self

    monkeypatch.setattr(MultiStepTrainable, "fit_prepared", frozen)
    run, _ = drive("resnet50_train_b256", 3)
    assert not run.check.correct
    assert rows(run)["param_change_norm_worst_leaf_gap"]["value"] \
        == pytest.approx(1.0, abs=1e-3)


def test_training_on_part_of_the_batch_is_not_correct(monkeypatch):
    """The fault the loss's limit is held against: every step trains on the
    first half of its rows twice over and never sees the second half."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.multistep import MultiStepTrainable
    real = MultiStepTrainable.fit_prepared

    def half(self, prepared):
        mode, stacked, K = prepared

        def cut(a):
            n = a.shape[1] // 2
            return jnp.concatenate([a[:, :n], a[:, :n]], axis=1)

        return real(self, (mode, jax.tree_util.tree_map(cut, stacked), K))

    monkeypatch.setattr(MultiStepTrainable, "fit_prepared", half)
    run, _ = drive("resnet50_train_b256", 5)
    assert not run.check.correct
    assert not rows(run)["loss_rel_gap_first_execution"]["ok"]


def test_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    real = DecodeEngine.read_ids     # where the loop reads a step's tokens
    monkeypatch.setattr(DecodeEngine, "read_ids", lambda self, ids:
                        (real(self, ids) + 1) % self.vocab)
    run, _ = drive("opt350m_batch_decode", 4, seconds=2.0)
    assert not run.check.correct
    assert not rows(run)["served_token_logit_gap_max"]["ok"]


def test_sound_serving_run_is_correct_and_rehearsal_prints_no_metrics(
        capsys):
    run, out = drive("opt350m_batch_decode", 2**31 + 9, seconds=2.0)
    assert run.check.correct, run.check.rows
    assert harness.report(run, out) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert "metrics" not in last and "rehearsal" in last


def test_serving_control_in_float8_is_not_correct():
    """The reference in float8, put in the program's place at the same
    prompts and tokens, reads above the limit that the sound run stays
    under (rehearsal size, the rehearsal file's own limit)."""
    run, _ = drive("opt350m_batch_decode", 11, control=1, seconds=2.0)
    row = rows(run)["served_token_logit_gap_max"]
    assert row["ok"]
    control = {r["check"]: r["value"] for r in run.control_rows}
    assert control["served_token_logit_gap_max"] > row["limit"]


def test_training_control_in_float8_is_not_correct():
    """The reference with its activations and products in float8, put in the
    program's place, fails the momentum comparison that the bf16 program
    passes (rehearsal size and the rehearsal file's limit: CPU readings over
    four seeds were 0.09-0.11 sound, 0.36-0.40 control)."""
    run, _ = drive("resnet50_train_b256", 12, control=1)
    row = rows(run)["momentum_rel_diff"]
    assert row["ok"] and run.check.correct, run.check.rows
    control = {r["check"]: r["value"] for r in run.control_rows}
    assert control["momentum_rel_diff"] > row["limit"]
