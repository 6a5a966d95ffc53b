"""The `granite4hmicro_chat_decode` cell at rehearsal size on the CPU: a sound
run is correct, a served token altered where it is produced is not, the
control's arithmetic moves the reference's logits by far more than the
configuration's own, and the roofline reader reads a slice as PERF.md section
3 says. (The wiring of the cell's files is `test_benchmark.py`'s, which finds
them by name.) Run by hand:  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import run as harness  # noqa: E402
from benchmarks.run import load_reader  # noqa: E402
from benchmarks.tests.test_correct import drive, rows  # noqa: E402

CELL = "granite4hmicro_chat_decode"


def test_sound_run_is_correct(capsys):
    run, out = drive(CELL, 2**31 + 21, seconds=2.0)
    assert run.check.correct, run.check.rows
    assert out["failed"] == 0 and out["attempted"] > 0
    assert harness.report(run, out) == 0
    assert "metrics" not in capsys.readouterr().out.strip().splitlines()[-1]


def test_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    real = DecodeEngine.read_ids     # where the loop reads a step's tokens
    monkeypatch.setattr(DecodeEngine, "read_ids", lambda self, ids:
                        (real(self, ids) + 1) % self.vocab)
    run, _ = drive(CELL, 6, seconds=2.0)
    assert not run.check.correct
    assert not rows(run)["served_token_logit_gap_max"]["ok"]


def test_float8_moves_the_reference_far_more_than_bfloat16():
    """At rehearsal size the float8 control cannot be held against the
    cell's limit the way `opt350m`'s is: with the tied matrix times 12 on a
    shallow residual stream, the input token's own logit leads every
    position by a wide margin (0.2-0.3 against a logit spread of 0.03), so
    float8 and the sound program pick the same tokens and both read 0.0.
    The separation is a property of the full depth and is measured on the
    chip (PERF.md section 2). What holds at any size: the control's
    arithmetic moves the logits several times as far as the configuration's
    own rounding does."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import granite4_h_micro as ref
    vocab, d, layers, heads = 512, 128, 6, 8
    params = ref.init_params(jax.random.PRNGKey(2), vocab, d, layers, 4 * d)
    ids = jnp.asarray(np.random.RandomState(3).randint(0, vocab, 96))
    f32, bf16, f8 = (np.asarray(ref.logits(params, ids, heads=heads,
                                           layers=layers, dtype=dt))
                     for dt in ("float32", "bfloat16", "float8"))
    assert np.abs(f8 - f32).max() > 3 * np.abs(bf16 - f32).max()


def test_ssm_step_roofline_reader():
    reader = load_reader("ssm_step_roofline_pct")
    cell = {"serve": {"slots": 64}}
    config = {"reference": "granite4_h_micro", "args": {"d_model": 2048}}
    peak = {"hbm_bytes_per_s": 819e9}
    # 15 executions of the step in the slice; two call sites among the top
    # ten, 0.40 and 0.44 ms a call: 271.6 MB / 819 GB/s = 0.3317 ms -> 79 %
    trace = {"programs": [["jit_step_fn", 15.0, 0.45],
                          ["jit_prefill_fn", 4.0, 0.05]],
             "device_ops": [["ssm_step.3", 15 * 0.40e-3], ["sort.5", 0.1],
                            ["ssm_step.17", 15 * 0.44e-3]]}
    obs = {"trace": trace, "cell": cell, "config": config, "peak": peak}
    assert reader.read(obs) == pytest.approx(100 * 0.33168 / 0.42, rel=1e-3)
    # a program without the kernel (the parent, another model): nothing
    trace["device_ops"] = [["sort.5", 0.1], ["fusion.1", 0.2]]
    assert reader.read(obs) is None
    assert reader.read(dict(obs, trace=None)) is None
