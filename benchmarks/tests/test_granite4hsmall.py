"""The `granite4hsmall_chat_decode` cell at rehearsal size on the CPU: a sound
run is correct, a served token altered where it is produced is not, the
reference given another share of the experts is another model, the control's
arithmetic moves the reference's logits by far more than the configuration's
own, and the roofline reader reads a slice as PERF.md section 3 says. (The
wiring of the cell's files is `test_benchmark.py`'s, which finds them by
name; the reference's constants are tied to the configuration file in
tests/test_moe.py.) Run by hand:  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
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

CELL = "granite4hsmall_chat_decode"


def test_sound_run_is_correct(capsys):
    run, out = drive(CELL, 2**31 + 33, seconds=4.0)
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


def test_float8_and_another_share_move_the_reference():
    """As for the sibling cell, at rehearsal size the input token's own
    logit leads every position, so the float8 control is held against the
    limit on the chip (PERF.md section 2). What holds at any size: float8
    moves the logits several times as far as bfloat16 does, and the routed
    part is in them — the reference told it holds experts 18..35 where the
    weights are experts 0..17's gives other logits (by little at d_model
    128, where an expert's output is a hundredth of the shared one's; at
    size it is a fifth, and tests/test_moe.py weighs it up)."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import granite4_h_small as ref
    vocab, d, layers, heads = 512, 128, 6, 8
    params = ref.init_params(jax.random.PRNGKey(2), vocab, d, layers,
                             d * 0.375)
    assert params["b0_moe"]["W1"].shape == (18, d, 1536)
    assert params["b0_mlp"]["W_out"].shape == (48, d)
    ids = jnp.asarray(np.random.RandomState(3).randint(0, vocab, 96))
    f32, bf16, f8 = (np.asarray(ref.logits(params, ids, heads=heads,
                                           layers=layers, dtype=dt))
                     for dt in ("float32", "bfloat16", "float8"))
    assert np.abs(f8 - f32).max() > 3 * np.abs(bf16 - f32).max()
    other = np.asarray(ref.logits(params, ids, heads=heads, layers=layers,
                                  first_expert=18))
    assert np.abs(other - f32).max() > 1e-4


def test_expert_gmm_roofline_reader():
    reader = load_reader("expert_gmm_roofline_pct")
    cell = {"serve": {"slots": 32}}
    config = {"reference": "granite4_h_small", "args": {"d_model": 4096}}
    peak = {"hbm_bytes_per_s": 819e9}
    # 20 executions of the step in the slice; three of its call sites among
    # the top ten at 0.50, 0.52 and 0.54 ms a call; a prefill's call site of
    # the same kernel and a 320-slot one are not the step's:
    # (339.7 + 1.3) MB / 819 GB/s = 0.4164 ms -> 80.1 %
    trace = {"programs": [["jit_step_fn", 20.0, 0.4],
                          ["jit_prefill_fn", 5.0, 0.06]],
             "device_ops": [["expert_gmm_32x1.3", 20 * 0.50e-3],
                            ["ssm_step.5", 0.008],
                            ["expert_gmm_1x256.3", 5 * 0.9e-3],
                            ["expert_gmm_32x1.11", 20 * 0.52e-3],
                            ["expert_gmm_32x1", 20 * 0.54e-3],
                            ["expert_gmm_32x10.2", 0.5]]}
    obs = {"trace": trace, "cell": cell, "config": config, "peak": peak}
    assert reader.read(obs) == pytest.approx(100 * 0.41642 / 0.52, rel=1e-3)
    # a program without the kernel (the parent, a model without experts) or
    # a reference without the byte count: nothing, and no error
    trace["device_ops"] = [["ssm_step.5", 0.1], ["fusion.1", 0.2]]
    assert reader.read(obs) is None
    assert reader.read(dict(obs, trace=None)) is None
    micro = dict(config, reference="granite4_h_micro")
    trace["device_ops"] = [["expert_gmm_32x1.3", 0.01]]
    assert reader.read(dict(obs, config=micro)) is None
