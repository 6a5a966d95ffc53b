"""The `ling3flash_reason_decode` cell at rehearsal size on the CPU: a sound
run is correct and its float8 control is not, a served token altered where
it is produced is not, the control's arithmetic moves the reference's logits
by far more than the configuration's own and another share of the experts is
another model, the
reference's byte counts are the ones PERF.md section 4 reckons with, and the
two roofline readers read a slice as PERF.md section 3 says. (The wiring of
the cell's files is `test_benchmark.py`'s, which finds them by name; the
reference's constants are tied to the configuration file in
tests/test_ling_hybrid.py.)
Run by hand:  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
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

CELL = "ling3flash_reason_decode"


def test_sound_run_is_correct_and_the_float8_control_is_not(capsys):
    """Unlike the two granite cells', this model's logits answer to every
    layer at rehearsal size too (an untied head, nothing multiplying the
    embedding), so the control can be held against the rehearsal's limit
    here: sound 0.006-0.009, float8 0.14-0.16 (two seeds), limit 0.03."""
    run, out = drive(CELL, 2**31 + 41, control=1, seconds=10.0)
    assert run.check.correct, run.check.rows
    assert out["failed"] == 0 and out["attempted"] > 0
    limit = rows(run)["served_token_logit_gap_max"]["limit"]
    assert run.control_rows[0]["value"] > 2 * limit
    assert rows(run)["served_token_logit_gap_max"]["value"] < limit / 2
    assert harness.report(run, out) == 0
    assert "metrics" not in capsys.readouterr().out.strip().splitlines()[-1]


def test_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    real = DecodeEngine.read_ids     # where the loop reads a step's tokens
    monkeypatch.setattr(DecodeEngine, "read_ids", lambda self, ids:
                        (real(self, ids) + 1) % self.vocab)
    run, _ = drive(CELL, 6, seconds=6.0)
    assert not run.check.correct
    assert not rows(run)["served_token_logit_gap_max"]["ok"]


def test_float8_and_another_share_move_the_reference():
    """float8 in every matrix product moves the logits several times as far
    as bfloat16 everywhere does (held against the cell's limit on the chip:
    PERF.md section 2), and the routed part is in them: the reference told
    it holds experts 64..127 where the weights are group 0's gives other
    logits."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import ling3_flash as ref
    vocab, d, layers, heads = 512, 160, 6, 2
    params = ref.init_params(jax.random.PRNGKey(2), vocab, d, layers, d * 2.4)
    assert params["b2_moe"]["W1"].shape == (64, d, 1536)
    assert params["b0_mlp"]["W_out"].shape == (384, d)
    assert "b1_moe" not in params and "b5_mla" in params and "b4_kda" in params
    ids = jnp.asarray(np.random.RandomState(3).randint(0, vocab, 96))
    f32, bf16, f8 = (np.asarray(ref.logits(params, ids, heads=heads,
                                           layers=layers, dtype=dt))
                     for dt in ("float32", "bfloat16", "float8"))
    assert np.abs(f8 - f32).max() > 3 * np.abs(bf16 - f32).max()
    other = np.asarray(ref.logits(params, ids, heads=heads, layers=layers,
                                  first_expert=64))
    assert np.abs(other - f32).max() > 1e-4


def test_byte_counts_are_the_ones_reckoned_with():
    from benchmarks.reference import ling3_flash as ref
    # the state [32, 128, 128] float32 a slot, read and written, + six rows
    assert ref.kda_step_bytes(128) == 128 * 32 * 4 * (2 * 128 * 128 + 6 * 128)
    assert ref.kda_step_bytes(128) == pytest.approx(549.5e6, rel=1e-3)
    # 576 bfloat16 values a live token; a slot's queries in, mixes out
    assert ref.mla_decode_bytes(128, 100_000) == 2 * 100_000 * 576 \
        + 128 * 32 * (2 * 576 + 4 * 512)
    parts = ref.decode_step_bytes(128, 128 * 985)
    assert set(parts) == {"weights", "experts", "kda_state", "conv_tail",
                          "latent"}
    assert parts["kda_state"] == 5 * ref.kda_step_bytes(128)
    assert sum(parts.values()) == pytest.approx(6.76e9, rel=0.01)
    assert 0.40 < parts["kda_state"] / sum(parts.values()) < 0.42
    # 2.08 B parameters, of which the held experts 1.51 B; 0.544 GMAC a token
    assert ref.decode_macs_per_token(19648, 2560, 6, 6144) \
        == pytest.approx(0.544e9, rel=1e-3)


OBS = {"cell": {"serve": {"slots": 128, "decode_max_len": 3072}},
       "config": {"reference": "ling3_flash", "args": {"d_model": 2560}},
       "peak": {"hbm_bytes_per_s": 819e9}}


def test_kda_step_roofline_reader():
    reader = load_reader("kda_step_roofline_pct")
    # 20 whole events of the step, five call sites merged under one name: 100
    # calls in 0.09 s = 0.9 ms a call against 549.45 MB / 819 GB/s = 0.6709
    trace = {"kernels": [["jit_step_fn", "kda_step", 100, 0.09],
                         ["jit_step_fn", "expert_gmm_128x1", 80, 0.07],
                         ["jit_prefill_fn", "kda_step", 7, 0.5]],
             "kernels_cover": [["jit_step_fn", 20, 0.2, 0.2]]}
    obs = dict(OBS, trace=trace, before={}, after={})
    assert reader.read(obs) == pytest.approx(100 * 0.67088 / 0.9, rel=1e-3)
    # a program without the kernel (the parent), a reference without the
    # byte count, no trace: nothing, and no error
    assert reader.read(dict(obs, trace={"kernels": trace["kernels"][1:2]})) \
        is None
    assert reader.read(dict(obs, trace=None)) is None
    small = dict(OBS["config"], reference="granite4_h_small")
    assert reader.read(dict(obs, config=small)) is None


def test_mla_decode_roofline_reader():
    reader = load_reader("mla_decode_roofline_pct")
    trace = {"kernels": [["jit_step_fn", "mla_decode", 20, 0.008]]}
    obs = dict(OBS, trace=trace, before={"decode_kv_live_pct": 30.0},
               after={"decode_kv_live_pct": 34.0})
    # the smaller gauge: 30 % of 128 x 3072 = 117,965 live tokens
    from benchmarks.reference import ling3_flash as ref
    floor = ref.mla_decode_bytes(128, 0.30 * 128 * 3072) / 819e9
    assert reader.read(obs) == pytest.approx(100 * floor / 0.4e-3, rel=1e-6)
    assert 45 < reader.read(obs) < 46
    assert reader.read(dict(obs, after={})) is None       # gauge not read
    assert reader.read(dict(obs, trace={"kernels": []})) is None
    assert reader.read(dict(obs, trace=None)) is None
