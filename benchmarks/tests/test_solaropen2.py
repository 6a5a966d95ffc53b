"""The `solaropen2_reason_decode` cell at rehearsal size on the CPU: a sound
run is correct and its float8 control is not, a served token altered where
it is produced is not, the control's arithmetic moves the reference's logits
by far more than the configuration's own and another share of the experts is
another model, the reference's byte counts are the ones PERF.md section 4
reckons with, and the new roofline reader reads a slice as PERF.md section 3
says. (The wiring of the cell's files is `test_benchmark.py`'s, which finds
them by name; the reference's constants are tied to the configuration file
in tests/test_solar_hybrid.py.)
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

CELL = "solaropen2_reason_decode"


def test_sound_run_is_correct_and_the_float8_control_is_not(capsys):
    """As `ling3_flash`'s, this model's logits answer to every layer at
    rehearsal size too (an untied head, nothing multiplying the embedding),
    so the control can be held against the rehearsal's limit here: sound
    0.000-0.005, float8 0.10-0.14 (two seeds), limit 0.03."""
    run, out = drive(CELL, 2**31 + 43, control=1, seconds=20.0)
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
    """float8 in every matrix product moves the logits more than twice as
    far as bfloat16 everywhere — the delta-rule state too, which the
    configuration keeps float32 — does (held against the cell's limit on the chip:
    PERF.md section 2), and the routed part is in them: the reference told
    it holds experts 40..79 where the weights are 0..39's gives other
    logits."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import solar_open2 as ref
    vocab, d, layers, heads = 512, 128, 4, 2
    params = ref.init_params(jax.random.PRNGKey(2), vocab, d, layers)
    assert params["b0_moe"]["W1"].shape == (40, d, 2560)
    assert params["b0_mlp"]["W_out"].shape == (1280, d)
    assert params["b1_kda"]["W_in"].shape == (d, 3 * 256 + 2 * 128)
    assert params["b0_attn"]["Wk"].shape == (d, 128)
    assert "b0_kda" not in params and "b1_attn" not in params
    ids = jnp.asarray(np.random.RandomState(3).randint(0, vocab, 96))
    f32, bf16, f8 = (np.asarray(ref.logits(params, ids, heads=heads,
                                           layers=layers, dtype=dt))
                     for dt in ("float32", "bfloat16", "float8"))
    assert np.abs(f8 - f32).max() > 2 * np.abs(bf16 - f32).max()
    other = np.asarray(ref.logits(params, ids, heads=heads, layers=layers,
                                  first_expert=40))
    assert np.abs(other - f32).max() > 1e-4


def test_byte_counts_are_the_ones_reckoned_with():
    from benchmarks.reference import solar_open2 as ref
    # the state [64, 128, 128] float32 a slot, read and written, + six rows
    assert ref.kda_step_bytes(192) == 192 * 64 * 4 * (2 * 128 * 128 + 6 * 128)
    assert ref.kda_step_bytes(192) == pytest.approx(1.648e9, rel=1e-3)
    # 40 experts of 3 x 4096 x 1280 bfloat16 + 192 rows in and out
    assert ref.expert_layer_bytes(192) == 2 * (40 * 3 * 4096 * 1280
                                               + 2 * 192 * 4096)
    assert ref.expert_layer_bytes(192) == pytest.approx(1.2614e9, rel=1e-3)
    # 2 x 8 x 128 bfloat16 values a live token; a slot's token rows, queries
    # in and contexts out
    assert ref.flash_decode_bytes(192, 300_000) == 2 * (
        2 * 300_000 * 8 * 128 + 192 * 2 * (8 + 64) * 128)
    parts = ref.decode_step_bytes(192, 192 * 1500)
    assert set(parts) == {"weights", "experts", "kda_state", "conv_tail",
                          "kv"}
    assert parts["kda_state"] == 3 * ref.kda_step_bytes(192)
    assert parts["kda_state"] == pytest.approx(4.94e9, rel=0.01)
    assert parts["experts"] == pytest.approx(5.01e9, rel=0.01)
    assert parts["weights"] == pytest.approx(1.58e9, rel=0.01)
    assert parts["kv"] == pytest.approx(1.19e9, rel=0.01)
    assert sum(parts.values()) == pytest.approx(12.8e9, rel=0.01)
    # P = 3.31 B parameters; 0.754 GMAC a token
    attn, kda = ref._mixer_weights(4096)
    held = 4 * (40 * 3 * 4096 * 1280 + 3 * 4096 * 1280 + 4096 * 320 + 320)
    assert 2 * 24576 * 4096 + attn + 3 * kda + held + 9 * 4096 \
        == pytest.approx(3.31e9, rel=2e-3)
    assert ref.decode_macs_per_token(24576, 4096, 4) \
        == pytest.approx(0.754e9, rel=1e-3)


OBS = {"cell": {"serve": {"slots": 192, "decode_max_len": 4096}},
       "config": {"reference": "solar_open2", "args": {"d_model": 4096}},
       "peak": {"hbm_bytes_per_s": 819e9}}


def test_flash_decode_roofline_reader():
    reader = load_reader("flash_decode_roofline_pct")
    trace = {"kernels": [["jit_step_fn", "flash_decode", 20, 0.05],
                         ["jit_step_fn", "kda_step", 60, 0.14],
                         ["jit_prefill_fn", "flash_fwd", 7, 0.5]]}
    obs = dict(OBS, trace=trace, before={"decode_kv_live_pct": 36.0},
               after={"decode_kv_live_pct": 38.0})
    # the smaller gauge: 36 % of 192 x 4096 = 283,116 live tokens
    from benchmarks.reference import solar_open2 as ref
    floor = ref.flash_decode_bytes(192, 0.36 * 192 * 4096) / 819e9
    assert reader.read(obs) == pytest.approx(100 * floor / 2.5e-3, rel=1e-6)
    assert 56 < reader.read(obs) < 58
    # the gauge not read, a program without the kernel (the parent on a cell
    # it cannot run), a reference without the byte count, no trace: nothing,
    # and no error
    assert reader.read(dict(obs, after={})) is None
    assert reader.read(dict(obs, trace={"kernels": trace["kernels"][1:]})) \
        is None
    assert reader.read(dict(obs, trace=None)) is None
    small = dict(OBS["config"], reference="granite4_h_small")
    assert reader.read(dict(obs, config=small)) is None


def test_the_kda_reader_reads_this_cells_reference_and_the_gmm_one_is_not_listed():
    """`kda_step_roofline_pct` finds its byte count in this configuration's
    reference under the name it asks for. `expert_gmm_roofline_pct` would
    too (`expert_layer_bytes`: all 40 held experts), and the cell does NOT
    list it: at the seeded weights the router's load is uneven enough that
    some held experts get no row in a step and are skipped, so the count from
    shapes read 102.8 in the cell's first traced run (PERF.md section 6; the
    honest count needs the group sizes out of the step, ROADMAP R-A2 (6))."""
    kda = load_reader("kda_step_roofline_pct")
    trace = {"kernels": [["jit_step_fn", "kda_step", 60, 0.15]]}
    obs = dict(OBS, trace=trace, before={}, after={})
    # 2.5 ms a call against 1.648 GB / 819 GB/s = 2.012 ms
    assert kda.read(obs) == pytest.approx(100 * 2.0121 / 2.5, rel=1e-3)
    cell = harness.load_json(ROOT / "benchmarks" / "workloads"
                             / f"{CELL}.json")
    assert "expert_gmm_roofline_pct" not in cell["per_layer"]
    assert {"kda_step_roofline_pct", "flash_decode_roofline_pct",
            "decode_attention_kernels_ms_per_step", "decode_mfu_pct",
            "device_idle_pct.serve"} <= set(cell["per_layer"])
