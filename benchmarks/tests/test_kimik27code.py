"""The `kimik27code_code_decode` cell at rehearsal size on the CPU — two
heads of the published widths behind the server, the scheduler and the
interpreted kernels —: a sound run is correct and its float8 control is not;
a fault planted in the new mathematics where the program computes it (the
rotary turn left off the cached key; the scores' m^2 dropped) is not, nor is
a served token altered where it is produced; the control's arithmetic moves
the reference's logits by more than the configuration's own; and the two
readers this cell brought read their own kernels' names, the prefill's by
the bucket in it, and nothing where there is nothing to read. (The wiring of
the cell's files is tests/test_benchmark_wiring.py's, which finds them by
name; the reference's constants and counts are tied to the configuration
file in tests/test_kimi_k2.py.)
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

CELL = "kimik27code_code_decode"


def test_sound_run_is_correct_and_the_float8_control_is_not(capsys):
    """The logits answer to every layer at rehearsal size too (an untied
    head, nothing multiplying the embedding), so the control can be held
    against the rehearsal's limit: sound 0.000, float8 0.16, limit 0.05."""
    run, out = drive(CELL, 2**31 + 52, control=1, seconds=6.0)
    assert run.check.correct, run.check.rows
    assert out["failed"] == 0 and out["attempted"] > 0
    limit = rows(run)["served_token_logit_gap_max"]["limit"]
    assert run.control_rows[0]["value"] > limit
    assert rows(run)["served_token_logit_gap_max"]["value"] < limit / 2
    assert harness.report(run, out) == 0
    assert "metrics" not in capsys.readouterr().out.strip().splitlines()[-1]


@pytest.mark.parametrize("fault", ["k_pe_not_turned", "mscale_dropped",
                                   "token_altered"])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    """Each in the PROGRAM, where the served path computes it: the cached
    key's rotary part left unturned (prefill and step alike, so the cache is
    consistent with itself and only the mathematics is wrong), the softmax
    scale without YaRN's m^2, and a served token changed where the loop
    reads a step's ids."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.nn.layers.convolution import rms_norm
    from deeplearning4j_tpu.nn.layers.mla import LatentAttentionLayerModule
    if fault == "k_pe_not_turned":
        def latent(self, params, x, pos):
            R = self.dims()[1]
            lat, k_pe = jnp.split(x @ params["Wkv_a"], [R], axis=-1)
            return rms_norm(lat, params["kv_norm"], self.conf.eps), k_pe
        monkeypatch.setattr(LatentAttentionLayerModule, "latent", latent)
    elif fault == "mscale_dropped":
        monkeypatch.setattr(
            LatentAttentionLayerModule, "scale", lambda self: float(
                self.dims()[2] + self.dims()[3]) ** -0.5)
    else:
        real = DecodeEngine.read_ids
        monkeypatch.setattr(DecodeEngine, "read_ids", lambda self, ids:
                            (real(self, ids) + 1) % self.vocab)
    run, _ = drive(CELL, 6, seconds=4.0)
    assert not run.check.correct
    assert not rows(run)["served_token_logit_gap_max"]["ok"]


def test_float8_and_another_share_move_the_reference():
    """float8 in every matrix product moves the logits more than twice as
    far as bfloat16 everywhere does (held against the cell's limit on the
    chip: PERF.md section 2), and the routed part is in them: the reference
    told it holds experts 12..23 where the weights are 0..11's gives other
    logits. A sequence padded inside `logits` reads as the unpadded one."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import kimi_k27_code as ref
    vocab, d, layers, heads = 512, 224, 3, 2
    params = ref.init_params(jax.random.PRNGKey(2), vocab, d, layers,
                             d * 18432 / 7168)
    assert params["b1_moe"]["W1"].shape == (12, d, 4096)
    assert params["b1_moe"]["Wg"].shape == (d, 384)
    assert params["b0_mlp"]["W_in"].shape == (d, 2 * 576)
    assert params["b1_mlp"]["W_in"].shape == (d, 4096)
    assert params["b2_mla"]["Wq_b"].shape == (1536, 2 * 192)
    assert "b0_moe" not in params and "Wgate" not in params["b0_mla"]
    ids = jnp.asarray(np.random.RandomState(3).randint(0, vocab, 96))
    f32, bf16, f8 = (np.asarray(ref.logits(params, ids, heads=heads,
                                           layers=layers, dtype=dt))
                     for dt in ("float32", "bfloat16", "float8"))
    assert np.abs(f8 - f32).max() > 2 * np.abs(bf16 - f32).max()
    other = np.asarray(ref.logits(params, ids, heads=heads, layers=layers,
                                  first_expert=12))
    assert np.abs(other - f32).max() > 1e-4
    ref_pad = ref.PAD_TO
    try:
        ref.PAD_TO = 64                  # 96 -> 128 positions inside
        padded = np.asarray(ref.logits(params, ids, heads=heads,
                                       layers=layers))
    finally:
        ref.PAD_TO = ref_pad
    assert padded.shape == f32.shape
    np.testing.assert_allclose(padded, f32, atol=1e-5)


OBS = {"cell": {"serve": {"slots": 128, "decode_max_len": 6144}},
       "config": {"reference": "kimi_k27_code", "args": {"d_model": 7168}},
       "peak": {"hbm_bytes_per_s": 819e9,
                "flops_per_s": {"bfloat16": 197e12}}}
# 40 steps whole in the slice, 5 latent layers a step; two prefills of 4,096
# and one of 2,048, 5 attention calls each
KERNELS = [["jit_step_fn", "mla_decode", 200, 0.28],
           ["jit_step_fn", "latent_append", 200, 0.008],
           ["jit_step_fn", "expert_gmm_128x1", 160, 0.16],
           ["jit_prefill_fn", "mla_prefill_4096", 10, 0.040],
           ["jit_prefill_fn", "mla_prefill_2048", 5, 0.006],
           ["jit_prefill_fn", "expert_gmm_1x4096", 8, 0.03]]
COVER = [["jit_step_fn", 40, 0.7, 0.7], ["jit_prefill_fn", 3, 0.4, 0.4]]


def test_the_two_new_readers_read_their_own_kernels_names():
    from benchmarks.reference import kimi_k27_code as ref
    per_step = load_reader("mla_kernels_ms_per_step")
    prefill = load_reader("mla_prefill_roofline_pct")
    decode = load_reader("mla_decode_roofline_pct")
    assert (per_step.UNIT, per_step.LAYER, per_step.MOVES, per_step.SOURCE) \
        == ("ms", "kernels", "serve_tokens_per_s", "device_trace")
    assert (prefill.UNIT, prefill.LAYER, prefill.MOVES, prefill.SOURCE) \
        == ("%", "kernels", "serve_tokens_per_s", "device_trace")
    obs = dict(OBS, trace={"kernels": KERNELS, "kernels_cover": COVER},
               before={"decode_kv_live_pct": 52.0},
               after={"decode_kv_live_pct": 50.0})
    # both kernels' self time over the step's whole events
    assert per_step.read(obs) == pytest.approx((0.28 + 0.008) / 40 * 1e3)
    # the buckets apart: operations by the bucket in the name
    flops = 10 * ref.mla_prefill_flops(4096) + 5 * ref.mla_prefill_flops(2048)
    assert prefill.read(obs) == pytest.approx(
        100 * flops / 0.046 / 197e12, rel=1e-9)
    assert 30 < prefill.read(obs) < 50
    # the accepted reader, on this configuration's byte count: the smaller
    # gauge's live tokens, 1.4 ms a call
    live = 0.50 * 128 * 6144
    assert decode.read(obs) == pytest.approx(
        100 * ref.mla_decode_bytes(128, live, 7168) / 819e9 / 1.4e-3,
        rel=1e-9)
    assert decode.read(obs) < 100
    # a slice with no whole prefill event; a program without the kernels
    # (the parent's, and every other cell's); no trace: nothing, no error
    steps_only = {"kernels": KERNELS[:3], "kernels_cover": COVER[:1]}
    assert prefill.read(dict(obs, trace=steps_only)) is None
    assert per_step.read(dict(obs, trace=steps_only)) == per_step.read(obs)
    other = {"kernels": [["jit_step_fn", "flash_decode", 100, 0.1],
                         ["jit_prefill_fn", "flash_fwd", 28, 0.1],
                         ["jit_prefill_fn", "mla_prefill_", 1, 0.1]],
             "kernels_cover": COVER}
    assert per_step.read(dict(obs, trace=other)) is None
    assert prefill.read(dict(obs, trace=other)) is None
    assert per_step.read(dict(obs, trace=None)) is None
    assert prefill.read(dict(obs, trace=None)) is None
    assert per_step.read(dict(obs, trace={"kernels": []})) is None
    # a reference without the count (a sibling's configuration)
    ling = {"reference": "ling3_flash", "args": {"d_model": 2560}}
    assert prefill.read(dict(obs, config=ling)) is None


def test_the_cell_is_the_issues_traffic_and_lists_its_readers():
    cell = harness.load_json(ROOT / "benchmarks" / "workloads"
                             / f"{CELL}.json")
    serve = cell["serve"]
    assert (serve["loop"], serve["clients"], serve["slots"],
            serve["decode_max_len"]) == ("closed", 128, 128, 6144)
    assert serve["mix"] == {
        "cycle": 32,
        "prompt_tokens": {"kind": "log_uniform", "min": 1024, "max": 4096},
        "new_tokens": {"kind": "log_uniform", "min": 512, "max": 2048}}
    assert (serve["queue_capacity"], serve["requests_drawn"], serve["ramp_s"],
            serve["timeout_s"], serve["check_requests"],
            serve["trace_delay_s"], serve["trace_seconds"]) \
        == (512, 4096, 30.0, 180, 8, 3.0, 1.0)
    assert {"mla_decode_roofline_pct", "mla_kernels_ms_per_step",
            "mla_prefill_roofline_pct", "decode_mfu_pct",
            "device_idle_pct.serve"} <= set(cell["per_layer"])
    # a share from shapes reads high where a held expert gets no row
    assert "expert_gmm_step_roofline_pct" not in cell["per_layer"]
    assert "expert_gmm_roofline_pct" not in cell["per_layer"]
    mix = harness.load_json(ROOT / "benchmarks" / "workloads"
                            / "mellum2_code_decode.json")["serve"]["mix"]
    assert {k: mix[k] for k in ("prompt_tokens", "new_tokens")} \
        == {k: serve["mix"][k] for k in ("prompt_tokens", "new_tokens")}
