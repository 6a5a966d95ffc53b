"""The `mellum2_code_decode` cell at rehearsal size on the CPU — the
configuration's own window of 1,024 behind a capacity of 1,280, so every
prompt fills its window layers' rings and every step wraps them, through the
server, the scheduler and the interpreted kernels —: a sound run is correct
and its float8 control is not, a served token altered where it is produced
is not, the control's arithmetic moves the reference's logits by far more
than the configuration's own and another share of the experts is another
model, the reference's byte counts are the ones the issue and PERF.md
section 4 reckon with, and the two attention readers each read their own
kernel's name and none of the other's. (The wiring of the cell's files is
tests/test_benchmark_wiring.py's, which finds them by name; the reference's
constants are tied to the configuration file in tests/test_mellum.py.)
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

CELL = "mellum2_code_decode"


def test_sound_run_is_correct_and_the_float8_control_is_not(capsys):
    """This model's logits answer to every layer at rehearsal size too (an
    untied head, nothing multiplying the embedding), so the control can be
    held against the rehearsal's limit here: sound 0.000, float8 0.03-0.09
    (three seeds), limit 0.02. The prompts are 1,024-1,200 tokens: the
    prefill's last 1,024 rows land in the rings at their remainders and the
    checked steps run on wrapped rings."""
    run, out = drive(CELL, 2**31 + 43, control=1, seconds=20.0)
    assert run.check.correct, run.check.rows
    assert out["failed"] == 0 and out["attempted"] > 0
    limit = rows(run)["served_token_logit_gap_max"]["limit"]
    assert run.control_rows[0]["value"] > limit
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
    far as bfloat16 everywhere does (held against the cell's limit on the
    chip: PERF.md section 2), and the routed part is in them: the reference
    told it holds experts 16..31 where the weights are 0..15's gives other
    logits. The window is in them too: 1,024 keys are not 1,100."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import mellum2 as ref
    vocab, d, layers, heads = 512, 144, 4, 2
    params = ref.init_params(jax.random.PRNGKey(2), vocab, d, layers)
    assert params["b0_moe"]["W1"].shape == (16, d, 1792)
    assert params["b0_moe"]["Wg"].shape == (d, 64)
    assert params["b3_attn"]["Wq"].shape == (d, 256)
    assert params["b3_attn"]["Wk"].shape == (d, 128)
    assert "b0_mlp" not in params and "route_bias" not in params["b0_moe"]
    ids = jnp.asarray(np.random.RandomState(3).randint(0, vocab, 96))
    f32, bf16, f8 = (np.asarray(ref.logits(params, ids, heads=heads,
                                           layers=layers, dtype=dt))
                     for dt in ("float32", "bfloat16", "float8"))
    assert np.abs(f8 - f32).max() > 2 * np.abs(bf16 - f32).max()
    other = np.asarray(ref.logits(params, ids, heads=heads, layers=layers,
                                  first_expert=16))
    assert np.abs(other - f32).max() > 1e-4
    long = jnp.asarray(np.random.RandomState(4).randint(0, vocab, 1100))
    whole = np.asarray(ref.logits(params, long, heads=heads, layers=layers))
    ref_window = ref.WINDOW
    try:
        ref.WINDOW = 2048
        unwindowed = np.asarray(ref.logits(params, long, heads=heads,
                                           layers=layers))
    finally:
        ref.WINDOW = ref_window
    np.testing.assert_array_equal(whole[:1024], unwindowed[:1024])
    assert np.abs(whole[1024:] - unwindowed[1024:]).max() > 1e-5


def test_byte_counts_are_the_ones_reckoned_with():
    from benchmarks.reference import mellum2 as ref
    # 2,048 B a position a layer: K and V, 4 heads of 128, bfloat16
    per_position = ref.flash_decode_bytes(0, 1)
    assert per_position == 2048
    # a slot of 6,144: 7 full layers at capacity + 21 rings of 1,024
    slot = 7 * per_position * 6144 + 21 * per_position * 1024
    assert slot == pytest.approx(132.1e6, rel=1e-3)
    assert 48 * slot == pytest.approx(6.34e9, rel=1e-3)
    assert 48 * 28 * per_position * 6144 == pytest.approx(16.9e9, rel=1e-2)
    # a full layer's call: the live tokens' rows, a slot's token rows in,
    # its 32 query rows in and 32 context rows out
    assert ref.flash_decode_bytes(48, 139_200) == 2 * (
        2 * 139_200 * 4 * 128 + 48 * 2 * (4 + 32) * 128)
    # a window layer's: 48 rings of 1,024 — 100.7 MB of rows
    assert ref.window_decode_bytes(48, 48 * 1024) == 2 * (
        2 * 48 * 1024 * 4 * 128 + 48 * 2 * (4 + 32) * 128)
    assert ref.window_decode_bytes(48, 48 * 1024) \
        == pytest.approx(101.5e6, rel=1e-2)
    # 16 experts of 3 x 2304 x 896 bfloat16 (198 MB) + 96 rows in and out
    assert ref.expert_layer_bytes(48) == 2 * (16 * 3 * 2304 * 896
                                              + 2 * 96 * 2304)
    assert ref.expert_layer_bytes(48) == pytest.approx(199.1e6, rel=1e-2)
    assert ref.expert_pairs_per_token() == 2.0
    # the step at the mean live context the issue reckons with (2.9 k)
    parts = ref.decode_step_bytes(48, 48 * 2900)
    assert set(parts) == {"weights", "experts", "kv", "window"}
    assert parts["experts"] == pytest.approx(5.55e9, rel=0.01)
    assert parts["kv"] + parts["window"] == pytest.approx(4.1e9, rel=0.03)
    # attention's own 1.19 GB, the routers 8 MB, the head and — the lookup
    # is one-hot x matrix — the embedding 0.113 GB each
    assert parts["weights"] == pytest.approx(1.19e9 + 0.008e9 + 2 * 0.113e9,
                                             rel=0.01)
    assert sum(parts.values()) == pytest.approx(11.1e9, rel=0.01)
    # P = 3.487 B parameters by the issue's count (the program's tree has
    # 69,120 zero-bias and last-norm leaves more); 1.002 GMAC a token
    layer = 21_233_664 + 147_456 + 4_608 + 16 * 6_193_152
    assert layer == 120_476_160
    assert 28 * layer + 2 * 24576 * 2304 == 3_486_578_688
    assert ref.decode_macs_per_token(24576, 2304, 28) == 28 * (
        21_233_664 + 147_456 + 2 * 6_193_152) + 2304 * 24576
    assert ref.decode_macs_per_token(24576, 2304, 28) \
        == pytest.approx(1.002e9, rel=1e-3)


OBS = {"cell": {"serve": {"slots": 48, "decode_max_len": 6144}},
       "config": {"reference": "mellum2",
                  "args": {"d_model": 2304, "window": 1024}},
       "peak": {"hbm_bytes_per_s": 819e9}}
# a step's 21 window calls and 7 full calls, 20 steps in the slice
KERNELS = [["jit_step_fn", "flash_decode_window", 420, 0.0672],
           ["jit_step_fn", "flash_decode", 140, 0.070],
           ["jit_step_fn", "expert_gmm_48x1", 560, 0.16],
           ["jit_prefill_fn_4096", "flash_fwd", 28, 0.1]]


def test_the_two_attention_readers_each_read_their_own_kernels_name():
    from benchmarks.reference import mellum2 as ref
    window = load_reader("window_decode_roofline_pct")
    full = load_reader("flash_decode_roofline_pct")
    assert (window.UNIT, window.LAYER, window.MOVES, window.SOURCE) == (
        "%", "kernels", "serve_tokens_per_s", "device_trace")
    obs = dict(OBS, trace={"kernels": KERNELS},
               before={"decode_kv_live_pct": 47.0, "decode_active_slots": 48},
               after={"decode_kv_live_pct": 49.0, "decode_active_slots": 47})
    # the smaller gauge: 47 active slots' rings, 0.16 ms a call
    floor = ref.window_decode_bytes(47, 47 * 1024) / 819e9
    assert window.read(obs) == pytest.approx(100 * floor / 0.16e-3, rel=1e-6)
    assert 70 < window.read(obs) < 80
    # the full layers' reader: 47 % of 48 x 6,144 live tokens, 0.5 ms a call
    live = 0.47 * 48 * 6144
    assert full.read(obs) == pytest.approx(
        100 * ref.flash_decode_bytes(48, live) / 819e9 / 0.5e-3, rel=1e-6)
    # neither reads the other's calls: without its own name, nothing
    only_full = {"kernels": [k for k in KERNELS
                             if k[1] != "flash_decode_window"]}
    only_window = {"kernels": [k for k in KERNELS if k[1] != "flash_decode"]}
    assert window.read(dict(obs, trace=only_full)) is None
    assert full.read(dict(obs, trace=only_window)) is None
    assert window.read(dict(obs, trace=only_window)) == window.read(obs)
    assert full.read(dict(obs, trace=only_full)) == full.read(obs)
    # the gauge not read, a configuration without a window (the parent's
    # cells), a reference without the byte count, no trace: nothing, and no
    # error
    assert window.read(dict(obs, after={})) is None
    assert window.read(dict(obs, trace=None)) is None
    solar = {"reference": "solar_open2", "args": {"d_model": 4096}}
    assert window.read(dict(obs, config=solar)) is None
    small = dict(OBS["config"], reference="granite4_h_small")
    assert window.read(dict(obs, config=small)) is None


def test_the_cell_lists_its_readers_and_not_the_two_it_must_not():
    """`decode_attention_kernels_ms_per_step` knows the names `flash_decode`
    and `kv_append` and would pass 7 layers' time off as attention's.
    `expert_gmm_roofline_pct` looks through the slice's ten longest
    operations OF THE PROGRAM WITH MOST DEVICE TIME: in this cell a 0.5 s
    slice that holds two prefills (160-220 ms each) is the prefill's, that
    reader finds no step call site and the result line would lack the
    metric — one traced run of three did (PR 47). The step's largest kernel
    is read by `expert_gmm_step_roofline_pct` instead, which asks for the
    step program's kernels by name."""
    cell = harness.load_json(ROOT / "benchmarks" / "workloads"
                             / f"{CELL}.json")
    assert {"window_decode_roofline_pct", "flash_decode_roofline_pct",
            "expert_gmm_step_roofline_pct", "decode_mfu_pct",
            "device_idle_pct.serve"} <= set(cell["per_layer"])
    assert "decode_attention_kernels_ms_per_step" not in cell["per_layer"]
    assert "expert_gmm_roofline_pct" not in cell["per_layer"]
    serve = cell["serve"]
    assert (serve["clients"], serve["slots"], serve["decode_max_len"]) == (
        48, 48, 6144)
    # ISSUE 47's traffic to the letter: the siblings' grid
    assert serve["mix"]["cycle"] == 256
    assert serve["mix"]["prompt_tokens"]["min"] >= 1024  # the window: full
    gmm = load_reader("expert_gmm_roofline_pct")
    by_step = load_reader("expert_gmm_step_roofline_pct")
    assert (by_step.UNIT, by_step.LAYER, by_step.MOVES, by_step.SOURCE) == (
        "%", "kernels", "serve_tokens_per_s", "device_trace")
    step = [[f"flash_decode.{i}", 0.01178] for i in range(14, 21)] \
        + [["expert_gmm_48x1.55", 0.007636], ["expert_gmm_48x1.40", 0.007635],
           ["expert_gmm_48x1.38", 0.007634]]
    obs = dict(OBS, cell=cell, trace={
        "device_ops": step, "programs": [["jit_step_fn", 27.0, 0.4813]]})
    # 0.2828 ms a call against 199.1 MB / 819 GB/s = 0.2431 ms
    assert gmm.read(obs) == pytest.approx(85.95, rel=1e-3)
    # the two recorded slices of PR 47 (chip runs, seeds 2147485407 and
    # 2147485306): the step's the larger program, then the prefill's
    steps_lead = {
        "device_ops": [["expert_gmm_48x1", 0.109342554]],
        "programs": [["jit_step_fn", 14.0, 0.256380094],
                     ["jit_prefill_fn", 1.0, 0.240082598]],
        "kernels": [["jit_step_fn", "expert_gmm_48x1", 387.0, 0.109342554],
                    ["jit_step_fn", "flash_decode_window", 291.0, 0.0500257],
                    ["jit_prefill_fn", "expert_gmm_1x4096", 28.0, 0.0272595]]}
    prefills_lead = {
        "device_ops": [["fusion.1", 0.116], ["flash_fwd", 0.0856],
                       ["expert_gmm_1x4096", 0.0237]],
        "programs": [["jit_prefill_fn", 2.0, 0.3216],
                     ["jit_step_fn", 9.0, 0.167]],
        "kernels": [["jit_prefill_fn", "fusion", 1148.0, 0.116],
                    ["jit_prefill_fn", "expert_gmm_1x4096", 28.0, 0.0237],
                    ["jit_prefill_fn", "expert_gmm_1x2048", 28.0, 0.0128],
                    ["jit_step_fn", "expert_gmm_48x1", 252.0, 0.070654]]}
    assert gmm.read(dict(obs, trace=prefills_lead)) is None
    assert by_step.read(dict(obs, trace=steps_lead)) == pytest.approx(
        86.03, rel=1e-3)
    assert by_step.read(dict(obs, trace=prefills_lead)) == pytest.approx(
        86.71, rel=1e-3)
    # a prefill's call sites alone, no trace, a reference without the count
    # (the parent's cells): nothing, and no error
    assert by_step.read(dict(obs, trace={
        "kernels": prefills_lead["kernels"][:3]})) is None
    assert by_step.read(dict(obs, trace=None)) is None
    opt = {"reference": "opt350m", "args": {"d_model": 1024}}
    assert by_step.read(dict(obs, trace=steps_lead, config=opt)) is None
