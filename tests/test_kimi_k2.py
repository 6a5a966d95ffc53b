"""The `kimi_k27_code` configuration's model at tiny widths, seeded, on the
CPU: `kimi_k2_lm` (latent attention in every layer — compressed queries, YaRN
on the rotary channels with the scores times m^2, no output gate — a leading
dense layer, then 12 of 384 sigmoid-routed experts beside a shared one)
through `output()` and through `DecodeEngine` — prefill, plain and blockwise,
then absorbed steps, slab and paged, jnp and kernels — against
benchmarks/reference/kimi_k27_code.py's one-pass logits; the three options
the latent layer gained, one by one; the blockwise prefill kernel against the
plain form; and `ling3_flash`'s latent layer, built as before, bit for bit.

Tolerance, with its reason:
- LOGP (2e-5 on log-probabilities, float32 parameters): the program and the
  reference order their float32 sums differently (blockwise and absorbed
  against one softmax over plain keys, rows sorted by expert against the
  masked sum) and both take cos and sin of the same float32 angles; measured
  2e-6. Leaving out m^2, the YaRN blend, the query latent's norm or the turn
  of k_pe, or computing the router or the softmax in bfloat16, moves the
  same numbers by more than a hundred times that
  (`test_a_piece_left_out_or_in_bfloat16_fails_the_tolerance`).
- LAYER (1e-5 of the output's largest magnitude, float32): one layer's
  output with its weights drawn several times wider, so that the scores
  spread and the softmax is sharp: outputs reach 10 and are sums of 256
  products that cancel, so the error is a row's, not an element's; measured
  8e-6 of the largest. A piece left out moves them by 1e-3 and more.
"""
import importlib
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import kimi_k27_code as ref
from deeplearning4j_tpu.decode.engine import DecodeEngine
from deeplearning4j_tpu.nn.conf.layers import (LatentAttentionLayer,
                                               MixtureOfExpertsLayer)
from deeplearning4j_tpu.nn.layers.convolution import rms_norm
from deeplearning4j_tpu.nn.layers.feedforward import \
    MixtureOfExpertsLayerModule
from deeplearning4j_tpu.nn.layers.mla import (LatentAttentionLayerModule,
                                              yarn_factors)
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.zoo.models import kimi_k2_lm

# the modules, not the functions of the same names the package re-exports
mla = importlib.import_module("deeplearning4j_tpu.nn.layers.mla")
mp = importlib.import_module("deeplearning4j_tpu.kernels.mla_prefill")

LOGP, LAYER = 2e-5, 1e-5
VOCAB, D_MODEL, LAYERS, HEADS = 96, 224, 3, 2
CONFIG = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
                     / "configs" / "kimi_k27_code.json").read_text())
YARN = CONFIG["args"]["yarn"]


@pytest.fixture
def blockwise(monkeypatch):
    """Every sequence attends blockwise, in blocks small enough that a tiny
    one is several (the layer forms scores whole up to 128 MiB)."""
    monkeypatch.setattr(mla, "PLAIN_SCORE_BYTES", 0)
    monkeypatch.setattr(mp, "_BLOCK_Q", 16)
    monkeypatch.setattr(mp, "_BLOCK_K", 8)
    monkeypatch.setattr(mp, "_REFERENCE_BLOCK_Q", 16)


# ---------------------------------------------------- configuration's file
def test_reference_constants_are_the_configurations():
    a = CONFIG["args"]
    assert ref.heads_of(a["d_model"]) == a["n_heads"] \
        == CONFIG["num_attention_heads"]
    assert a["d_model"] * a["ffn_mult"] == CONFIG["intermediate_size"]
    for const, arg, key in [
            ("Q_LORA_RANK", "q_lora_rank", "q_lora_rank"),
            ("KV_LORA_RANK", "kv_lora_rank", "kv_lora_rank"),
            ("QK_NOPE_HEAD_DIM", "qk_nope_head_dim", "qk_nope_head_dim"),
            ("QK_ROPE_HEAD_DIM", "qk_rope_head_dim", "qk_rope_head_dim"),
            ("V_HEAD_DIM", "v_head_dim", "v_head_dim"),
            ("ROPE_THETA", "rope_theta", "rope_theta"),
            ("FIRST_K_DENSE", "first_k_dense", "first_k_dense_replace"),
            ("EXPERTS_PER_TOKEN", "experts_per_token", "num_experts_per_tok"),
            ("ROUTED_SCALING", "routed_scaling", "routed_scaling_factor"),
            ("EXPERT_HIDDEN", "expert_hidden", "moe_intermediate_size"),
            ("SHARED_HIDDEN", "shared_hidden", "moe_intermediate_size"),
            ("RMS_EPS", "rms_norm_eps", "rms_norm_eps")]:
        assert getattr(ref, const) == a[arg] == CONFIG[key], const
    assert ref.N_EXPERTS == a["n_experts"] \
        == CONFIG["published"]["n_routed_experts"]
    assert ref.EXPERTS_HELD == a["experts_held"] == CONFIG["n_routed_experts"]
    assert ref.FIRST_EXPERT == a["first_expert"]
    scaling = CONFIG["rope_scaling"]
    assert {k: scaling[k] for k in YARN} == YARN
    assert (ref.YARN_FACTOR, ref.YARN_ORIGINAL, ref.YARN_BETA_FAST,
            ref.YARN_BETA_SLOW, ref.YARN_MSCALE, ref.YARN_MSCALE_ALL_DIM) \
        == tuple(YARN[k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim"))
    assert set(CONFIG["reduced"]) == set(CONFIG["published"])


def test_yarn_is_the_issues_arithmetic():
    """Channels 8 .. 20 of 32, cos and sin times 1, the scores times
    (0.1 ln 64 + 1)^2 = 2.00474."""
    assert ref.yarn_range() == (8, 20)
    on_cos, on_scores = yarn_factors(YARN)
    assert on_cos == 1.0 and abs(on_scores - 2.00474) < 1e-5
    assert abs(ref.yarn_mscale(1.0) - 1.41589) < 1e-5
    f, a = ref.rope_table()
    e, _ = ref.rope_table(ramped=False)
    assert a == 1.0
    np.testing.assert_array_equal(f[:9], e[:9])             # theta's own
    np.testing.assert_allclose(f[20:], e[20:] / 64, rtol=1e-6)
    assert np.all((f[9:20] < e[9:20]) & (f[9:20] > e[9:20] / 64))


# ---------------------------------------------------- the model, end to end
def place(net, params):
    assert {k: sorted(v) for k, v in params.items()} \
        == {k: sorted(v) for k, v in net.params.items()}
    net.params = {n: {k: jnp.asarray(params[n][k], old.dtype)
                      for k, old in leaves.items()}
                  for n, leaves in net.params.items()}


def log_softmax(z):
    z = np.asarray(z, np.float64)
    return z - np.log(np.sum(np.exp(z), axis=-1, keepdims=True))


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(jax.random.PRNGKey(3), VOCAB, D_MODEL, LAYERS,
                           D_MODEL * CONFIG["args"]["ffn_mult"])


def tiny(weights, **over):
    """The dense layer and two expert layers at d_model 224: two heads of
    the published widths (query latent 1,536, key/value latent 512, 128 | 64
    against 128), 384 routed experts of which 0-11 are held."""
    net = kimi_k2_lm(vocab_size=VOCAB, d_model=D_MODEL, n_layers=LAYERS,
                     n_heads=HEADS, yarn=YARN, experts_held=ref.EXPERTS_HELD,
                     **over).init()
    place(net, weights)
    return net


def reference_logp(weights, ids, **how):
    return log_softmax(ref.logits(weights, jnp.asarray(ids), heads=HEADS,
                                  layers=LAYERS, **how))


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernels"])
@pytest.mark.parametrize("form", ["plain", "blockwise"])
def test_output_is_the_references_logits(weights, use_pallas, form, request):
    if form == "blockwise":
        request.getfixturevalue("blockwise")
    net = tiny(weights, use_pallas=use_pallas)
    confs = [net.conf.vertices[f"b{i}_mla"].layer_conf for i in range(LAYERS)]
    assert all(c.q_lora_rank == 1536 and c.rope_yarn == YARN
               and not c.output_gate for c in confs)
    assert "b0_moe" not in net.params and "b1_moe" in net.params
    ids = np.random.RandomState(0).randint(0, VOCAB, 48)
    want = reference_logp(weights, ids)
    probs = np.asarray(net.output(np.eye(VOCAB, dtype=np.float32)[ids][None]))
    np.testing.assert_allclose(np.log(probs[0]), want, atol=LOGP, rtol=0)
    other = reference_logp(weights, ids, first_expert=12)
    assert np.abs(other - want).max() > 1e-3      # the share is in them


_ENGINES = {}


def engine(weights, paged, use_pallas):
    """One engine a (layout, path), shared by the prompts: its executables
    compile once."""
    key = (paged, use_pallas)
    if key not in _ENGINES:
        _ENGINES[key] = DecodeEngine(
            tiny(weights, use_pallas=use_pallas), slots=2, max_len=64,
            **({"paged": True, "block_size": 8} if paged else {}))
    return _ENGINES[key]


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernels"])
@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("n_prompt", [5, 16, 33],
                         ids=["short", "bucket", "longer"])
def test_prefill_then_steps_are_the_references_one_pass(weights, paged,
                                                       n_prompt, use_pallas):
    """The cache is one latent row a token a layer, `[slots, capacity, 640]`
    (under a paged engine still a slab): a prefill in the plain form writes
    the prompt's rows, then 11 absorbed steps — each row of probabilities is
    the reference's at that position. With `use_pallas` through
    `latent_append`, `mla_decode` and `expert_gmm`, interpreted."""
    eng = engine(weights, paged, use_pallas)
    assert [e["latent"].shape for e in eng._entries.values()] \
        == [(2, 64, 640)] * LAYERS
    ids = list(np.random.RandomState(n_prompt).randint(0, VOCAB, 48))
    want = reference_logp(weights, ids)
    cache = eng.init_cache()
    cache, _, _ = eng.prefill(cache, 0, list(range(1, 24)))   # a reused slot
    cache, _, probs = eng.prefill(cache, 0, ids[:n_prompt])
    rows = [np.asarray(eng.read_probs(probs))]
    for t in range(n_prompt, n_prompt + 11):
        cache, _, probs = eng.step(cache, np.asarray([ids[t], 0], np.int32))
        rows.append(np.asarray(eng.read_probs(probs[0])))
    np.testing.assert_allclose(np.log(np.stack(rows)),
                               want[n_prompt - 1:n_prompt + 11], atol=LOGP,
                               rtol=0)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernel"])
def test_blockwise_prefill_then_steps_and_verify_are_the_references(
        weights, use_pallas, blockwise):
    """The same through the blockwise forms: the prefill's attention in
    query and key blocks (the kernel interpreted, or `jax.numpy` a query
    block at a time), the steps absorbed, and a verify window — queries that
    do not start at position 0 — a query block at a time against the slot's
    rows."""
    eng = DecodeEngine(tiny(weights, use_pallas=use_pallas), slots=2,
                       max_len=64)
    ids = list(np.random.RandomState(7).randint(0, VOCAB, 64))
    want = reference_logp(weights, ids)
    cache = eng.init_cache()
    cache, _, probs = eng.prefill(cache, 1, ids[:21])
    rows = [np.asarray(eng.read_probs(probs))]
    for t in range(21, 27):
        cache, _, probs = eng.step(cache, np.asarray([0, ids[t]], np.int32))
        rows.append(np.asarray(eng.read_probs(probs[1])))
    np.testing.assert_allclose(np.log(np.stack(rows)), want[20:27],
                               atol=LOGP, rtol=0)
    cache, probs = eng.verify(cache, 1, ids[27:59], 27)
    np.testing.assert_allclose(np.log(np.asarray(probs)), want[27:59],
                               atol=LOGP, rtol=0)


def test_a_piece_left_out_or_in_bfloat16_fails_the_tolerance(weights):
    """What is new in the mathematics, and what the configuration states as
    float32: without the scores' m^2, the frequencies' blend, the query
    latent's norm or the turn of k_pe, or with the router or the softmax in
    bfloat16, the logits move by far more than LOGP allows."""
    ids = np.random.RandomState(0).randint(0, VOCAB, 300)
    want = reference_logp(weights, ids)
    for piece in ("mscale", "yarn_ramp", "q_norm", "k_rope"):
        moved = np.abs(reference_logp(weights, ids, without=(piece,)) - want)
        assert moved.max() > 100 * LOGP, piece
    for how in ({"router_dtype": "bfloat16"}, {"softmax_dtype": "bfloat16"}):
        assert np.abs(reference_logp(weights, ids, **how)
                      - want).max() > 100 * LOGP, how


# ------------------------------------------------- the latent layer's options
def kimi_layer(**over):
    a = CONFIG["args"]
    over.setdefault("n_heads", HEADS)
    return LatentAttentionLayer(
        n_in=D_MODEL, n_out=D_MODEL,
        q_lora_rank=a["q_lora_rank"], kv_lora_rank=a["kv_lora_rank"],
        qk_nope_head_dim=a["qk_nope_head_dim"],
        qk_rope_head_dim=a["qk_rope_head_dim"], v_head_dim=a["v_head_dim"],
        rope_theta=a["rope_theta"], rope_yarn=YARN, output_gate=False,
        eps=a["rms_norm_eps"], weight_init="xavier", activation="identity",
        **over)


def test_layer_with_compressed_queries_yarn_and_no_gate_is_the_references(
        weights):
    """The reference's attention half at its own widths, float32 parameters
    drawn four times wider, 300 positions (the YaRN blend shows from a few
    hundred on)."""
    mod = LatentAttentionLayerModule(kimi_layer())
    drawn, _, _ = mod.init(jax.random.PRNGKey(0), None, jnp.float32)
    assert sorted(drawn) == sorted(weights["b1_mla"]) == [
        "Wkv_a", "Wkv_b", "Wo", "Wq_a", "Wq_b", "kv_norm", "q_norm"]
    assert {k: v.shape for k, v in drawn.items()} \
        == {k: v.shape for k, v in weights["b1_mla"].items()}
    params = {k: v.astype(jnp.float32) * (1 if "norm" in k else 4)
              for k, v in weights["b1_mla"].items()}
    h = jnp.asarray(np.random.RandomState(0).randn(300, D_MODEL), jnp.float32)
    x = ref._rms(h, 1.0)[None]
    got = mod.forward(params, {}, x)[0][0]
    want = ref._attention_half(h, {"gamma": jnp.ones(D_MODEL)}, params,
                               dtype="float32", softmax_dtype="float32",
                               without=()) - h
    np.testing.assert_allclose(got, want, atol=LAYER * np.abs(want).max(),
                               rtol=0)
    for piece in ("mscale", "yarn_ramp", "q_norm", "k_rope"):
        off = ref._attention_half(h, {"gamma": jnp.ones(D_MODEL)}, params,
                                  dtype="float32", softmax_dtype="float32",
                                  without=(piece,)) - h
        assert np.abs(np.asarray(off - want)).max() > 1e-3, piece


@pytest.mark.parametrize("masked", [False, True], ids=["whole", "masked"])
@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernel"])
def test_blockwise_attention_is_the_plain_form(use_pallas, masked, request):
    """One layer, 96 positions: whole scores against query blocks of 16 and
    key blocks of 8 — the kernel interpreted, and its `jax.numpy` form —,
    with and without a key mask behind position 70."""
    mod = LatentAttentionLayerModule(kimi_layer(use_pallas=use_pallas))
    params, _, _ = mod.init(jax.random.PRNGKey(1), None, jnp.float32)
    params = {k: v * (1 if "norm" in k else 3) for k, v in params.items()}
    x = jnp.asarray(np.random.RandomState(2).randn(2, 96, D_MODEL),
                    jnp.float32)
    mask = (jnp.arange(96) < 70)[None].astype(jnp.float32).repeat(2, 0) \
        if masked else None
    want = mod.forward(params, {}, x, mask=mask)[0]
    request.getfixturevalue("blockwise")
    got = mod.forward(params, {}, x, mask=mask)[0]
    assert np.abs(np.asarray(want)).max() > 0.1
    np.testing.assert_allclose(got, want, atol=LAYER * np.abs(want).max(),
                               rtol=0)


def test_the_layer_chooses_its_form_by_the_scores_size():
    """Whole scores up to 128 MiB — 32 heads at 1,024 x 1,024, the largest
    the `ling3_flash` cell forms —, blockwise beyond: 64 heads at any of
    this configuration's prefill buckets."""
    seen = []

    class Spy(LatentAttentionLayerModule):
        def attend_plain(self, *a):
            seen.append("plain")
            raise StopIteration

    def form(heads, T):
        mod = Spy(kimi_layer(n_heads=heads))
        shapes = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
        pos = jax.ShapeDtypeStruct((1, T), jnp.int32)
        params = jax.eval_shape(lambda: mod.init(
            jax.random.PRNGKey(0), None, jnp.bfloat16)[0])
        try:
            jax.eval_shape(
                lambda p, qn, qp, lat, kp, pos: mod.attend(
                    p, qn, qp, lat, kp, pos, None, True),
                params, shapes(1, T, heads, 128), shapes(1, T, heads, 64),
                shapes(1, T, 512), shapes(1, T, 64), pos)
        except StopIteration:
            return "plain"
        return "blockwise"

    assert form(32, 1024) == "plain"
    assert form(64, 1024) == form(64, 2048) == form(32, 2048) == "blockwise"


def test_new_scopes_and_the_counted_fallback(monkeypatch):
    """`mla_queries` names the compression, `mla_prefill` the blockwise
    attention beside `mla_attention`; a sequence the kernel's blocks do not
    tile gives way to the `jax.numpy` form, counted."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    mod = LatentAttentionLayerModule(kimi_layer(use_pallas=True))
    params, _, _ = mod.init(jax.random.PRNGKey(1), None, jnp.float32)
    x = jnp.zeros((1, 24, D_MODEL), jnp.float32)
    text = str(jax.jit(lambda p, x: mod.forward(p, {}, x)[0]).lower(
        params, x).compiler_ir("stablehlo").operation.get_asm(
            enable_debug_info=True))
    assert "mla_queries" in text and "mla_attention" in text
    assert "mla_prefill" not in text
    monkeypatch.setattr(mla, "PLAIN_SCORE_BYTES", 0)
    counter = get_registry().counter("pallas_fallback_total", "")
    label = dict(kernel="mla_prefill", path="blockwise",
                 shape="T=24,H=2,interpret=False")
    before = counter.get(**label)
    monkeypatch.setattr(mp, "_interpret_default", lambda: False)
    text = str(jax.jit(lambda p, x: mod.forward(p, {}, x)[0]).lower(
        params, x).compiler_ir("stablehlo").operation.get_asm(
            enable_debug_info=True))
    assert "mla_prefill" in text and "mla_queries" in text
    assert counter.get(**label) == before + 1


# ----------------------------- `ling3_flash`'s layer, built as before PR 50
def old_rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freq
    ang = ang.reshape(pos.shape + (1,) * (x.ndim - pos.ndim - 1) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def old_forward(conf, params, x, mask):
    """nn/layers/mla.py's `forward` as it stood at PR 49, in one piece."""
    H, R, Dn, Dr, Dv = (conf.n_heads, conf.kv_lora_rank,
                        conf.qk_nope_head_dim, conf.qk_rope_head_dim,
                        conf.v_head_dim)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    lat, k_pe = jnp.split(x @ params["Wkv_a"], [R], axis=-1)
    lat = rms_norm(lat, params["kv_norm"], conf.eps)
    k_pe = old_rope(k_pe, pos, conf.rope_theta)
    q = (x @ params["Wq"]).reshape(x.shape[:-1] + (H, Dn + Dr))
    q_nope, q_pe = q[..., :Dn], old_rope(q[..., Dn:], pos, conf.rope_theta)
    w = params["Wkv_b"].reshape(R, H, Dn + Dv)
    k_nope = jnp.einsum("bkr,rhn->bkhn", lat, w[..., :Dn])
    v = jnp.einsum("bkr,rhv->bkhv", lat, w[..., Dn:])
    s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                      preferred_element_type=jnp.float32)) \
        * float(Dn + Dr) ** -0.5
    keep = jnp.arange(lat.shape[1])[None, None, :] <= pos[:, :, None]
    if mask is not None:
        keep = keep & (mask[:, None, :] > 0)
    p = jax.nn.softmax(jnp.where(keep[:, None], s, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhv->bqhv", p.astype(v.dtype), v)
    gate = jax.nn.sigmoid((x @ params["Wgate"]).astype(jnp.float32))
    y = (o * gate[..., None].astype(o.dtype)).reshape(o.shape[:-2] + (-1,))
    y = y.astype(x.dtype) @ params["Wo"]
    return y if mask is None else y * mask[:, :, None].astype(y.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_ling3_flashs_latent_layer_is_leaf_for_leaf_and_bit_for_bit_as_it_was(
        dtype):
    """The layer as `ling_hybrid_lm` builds it (full-rank queries, plain
    rotary, the head-wise gate: the three options at their defaults): the
    leaves and their draws are PR 49's, and so is every bit of the output,
    jitted as a model's forward is, with and without a mask."""
    a = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
                    / "configs" / "ling3_flash.json").read_text())["args"]
    conf = LatentAttentionLayer(
        n_in=160, n_out=160, n_heads=2, kv_lora_rank=a["kv_lora_rank"],
        qk_nope_head_dim=a["qk_nope_head_dim"],
        qk_rope_head_dim=a["qk_rope_head_dim"], v_head_dim=a["v_head_dim"],
        rope_theta=a["rope_theta"], eps=a["rms_norm_eps"],
        weight_init="xavier", activation="identity")
    assert (conf.q_lora_rank, conf.rope_yarn, conf.output_gate) \
        == (None, None, True)
    mod = LatentAttentionLayerModule(conf)
    params, _, _ = mod.init(jax.random.PRNGKey(5), None, dtype)
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(5), 5)
    mk = lambda k, i, o: init_weights(k, (i, o), "xavier", fan_in=i,
                                      fan_out=o, distribution=conf.dist,
                                      dtype=dtype)
    old = {"Wq": mk(k1, 160, 2 * 192), "Wkv_a": mk(k2, 160, 576),
           "kv_norm": jnp.ones((512,), dtype), "Wkv_b": mk(k3, 512, 2 * 256),
           "Wgate": mk(k4, 160, 2), "Wo": mk(k5, 2 * 128, 160)}
    assert sorted(params) == sorted(old)
    for k in old:
        np.testing.assert_array_equal(np.asarray(params[k], np.float32),
                                      np.asarray(old[k], np.float32), k)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 40, 160), dtype)
    mask = (jnp.arange(40) < 29)[None].astype(dtype).repeat(2, 0)
    for m in (None, mask):
        got = jax.jit(lambda p, x: mod.forward(p, {}, x, mask=m)[0])(
            params, x)
        want = jax.jit(lambda p, x: old_forward(conf, p, x, m))(params, x)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


# ------------------------------------------------------------ the chip's share
def test_eight_shares_add_up_to_the_uncut_layer(monkeypatch):
    """24 routed experts in eight shares of 3 (the cell: 384 in 32 of 12):
    the shares' routed parts, with the shared expert — which every chip
    computes alike — counted once, add up to the uncut layer; in the program
    and in the reference alike."""
    monkeypatch.setattr(ref, "N_EXPERTS", 24)
    d, T = 64, 50
    w = ref.init_params(jax.random.PRNGKey(2), 32, d, 2, 96,
                        experts_held=24)
    norm, mlp, moe = w["b1_norm2"], w["b1_mlp"], w["b1_moe"]
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) * 8, t)
    mlp, moe = f32(mlp), f32(moe)
    moe["route_bias"] = moe["route_bias"] / 8
    h = jnp.asarray(np.random.RandomState(0).randn(T, d), jnp.float32)
    half = lambda moe_, first: ref._routed_half(
        h, norm, mlp, moe_, dtype="float32", first_expert=first,
        router_dtype="float32") - h
    whole = half(moe, 0)
    shared = ref._gated(ref._rms(h, 1.0), mlp, lambda a: a)
    parts = []
    for first in range(0, 24, 3):
        cut = dict(moe, W1=moe["W1"][first:first + 3],
                   W2=moe["W2"][first:first + 3])
        parts.append(half(cut, first) - shared)
        conf = MixtureOfExpertsLayer(
            n_in=d, n_out=d, n_experts=24, top_k=ref.EXPERTS_PER_TOKEN,
            gated=True, n_hidden=ref.EXPERT_HIDDEN, experts_held=3,
            first_expert=first, score_function="sigmoid", n_groups=1,
            routed_scaling=ref.ROUTED_SCALING, activation="identity")
        got = MixtureOfExpertsLayerModule(conf).forward(
            cut, {}, ref._rms(h, 1.0))[0]
        np.testing.assert_allclose(got, parts[-1], atol=2e-5, rtol=0)
    assert all(np.abs(np.asarray(p)).max() > 1e-3 for p in parts)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5, rtol=0)


# -------------------------------------------------- counts the readers divide by
def test_operation_and_byte_counts_are_the_shapes():
    a = CONFIG["args"]
    d, L, V = a["d_model"], a["n_layers"], a["vocab_size"]
    attention = 101_124_096 - 1536 - 512          # the issue's, less the norms
    assert ref._attention_weights(d) == attention
    expert = 3 * d * 2048
    assert expert == 44_040_192
    assert ref.decode_macs_per_token(V, d, L, d * a["ffn_mult"]) == \
        L * attention + 3 * d * 18432 \
        + 4 * (expert + d * 384 + 0.25 * expert) + d * V
    # a 4,096 bucket: 64 heads, 320 multiply-adds a pair at or under the
    # diagonal
    assert ref.mla_prefill_flops(4096, d) == 2 * 64 * 320 * 4096 * 4097 // 2
    # 128 slots holding 400,000 tokens: their 576-wide bfloat16 rows once,
    # 64 query rows of 576 in and 64 float32 mixes of 512 out a slot
    assert ref.mla_decode_bytes(128, 400_000, d) == \
        2 * 400_000 * 576 + 128 * 64 * (2 * 576 + 4 * 512)
    parts = ref.decode_step_bytes(128, 400_000)
    assert parts["latent"] == L * ref.mla_decode_bytes(128, 400_000, d)
    assert 6.4e9 < parts["weights"] + parts["experts"] < 7.0e9
