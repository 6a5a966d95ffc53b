"""The routed expert layer (MixtureOfExpertsLayer, kernels/expert_gmm.py) and
the `granite_hybrid_lm` that uses it, at tiny sizes on the CPU, seeded weights:

- the layer against the dense all-experts einsum it once was (kept HERE as
  the oracle): k < E and k = E, [b, f] and [b, t, f], both kinds of expert,
  one expert starved and one taking every token, and its gradient; under
  a mask the positions kept bit for bit, the others off the layer;
- `expert_gmm` in interpret mode against its plain form: ragged groups, empty
  ones, row tiles past the last group, several blocks a product;
- the share ties to the model: four layers holding a quarter of the experts
  each, the same router, add up (the shared expert counted once) to the uncut
  reference's whole layer; one share through a one-period model's logits;
- `output()` of a one-period model against the reference's logits, and
  prefill then token-by-token decode through DecodeEngine, slab and paged;
- head_dim 128 through `flash_decode`; `n_experts=0` builds the graph that
  was there; the reference's constants are the configuration file's.
"""
import importlib
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import granite4_h_small as ref
from deeplearning4j_tpu.decode import DecodeEngine
from deeplearning4j_tpu.kernels import flash_decode
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import MixtureOfExpertsLayer
from deeplearning4j_tpu.nn.layers.feedforward import \
    MixtureOfExpertsLayerModule
from deeplearning4j_tpu.telemetry.registry import get_registry
from deeplearning4j_tpu.zoo.models import granite_hybrid_lm

eg = importlib.import_module("deeplearning4j_tpu.kernels.expert_gmm")
fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")
ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / "granite4_h_small.json").read_text())
F, E = 12, 8        # features, experts


def layer(dtype=jnp.float64, seed=0, **conf):
    args = dict(n_in=F, n_out=F, n_experts=E, top_k=3, gated=True, n_hidden=6,
                activation="identity", weight_init="xavier")
    args.update(conf)
    mod = MixtureOfExpertsLayerModule(MixtureOfExpertsLayer(**args))
    params, _, _ = mod.init(jax.random.PRNGKey(seed),
                            InputType.feed_forward(F), dtype)
    if not args["gated"]:               # biases that matter
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
        params["b1"] = jax.random.normal(k1, params["b1"].shape, dtype)
        params["b2"] = jax.random.normal(k2, params["b2"].shape, dtype)
    return mod, params


def dense_oracle(mod, params, x):
    """What the layer was before it sorted rows: EVERY held expert on EVERY
    token by dense einsum, times a gate that is zero where the router did
    not choose the expert (softmax over the chosen logits)."""
    c = mod.conf
    _, held, first, hidden, k = mod._sizes()
    xt = x.reshape(-1, x.shape[-1])
    r = xt @ params["Wg"]
    top, chosen = jax.lax.top_k(r, k)
    hit = chosen[:, :, None] == jnp.arange(r.shape[1])[None, None]
    gates = jnp.sum(jax.nn.softmax(top, axis=-1)[:, :, None] * hit, axis=1)
    gates = gates[:, first:first + held]
    h = jnp.einsum("tf,efh->eth", xt, params["W1"])
    if c.gated:
        a, b = jnp.split(h, 2, axis=-1)
        y = jnp.einsum("eth,eho->eto", jax.nn.silu(a) * b, params["W2"])
    else:
        h = jax.nn.relu(h + params["b1"][:, None, :])
        y = jnp.einsum("eth,eho->eto", h, params["W2"]) \
            + params["b2"][:, None, :]
    return jnp.einsum("te,eto->to", gates, y).reshape(*x.shape[:-1], -1)


def inputs(shape, seed=1):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape, F))


# ------------------------------------------------- the layer and its oracle
@pytest.mark.parametrize("shape", [(7,), (2, 5)], ids=["b_f", "b_t_f"])
@pytest.mark.parametrize("top_k", [3, E], ids=["k_lt_E", "k_eq_E"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu"])
def test_layer_is_the_dense_all_experts_sum(gated, top_k, shape):
    mod, params = layer(gated=gated, top_k=top_k)
    x = inputs(shape)
    got, _, _ = mod.forward(params, {}, x)
    assert got.shape == x.shape
    np.testing.assert_allclose(got, dense_oracle(mod, params, x), atol=1e-12)


@pytest.mark.parametrize("held,first", [(2, 0), (3, 5), (8, 0)])
def test_a_share_is_its_experts_part_of_the_sum(held, first):
    mod, params = layer(experts_held=held, first_expert=first)
    assert params["W1"].shape == (held, F, 12) and params["Wg"].shape == (F, E)
    x = inputs((9,))
    np.testing.assert_allclose(mod.forward(params, {}, x)[0],
                               dense_oracle(mod, params, x), atol=1e-12)


def test_a_starved_expert_and_one_that_takes_every_token_drop_no_pair():
    """A constant feature steers the router: expert 2 is every token's
    first choice and expert 5 nobody's. The rows are sized for the worst
    case and the group sizes are data, so the result is still the oracle's:
    no capacity, no dropped pair."""
    mod, params = layer(top_k=3)
    x = inputs((40,)).at[:, 0].set(1.0)
    params["Wg"] = params["Wg"].at[0, 2].set(50.0).at[0, 5].set(-50.0)
    experts, gates = mod.route(params, x)
    load = np.bincount(np.asarray(experts).ravel(), minlength=E)
    assert load[2] == 40 and load[5] == 0 and load.sum() == 40 * 3
    assert load.max() / load.mean() == pytest.approx(E / 3)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-12)
    at = mod.layout(params, x)
    assert int(np.asarray(at["live"]).sum()) == 40 * 3      # every pair a row
    np.testing.assert_allclose(mod.forward(params, {}, x)[0],
                               dense_oracle(mod, params, x), atol=1e-12)


@pytest.mark.parametrize("how", ["gated", "relu", "gated_kernel"])
def test_gradient_is_the_oracles(how):
    """By autodiff through the gather and the weighted sum, the grouped
    product by `ragged_dot`'s rules or, with the kernel, by the custom_vjp
    that hands the backward to the plain form."""
    mod, params = layer(gated=how != "relu", top_k=3,
                        use_pallas=how == "gated_kernel")
    x = inputs((2, 6))
    w = inputs((2, 6), seed=2)
    loss = lambda f: lambda p, x: jnp.sum(f(p, x) * w)
    got = jax.grad(loss(lambda p, x: mod.forward(p, {}, x)[0]),
                   argnums=(0, 1))(params, x)
    want = jax.grad(loss(lambda p, x: dense_oracle(mod, p, x)),
                    argnums=(0, 1))(params, x)
    tol = 1e-5 if how == "gated_kernel" else 1e-10  # float32 accumulators
    for g, o in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, o, atol=tol)
    assert float(jnp.abs(got[0]["Wg"]).max()) > 0      # the gates' path


def test_layer_sets_its_gauges():
    mod, params = layer(dtype=jnp.float32, experts_held=4, first_expert=4)
    mod.forward(params, {}, inputs((3,)).astype(jnp.float32))
    reg = get_registry()
    assert reg.get("moe_experts").get(routed=E, held=4, top_k=3) == 4
    assert reg.get("moe_expert_weight_bytes").get() \
        == 4 * (F * 12 + 6 * F) * 4


# ------------------------------------------------------------------ the mask
def _steered(mod, params, x, case):
    """`case` steers the router as the test above does: "starved" (expert 2
    every token's first choice, expert 5 nobody's), "one_takes_all" (the
    layer holds expert 2 alone), "all_held" (the layer holds every expert:
    every pair of a token the mask keeps takes a row)."""
    if case != "all_held":
        x = x.at[:, 0].set(1.0)
        params = dict(params, Wg=params["Wg"].at[0, 2].set(50.0)
                      .at[0, 5].set(-50.0))
    return params, x


MASK_CASES = {"starved": dict(), "one_takes_all": dict(experts_held=1,
                                                       first_expert=2),
              "all_held": dict(), "a_share": dict(experts_held=4,
                                                  first_expert=2)}


@pytest.mark.parametrize("how", ["gated", "relu", "gated_kernel"])
@pytest.mark.parametrize("case", list(MASK_CASES))
def test_masked_positions_hold_no_pair(case, how):
    """A prefill's padding takes no row: with a mask the valid positions
    are `mask=None`'s bit for bit (rows are independent in both products),
    the others zero, and `live` / `n_tiles` count the valid tokens' held
    pairs only — what they would be were the padding not there."""
    mod, params = layer(jnp.bfloat16, gated=how != "relu",
                        use_pallas=how == "gated_kernel", **MASK_CASES[case])
    params, x = _steered(mod, params, inputs((96,)).astype(jnp.bfloat16),
                         case)
    x, valid = x[None], 61
    mask = (jnp.arange(96) < valid).astype(jnp.bfloat16)[None]
    want = mod.forward(params, {}, x)[0]
    got, _, out_mask = mod.forward(params, {}, x, mask=mask)
    assert out_mask is mask and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got[0, :valid], np.float32),
                                  np.asarray(want[0, :valid], np.float32))
    assert float(jnp.abs(want[0, valid:].astype(jnp.float32)).max()) > 0
    assert not np.asarray(got[0, valid:], np.float32).any()
    at = mod.layout(params, x[0], mask[0] != 0)
    alone = mod.layout(params, x[0, :valid])
    held_pairs = int(np.asarray(alone["here"]).sum())
    assert int(np.asarray(at["here"]).sum()) == held_pairs
    assert int(np.asarray(at["live"]).sum()) == held_pairs
    assert not np.asarray(at["here"][valid:]).any()
    # the tile is sized for all 96 positions' pairs, the tiles counted are
    # those of the 61's: each held expert's pairs among them, rounded up
    experts, _ = mod.route(params, x[0, :valid])
    _, held, first, _, _ = mod._sizes()
    counts = np.bincount((np.asarray(experts) - first)[
        np.asarray(alone["here"])], minlength=held)
    assert at["tm"] >= alone["tm"] and int(at["n_tiles"]) == sum(
        -(-int(c) // at["tm"]) for c in counts)
    everything = mod.forward(params, {}, x, mask=jnp.ones_like(mask))[0]
    np.testing.assert_array_equal(np.asarray(everything, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("how", ["gated", "relu", "gated_kernel"])
def test_gradient_under_a_mask_is_the_oracles(how):
    """A masked position takes and gives no gradient, the others the dense
    oracle's: the rows the dispatch no longer zeroes (padding rows of live
    tiles hold some token's row) are read by nothing, so their cotangent is
    zero and no token's gradient sees them."""
    mod, params = layer(gated=how != "relu", top_k=3,
                        use_pallas=how == "gated_kernel")
    x, w = inputs((2, 6)), inputs((2, 6), seed=2)
    mask = jnp.asarray([[1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 1, 0]], x.dtype)
    loss = lambda f: lambda p, x: jnp.sum(f(p, x) * w * mask[:, :, None])
    got = jax.grad(loss(lambda p, x: mod.forward(p, {}, x, mask=mask)[0]),
                   argnums=(0, 1))(params, x)
    want = jax.grad(loss(lambda p, x: dense_oracle(mod, p, x)),
                    argnums=(0, 1))(params, x)
    tol = 1e-5 if how == "gated_kernel" else 1e-10
    for g, o in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, o, atol=tol)
    assert not np.asarray(got[1])[mask == 0].any()
    assert float(jnp.abs(got[0]["Wg"]).max()) > 0


# ---------------------------------------------------------------- the kernel
def grouped(sizes, tm, extra_tiles, k, h, n, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    sizes = jnp.asarray(sizes, jnp.int32)
    tiles_max = int(sizes.sum()) // tm + len(sizes) + extra_tiles
    tile_group, n_tiles, tile_start = eg.group_tiles(sizes, tm, tiles_max)
    rows = np.zeros((tiles_max * tm, k), np.float32)
    for g, size in enumerate(np.asarray(sizes)):
        at = int(tile_start[g]) * tm
        rows[at:at + size] = rng.randn(size, k)
    w1 = rng.randn(len(sizes), k, 2 * h) * 0.3
    w2 = rng.randn(len(sizes), h, n) * 0.3
    return (jnp.asarray(rows, dtype), jnp.asarray(w1, dtype),
            jnp.asarray(w2, dtype), tile_group, n_tiles)


def test_group_tiles_lays_groups_out_on_tile_boundaries():
    tile_group, n_tiles, tile_start = eg.group_tiles(
        jnp.asarray([3, 0, 9, 4, 0], jnp.int32), 4, 11)
    assert int(n_tiles) == 5
    assert list(np.asarray(tile_start)) == [0, 1, 1, 4, 5]
    assert list(np.asarray(tile_group))[:5] == [0, 2, 2, 2, 3]
    assert eg.row_tile(320, 72, 2) == 16 and eg.row_tile(2560, 72, 2) == 128
    assert eg.row_tile(20480, 72, 2) == 512 and eg.row_tile(6, 8, 4) == 8


@pytest.mark.parametrize("sizes", [[3, 0, 9, 4, 0], [0, 0, 5], [8, 8]],
                         ids=["ragged_with_empty", "leading_empty", "full"])
def test_expert_gmm_interpreted_is_its_plain_form(sizes):
    """Tiles past `n_tiles` are not visited (two spare ones here): the plain
    form leaves their rows zero, the kernel undefined."""
    tm, k, h, n = 4, 32, 16, 24
    rows, w1, w2, tile_group, n_tiles = grouped(sizes, tm, 2, k, h, n)
    live = int(n_tiles) * tm
    want = np.zeros((rows.shape[0], n), np.float32)
    for t in range(int(n_tiles)):
        g, r = int(tile_group[t]), np.asarray(rows[t * tm:(t + 1) * tm])
        a, b = np.split(r @ np.asarray(w1[g]), 2, axis=-1)
        want[t * tm:(t + 1) * tm] = (a / (1 + np.exp(-a)) * b) \
            @ np.asarray(w2[g])
    plain = eg._gmm_reference(rows, w1, w2, tile_group, n_tiles)
    np.testing.assert_allclose(plain, want, atol=2e-5)
    assert not np.asarray(plain[live:]).any()
    # whole matrices a block, and 4 x 3 blocks so both products take steps
    for got in (eg.expert_gmm(rows, w1, w2, tile_group, n_tiles,
                              interpret=True),
                eg._gmm_call(rows, w1, w2, tile_group, n_tiles[None], 8, 8,
                             True, "expert_gmm_test")):
        np.testing.assert_allclose(got[:live], want[:live], atol=2e-5)


def test_expert_gmm_blocks_and_fallback():
    # the cell's expert: 4096 -> 2 x 768 -> 4096 in bfloat16, 3 MB blocks
    assert eg._gmm_blocks(4096, 768, 4096, 2, 16, False) == (1024, 2048)
    assert eg._gmm_blocks(4096, 768, 4096, 2, 8, False) is None    # packing
    assert eg._gmm_blocks(4096, 96, 4096, 2, 16, False) is None    # the gate
    counter = get_registry().counter("pallas_fallback_total")
    label = dict(kernel="expert_gmm", path="jnp",
                 shape="tm=4,k=32,h=16,n=24,interpret=False")
    before = counter.get(**label)
    rows, w1, w2, tile_group, n_tiles = grouped([3, 5], 4, 0, 32, 16, 24)
    got = eg.expert_gmm(rows, w1, w2, tile_group, n_tiles, interpret=False)
    assert counter.get(**label) == before + 1
    np.testing.assert_allclose(
        got, eg._gmm_reference(rows, w1, w2, tile_group, n_tiles))


# -------------------------------------------- the share and the whole layer
def _softmax_case():
    """`granite4_h_small`'s: softmax over the chosen logits, 8 experts, 3 a
    token, four shares of two."""
    return dict(n=8, k=3, shares=4, conf={}, leaves={},
                gates=lambda x, Wg, leaves: ref.gates_of(x, Wg, 3))


def _sigmoid_case():
    """`ling3_flash`'s: sigmoid scores, a selection-only bias, the 4 best of
    8 groups, 8 experts a token, gates renormalised and x 2.5; 64 experts,
    eight shares of one routing group each."""
    from benchmarks.reference import ling3_flash as ling
    bias = jnp.asarray(np.random.RandomState(1).randn(64) * 0.01)
    return dict(
        n=64, k=ling.EXPERTS_PER_TOKEN, shares=8,
        conf=dict(score_function="sigmoid", n_groups=ling.N_GROUPS,
                  topk_groups=ling.TOPK_GROUPS,
                  routed_scaling=ling.ROUTED_SCALING),
        leaves={"route_bias": bias},
        gates=lambda x, Wg, leaves: ling.gates_of(x, Wg, leaves["route_bias"],
                                                  x.dtype))


def _one_group_case():
    """`solar_open2`'s: sigmoid scores, a selection-only bias, ONE group, 8
    of 320 experts a token, gates renormalised (x 1); eight shares of 40."""
    from benchmarks.reference import solar_open2 as solar
    bias = jnp.asarray(np.random.RandomState(1).randn(solar.N_EXPERTS) * 0.01)
    return dict(
        n=solar.N_EXPERTS, k=solar.EXPERTS_PER_TOKEN,
        shares=solar.N_EXPERTS // solar.EXPERTS_HELD,
        conf=dict(score_function="sigmoid", n_groups=1,
                  routed_scaling=solar.ROUTED_SCALING),
        leaves={"route_bias": bias},
        gates=lambda x, Wg, leaves: solar.gates_of(
            x, Wg, leaves["route_bias"], x.dtype))


@pytest.mark.parametrize("case", [_softmax_case, _sigmoid_case,
                                  _one_group_case],
                         ids=["granite4_h_small", "ling3_flash",
                              "solar_open2"])
def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer(case):
    """model-configs guide, section 4: the expert layer built once a share
    with first_expert 0, 1/n, 2/n .. of the experts and the same router; the
    routed parts, plus the shared expert counted once, are the uncut
    reference's whole layer (every expert on every row)."""
    c = case()
    d, hidden, shared, n, k = 16, 8, 12, c["n"], c["k"]
    held = n // c["shares"]
    rng = np.random.RandomState(0)
    Wg, W1, W2 = (jnp.asarray(rng.randn(*s) * 0.5) for s in
                  ((d, n), (n, d, 2 * hidden), (n, hidden, d)))
    W_in, W_out = (jnp.asarray(rng.randn(*s) * 0.5) for s in
                   ((d, 2 * shared), (shared, d)))
    x = jnp.asarray(rng.randn(11, d))
    gates = c["gates"](x, Wg, c["leaves"])
    # float32 gates (the router's dtype) on float64 rows
    g, u = jnp.split(x @ W_in, 2, axis=-1)
    whole = ref.expert_sum(x, gates, W1, W2, x.dtype, lambda a: a) \
        + (jax.nn.silu(g) * u) @ W_out
    parts = []
    for first in range(0, n, held):
        mod = MixtureOfExpertsLayerModule(MixtureOfExpertsLayer(
            n_in=d, n_out=d, n_experts=n, top_k=k, gated=True,
            n_hidden=hidden, experts_held=held, first_expert=first,
            activation="identity", **c["conf"]))
        share = dict(c["leaves"], Wg=Wg, W1=W1[first:first + held],
                     W2=W2[first:first + held])
        parts.append(mod.forward(share, {}, x)[0])
        # and the reference given that share is that part
        np.testing.assert_allclose(parts[-1], ref.expert_sum(
            x, gates[:, first:first + held], share["W1"], share["W2"],
            x.dtype, lambda a: a), atol=1e-6)
    assert sum(float(jnp.abs(p).max()) > 0 for p in parts) > c["shares"] // 2
    np.testing.assert_allclose(sum(parts) + (jax.nn.silu(g) * u) @ W_out,
                               whole, atol=1e-6)


D_MODEL, LAYERS, HEADS, VOCAB, TOP_K = 128, 10, 8, 96, 3


def one_period(first_expert, held, **over):
    """One period of the configuration's pattern (attention at 5) at d_model
    128: 8 routed experts of width 16, 3 a token, beside a shared expert of
    48; Mamba-2 with the reference's own 64-wide heads and state of 128."""
    a = CONFIG["args"]
    args = dict(
        vocab_size=VOCAB, d_model=D_MODEL, n_layers=LAYERS, n_heads=HEADS,
        n_kv_heads=HEADS // ref.QUERY_HEADS_PER_KV, attention_layers=(5,),
        ffn_mult=a["ffn_mult"], mamba_d_head=a["mamba_d_head"],
        mamba_d_state=a["mamba_d_state"], mamba_d_conv=a["mamba_d_conv"],
        mamba_chunk_size=8, embedding_multiplier=a["embedding_multiplier"],
        attention_multiplier=a["attention_multiplier"],
        residual_multiplier=a["residual_multiplier"],
        logits_scaling=a["logits_scaling"], rms_norm_eps=a["rms_norm_eps"],
        n_experts=8, experts_per_token=TOP_K, expert_hidden=16,
        experts_held=held, first_expert=first_expert)
    args.update(over)
    return granite_hybrid_lm(**args).init()


@pytest.fixture
def tiny_reference(monkeypatch):
    """The reference's module constants at the tiny model's sizes; the
    expert matrices times 8 (exact in bfloat16), so that at these widths
    the routed part weighs as much in the logits as it does at size."""
    def sized(held):
        monkeypatch.setattr(ref, "N_EXPERTS", 8)
        monkeypatch.setattr(ref, "EXPERT_HIDDEN", 16)
        monkeypatch.setattr(ref, "EXPERTS_HELD", held)
        params = ref.init_params(jax.random.PRNGKey(3), VOCAB, D_MODEL,
                                 LAYERS, D_MODEL * CONFIG["args"]["ffn_mult"])
        for name, leaves in params.items():
            if name.endswith("_moe"):
                leaves["W1"], leaves["W2"] = leaves["W1"] * 8, leaves["W2"] * 8
        return params
    return sized


def place(net, params):
    assert {k: sorted(v) for k, v in params.items()} \
        == {k: sorted(v) for k, v in net.params.items()}
    net.params = {n: {k: jnp.asarray(params[n][k], old.dtype)
                      for k, old in leaves.items()}
                  for n, leaves in net.params.items()}


def log_softmax(z):
    z = np.asarray(z, np.float64)
    return z - np.log(np.sum(np.exp(z), axis=-1, keepdims=True))


@pytest.mark.parametrize("first,held,use_pallas",
                         [(0, 2, False), (0, 2, True), (4, 2, False),
                          (0, 8, False)],
                         ids=["share0", "share0_kernel", "share2", "uncut"])
def test_one_period_output_matches_the_reference_logits(
        tiny_reference, first, held, use_pallas):
    """float32 both sides; the program sorts rows by expert (and with the
    kernel runs it interpreted), the reference runs every held expert on
    every row. `share2` is the second test that ties the share to the model:
    one share through the logits against the reference given that share."""
    params = tiny_reference(held)
    net = one_period(first, held, use_pallas=use_pallas)
    place(net, params)
    ids = np.random.RandomState(0).randint(0, VOCAB, 21)
    want = log_softmax(ref.logits(
        params, jnp.asarray(ids), heads=HEADS, layers=LAYERS,
        first_expert=first, experts_per_token=TOP_K))
    probs = np.asarray(net.output(np.eye(VOCAB, dtype=np.float32)[ids][None]))
    np.testing.assert_allclose(np.log(probs[0]), want, atol=2e-5, rtol=0)
    if held < 8:        # and the share is not the whole: the uncut differs
        other = log_softmax(ref.logits(
            params, jnp.asarray(ids), heads=HEADS, layers=LAYERS,
            first_expert=(first + 2) % 8, experts_per_token=TOP_K))
        assert np.abs(other - want).max() > 1e-3


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("n_prompt", [5, 16, 19],
                         ids=["padded", "bucket", "longer"])
def test_prefill_then_decode_is_the_full_forward(paged, n_prompt):
    """Three blocks (Mamba-2, attention, Mamba-2), each with routed experts
    beside the shared one: a prompt padded to its bucket hands the layer
    the prefill's mask, so its padded positions hold no pair; every step's
    row of probabilities is the full forward's."""
    V = 48
    net = granite_hybrid_lm(
        vocab_size=V, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
        attention_layers=(1,), mamba_d_head=8, mamba_d_state=16,
        mamba_chunk_size=8, embedding_multiplier=12,
        attention_multiplier=0.125, residual_multiplier=0.22,
        logits_scaling=16, ffn_mult=0.375, n_experts=8, experts_per_token=3,
        expert_hidden=8, experts_held=4, first_expert=2, seed=7).init()
    eng = DecodeEngine(net, slots=2, max_len=32, paged=paged, block_size=8)
    cache = eng.init_cache()
    assert {n: sorted(e) for n, e in cache["layers"].items()} == {
        "b0_mamba": ["conv", "ssm"], "b1_attn": ["k", "v"],
        "b2_mamba": ["conv", "ssm"]}          # the expert layer keeps nothing
    prompt = list(np.random.RandomState(n_prompt).randint(0, V, n_prompt))
    cache, nid, probs = eng.prefill(cache, 1, prompt)
    got, rows = [nid], [probs]
    ids = np.zeros((2,), np.int32)
    for _ in range(5):
        ids[1] = got[-1]
        cache, nxt, probs = eng.step(cache, ids)
        got.append(int(nxt[1]))
        rows.append(probs[1])
    full = np.asarray(net.output(
        np.eye(V, dtype=np.float32)[np.asarray(prompt + got[:-1])][None]))
    want = full[0, n_prompt - 1:]
    np.testing.assert_allclose(np.stack(rows), want, rtol=2e-4, atol=1e-7)
    assert got == [int(r.argmax()) for r in want]
    assert eng.executable_counts()["decode_step"] == 1


# ------------------------------------------------ what else the row brought
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_decode_at_head_dim_128(dtype):
    """The row's attention: 4 query heads a K/V head of 128. The kernel
    path (interpreted here; compiled for the v5e in test_tpu_compile.py),
    not a counted fallback."""
    rng = np.random.RandomState(4)
    S, C, Hq, Hkv, D = 3, 64, 8, 2, 128
    q = jnp.asarray(rng.randn(S, 1, Hq, D), dtype)
    k, v = (jnp.asarray(rng.randn(S, C, Hkv, D), dtype) for _ in range(2))
    lengths = jnp.asarray([1, 37, 64], jnp.int32)
    counter = get_registry().counter("pallas_fallback_total")
    before = sum(n for ls, n in counter.series()
                 if ls.get("kernel") == "flash_decode")
    got = flash_decode(q, k, v, lengths, scale=0.0078125, block_k=32)
    assert sum(n for ls, n in counter.series()
               if ls.get("kernel") == "flash_decode") == before
    want = fa._decode_reference(q, k, v, lengths, 0.0078125)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2 if dtype == "bfloat16" else 1e-5)
    assert fa._decode_block(1024, 32, 128, 2, 1024, False) == 256


def test_without_experts_the_builder_builds_the_graph_that_was_there():
    """`n_experts=0` (granite4_h_micro): the vertices, their order and the
    shared MLP's integer width are the parent's; no expert layer, no add.
    (Against the parent commit the lowered step and prefill of both old
    serve configurations were compared once: PERF.md section 6.)"""
    net = granite_hybrid_lm(vocab_size=32, d_model=16, n_layers=2, n_heads=2,
                            attention_layers=(1,), mamba_d_head=8,
                            mamba_d_state=8, ffn_mult=4)
    names = [n for n in net.conf.vertices if n != "tokens"]
    want = ["embed", "embed_x"]
    for i, mixer in enumerate(["mamba", "attn"]):
        want += [f"b{i}_norm1", f"b{i}_{mixer}", f"b{i}_res1x", f"b{i}_res1",
                 f"b{i}_norm2", f"b{i}_mlp", f"b{i}_res2x", f"b{i}_res2"]
    assert names == want + ["norm", "out"]
    n_hidden = net.conf.vertices["b0_mlp"].layer_conf.n_hidden
    assert n_hidden == 64 and isinstance(n_hidden, int)
    routed = granite_hybrid_lm(vocab_size=32, d_model=16, n_layers=1,
                               n_heads=2, attention_layers=(), mamba_d_head=8,
                               mamba_d_state=8, ffn_mult=0.375, n_experts=4,
                               experts_per_token=2, expert_hidden=8)
    assert [n for n in routed.conf.vertices if n.startswith("b0_")][5:8] \
        == ["b0_mlp", "b0_moe", "b0_ffn"]
    assert routed.conf.vertices["b0_mlp"].layer_conf.n_hidden == 6


def test_the_references_constants_are_the_configuration_files():
    a, pub = CONFIG["args"], CONFIG["published"]
    assert ref.QUERY_HEADS_PER_KV == a["n_heads"] // a["n_kv_heads"]
    assert [i for i in ref.ATTENTION_LAYERS if i < a["n_layers"]] \
        == a["attention_layers"] == [
            i for i, t in enumerate(pub["layer_types"][:a["n_layers"]])
            if t == "attention"]
    assert list(ref.ATTENTION_LAYERS) == [
        i for i, t in enumerate(pub["layer_types"]) if t == "attention"]
    assert ref.mamba_dims(a["d_model"])[:2] == (
        a["mamba_n_heads"], a["mamba_n_heads"] * a["mamba_d_head"])
    assert (ref.MAMBA_EXPAND, ref.MAMBA_D_HEAD, ref.MAMBA_D_STATE,
            ref.MAMBA_D_CONV) == (pub["mamba_expand"], a["mamba_d_head"],
                                  a["mamba_d_state"], a["mamba_d_conv"])
    assert (ref.EMBEDDING_MULTIPLIER, ref.ATTENTION_MULTIPLIER,
            ref.RESIDUAL_MULTIPLIER, ref.LOGITS_SCALING, ref.RMS_EPS) == (
        a["embedding_multiplier"], a["attention_multiplier"],
        a["residual_multiplier"], a["logits_scaling"], a["rms_norm_eps"])
    assert (ref.N_EXPERTS, ref.EXPERTS_PER_TOKEN, ref.EXPERT_HIDDEN,
            ref.EXPERTS_HELD, ref.FIRST_EXPERT) == (
        a["n_experts"], a["experts_per_token"], a["expert_hidden"],
        a["experts_held"], a["first_expert"])
    # the cut is the one stated: depth, experts held, vocabulary; every
    # width, the router's 72 outputs and 10 experts a token as published
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_local_experts",
                                 "vocab_size"]
    assert {k for k, v in pub.items() if CONFIG[k] != v} \
        == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["num_local_experts"],
            CONFIG["vocab_size"]) == (a["n_layers"], a["experts_held"],
                                      a["vocab_size"]) == (10, 18, 25088)
    assert (a["n_experts"], a["experts_per_token"], a["expert_hidden"],
            a["d_model"], int(a["d_model"] * a["ffn_mult"])) == (
        pub["num_local_experts"], pub["num_experts_per_tok"],
        pub["intermediate_size"], pub["hidden_size"],
        pub["shared_intermediate_size"])
    assert a["d_model"] // a["n_heads"] == 128
    assert a["vocab_size"] * 4 == pub["vocab_size"] \
        and a["experts_held"] * 4 == pub["num_local_experts"] \
        and "4 chips share each layer" in CONFIG["deployment"]
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        rows = [json.loads(line) for line in catalog.read_text().splitlines()]
        row = {r["name"]: r for r in rows}["granite-4.0-h-small"]
        assert pub == row["config"] and CONFIG["source"] == row["source_url"]
    # the issue's arithmetic: a layer's held experts 339.7 MB; 10 layers'
    # parameters 2.956 B; a step's bytes 8.6 GB, experts 44 %, state 49 %
    assert ref.expert_layer_bytes(32) == pytest.approx(339.7e6 + 1.3e6,
                                                       rel=2e-3)
    assert ref.ssm_step_bytes(32) == pytest.approx(268.4e6 + 3.2e6, rel=2e-3)
    parts = ref.decode_step_bytes(32, 32 * 256)
    total = sum(parts.values())
    assert 8.3e9 < total < 8.9e9
    assert parts["experts"] / total == pytest.approx(0.40, abs=0.03)
