"""The decode contract lives on the layer (nn/layers/base.py), not in decode/:

- a stateful layer defined HERE — a causal running mean over time, one
  `[slots, n_out]` row of running sums per slot — decodes through
  DecodeEngine on the slab and the paged layout with no edit under decode/;
  declared non-rewindable, verify() refuses it and carry_snapshot holds it.
- a cache leaf is placed on a serving mesh by the axis its layer declared,
  whatever its rank (a 3-D latent-style leaf has no head axis to guess).
- decode/engine.py names no layer class.
"""
import ast
import pathlib
from dataclasses import dataclass

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.decode import DecodeEngine, DecodeUnsupported
from deeplearning4j_tpu.decode import engine as engine_module
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (BaseRecurrentConf,
                                               BatchNormalization, DenseLayer,
                                               RnnOutputLayer,
                                               register_layer_conf)
from deeplearning4j_tpu.nn.layers.base import (BaseLayerModule, CacheLeaf,
                                               register_impl)
from deeplearning4j_tpu.nn.multilayer.network import MultiLayerNetwork
from deeplearning4j_tpu.serving.mesh import MeshContext

V = 12   # vocab
F = 8    # hidden width


@register_layer_conf
@dataclass
class RunningMeanLayer(BaseRecurrentConf):
    """y[t] = mean of x[0..t] over the real (unmasked) positions."""


@register_impl("RunningMeanLayer")
class RunningMeanLayerModule(BaseLayerModule):
    decode_rewindable = False       # a running sum cannot be un-added

    def init(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        m = jnp.ones(x.shape[:2], x.dtype) if mask is None \
            else mask.astype(x.dtype)
        sums = jnp.cumsum(x * m[:, :, None], axis=1)
        count = jnp.maximum(jnp.cumsum(m, axis=1), 1)
        return sums / count[:, :, None], state, mask

    def decode_unsupported(self):
        return None

    def decode_entry(self, geom):
        return {"sum": CacheLeaf((geom.slots, int(self.conf.n_out)),
                                 geom.dtype)}

    def decode_prefill(self, params, state, x, entry, ctx):
        y = self.forward(params, state, x, mask=ctx.mask)[0]
        total = jnp.sum(x * ctx.mask[:, :, None], axis=1)        # [1, F]
        at = (ctx.slot, jnp.zeros((), ctx.slot.dtype))
        return y, {"sum": lax.dynamic_update_slice(
            entry["sum"], total.astype(entry["sum"].dtype), at)}

    def decode_step(self, params, state, x, entry, ctx):
        total = entry["sum"] + x[:, 0]
        y = total / ctx.kv_valid[:, None].astype(total.dtype)
        return y[:, None], {"sum": total}


@register_layer_conf
@dataclass
class LatentLikeLayer(BaseRecurrentConf):
    """Identity that declares a 3-D `[slots, capacity, n_out]` cache leaf
    (the shape of a latent cache: no head axis) and never touches it."""
    model_axis: int | None = None


@register_impl("LatentLikeLayer")
class LatentLikeLayerModule(BaseLayerModule):
    positionwise = True

    def init(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return x, state, mask

    def decode_entry(self, geom):
        return {"latent": CacheLeaf(
            (geom.slots, geom.capacity, int(self.conf.n_out)), geom.dtype,
            self.conf.model_axis)}


def _net(*middle, seed=3):
    b = NeuralNetConfiguration.builder().seed(seed).list()
    b.layer(DenseLayer(n_out=F, activation="tanh"))
    for conf in middle:
        b.layer(conf)
    b.layer(RnnOutputLayer(n_out=V, activation="softmax", loss="MCXENT"))
    return MultiLayerNetwork(
        b.input_type(InputType.recurrent(V)).build()).init()


def _naive_greedy(net, prompt, n):
    """(ids, last-position probability rows) of re-running the full forward
    on the growing sequence."""
    ids, out, rows = list(prompt), [], []
    for _ in range(n):
        x = np.eye(V, dtype=np.float32)[np.asarray(ids)][None]
        rows.append(np.asarray(net.output(x))[0, -1])
        out.append(int(rows[-1].argmax()))
        ids.append(out[-1])
    return out, np.stack(rows)


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_a_layer_defined_outside_decode_streams_token_for_token(paged):
    """Two slots joined at different steps: each request's tokens (and
    probability rows) equal net.output(...) on its own growing sequence."""
    net = _net(RunningMeanLayer(n_out=F),
               DenseLayer(n_out=F, activation="tanh"))
    prompts, n = {1: [3, 1, 4, 1, 5], 0: [9, 2, 6]}, 7
    want = {s: _naive_greedy(net, p, n) for s, p in prompts.items()}
    eng = DecodeEngine(net, slots=2, max_len=32, paged=paged, block_size=4)
    cache = eng.init_cache()
    assert cache["layers"]["1"]["sum"].shape == (2, F)
    got, rows = {1: [], 0: []}, {1: [], 0: []}
    ids = np.zeros((2,), np.int32)
    cache, nid, probs = eng.prefill(cache, 1, prompts[1])
    got[1].append(nid)
    rows[1].append(probs)
    step = 0
    while any(len(g) < n for g in got.values()):
        step += 1
        if step == 4:                       # slot 0 joins three tokens later
            cache, nid, probs = eng.prefill(cache, 0, prompts[0])
            got[0].append(nid)
            rows[0].append(probs)
            continue
        for s in got:
            if got[s]:
                ids[s] = got[s][-1]
        cache, nxt, probs = eng.step(cache, ids)
        for s in got:
            if got[s] and len(got[s]) < n:
                got[s].append(int(nxt[s]))
                rows[s].append(probs[s])
    for s in prompts:
        assert got[s] == want[s][0]
        np.testing.assert_allclose(np.stack(rows[s]), want[s][1],
                                   rtol=1e-4, atol=1e-6)
    assert eng.executable_counts()["decode_step"] == 1


def test_a_non_rewindable_entry_is_refused_by_verify_and_snapshotted():
    net = _net(RunningMeanLayer(n_out=F))
    eng = DecodeEngine(net, slots=2, max_len=16)
    assert eng.has_recurrent()
    cache, _, _ = eng.prefill(eng.init_cache(), 1, [1, 2, 3])
    with pytest.raises(DecodeUnsupported):
        eng.verify(cache, 1, [4, 5], 3)
    snap = eng.carry_snapshot(cache)
    assert set(snap["layers"]) == {"1"}
    np.testing.assert_array_equal(snap["layers"]["1"]["sum"],
                                  np.asarray(cache["layers"]["1"]["sum"]))
    stepped, _, _ = eng.step(cache, np.array([0, 7], np.int32))
    back = eng.carry_restore(stepped, snap)
    np.testing.assert_array_equal(np.asarray(back["layers"]["1"]["sum"]),
                                  snap["layers"]["1"]["sum"])
    np.testing.assert_array_equal(np.asarray(back["lengths"]), [0, 3])


def test_a_layer_that_answers_nothing_is_refused_by_name():
    with pytest.raises(DecodeUnsupported, match="BatchNormalizationModule"):
        DecodeEngine(_net(BatchNormalization()), slots=1, max_len=16)


@pytest.mark.parametrize("axis,spec", [(None, P()),
                                       (2, P(None, None, "model"))])
def test_a_cache_leaf_is_placed_by_its_declared_axis_not_its_rank(axis, spec):
    """1 x 4 mesh: a 3-D leaf declared with no model axis replicates (the
    rank rule knew 4-D and 2-D only), declared with one it splits there."""
    ctx = MeshContext({"n_data": 1, "n_model": 4}, devices=jax.devices()[:4])
    net = ctx.wrap(_net(LatentLikeLayer(n_out=F, model_axis=axis),
                        RunningMeanLayer(n_out=F)))
    eng = DecodeEngine(net, slots=2, max_len=16)
    cache = eng.init_cache()
    latent = cache["layers"]["1"]["latent"]
    assert latent.shape == (2, 16, F)
    assert latent.sharding.spec == spec
    # the 2-D running sums declared no axis either: replicated, where the
    # rank rule split every 2-D leaf on axis 1
    assert cache["layers"]["2"]["sum"].sharding.spec == P()
    assert eng.cache_shardings()["lengths"].spec == P()
    whole = eng.cache_bytes()
    split = 2 * 16 * F * 4 * (3 if axis is not None else 0) // 4
    assert eng.cache_bytes(per_shard=True) == whole - split
    # and it decodes under the mesh as it does on one device
    assert eng.generate([5, 1, 2], 4) == \
        _naive_greedy(net.mesh_inner, [5, 1, 2], 4)[0]


@pytest.mark.parametrize("shape,axis", [((4, 8), 1), ((4, 16, 8), 1),
                                        ((4, 16, 8, 4), 2),
                                        ((4, 16, 6, 4), 2)])
def test_mesh_cache_sharding_takes_the_axis(shape, axis):
    ctx = MeshContext({"n_data": 1, "n_model": 4}, devices=jax.devices()[:4])
    got = ctx.cache_sharding(shape, axis).spec
    if shape[axis] % 4:            # uneven: degrades to replicated
        assert got == P()
    else:
        assert got == P(*[None] * axis, "model", *[None] * (len(shape)
                                                           - axis - 1))
    assert ctx.cache_sharding(shape).spec == P()


@pytest.mark.parametrize("experts", [0, 6], ids=["shared_mlp", "routed"])
@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_a_model_of_both_entry_kinds_decodes_with_decode_untouched(paged,
                                                                   experts):
    """What PR 31 proves of the contract: the hybrid decoder (Mamba-2 layers
    keeping a fixed-size state and a conv tail, grouped-query attention
    keeping K/V rows) came as layer files, and decodes — two slots joined at
    different steps, token for token the full forward — through an engine,
    a scheduler and a serving plane that name none of it. No answer had to
    be added to BaseLayerModule. And what PR 33 proves (`routed`): a routed
    expert layer in every block, between the two kinds of cache entry, that
    sorts each step's rows by expert, is `positionwise` and nothing else —
    it decodes with no edit under decode/ or serving/ (that PR's `git diff`
    there is empty) and no new answer either."""
    from deeplearning4j_tpu.zoo.models import granite_hybrid_lm
    vocab = 40
    net = granite_hybrid_lm(
        vocab_size=vocab, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
        attention_layers=(1,), mamba_d_head=8, mamba_d_state=16,
        mamba_chunk_size=8, embedding_multiplier=12, residual_multiplier=0.22,
        attention_multiplier=0.125, logits_scaling=8, seed=11,
        n_experts=experts, experts_per_token=2, expert_hidden=8,
        experts_held=experts // 2 or None, first_expert=experts // 3).init()
    eye = np.eye(vocab, dtype=np.float32)

    def naive(prompt, n):
        ids, out = list(prompt), []
        for _ in range(n):
            out.append(int(np.asarray(net.output(eye[ids][None]))[0, -1]
                           .argmax()))
            ids.append(out[-1])
        return out

    prompts, n = {1: [3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 0: [9, 2, 6]}, 6
    eng = DecodeEngine(net, slots=2, max_len=32, paged=paged, block_size=8)
    cache = eng.init_cache()
    assert {frozenset(e) for e in cache["layers"].values()} \
        == {frozenset({"ssm", "conv"}), frozenset({"k", "v"})}
    got = {1: [], 0: []}
    ids = np.zeros((2,), np.int32)
    cache, nid, _ = eng.prefill(cache, 1, prompts[1])
    got[1].append(nid)
    for step in range(1, 2 * n + 2):
        if step == 3:                   # slot 0 joins two tokens later
            cache, nid, _ = eng.prefill(cache, 0, prompts[0])
            got[0].append(nid)
            continue
        for s in got:
            if got[s]:
                ids[s] = got[s][-1]
        cache, nxt, _ = eng.step(cache, ids)
        for s in got:
            if got[s] and len(got[s]) < n:
                got[s].append(int(nxt[s]))
    assert got == {s: naive(p, n) for s, p in prompts.items()}
    with pytest.raises(DecodeUnsupported):      # a state cannot rewind
        eng.verify(cache, 1, [4, 5], 3)
    assert set(eng.carry_snapshot(cache)["layers"]) == {"b0_mamba",
                                                        "b2_mamba"}
    # nothing under decode/ or serving/ knows the new layers by name
    pkg = pathlib.Path(engine_module.__file__).parents[1]
    for path in [*(pkg / "decode").glob("*.py"),
                 *(pkg / "serving").glob("*.py")]:
        text = path.read_text().lower()
        for word in ("mamba", "ssm_", "rmsnorm", "gateddense", "granite",
                     "lmhead", "n_kv_heads", "expert", "moe_"):
            assert word not in text, (path.name, word)


def test_the_engine_names_no_layer_class():
    tree = ast.parse(pathlib.Path(engine_module.__file__).read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            assert "nn.layers" not in (node.module or ""), node.module
        if isinstance(node, ast.Import):
            assert all("nn.layers" not in a.name for a in node.names)
        if isinstance(node, ast.Call) \
                and getattr(node.func, "id", None) == "isinstance":
            # the plan tells the two network classes apart; nothing asks a
            # node's module (or a cache entry) what it is
            assert ast.unparse(node.args[0]) == "model", ast.unparse(node)
    src = ast.unparse(tree)
    for gone in ("_POSITIONWISE", "_check_layer", "_walk_step",
                 "_walk_prefill", "_walk_verify", "'h' in entry"):
        assert gone not in src, gone


# -- the K/V leaf is declared from what the engine observes (PR 44) ----------
def _packing_net(use_pallas, seed=7, **heads):
    """One causal attention layer with `opt350m`'s heads (16 of 64: 8 rows of
    128 lanes a position), or `heads`, on an 8-wide stream."""
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    heads = {"n_heads": 16, **heads}
    return _net(SelfAttentionLayer(n_out=F, head_dim=64, causal=True,
                                   use_pallas=use_pallas,
                                   activation="identity", **heads), seed=seed)


def _through_every_leg(eng):
    """Tokens and probability rows of prefill, three steps, a verify window
    over a rolled-back slot, and a step after it."""
    rows, toks = [], []
    cache, nid, probs = eng.prefill(eng.init_cache(), 1, [3, 1, 4, 1, 5, 9])
    toks.append(nid), rows.append(eng.read_probs(probs))
    ids = np.zeros((2,), np.int32)
    for _ in range(3):
        ids[1] = toks[-1]
        cache, nxt, probs = eng.step(cache, ids)
        toks.append(int(nxt[1])), rows.append(eng.read_probs(probs[1]))
    cache = eng.set_length(cache, 1, 7)         # two tokens rolled back
    cache, window = eng.verify(cache, 1, [2, 7, 1], 7)
    rows.extend(window)
    cache = eng.set_length(cache, 1, 9)         # two of the window accepted
    ids[1] = 1
    cache, nxt, probs = eng.step(cache, ids)
    toks.append(int(nxt[1])), rows.append(eng.read_probs(probs[1]))
    return toks, np.stack(rows)


@pytest.mark.parametrize("how,leaf", [
    ({}, (2, 32, 8, 128)),                              # packed
    ({"mesh": 4}, (2, 32, 16, 64)),                     # 4 heads a shard
    ({"paged": True, "block_size": 8}, (9, 8, 16, 64)),  # the pool's shape
    ({"use_pallas": False}, (2, 32, 16, 64)),           # no kernel reads it
], ids=["packed", "mesh_1x4", "paged", "no_kernel"])
def test_the_kv_leaf_packs_where_one_shards_heads_fill_whole_tiles(how, leaf):
    """16 heads of 64 are 8 rows of 128 lanes a position: the slab leaf is
    declared `[slots, capacity, 8, 128]` where the step's kernel reads it and
    ONE shard holds whole (8, 128) tiles — not under a 1 x 4 mesh (2 rows a
    shard), not paged, not without the kernel. Whatever the leaf, the engine
    gives what the plain (`use_pallas=False`) engine gives, token for token
    and row for row, through prefill, step, rollback and `verify`."""
    how = dict(how)
    net = _packing_net(how.pop("use_pallas", True))
    if "mesh" in how:
        net = MeshContext({"n_data": 1, "n_model": how.pop("mesh")},
                          devices=jax.devices()[:4]).wrap(net)
    eng = DecodeEngine(net, slots=2, max_len=32, **how)
    assert eng._entries["1"]["k"].shape == eng._entries["1"]["v"].shape == leaf
    assert eng.init_cache()["layers"]["1"]["k"].shape == leaf
    want = DecodeEngine(_packing_net(False), slots=2, max_len=32)
    if eng.paged:           # no verify on the pool: the tokens of a request
        assert eng.generate([3, 1, 4, 1, 5, 9], 8) == \
            want.generate([3, 1, 4, 1, 5, 9], 8)
        return
    toks, rows = _through_every_leg(eng)
    want_toks, want_rows = _through_every_leg(want)
    assert toks == want_toks
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("kv,how,leaf", [
    (8, {}, (2, 16, 8, 128)),               # packed, two positions a tile
    (4, {}, (2, 8, 8, 128)),                # 2 rows a position: four
    (2, {}, (2, 4, 8, 128)),                # 1 row: eight
    (8, {"mesh": 4}, (2, 32, 8, 64)),       # a tile would mix shards' rows
    (8, {"paged": True, "block_size": 8}, (9, 8, 8, 64)),
    (8, {"use_pallas": False}, (2, 32, 8, 64)),
], ids=["8_heads", "4_heads", "2_heads", "mesh_1x4", "paged", "no_kernel"])
def test_the_kv_leaf_packs_into_whole_tiles_where_rows_are_half_a_tile(
        kv, how, leaf):
    """32 query heads on 8 K/V heads of 64 (`granite4_h_micro`'s layer) are
    4 rows of 128 lanes a position, half a tile: the slab leaf is declared
    packed AND in whole tiles, `[slots, capacity * 4 / 8, 8, 128]`, where
    the step's kernel reads it and no model axis splits the heads — plain
    under a mesh, paged, or without the kernel. Whatever the leaf, the
    engine gives what the plain engine gives, token for token and row for
    row, through prefill, step, rollback and `verify` (whose window starts
    inside a tile: position 7)."""
    how = dict(how)
    heads = dict(n_heads=32, n_kv_heads=kv)
    net = _packing_net(how.pop("use_pallas", True), **heads)
    if "mesh" in how:
        net = MeshContext({"n_data": 1, "n_model": how.pop("mesh")},
                          devices=jax.devices()[:4]).wrap(net)
    eng = DecodeEngine(net, slots=2, max_len=32, **how)
    assert eng._entries["1"]["k"].shape == eng._entries["1"]["v"].shape == leaf
    assert eng.init_cache()["layers"]["1"]["k"].shape == leaf
    want = DecodeEngine(_packing_net(False, **heads), slots=2, max_len=32)
    assert eng.cache_bytes() == want.cache_bytes() or eng.paged
    if eng.paged:
        assert eng.generate([3, 1, 4, 1, 5, 9], 8) == \
            want.generate([3, 1, 4, 1, 5, 9], 8)
        return
    toks, rows = _through_every_leg(eng)
    want_toks, want_rows = _through_every_leg(want)
    assert toks == want_toks
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-6)


def test_a_windows_ring_of_half_tile_packed_rows_decodes_as_the_plain_one():
    """A sliding window over 8 K/V heads of 64: the ring (8 positions) is
    declared packed in whole tiles too, a prompt longer than the ring is
    cut and turned into it by a plain reshape, and the tokens are the plain
    engine's well past the wrap."""
    heads = dict(n_heads=32, n_kv_heads=8, window=8)
    eng = DecodeEngine(_packing_net(True, **heads), slots=2, max_len=64)
    assert eng._entries["1"]["k"].shape == (2, 4, 8, 128)
    want = DecodeEngine(_packing_net(False, **heads), slots=2, max_len=64)
    assert want._entries["1"]["k"].shape == (2, 8, 8, 64)
    for prompt in ([3, 1, 4], list(range(1, 12)), list(range(20))):
        assert eng.generate(prompt, 14) == want.generate(prompt, 14)

