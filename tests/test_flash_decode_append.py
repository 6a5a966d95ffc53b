"""The decode kernel that appends (`flash_decode_append` of
`kernels/flash_attention.py`, interpret mode on the CPU) against the two
calls it folds: the slabs it leaves are `kv_append`'s and its rows are
`flash_decode`'s on the appended cache, BIT FOR BIT — the token's lane in
VMEM holds what the round trip through HBM would have brought back, and the
block's arithmetic is the same. Key blocks of 256 positions in a capacity of
1024 (four blocks a slot, two 128-position tiles a block), so the position
falls in the first and in the last block of a slot, on lane 0 and lane 127 of
a tile, in either tile of a block, at C - 1 (the engine's clamp), with short
and full slots in one call. Shapes that do not tile take the two calls and
count in `pallas_fallback_total{kernel="flash_decode"}`. A PACKED cache
(`packed_rows`: heads of 64 two to a 128-lane row, since PR 44) takes the
row-major kernel: the slabs bit for bit after unpacking, the rows to float32
rounding. Packed rows that are half a tile a position or less (8, 4 or 2
heads of 64: `tiled_rows`, since PR 49) are declared in whole tiles as well
and take the same kernel with both of its halves on. The compiled kernel —
one launch, in place — is in tests/test_tpu_compile.py."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

SLOTS, CAPACITY, KV_HEADS, DIM, BLOCK = 4, 1024, 2, 16, 256
C = CAPACITY
POSITIONS = {
    "first_block_lane_0": [0] * SLOTS,
    "first_block_lane_127": [127] * SLOTS,
    "first_block_second_tile": [128, 255, 129, 254],
    "last_block_lane_0": [C - BLOCK] * SLOTS,
    "last_block_lane_127": [C - BLOCK + 127] * SLOTS,
    "clamped_at_capacity": [C - 1] * SLOTS,
    "short_and_full": [0, C - 1, 300, 127],
    "block_edges": [255, 256, 511, 512],
}


def bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def operands(dtype, heads, seed=0, shape=(SLOTS, CAPACITY, KV_HEADS, DIM)):
    S, _, H, D = shape
    rng = np.random.default_rng(seed)
    k, v = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2))
    k_new, v_new = (jnp.asarray(rng.normal(size=(S, 1, H, D)), dtype)
                    for _ in range(2))
    # a value a sum over zeros would not hand through
    k_new = k_new.at[0, 0, 0, 0].set(-0.0)
    q = jnp.asarray(rng.normal(size=(S, 1, heads, D)), dtype)
    return q, k, v, k_new, v_new


def fused(*args, **how):
    return jax.jit(lambda *a: fa.flash_decode_append(
        *a, block_k=BLOCK, **how))(*args)


@pytest.mark.parametrize("positions", POSITIONS)
@pytest.mark.parametrize("heads", [KV_HEADS, 3 * KV_HEADS],
                         ids=["equal_heads", "grouped_heads"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
def test_fused_call_matches_append_then_decode_bit_for_bit(dtype, heads,
                                                           positions):
    q, k, v, k_new, v_new = operands(dtype, heads)
    pos = jnp.asarray(POSITIONS[positions], jnp.int32)
    size = jnp.dtype(dtype).itemsize
    assert fa._decode_block(C, heads, DIM, size, BLOCK, True) == BLOCK
    assert fa._append_block(C, DIM, size, True) == 128
    want_k, want_v = fa.kv_append(k, v, k_new, v_new, pos)
    want = fa.flash_decode(q, want_k, want_v, pos + 1, block_k=BLOCK)
    got, got_k, got_v = fused(q, k, v, k_new, v_new, pos)
    assert got.shape == q.shape and got.dtype == q.dtype
    for g, w in ((got_k, want_k), (got_v, want_v), (got, want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(bits(g), bits(w))
    # and against the plain semantics: XLA's update, the masked row
    ref_k, ref_v = fa._append_reference(k, v, k_new, v_new, pos)
    np.testing.assert_array_equal(bits(got_k), bits(ref_k))
    np.testing.assert_array_equal(bits(got_v), bits(ref_v))
    ref = fa._decode_reference(q, ref_k, ref_v, pos + 1, DIM ** -0.5)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("positions", ["short_and_full", "block_edges"])
def test_fused_call_touches_nothing_but_the_appended_position(positions):
    """Every slot: the token at its position, K's in K and V's in V, and
    every other element of both slabs as it was — also past the slot's
    length, where the kernel never reads."""
    q, k, v, k_new, v_new = operands(jnp.float32, KV_HEADS, seed=2)
    at = np.asarray(POSITIONS[positions])
    _, got_k, got_v = fused(q, k, v, k_new, v_new,
                            jnp.asarray(at, jnp.int32))
    untouched = np.ones(k.shape, bool)
    untouched[np.arange(SLOTS), at] = False
    for got, old, new in ((got_k, k, k_new), (got_v, v, v_new)):
        np.testing.assert_array_equal(bits(got)[np.arange(SLOTS), at],
                                      bits(new)[:, 0])
        np.testing.assert_array_equal(bits(got)[untouched],
                                      bits(old)[untouched])
    assert not np.array_equal(bits(got_k), bits(got_v))


def test_fused_call_is_one_kernel_named_flash_decode():
    q, k, v, k_new, v_new = operands(jnp.float32, KV_HEADS)
    pos = jnp.zeros((SLOTS,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(fa.flash_decode_append)(q, k, v, k_new, v_new,
                                                       pos))
    assert jaxpr.count("pallas_call") == 1
    assert "flash_decode" in jaxpr and "kv_append" not in jaxpr
    plain = str(jax.make_jaxpr(lambda *a: fa.flash_decode_append(
        *a, use_pallas=False))(q, k, v, k_new, v_new, pos))
    assert "pallas_call" not in plain and "scatter" in plain


@pytest.mark.parametrize("shape,interpret,kernels", [
    ((2, 256, 2, 128), False, None),  # row-major, 2 K/V heads: no whole tile
    ((2, 1000, 2, 64), False, None),  # a capacity 128 does not divide
    ((2, 64, 2, 16), False, None),    # capacity under one lane tile
    ((2, 32, 2, 192), True, ["flash_decode"]),  # head_dim no multiple of 128
    ((2, 1000, 16, 64), False, None),   # the same capacity, the cache PACKED
], ids=str)
def test_shapes_that_do_not_tile_take_the_two_calls_and_count(shape,
                                                              interpret,
                                                              kernels):
    """What `_append_block`, `_decode_block` and (since PR 43, for a
    row-major cache) `_rows_block` all refuse is the two calls (compiled
    shapes are only traced here: no TPU), counted once; at head_dim >= 128
    the append is then XLA's update and the decode kernel still runs. (8
    K/V heads of 128 take the row-major kernel: tests/test_solar_hybrid.py.)"""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    S, C_, H, D = shape
    q, k, v, k_new, v_new = operands(jnp.float32, H, seed=1, shape=shape)
    if fa.packed_rows(H, D):    # unpacked for the two calls, packed again
        k, v = (x.reshape(S, C_, -1, 128) for x in (k, v))
    pos = jnp.asarray([C_ - 1, 3], jnp.int32)
    labels = dict(kernel="flash_decode", path="kv_append+flash_decode",
                  shape=f"C={C_},D={D},interpret={interpret}")
    counter = lambda: get_registry().get("pallas_fallback_total")
    before = counter().get(**labels) if counter() else 0
    call = lambda **how: (lambda *a: fa.flash_decode_append(
        *a, interpret=interpret, **how))
    jaxpr = str(jax.make_jaxpr(call())(q, k, v, k_new, v_new, pos))
    assert counter().get(**labels) == before + 1
    if not interpret:       # traced only: which kernels the two calls keep
        assert "kv_append" not in jaxpr
        assert jaxpr.count("pallas_call") == (1 if C_ % 128 == 0 else 0)
        return
    assert jaxpr.count("pallas_call") == len(kernels)
    got, got_k, got_v = call()(q, k, v, k_new, v_new, pos)
    want_k, want_v = fa._append_reference(k, v, k_new, v_new, pos)
    want = fa.flash_decode(q, want_k, want_v, pos + 1, interpret=True)
    for g, w in ((got_k, want_k), (got_v, want_v), (got, want)):
        np.testing.assert_array_equal(bits(g), bits(w))
    # use_pallas=False is a choice, not a fallback: not counted
    call(use_pallas=False)(q, k, v, k_new, v_new, pos)
    assert counter().get(**labels) == before + 2


# ---------------------------------------------------------- the packed cache
PACKED_SHAPE = (SLOTS, CAPACITY, 16, 64)        # opt350m's heads: 8 rows of 128


def packed(x):
    """[S, T, H, D] -> [S, T, H * D // 128, 128]: the layer's packing."""
    return x.reshape(*x.shape[:2], -1, 128)


@pytest.mark.parametrize("positions", ["first_block_lane_0",
                                       "clamped_at_capacity",
                                       "short_and_full", "block_edges"])
@pytest.mark.parametrize("heads", [16, 32], ids=["16_on_16", "32_on_16"])
def test_packed_cache_matches_append_then_decode_on_the_unpacked_one(
        heads, positions):
    """16 K/V heads of 64 in float32, two to a row (`opt350m`'s layer, and
    grouped 32 query heads on them): `flash_decode_append` knows the packed
    leaf by its shape and runs the row-major kernel on it — the output to
    float32 rounding of `kv_append` + `flash_decode` on the unpacked cache
    (other products in another order), both slabs bit for bit after
    unpacking, and nothing counted as a fallback."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    q, k, v, k_new, v_new = operands(jnp.float32, heads, seed=3,
                                     shape=PACKED_SHAPE)
    pos = jnp.asarray(POSITIONS[positions], jnp.int32)
    assert fa.packed_rows(16, 64) == 8 and packed(k).shape[2:] == (8, 128)
    assert fa._rows_block(C, 8, 128, 4, BLOCK, True) == BLOCK
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    before = fallbacks.get()
    want_k, want_v = fa.kv_append(k, v, k_new, v_new, pos)
    want = fa.flash_decode(q, want_k, want_v, pos + 1, block_k=BLOCK)
    got, got_k, got_v = fused(q, packed(k), packed(v), k_new, v_new, pos)
    assert fallbacks.get() == before
    assert got.shape == q.shape and got.dtype == q.dtype
    assert got_k.shape == got_v.shape == packed(k).shape
    np.testing.assert_array_equal(bits(got_k.reshape(k.shape)), bits(want_k))
    np.testing.assert_array_equal(bits(got_v.reshape(v.shape)), bits(want_v))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6,
                               atol=2e-6)
    # the two references on a packed cache: unpacked for them, packed again
    ref, ref_k, ref_v = fused(q, packed(k), packed(v), k_new, v_new, pos,
                              use_pallas=False)
    np.testing.assert_array_equal(bits(ref_k), bits(got_k))
    np.testing.assert_array_equal(bits(ref_v), bits(got_v))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-6,
                               atol=2e-6)


def test_packed_call_touches_nothing_but_the_appended_position():
    q, k, v, k_new, v_new = operands(jnp.float32, 16, seed=4,
                                     shape=PACKED_SHAPE)
    at = np.asarray(POSITIONS["short_and_full"])
    _, got_k, got_v = fused(q, packed(k), packed(v), k_new, v_new,
                            jnp.asarray(at, jnp.int32))
    untouched = np.ones(k.shape, bool)
    untouched[np.arange(SLOTS), at] = False
    for got, old, new in ((got_k, k, k_new), (got_v, v, v_new)):
        got = bits(got.reshape(old.shape))
        np.testing.assert_array_equal(got[np.arange(SLOTS), at],
                                      bits(new)[:, 0])
        np.testing.assert_array_equal(got[untouched], bits(old)[untouched])


def test_packed_call_is_one_kernel_named_flash_decode():
    q, k, v, k_new, v_new = operands(jnp.float32, 16, shape=PACKED_SHAPE)
    pos = jnp.zeros((SLOTS,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(fa.flash_decode_append)(
        q, packed(k), packed(v), k_new, v_new, pos))
    assert jaxpr.count("pallas_call") == 1
    assert "flash_decode" in jaxpr and "kv_append" not in jaxpr


@pytest.mark.parametrize("H,D,shards,rows", [
    (16, 64, 1, 8), (32, 64, 2, 16), (32, 32, 1, 8), (64, 16, 1, 8),
    (8, 64, 1, None),       # granite4_h_micro: 4 rows, half a tile: not
                            # this leaf but `tiled_rows`' (below)
    (16, 64, 4, None),      # opt350m on a 1 x 4 mesh: 2 rows a shard
    (16, 64, 2, None),      # ... and on two model shards: 4
    (16, 64, 3, 8),         # an axis that does not divide the heads: whole
    (8, 128, 1, None),      # a row already
    (16, 96, 1, None),      # does not divide the lanes
], ids=str)
def test_packed_rows_asks_for_whole_tiles_in_the_shard(H, D, shards, rows):
    assert fa.packed_rows(H, D, shards) == rows


# ---------------------------------- packed AND in whole tiles: the two compose
@pytest.mark.parametrize("C_,H,D,shards,tiles", [
    (1024, 8, 64, 1, 512),      # granite4_h_micro: 4 rows, two positions a tile
    (1024, 4, 64, 1, 256),      # 2 rows: four positions a tile
    (1024, 2, 64, 1, 128),      # 1 row: eight
    (1024, 8, 32, 1, 256),      # four heads to a row
    (1024, 16, 64, 1, None),    # whole tiles a position: `packed_rows`' leaf
    (1024, 8, 64, 2, None),     # a model axis: a tile would mix shards' rows
    (1024, 16, 64, 4, None),    # opt350m on a 1 x 4 mesh stays unpacked
    (1024, 6, 64, 1, None),     # 3 rows divide no tile
    (1024, 1, 64, 1, None),     # half a lane row
    (1024, 8, 96, 1, None),     # does not divide the lanes
    (1023, 8, 64, 1, None),     # an odd count of positions: half a tile over
    (1022, 8, 64, 1, 511), (1022, 4, 64, 1, None),
], ids=str)
def test_tiled_rows_packs_narrow_heads_first(C_, H, D, shards, tiles):
    assert fa.tiled_rows(C_, H, D, shards) == tiles
    # one leaf a cache: never both
    assert not (tiles and fa.packed_rows(H, D, shards))


def composite(x):
    """[S, T, H, D] -> [S, T * H * D // 1024, 8, 128]: packed, whole tiles."""
    return x.reshape(x.shape[0], -1, 8, 128)


COMPOSITE_POSITIONS = {
    "length_1": [0] * SLOTS,
    "a_tiles_two_positions": [6, 7, 300, 301],
    "a_blocks_edge": [255, 256, 254, 257],
    "capacity": [C - 1, C - 2, 0, C - 1],
}


@pytest.mark.parametrize("positions", COMPOSITE_POSITIONS)
@pytest.mark.parametrize("ring", [False, True], ids=["slab", "ring"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("H", [8, 4, 2], ids=lambda h: f"32_on_{h}")
def test_packed_cache_in_whole_tiles_matches_append_then_decode(
        H, dtype, ring, positions):
    """32 query heads on 8 K/V heads of 64 (`granite4_h_micro`'s layer; 4
    and 2 heads: 2 rows and 1 row a position), the leaf packed and declared
    in whole tiles, `[S, C * rows / 8, 8, 128]`: `flash_decode_append` knows
    it by its shape against the token's and runs the row-major kernel with
    `pack` and the staged tile write both on — both slabs bit for bit
    `kv_append`'s / the plain update's after unpacking, the output against
    the masked row at the file's tolerance, nothing counted as a fallback.
    A ring (`ring=True`) two turns on writes at pos % C and reads all of
    it."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    shape = (SLOTS, CAPACITY, H, 64)
    q, k, v, k_new, v_new = operands(dtype, 32, seed=6, shape=shape)
    pos = jnp.asarray(COMPOSITE_POSITIONS[positions], jnp.int32)
    if ring:        # the same places, a turn or two on for some
        pos = pos + jnp.asarray([0, C, 2 * C, 0], jnp.int32)
    at = pos % C
    size = jnp.dtype(dtype).itemsize
    assert fa.tiled_rows(C, H, 64) == C * H // 16
    assert fa._rows_block(C, H // 2, 128, size, BLOCK, True, tiled=True) \
        == BLOCK
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    before = fallbacks.get()
    got, got_k, got_v = fused(q, composite(k), composite(v), k_new, v_new,
                              pos, ring=ring)
    assert fallbacks.get() == before
    assert got.shape == q.shape and got.dtype == q.dtype
    assert got_k.shape == got_v.shape == (SLOTS, C * H // 16, 8, 128)
    want_k, want_v = fa.kv_append(k, v, k_new, v_new, at)
    ref_k, ref_v = fa._append_reference(k, v, k_new, v_new, at)
    for g, w, r in ((got_k, want_k, ref_k), (got_v, want_v, ref_v)):
        np.testing.assert_array_equal(bits(g.reshape(w.shape)), bits(w))
        np.testing.assert_array_equal(bits(g.reshape(r.shape)), bits(r))
    ref = fa._decode_reference(q, ref_k, ref_v, jnp.minimum(pos + 1, C),
                               64 ** -0.5)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), rtol=tol,
                               atol=tol)
    # the two references on the composite leaf: unpacked, packed again
    off, off_k, off_v = fused(q, composite(k), composite(v), k_new, v_new,
                              pos, ring=ring, use_pallas=False)
    np.testing.assert_array_equal(bits(off_k), bits(got_k))
    np.testing.assert_array_equal(bits(off_v), bits(got_v))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(off, np.float32), rtol=tol,
                               atol=tol)


def test_composite_call_is_one_kernel_and_touches_nothing_else():
    q, k, v, k_new, v_new = operands(jnp.bfloat16, 32, seed=7,
                                     shape=(SLOTS, CAPACITY, 8, 64))
    at = np.asarray(POSITIONS["short_and_full"])
    pos = jnp.asarray(at, jnp.int32)
    jaxpr = str(jax.make_jaxpr(fa.flash_decode_append)(
        q, composite(k), composite(v), k_new, v_new, pos))
    assert jaxpr.count("pallas_call") == 1
    assert "flash_decode" in jaxpr and "kv_append" not in jaxpr
    assert "transpose" not in jaxpr
    _, got_k, got_v = fused(q, composite(k), composite(v), k_new, v_new, pos)
    untouched = np.ones(k.shape, bool)
    untouched[np.arange(SLOTS), at] = False
    for got, old, new in ((got_k, k, k_new), (got_v, v, v_new)):
        got = bits(got.reshape(old.shape))
        np.testing.assert_array_equal(got[np.arange(SLOTS), at],
                                      bits(new)[:, 0])
        np.testing.assert_array_equal(got[untouched], bits(old)[untouched])


def test_a_leaf_that_is_no_view_of_the_tokens_heads_is_refused():
    q, k, v, k_new, v_new = operands(jnp.float32, 32, seed=8,
                                     shape=(2, 256, 8, 64))
    pos = jnp.zeros((2,), jnp.int32)
    for wrong in ((2, 256, 2, 256), (2, 128, 16, 64), (2, 512, 4, 64)):
        with pytest.raises(AssertionError, match="a cache of"):
            fa.flash_decode_append(q, k.reshape(wrong), v.reshape(wrong),
                                   k_new, v_new, pos)


def test_rows_that_do_not_fill_a_tile_take_the_kernel_they_took():
    """8 K/V heads of 64 on a PLAIN leaf (what a model shard of a mesh, or a
    caller that declares nothing, still hands over: the layer itself has
    declared `granite4_h_micro`'s packed in whole tiles since PR 49): the
    positions-minor kernel as before, its block the one it chose before,
    nothing new counted."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    shape = (2, 512, 8, 64)
    q, k, v, k_new, v_new = operands(jnp.bfloat16, 32, seed=5, shape=shape)
    pos = jnp.asarray([511, 7], jnp.int32)
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    before = fallbacks.get()
    jaxpr = jax.make_jaxpr(fa.flash_decode_append)(q, k, v, k_new, v_new, pos)
    assert fallbacks.get() == before
    assert "transpose" in str(jaxpr)        # the [S, H, D, C] view of it
    assert get_registry().get("flash_decode_block").get(
        C=512, H=8, D=64, itemsize=2) == fa._decode_block(512, 32, 64, 2,
                                                          1024, True)
