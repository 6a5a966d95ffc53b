"""Decode attention over a latent cache, and the append that feeds it.

A latent-attention layer (nn/layers/mla.py) caches ONE row a token — the
normed latent `c` (`rank` wide) beside the rotary key `k_pe` — shared by all
heads. In the absorbed form of a decode step head h's query is `[q_nope_h
W_UK,h^T | q_pe_h]`, as wide as the row, its score against a token is the dot
product with that token's row, and what it mixes is the row's first `rank`
values: multi-query attention whose keys are 576 wide and whose values are
the keys' first 512. `flash_decode` takes K and V of one width a head, and
walks heads on the VPU; here all heads share the row, so a key block is read
ONCE for the 32 heads and both products run on the MXU.

`mla_decode` (kernel `mla_decode`): the grid walks the slots; the kernel
walks a slot's LIVE key blocks only, copying each `[block, width]` tile from
the cache (left in HBM) into VMEM itself, for the reason `flash_decode` gives
(a clamped index map would still move every byte). The call's (slot, live
block) pairs are one sequence and `_DEPTH` of its copies are kept in flight
in a ring of `_DEPTH + 1` buffers: the copy of pair p + `_DEPTH` is started
before pair p's is waited for, across the slots' boundaries, so a copy
has the time of `_DEPTH` blocks' arithmetic to arrive in, not of one (with
one copy ahead the loop waited on every block). Blocks are computed on in
the order they lie. Scores,
the online softmax and the accumulator are float32; the two products
multiply in the cache's dtype. `mla_decode_block{C,W,depth}` says at trace
time which plan a program runs.

`latent_append` (kernel `latent_append`): every slot's new row into its
position, in place: the output is aliased onto the cache and of each slot
only the sublane tile that holds the position is read and written back.

Both give way to their plain forms when the shapes do not tile, under a
serving mesh or with `use_pallas=False`, counted in
`pallas_fallback_total{kernel=...}` like the other kernels'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import (NEG_INF, _fit_block, _interpret_default,
                              _live_blocks, _note_fallback)

# A key block's positions, and how many block copies the kernel keeps in
# flight. Alone on a v5e at 64 heads on a 128 x 6,144 x 640 bfloat16 slab
# with `kimik27code_code_decode`'s lengths (359 k live rows; PR 51), ms a call
# at depth 1 / 2 / 3 / 4 / 6: blocks of 128 1.72 / 1.63 / 1.63 / 1.63 / 1.63,
# of 256 1.118 / 0.973 / 0.976 / 0.975 / 0.976, of 512 0.826 / 0.682 / 0.682
# / 0.682 / 0.683. Depth 2 is all there is to have: past it a block's
# arithmetic (0.60 us a 256-position block with no copy in the loop, at 32
# heads as at 64) is the longer, its copy 0.45. A slot's blocks past its
# length are not read, so a shorter block reads less (4 % past the live rows
# at 256, 8 % at 512 at that mix) — and yet 512 is the faster by 30 %: the
# arithmetic costs by the block more than by the position. The block stays
# 256 here because PR 51 changed one thing, the depth; ROADMAP S13 (d).
_BLOCK_POSITIONS = 256
_DEPTH = 2


def _mla_reference(q, latent, lengths, rank):
    """q [S, H, W], latent [S, C, W], lengths [S] -> [S, H, rank] float32:
    softmax over each slot's first `lengths` rows of q . row, times the
    rows' first `rank` values. A slot of length 0 gives the uniform average
    (callers never read it)."""
    C = latent.shape[1]
    lat = latent.astype(jnp.float32)
    s = jnp.einsum("shw,scw->shc", q.astype(jnp.float32), lat)
    valid = jnp.arange(C)[None, :] < lengths[:, None]
    p = jax.nn.softmax(jnp.where(valid[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("shc,scr->shr", p, lat[:, :, :rank])


def _note_mla_block(block_c, **plan):
    """The plan `mla_decode` runs for these shapes, as the gauge
    `mla_decode_block{C,W,depth}` beside `pallas_fallback_total`: the key
    block's positions, and in `depth` how many block copies the kernel keeps
    in flight. Set at trace time, so once per compiled program."""
    from ..telemetry.registry import get_registry
    get_registry().gauge(
        "mla_decode_block",
        "Key-block length (cache positions) of the mla_decode kernel, "
        "labeled with the number of block copies it keeps in flight: a "
        "slot's blocks wholly past its length are not read").set(
            block_c, **plan)


def _mla_kernel(len_ref, q_ref, lat_hbm, o_ref, buf, sem, ring_ref, acc_ref,
                m_ref, l_ref, *, block_c, nk, slots, rank, depth):
    """One slot: q_ref [1, H, W], lat_hbm the whole [S, C, W] cache in HBM,
    o_ref [1, H, rank]. The call's (slot, live block) pairs are one
    sequence, pair p copied into buffer p % (depth + 1); when pair p is
    waited for, the copies of pairs p + 1 .. p + depth are out. Three
    scalars cross the grid step in `ring_ref` (SMEM): the buffer this slot's
    first block is in, and the (slot, block) of the next pair to copy —
    up to `depth` pairs ahead, so in a later slot, several slots later where
    slots hold fewer live blocks than `depth`. Past the last slot's last
    block there is no pair and nothing is started, so the last wait of the
    last slot leaves no copy out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    si = pl.program_id(0)
    length = len_ref[si]
    live = _live_blocks(length, block_c, nk)

    def copy(slot, block, b):
        at = pl.ds(pl.multiple_of(block * block_c, block_c), block_c)
        return pltpu.make_async_copy(lat_hbm.at[slot, at, :], buf.at[b],
                                     sem.at[b])

    def start(slot, block, b):
        """Start the copy of pair (slot, block), if there is one, into
        buffer b; returns the pair after it (slot == slots: none)."""
        @pl.when(slot < slots)
        def _():
            copy(slot, block, b).start()
        last = block + 1 >= _live_blocks(
            len_ref[jnp.minimum(slot, slots - 1)], block_c, nk)
        return (jnp.minimum(slot + last.astype(jnp.int32), slots),
                jnp.where(last, 0, block + 1))

    @pl.when(si == 0)
    def _first():
        pair = jnp.int32(0), jnp.int32(0)
        for b in range(depth):
            pair = start(*pair, b)
        ring_ref[0] = 0
        ring_ref[1], ring_ref[2] = pair

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = q_ref[0]                                            # [H, W]

    def block(j, ring):
        b, slot, ahead = ring
        # the buffer before b in the ring: pair j - 1's, computed on already
        slot, ahead = start(slot, ahead, jnp.where(b == 0, depth, b - 1))
        copy(si, j, b).wait()
        rows = buf[b]                                       # [block_c, W]
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = j * block_c + jax.lax.broadcasted_iota(jnp.int32,
                                                      (1, block_c), 1)
        s = jnp.where(kpos < length, s, NEG_INF)            # [H, block_c]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(rows.dtype), rows[:, :rank],
            preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        return jnp.where(b == depth, 0, b + 1), slot, ahead

    ring_ref[0], ring_ref[1], ring_ref[2] = jax.lax.fori_loop(
        0, live, block, (ring_ref[0], ring_ref[1], ring_ref[2]))

    # l >= 1 always: a fully masked slot sums exp(0) per position
    o_ref[0] = acc_ref[...] / l_ref[...]


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _mla_call(q, latent, lengths, rank, block_c, interpret, depth):
    """Jitted for the reason `_decode_call` is: one trace, one lowering."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, W = q.shape
    C = latent.shape[1]
    return pl.pallas_call(
        functools.partial(_mla_kernel, block_c=block_c, nk=C // block_c,
                          slots=S, rank=rank, depth=depth),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, H, W), lambda s, lens: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, rank), lambda s, lens: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((depth + 1, block_c, W), latent.dtype),  # tiles
                pltpu.SemaphoreType.DMA((depth + 1,)),
                pltpu.SMEM((3,), jnp.int32),     # buffer, next pair to copy
                pltpu.VMEM((H, rank), jnp.float32),          # acc
                pltpu.VMEM((H, 1), jnp.float32),             # running max
                pltpu.VMEM((H, 1), jnp.float32),             # running sum
            ]),
        out_shape=jax.ShapeDtypeStruct((S, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode",
    )(lengths, q, latent)


def mla_decode(q, latent, lengths, *, rank, use_pallas=True, interpret=None):
    """Absorbed-form decode attention: ONE query row a head a slot against
    the slot's cached latent rows.

    q: [slots, heads, width] — `[q_nope W_UK^T | q_pe]` with the softmax
    scale folded in, in the cache's dtype; latent: [slots, capacity, width]
    — the cache, the step's own row already appended; lengths: [slots]
    int32, valid rows a slot (the current token's included). Returns
    [slots, heads, rank] float32: softmax(q . row) over the valid rows,
    times the rows' first `rank` values (the up-projection W_UV is the
    layer's). A key block wholly past a slot's length is neither read nor
    computed on."""
    S, H, W = q.shape
    C = latent.shape[1]
    lengths = jnp.asarray(lengths, jnp.int32)
    if not use_pallas:
        return _mla_reference(q, latent, lengths, rank)
    if interpret is None:
        interpret = _interpret_default()
    block_c = _fit_block(C, _BLOCK_POSITIONS, 1 if interpret else 128)
    if block_c is None or not jax.sharding.get_abstract_mesh().empty:
        _note_fallback("mla_decode",
                       "reference" if block_c is None else "reference_mesh",
                       C=C, W=W, interpret=interpret)
        return _mla_reference(q, latent, lengths, rank)
    _note_mla_block(block_c, C=C, W=W, depth=_DEPTH)
    return _mla_call(q.astype(latent.dtype), latent, lengths, rank, block_c,
                     interpret, _DEPTH)


def _latent_append_reference(latent, rows, pos):
    return latent.at[jnp.arange(latent.shape[0]), pos].set(rows)


def _latent_append_kernel(pos_ref, new_ref, tile_ref, out_ref, *, tile):
    """One slot: tile_ref / out_ref the same [1, tile, W] sublane tile of the
    same buffer, the one that holds pos[slot]; row pos % tile is replaced,
    every other row written back as read. Through float32, which hands a
    bfloat16 through bit for bit."""
    from jax.experimental import pallas as pl
    hit = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) \
        == pos_ref[pl.program_id(0)] % tile
    out_ref[0] = jnp.where(hit, new_ref[0].astype(jnp.float32),
                           tile_ref[0].astype(jnp.float32)
                           ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _latent_append_call(latent, rows, pos, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, C, W = latent.shape
    at = pl.BlockSpec((1, tile, W), lambda s, pos: (s, pos[s] // tile, 0))
    return pl.pallas_call(
        functools.partial(_latent_append_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, 1, W), lambda s, pos: (s, 0, 0)), at],
            out_specs=at),
        out_shape=jax.ShapeDtypeStruct(latent.shape, latent.dtype),
        # operands count from the prefetched scalar: 2 is the cache
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_append",
    )(pos, rows[:, None, :], latent)


def latent_append(latent, rows, pos, *, use_pallas=True, interpret=None):
    """latent [slots, capacity, width] with latent[s, pos[s]] = rows[s],
    every other element as it was; in place when the cache is donated.
    rows: [slots, width] in the cache's dtype; pos: [slots] int32 inside
    [0, capacity)."""
    S, C, W = latent.shape
    pos = jnp.asarray(pos, jnp.int32)
    rows = rows.astype(latent.dtype)
    if not use_pallas:
        return _latent_append_reference(latent, rows, pos)
    if interpret is None:
        interpret = _interpret_default()
    tile = _fit_block(C, 32 // latent.dtype.itemsize,
                      1 if interpret else 32 // latent.dtype.itemsize)
    if tile is None or not jax.sharding.get_abstract_mesh().empty:
        _note_fallback("latent_append",
                       "scatter" if tile is None else "scatter_mesh",
                       C=C, W=W, interpret=interpret)
        return _latent_append_reference(latent, rows, pos)
    return _latent_append_call(latent, rows, pos, tile, interpret)
