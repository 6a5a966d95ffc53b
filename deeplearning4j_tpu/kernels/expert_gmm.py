"""The grouped product of a routed expert layer: rows sorted by expert, each
row tile times ITS expert's matrices, the experts that got no row never read.

An expert layer (nn/layers/feedforward.py, MixtureOfExpertsLayerModule) lays
the (token, expert) pairs it has to compute out as rows `[m, k]` sorted by
expert, every expert's run starting on a multiple of the row tile `tm` and
padded to the next (`group_tiles`), so a row tile belongs to ONE expert and
nothing has to be masked. A padding row holds ANY finite row (the layer
leaves some token's there): both products treat rows apart, and the caller
reads only the rows it laid pairs on:

    tile t, of group g = tile_group[t]:
        (a, b) = split(rows[t] @ w1[g])          w1 [g, k, 2 h]
        out[t] = (silu(a) * b) @ w2[g]           w2 [g, h, n]

`expert_gmm` does that with ONE `pallas_call` a layer, so a layer is one
device operation whose bytes are the layer's (`tile_group` and `n_tiles` are
scalar-prefetched; the grid's first extent IS `n_tiles`, so the tiles after
the last row are not visited and an expert without rows costs nothing). A
tile walks the `k` blocks of its expert's `w1` into a float32 accumulator,
gates it in float32, and then walks the `n` blocks of `w2`; both matrices
stream through VMEM once a tile in blocks whose rows are contiguous in HBM.
The grid is the one of `jax.experimental.pallas.ops.tpu.megablox.gmm` (tiles
chosen by prefetched scalars, a data-dependent extent) with its masked store
and its metadata (two `repeat`s, a histogram and two rolls a call) traded
for the aligned layout, and the two products and the gate fused. The kernel
is named `expert_gmm_<what called it>` (`tag`: the leading shape of the
layer's input), which tells a trace reader the decode step's call sites from
a prefill's.

The plain form (`_gmm_reference`: two `lax.ragged_dot`s) is the semantics,
the path off the TPU, the backward (`jax.vjp` of it: no backward kernel yet)
and the fallback when the shapes do not tile or under a serving mesh,
counted in `pallas_fallback_total{kernel="expert_gmm"}` like the others'.
Rows of the tiles past `n_tiles` are never read and come out zero in the
plain form, UNDEFINED in the kernel's output: the caller reads only the rows
it laid out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import (LANES, _fit_block, _interpret_default,
                              _note_fallback)

# one block of w1 [tk, 2 h] / of w2 [h, tn]; two of each are in flight
_WEIGHT_BLOCK_BYTES = 3 << 20
_VMEM_LIMIT_BYTES = 64 << 20
_ROW_TILES = (8, 16, 32, 64, 128, 256, 512)


def row_tile(pairs, n_experts, itemsize):
    """Rows a tile for `pairs` (token, expert) pairs spread over `n_experts`:
    the power of two that holds twice an expert's mean share, so nearly
    every expert is one tile and its matrices stream once; at least a packed
    sublane tile (16 rows of bfloat16), at most 512.

    The floor is what MANY SMALL GROUPS pay. `granite4_h_small`'s decode
    step lays 80 pairs over 18 held experts (4.4 rows a group in tiles of
    16: 3.6 rows computed a pair). `ling3_flash`'s lays 126 pairs over 64
    held experts — one routing group of 512 under group-limited routing, 2
    rows a group at the mean, 53 of the 64 touched — into 53 tiles of 16:
    846 rows computed for 126 pairs, 6.8 a pair (counted over 20 seeded
    routers at 128 rows; 2.0 a pair at 512 and 1,024 rows). The padding
    costs arithmetic the MXU has to spare and no bytes: a tile streams its
    expert's matrices once whatever it holds, so the step's cost is the
    touched experts' 11.8 MB each, and an 8-row tile would save nothing a
    bfloat16 sublane tile allows."""
    want = max(32 // itemsize, 2 * pairs // max(1, n_experts))
    return next((t for t in _ROW_TILES if t >= want), _ROW_TILES[-1])


def group_tiles(group_sizes, tm, max_tiles):
    """The aligned layout of `group_sizes` [g] rows: group i takes
    ceil(size_i / tm) row tiles, one group after another. Returns
    (tile_group [max_tiles] int32: the group of each tile, the last group's
    for the tiles after `n_tiles`; n_tiles: tiles that hold rows; tile_start
    [g]: each group's first tile). `max_tiles` >= rows // tm + g always
    suffices."""
    tiles = (group_sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    last = group_sizes.shape[0] - 1
    # a compare and a sum, not `searchsorted`: that is a `while` on the TPU
    tile_group = jnp.minimum(jnp.sum(
        tile_end[None, :] <= jnp.arange(max_tiles)[:, None], axis=1), last)
    return tile_group.astype(jnp.int32), tile_end[-1].astype(jnp.int32), \
        (tile_end - tiles).astype(jnp.int32)


def _silu_gate(acc, hidden):
    a, b = acc[:, :hidden], acc[:, hidden:]
    return a / (1.0 + jnp.exp(-a)) * b


def tile_rows(tile_group, n_tiles, groups, tm):
    """[groups] int32: the rows each group takes in the aligned layout, its
    tiles (those before `n_tiles`) times `tm` — `lax.ragged_dot`'s sizes."""
    live = jnp.arange(tile_group.shape[0]) < n_tiles
    return tm * jnp.sum((tile_group[:, None] == jnp.arange(groups)[None])
                        & live[:, None], axis=0, dtype=jnp.int32)


def _gmm_reference(rows, w1, w2, tile_group, n_tiles):
    """rows [m, k], w1 [g, k, 2 h], w2 [g, h, n] -> [m, n]; rows of tiles
    past `n_tiles` come out zero."""
    sizes = tile_rows(tile_group, n_tiles, w1.shape[0],
                      rows.shape[0] // tile_group.shape[0])
    acc = jnp.promote_types(rows.dtype, jnp.float32)
    h = _silu_gate(lax.ragged_dot(rows, w1, sizes,
                                  preferred_element_type=acc),
                   w2.shape[1]).astype(rows.dtype)
    return lax.ragged_dot(h, w2, sizes,
                          preferred_element_type=acc).astype(rows.dtype)


def _gmm_blocks(k, hidden, n, itemsize, tm, interpret):
    """(tk, tn): the rows of w1 and the columns of w2 a block — the largest
    divisors inside `_WEIGHT_BLOCK_BYTES` — or None => fall back. Compiled,
    the gate splits the accumulator at a lane tile (hidden a multiple of
    128), blocks are multiples of 128 and `tm` a packed sublane tile;
    interpret mode takes anything."""
    if interpret:
        return k, n
    if hidden % LANES or tm % (32 // itemsize):
        return None
    tk = _fit_block(k, max(LANES, _WEIGHT_BLOCK_BYTES
                           // (2 * hidden * itemsize)), LANES)
    tn = _fit_block(n, max(LANES, _WEIGHT_BLOCK_BYTES
                           // (hidden * itemsize)), LANES)
    return None if tk is None or tn is None else (tk, tn)


def _gmm_kernel(group_ref, n_ref, x_ref, w1_ref, w2_ref, o_ref, acc_ref,
                h_ref, *, s1, hidden):
    """Grid (tile, s): steps s < s1 add block s of rows[t] @ w1[g] to the
    accumulator, step s1 - 1 gates it into h_ref, steps s >= s1 write block
    s - s1 of h @ w2[g]. The index maps hold each operand at its last block
    while the other product runs, so nothing is copied twice."""
    from jax.experimental import pallas as pl
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < s1)
    def _():
        acc_ref[...] += jnp.dot(x_ref[...], w1_ref[...],
                                preferred_element_type=acc_ref.dtype)

    @pl.when(s == s1 - 1)
    def _():
        h_ref[...] = _silu_gate(acc_ref[...], hidden).astype(h_ref.dtype)

    @pl.when(s >= s1)
    def _():
        o_ref[...] = jnp.dot(h_ref[...], w2_ref[...],
                             preferred_element_type=acc_ref.dtype
                             ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _gmm_call(rows, w1, w2, tile_group, n_tiles, tk, tn, interpret, name):
    """Jitted, so the layers of one program share one trace and one lowering
    of the kernel (as `_decode_call`, `_ssm_step_call`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    m, k = rows.shape
    hidden, n = w2.shape[1], w2.shape[2]
    tm = m // tile_group.shape[0]
    s1, s2 = k // tk, n // tn
    acc = jnp.promote_types(rows.dtype, jnp.float32)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, s1=s1, hidden=hidden),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles[0], s1 + s2),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda t, s, grp, nt:
                             (t, jnp.minimum(s, s1 - 1))),
                pl.BlockSpec((None, tk, 2 * hidden), lambda t, s, grp, nt:
                             (grp[t], jnp.minimum(s, s1 - 1), 0)),
                pl.BlockSpec((None, hidden, tn), lambda t, s, grp, nt:
                             (grp[t], 0, jnp.maximum(s - s1, 0))),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda t, s, grp, nt:
                                   (t, jnp.maximum(s - s1, 0))),
            scratch_shapes=[pltpu.VMEM((tm, 2 * hidden), acc),
                            pltpu.VMEM((tm, hidden), rows.dtype)]),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(tile_group, n_tiles, rows, w1, w2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _gmm(rows, w1, w2, tile_group, n_tiles, tk, tn, interpret, name):
    return _gmm_call(rows, w1, w2, tile_group, n_tiles[None], tk, tn,
                     interpret, name)


def _gmm_fwd(rows, w1, w2, tile_group, n_tiles, tk, tn, interpret, name):
    return _gmm(rows, w1, w2, tile_group, n_tiles, tk, tn, interpret, name), \
        (rows, w1, w2, tile_group, n_tiles)


def _gmm_bwd(tk, tn, interpret, name, res, g):
    rows, w1, w2, tile_group, n_tiles = res
    _, vjp = jax.vjp(lambda r, a, b: _gmm_reference(r, a, b, tile_group,
                                                    n_tiles), rows, w1, w2)
    return (*vjp(g), None, None)    # rows past n_tiles take no part in it


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def expert_gmm(rows, w1, w2, tile_group, n_tiles, *, use_pallas=True,
               interpret=None, tag=""):
    """Every row tile through its expert's gated feed-forward.

    rows: [m, k], m = len(tile_group) * tm, in `group_tiles`'s layout (each
    group's rows from a tile boundary on; up to the next boundary, and past
    tile `n_tiles`, any finite rows: nothing reads their products);
    w1: [g, k, 2 * h] and w2: [g, h, n] — the experts held, no biases;
    tile_group: [tiles] int32, n_tiles: int32 scalar. Returns [m, n] in the
    rows' dtype: (silu(a) * b) @ w2[g] with (a, b) = split(rows @ w1[g]),
    accumulated and gated in float32. Rows of tiles past `n_tiles` are
    undefined (zero in the plain form).

    Gives way to `_gmm_reference` when the shapes do not tile
    (`_gmm_blocks`), under a serving mesh (GSPMD cannot partition a Mosaic
    kernel; the per-shard form is expert parallelism, not written) or with
    `use_pallas=False`; the first two are counted. Differentiable: the
    backward is the plain form's."""
    n_tiles = jnp.asarray(n_tiles, jnp.int32)
    if not use_pallas:
        return _gmm_reference(rows, w1, w2, tile_group, n_tiles)
    if interpret is None:
        interpret = _interpret_default()
    (m, k), hidden, n = rows.shape, w2.shape[1], w2.shape[2]
    tm = m // tile_group.shape[0]
    blocks = _gmm_blocks(k, hidden, n, rows.dtype.itemsize, tm, interpret)
    if blocks is None or not jax.sharding.get_abstract_mesh().empty:
        _note_fallback("expert_gmm", "jnp" if blocks is None else "jnp_mesh",
                       tm=tm, k=k, h=hidden, n=n, interpret=interpret)
        return _gmm_reference(rows, w1, w2, tile_group, n_tiles)
    return _gmm(rows, w1, w2, tile_group, n_tiles, *blocks, interpret,
                "expert_gmm" + ("_" + tag if tag else ""))
