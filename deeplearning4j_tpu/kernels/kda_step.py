"""One token of the delta-rule recurrence of Kimi Delta Attention, every
decode slot, in place.

The decode step of a KDA layer (nn/layers/kda.py) keeps, per slot and head, a
matrix state `S[d_k, d_v]` and advances it by one token under a per-channel
decay `a = exp(g)` (one value a row of S, not one a head as `ssm_step` has):

    S' = Diag(a) S
    u  = beta (v - S'^T k)              the rank-1 correction reads the
    S  = S' + k u^T                     DECAYED state: `ssm_step` has no such
    o  = S^T q                          term

`kda_step` does that for all slots with ONE `pallas_call` (named `kda_step`)
whose state operand is aliased onto its output: each element of the state is
read once and written once and the state is never held twice. A grid step is
one HEAD GROUP of one slot — the most heads whose state fits
`_STATE_TILE_BYTES`: all 32 heads of 128 x 128 float32 at `ling3_flash`'s
width (2 MB, one group a slot), two groups of 32 at `solar_open2`'s 64 heads
(4 MB a slot) — and walks the group's heads unrolled. d_v lies on the lanes
and d_k on the sublanes, so S'^T k and S^T q are sums down the sublanes
(vector adds, no reduction across lanes) that leave as lane-dense rows, and
`a`, `k`, `q` and `beta k` are wanted as columns. They ride in ONE operand
`[slots, groups, d_k, 4 Hg]` — column h of each quarter is the group's head
h's vector — which is exactly one lane tile a group at 32 heads: a `[d_k,
1]` column an operand a head would each pad to 128 lanes in VMEM (2 MB an
operand a group).

The plain `jax.numpy` form (`_kda_step_reference`) is the semantics, the
path off the TPU, and the fallback when the shapes do not tile or under a
serving mesh, counted in `pallas_fallback_total{kernel="kda_step"}` like the
other kernels'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import LANES, _interpret_default, _note_fallback

# a head group's state [Hg, d_k, d_v]; in and out, double-buffered, four of them
_STATE_TILE_BYTES = 2 << 20
_VMEM_LIMIT_BYTES = 32 << 20


def _kda_step_reference(state, decay, k, q, beta, v):
    """state [S, H, Dk, Dv]; decay, k, q [S, H, Dk]; beta [S, H]; v [S, H,
    Dv] -> (state', o [S, H, Dv]), all in the state's dtype."""
    new = state * decay[..., None]
    u = beta[..., None] * (v - jnp.sum(new * k[..., None], axis=2))
    new = new + k[..., None] * u[:, :, None, :]
    return new, jnp.sum(new * q[..., None], axis=2)


def _kda_tiles(H, Dk, Dv, itemsize, interpret):
    """Heads a grid step: the largest divisor of H whose state [Hg, d_k,
    d_v] fits `_STATE_TILE_BYTES` — or None where the compiled kernel cannot
    walk it: d_v has to be a multiple of the lanes, d_k of the sublanes, and
    a group that is not the whole slot a multiple of 8 heads (its rows
    `beta v` and `o` are a [Hg, d_v] block). Interpret mode takes any
    shape."""
    if not interpret and (Dv % LANES or Dk % 8):
        return None
    fits = max(1, _STATE_TILE_BYTES // (Dk * Dv * itemsize))
    for Hg in range(min(H, fits), 0, -1):
        if H % Hg == 0 and (interpret or Hg == H or Hg % 8 == 0):
            return Hg
    return None


def _kda_step_kernel(cols_ref, bv_ref, s_ref, so_ref, o_ref, *, heads):
    """One head group of one slot: cols_ref [1, 1, Dk, >= 4 Hg] holds the
    columns (decay | k | q | beta k, a head a lane), bv_ref / o_ref [1, Hg,
    Dv] the rows beta v and o, s_ref and so_ref the same [1, Hg, Dk, Dv]
    tile of the same buffer."""
    for h in range(heads):
        col = lambda i: cols_ref[0, 0, :, i * heads + h:i * heads + h + 1]
        new = s_ref[0, h] * col(0)
        u = bv_ref[0, h:h + 1, :] - jnp.sum(new * col(3), axis=0,
                                            keepdims=True)
        new = new + col(1) * u
        so_ref[0, h] = new
        o_ref[0, h:h + 1, :] = jnp.sum(new * col(2), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _kda_step_call(state, decay, k, q, beta, v, Hg, interpret):
    """Jitted, so the layers of one step program share one trace and one
    lowering of the kernel (as `_ssm_step_call`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, Dk, Dv = state.shape
    G = H // Hg
    cols = jnp.concatenate(
        [jnp.swapaxes(a.reshape(S, G, Hg, Dk), 2, 3)
         for a in (decay, k, q, beta[..., None] * k)], axis=3)
    width = -(-4 * Hg // LANES) * LANES
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, 0), (0, width - 4 * Hg)))
    tile = pl.BlockSpec((1, Hg, Dk, Dv), lambda s, g: (s, g, 0, 0))
    rows = pl.BlockSpec((1, Hg, Dv), lambda s, g: (s, g, 0))
    return pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=Hg),
        grid=(S, G),
        in_specs=[pl.BlockSpec((1, 1, Dk, width), lambda s, g: (s, g, 0, 0)),
                  rows, tile],
        out_specs=[tile, rows],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, H, Dv), state.dtype)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="kda_step",
    )(cols, beta[..., None] * v, state)


def kda_step(state, decay, k, q, beta, v, *, use_pallas=True, interpret=None):
    """Advance every slot's delta-rule state one token, in place when it is
    donated.

    state: [slots, heads, d_k, d_v] — the cache leaf, float32 (or whatever
    accumulation dtype the layer keeps); decay: [slots, heads, d_k] — exp(g)
    of the token, a value a channel; k, q: [slots, heads, d_k], normalised
    by the layer; beta: [slots, heads]; v: [slots, heads, d_v]. Everything
    is computed in the state's dtype. Returns (state', o [slots, heads,
    d_v]); the output norm and gate are the layer's.

    Gives way to `_kda_step_reference` when the shapes do not tile
    (`_kda_tiles`), under a serving mesh (GSPMD cannot partition a Mosaic
    kernel and the per-shard wrapper is not written) or with
    `use_pallas=False`; the first two are counted."""
    _, H, Dk, Dv = state.shape
    dt = state.dtype
    decay, k, q, beta, v = (jnp.asarray(a, dt) for a in (decay, k, q, beta, v))
    if not use_pallas:
        return _kda_step_reference(state, decay, k, q, beta, v)
    if interpret is None:
        interpret = _interpret_default()
    Hg = _kda_tiles(H, Dk, Dv, dt.itemsize, interpret)
    if not Hg or not jax.sharding.get_abstract_mesh().empty:
        _note_fallback("kda_step", "jnp_mesh" if Hg else "jnp", H=H,
                       Dk=Dk, Dv=Dv, interpret=interpret)
        return _kda_step_reference(state, decay, k, q, beta, v)
    return _kda_step_call(state, decay, k, q, beta, v, Hg, interpret)
