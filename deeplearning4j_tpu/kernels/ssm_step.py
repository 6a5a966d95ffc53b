"""One token of a diagonal state-space recurrence, every decode slot, in place.

The decode step of a Mamba-2 layer (nn/layers/mamba.py) keeps, per slot, a
state `S[n, c]` — `n` the state index, `c` the channel (head x head_dim) —
and advances it by one token:

    S'[n, c] = decay[c] * S[n, c] + b[n] * dtx[c]
    y[c]     = sum_n S'[n, c] * c_[n]

`ssm_step` does that for all slots with ONE `pallas_call` (named `ssm_step`)
whose state operand is aliased onto its output, as `kv_append` does for the
KV cache: each element of the state is read once and written once, and the
state is never held twice. The channels lie on the lanes and the state index
on the sublanes, so the update is a row (`decay`, `dtx`) and a column (`b`,
`c_`) broadcast against a tile, and `y` a sum down the sublanes — vector
adds, no reduction across lanes — that leaves the kernel as a lane-dense
row. At 64 slots x 128 x 4096 float32 a call moves 2 x 134 MB.

The plain `jax.numpy` form (`_ssm_step_reference`) is the semantics, the
path off the TPU, and the fallback when the shapes do not tile, counted in
`pallas_fallback_total{kernel="ssm_step"}` like the attention kernels'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import (LANES, _fit_block, _interpret_default,
                              _note_fallback)

# one state tile [N, block]; in and out, double-buffered, four of them stay
# inside the default scoped VMEM
_STATE_TILE_BYTES = 2 << 20


def _ssm_step_reference(state, decay, dtx, b, c):
    """state [S, N, C]; decay, dtx [S, C]; b, c [S, N] -> (state', y [S, C]),
    all in the state's dtype."""
    new = state * decay[:, None, :] + b[:, :, None] * dtx[:, None, :]
    return new, jnp.sum(new * c[:, :, None], axis=1)


def _ssm_block(N, C, itemsize, interpret):
    """Channels a tile — the largest divisor of C whose [N, block] tile fits
    `_STATE_TILE_BYTES` — or None => fall back. Compiled, the channels lie on
    the lanes (a multiple of 128) and the state index on the sublanes (a
    multiple of 8); interpret mode takes any divisor."""
    c_align, n_align = (1, 1) if interpret else (LANES, 8)
    if N % n_align:
        return None
    target = min(C, max(c_align, _STATE_TILE_BYTES // (N * itemsize)))
    return _fit_block(C, target, c_align)


def _ssm_step_kernel(decay_ref, dtx_ref, b_ref, c_ref, s_ref, so_ref, y_ref):
    """One slot's [N, block] tile: decay_ref / dtx_ref / y_ref are [1, 1,
    block] rows, b_ref / c_ref [1, N, 1] columns, s_ref and so_ref the same
    tile of the same buffer (aliased)."""
    new = s_ref[0] * decay_ref[0] + b_ref[0] * dtx_ref[0]
    so_ref[0] = new
    y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _ssm_step_call(state, decay, dtx, b, c, block, interpret):
    """Jitted, so the layers of one step program share one trace and one
    lowering of the kernel (as `_decode_call`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, N, C = state.shape
    row = pl.BlockSpec((1, 1, block), lambda s, j: (s, 0, j))
    col = pl.BlockSpec((1, N, 1), lambda s, j: (s, 0, 0))
    tile = pl.BlockSpec((1, N, block), lambda s, j: (s, 0, j))
    new, y = pl.pallas_call(
        _ssm_step_kernel,
        grid=(S, C // block),
        in_specs=[row, row, col, col, tile],
        out_specs=[tile, row],
        out_shape=[jax.ShapeDtypeStruct((S, N, C), state.dtype),
                   jax.ShapeDtypeStruct((S, 1, C), state.dtype)],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_step",
    )(decay[:, None, :], dtx[:, None, :], b[:, :, None], c[:, :, None], state)
    return new, y[:, 0]


def ssm_step(state, decay, dtx, b, c, *, use_pallas=True, interpret=None):
    """Advance every slot's state one token, in place when it is donated.

    state: [slots, d_state, channels] — the cache leaf, float32 (or whatever
    accumulation dtype the layer keeps); decay, dtx: [slots, channels] — each
    channel's decay exp(dt * A) of its head and its input dt * x; b, c:
    [slots, d_state] — the token's input and output projections (one group:
    shared by all channels). Everything is computed in the state's dtype.
    Returns (state', y [slots, channels]); the skip term D * x and the gate
    are the layer's.

    Gives way to `_ssm_step_reference` when the shapes do not tile
    (`_ssm_block`), under a serving mesh (GSPMD cannot partition a Mosaic
    kernel and the per-shard wrapper is not written) or with
    `use_pallas=False`; the first two are counted."""
    _, N, C = state.shape
    dt = state.dtype
    decay, dtx, b, c = (jnp.asarray(a, dt) for a in (decay, dtx, b, c))
    if not use_pallas:
        return _ssm_step_reference(state, decay, dtx, b, c)
    if interpret is None:
        interpret = _interpret_default()
    block = _ssm_block(N, C, dt.itemsize, interpret)
    if block is None or not jax.sharding.get_abstract_mesh().empty:
        _note_fallback("ssm_step", "jnp" if block is None else "jnp_mesh",
                       N=N, C=C, interpret=interpret)
        return _ssm_step_reference(state, decay, dtx, b, c)
    return _ssm_step_call(state, decay, dtx, b, c, block, interpret)
