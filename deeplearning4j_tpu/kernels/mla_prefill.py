"""Blockwise causal attention of a latent-attention layer's PLAIN form: keys
of two parts against values of a third width.

In the plain form of multi-head latent attention (nn/layers/mla.py: a
sequence, a prefill) head h's key at a position is `[k_nope_h | k_pe]` — a
part of its own `Dn` wide beside ONE rotary part `Dr` wide shared by all
heads — and its value `Dv` wide: 128 | 64 against 128 in the published
models. `flash_attention` takes q, k and v of one width, so the layer had to
form the `[heads, T, T]` float32 scores whole: 4.3 GB at 64 heads and 4,096
positions. Here a score tile is the SUM OF TWO PRODUCTS,

    s = (q_nope . k_nope^T + q_pe . k_pe^T) * scale        float32

and the online softmax and the accumulator are `flash_attention`'s: grid
(batch x heads, query blocks, key blocks), the key blocks innermost, (acc,
m, l) in VMEM across them, the blocks above the diagonal skipped. The shared
rotary key is ONE `[batch, T, Dr]` operand whose index map drops the head,
so it is never repeated to the heads in HBM. The products multiply in the
operands' dtype (bfloat16 on the MXU, the probabilities rounded to it for
the mix; float32 operands at full precision: Mosaic's default for them is
one bfloat16 pass) and accumulate in float32. Nothing is padded to a common
width: 2 x (Dn + Dr + Dv) operations a (query, key, head), the plain form's
own count. The kernel is named `mla_prefill_<T>` after the positions of its
call, which tells a trace reader one prefill bucket's call sites from
another's (as `expert_gmm_<caller>` does). Forward only.

`mla_attend_blockwise` is the same arithmetic in `jax.numpy`, a query block
at a time against all the keys (`[b, heads, block, Tk]` scores): the path
with `use_pallas=False`, for queries that do not start at position 0 (a
verify window) and for shapes that do not tile, the last counted in
`pallas_fallback_total{kernel="mla_prefill"}` like the other kernels'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import (LANES, NEG_INF, _causal_fold, _causal_keep,
                              _fit_block, _interpret_default, _mask_fold,
                              _mask_spec, _note_fallback, _prep_mask)

_BLOCK_Q = 512
_BLOCK_K = 512
_REFERENCE_BLOCK_Q = 256     # `mla_attend_blockwise`'s query block


def mla_attend_blockwise(q_nope, q_pe, k_nope, k_pe, v, q_pos, valid, scale,
                         block_q=_REFERENCE_BLOCK_Q):
    """q_nope [b, tq, H, Dn], q_pe [b, tq, H, Dr] at positions q_pos [b, tq]
    against k_nope [b, tk, H, Dn], k_pe [b, tk, Dr], v [b, tk, H, Dv] at
    positions 0 .. tk - 1; valid [b, tk] or None masks keys. One query block
    after another (`lax.map`), float32 scores and softmax; -> [b, tq, H,
    Dv] in v's dtype."""
    b, tq = q_pos.shape
    tk = k_nope.shape[1]
    bq = _fit_block(tq, min(block_q, tq), 1)
    nq = tq // bq
    kpos = jnp.arange(tk)[None, None, :]

    def block(at):
        qn, qp, pos = at                        # [b, bq, H, .], [b, bq]
        s = (jnp.einsum("bqhn,bkhn->bhqk", qn, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bkr->bhqk", qp, k_pe,
                          preferred_element_type=jnp.float32)) * scale
        keep = kpos <= pos[:, :, None]
        if valid is not None:
            keep = keep & (valid[:, None, :] > 0)
        p = jax.nn.softmax(jnp.where(keep[:, None], s, NEG_INF), axis=-1)
        return jnp.einsum("bhqk,bkhv->bqhv", p.astype(v.dtype), v)

    split = lambda a: jnp.moveaxis(
        a.reshape((b, nq, bq) + a.shape[2:]), 1, 0)
    out = lax.map(block, (split(q_nope), split(q_pe), split(q_pos)))
    return jnp.moveaxis(out, 0, 1).reshape((b, tq) + out.shape[3:])


def _prefill_kernel(*refs, scale, block_q, block_k, nk, has_mask):
    from jax.experimental import pallas as pl
    it = iter(refs)
    qn_ref, qp_ref, kn_ref, kp_ref, v_ref = (next(it) for _ in range(5))
    km_ref = next(it) if has_mask else None
    o_ref, acc_ref, m_ref, l_ref = next(it), next(it), next(it), next(it)
    qi, ki = pl.program_id(1), pl.program_id(2)
    exact = lax.Precision.HIGHEST if v_ref.dtype == jnp.float32 else None
    dot = functools.partial(lax.dot_general, precision=exact,
                            preferred_element_type=jnp.float32)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(_causal_keep(qi, ki, 0, 0, block_q, block_k))
    def _accumulate():
        rows = (((1,), (1,)), ((), ()))          # a . b^T
        s = (dot(qn_ref[0], kn_ref[0], rows)
             + dot(qp_ref[0], kp_ref[0], rows)) * scale     # [bq, bk]
        s = _causal_fold(s, qi, ki, 0, 0, block_q, block_k)
        if has_mask:
            s = _mask_fold(s, km_ref)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + dot(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, ...] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _prefill_call(q_nope, q_pe, k_nope, k_pe, v, km, scale, block_q, block_k,
                  interpret):
    """Jitted for the reason `_decode_call` is: the layers of one program
    share one trace and one lowering of the kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, T, H, Dn = q_nope.shape
    Dr, Dv = q_pe.shape[-1], v.shape[-1]
    fold = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * H, T, x.shape[-1])
    q_at = lambda b, qi, ki: (b, qi, 0)
    k_at = lambda b, qi, ki: (b, ki, 0)
    in_specs = [pl.BlockSpec((1, block_q, Dn), q_at),
                pl.BlockSpec((1, block_q, Dr), q_at),
                pl.BlockSpec((1, block_k, Dn), k_at),
                # one rotary key a token for all heads: the head is dropped
                pl.BlockSpec((1, block_k, Dr),
                             lambda b, qi, ki: (b // H, ki, 0)),
                pl.BlockSpec((1, block_k, Dv), k_at)]
    args = [fold(q_nope), fold(q_pe), fold(k_nope), k_pe, fold(v)]
    if km is not None:
        in_specs.append(_mask_spec(H, block_k, kdim=2))
        args.append(km)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, nk=T // block_k,
                          has_mask=km is not None),
        grid=(B * H, T // block_q, T // block_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, Dv), q_at),
        out_shape=jax.ShapeDtypeStruct((B * H, T, Dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, Dv), jnp.float32),      # acc
                        pltpu.VMEM((block_q, LANES), jnp.float32),   # max
                        pltpu.VMEM((block_q, LANES), jnp.float32)],  # sum
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"mla_prefill_{T}",
    )(*args)
    return jnp.swapaxes(out.reshape(B, H, T, Dv), 1, 2)


def mla_prefill(q_nope, q_pe, k_nope, k_pe, v, *, scale, key_mask=None,
                use_pallas=True, interpret=None):
    """Causal attention of a whole sequence from position 0, the plain form
    of latent attention: q_nope, k_nope [b, T, H, Dn]; q_pe [b, T, H, Dr]
    and k_pe [b, T, Dr] turned; v [b, T, H, Dv]; `scale` on the summed
    scores; key_mask [b, T] or None masks keys. -> [b, T, H, Dv] in v's
    dtype. No `[T, T]` score matrix is formed on either path."""
    B, T, H, _ = q_nope.shape
    if use_pallas:
        if interpret is None:
            interpret = _interpret_default()
        bq = _fit_block(T, _BLOCK_Q, 1 if interpret else 8)
        bk = _fit_block(T, _BLOCK_K, 1 if interpret else LANES)
        if bq is not None and bk is not None \
                and jax.sharding.get_abstract_mesh().empty:
            km = None if key_mask is None else _prep_mask(key_mask, B, T)
            return _prefill_call(q_nope, q_pe, k_nope, k_pe, v, km,
                                 float(scale), bq, bk, interpret)
        _note_fallback("mla_prefill", "blockwise" if bq is None or bk is None
                       else "blockwise_mesh", T=T, H=H, interpret=interpret)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    return mla_attend_blockwise(q_nope, q_pe, k_nope, k_pe, v, pos, key_mask,
                                scale)
