"""Flash attention forward AND backward as Pallas TPU kernels.

The K/V stream tiles through VMEM with an online-softmax accumulator held in
scratch, so the [Tq, Tk] score matrix never materializes in HBM — the same
math as parallel/ring_attention.py's blockwise path, but hand-scheduled:
grid (batch*heads, q-blocks, k-blocks) with the k dimension innermost
("arbitrary" semantics) carrying (acc, m, l) scratch across iterations.

Backward is fused and linear-memory: the forward additionally emits the
per-row log-sum-exp (LSE) residual, and two backward kernels recompute the
probability blocks from (q, k, lse) on the fly —
  dQ    : grid (BH, q-blocks, k-blocks), k innermost, dq accumulated in VMEM
  dK/dV : grid (BH, k-blocks, q-blocks), q innermost, dk/dv in VMEM
so training never materializes [Tq, Tk] either. LSE and the dO·O row
contraction are stored lane-broadcast ([BH, T, 128] f32, 512 B/row) — the
layout Mosaic handles natively for row-vector operands (a plain [BH, T]
residual would need a lane→sublane transpose inside the kernel).

Masking: a key-validity mask ([batch, Tk], shared across heads via the
block index map — no H× replication in HBM) folds into the score tile at
the same place the causal iota mask sits, in the forward AND both backward
kernels, so variable-length/packed batches keep the fast path (reference
mask contract: nn/api/Layer.java:309 feedForwardMaskArray /
util/MaskedReductionUtil.java). Masked scores are the finite NEG_INF, so a
row with no valid key degrades to the reference softmax's uniform average
(under `causal` that uniform spans only the non-skipped ≤-diagonal blocks —
a degenerate case no real padded batch hits: padding leaves every query at
least one causally-visible valid key).

Ring hookup: `flash_attention_lse` additionally returns the per-row LSE and
takes dynamic global q/k position offsets (SMEM scalars) for the causal
mask, which is exactly what a ring-attention step needs to run this kernel
on each visiting K/V shard (parallel/ring_attention.py merges the per-shard
(out, lse) partials by log-sum-exp). The LSE cotangent folds into the
backward for free: ds = p·(dp − Δ) with Δ = rowsum(dO·O) − g_lse.

Decode (`flash_decode`: one query per cache slot against a [slots,
capacity, H, D] KV cache) is a kernel of its own, `_decode_kernel`, and
shares no grid with the above: grid (slots), the cache left in HBM, and per
slot a loop over the key blocks the slot has FILLED, each copied into VMEM
by the kernel itself (double-buffered, the next slot's first block under
this slot's last). A tile is all heads of one slot's key block as the cache
buffer holds it ([H, D, positions], the positions on the lanes), the
per-head products run on the VPU in float32, and the validity mask is an
iota against the slot's length in SMEM. A block wholly past the slot's
length is neither copied nor computed on, so a step's HBM traffic follows
the lengths, not the capacity. What selects the kernel is the entry point;
nothing of the cache is folded, copied or masked in HBM on the way. The
step's new K and V reach the cache through the same kernel
(`flash_decode_append`): the cache is aliased onto two outputs, and when a
slot's last live block — the one that holds its append position — is in
VMEM, the token goes into its lane there and the 128 positions round it are
copied back, so a layer of the step is one launch and reads that tile once.
`kv_append` is the append alone, on the same view of the same buffer with
its output aliased onto it: grid (slots), of each slot the one block of 128
positions that holds its append position goes through VMEM, nothing else is
touched; it is what the fused kernel is held to, and its other half where
it gives way.

All of that is the cache as the TPU stores it for head_dim < 128, positions
on the lanes. A head_dim that is a multiple of 128 is stored ROW-MAJOR — a
position's [H, head_dim] values one run of tiles — and `flash_decode_append`
then runs `_decode_rows_kernel` (the same name in a trace): a block's
positions x heads as the rows of one tile, both products on the MXU for all
query heads at once, the token's rows written by one copy a buffer. The
read-only `flash_decode` still hands such a cache to `_decode_kernel` through
a transposing copy. Narrower heads reach the same kernel PACKED
(`packed_rows`): a cache declared [slots, capacity, H * D // 128, 128], 128
// D heads side by side on a row, is stored row-major too — 16 heads of 64 in
float32 are one (8, 128) tile a position — and a token is then two 4 KB
copies where the positions-minor cache has it in one lane of 256 tiles.
Fewer heads of 128 than a tile has sublanes (4: half a tile a position)
reach it declared in WHOLE TILES (`tiled_rows`: [slots, capacity * H // 8, 8,
128]); a copy to HBM starts and ends on a tile, so the kernel reads the
token's tile, replaces its rows and writes the tile back. The two compose:
narrow heads whose packed rows are 1, 2 or 4 a position (8 heads of 64: 4
rows, half a tile) are declared packed AND in whole tiles, [slots, capacity *
rows // 8, 8, 128] — `tiled_rows` packs them first —, and the kernel runs
with both halves on. A sliding window's
cache is a RING of the window's positions (`flash_decode_append(ring=True)`,
`flash_decode_window` in a trace): position p at p % ring, every block read
once the ring has filled. The training forward takes the window as the
lower end of its causal mask and skips the key blocks wholly below it.

Gives way to a pure-JAX path (see `flash_attention`) when shapes don't tile,
so callers can use it unconditionally; each such call is counted in
`pallas_fallback_total` and logged once per shape (`_note_fallback`).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

NEG_INF = -1e30


LANES = 128  # lse/delta residuals are stored broadcast over one lane tile


def _interpret_default():
    """The one place that decides whether a kernel is compiled or
    interpreted: compiled on a TPU, interpreted on the CPU (tests and
    rehearsals), and an error on any other backend — a platform that is
    neither must not run the Pallas interpreter on an accelerator unseen."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' and interpreted on 'cpu'; "
        f"the default backend is {backend!r}. Pass interpret= explicitly.")


def _per_shard(fn, arrays, B, H):
    """Run `fn(*arrays)` — a Pallas call on [batch, time, heads, head_dim]
    tensors (plus [batch, ...] masks/lengths) — once per shard of the
    ambient mesh. GSPMD cannot partition a Mosaic kernel ("wrap the call in
    a shard_map"), and attention is independent across batch rows and
    heads, so under a mesh set with `jax.set_mesh` (the serving mesh does
    that around its dispatches) the batch splits over the data axis and the
    heads over the model axis, each only where it divides; no collective is
    needed. With no ambient mesh this is a plain call."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return fn(*arrays)
    from jax.sharding import PartitionSpec as P
    from ..parallel.sharding import DATA_AXIS, MODEL_AXIS

    def axis(name, n):
        size = mesh.shape.get(name, 1)
        return name if size > 1 and n % size == 0 else None
    b, h = axis(DATA_AXIS, B), axis(MODEL_AXIS, H)
    tensor = P(b, None, h, None)
    specs = tuple(tensor if a.ndim == 4 else P(b, *[None] * (a.ndim - 1))
                  for a in arrays)
    return jax.shard_map(fn, in_specs=specs, out_specs=tensor,
                         check_vma=False)(*arrays)


def _heads_per_shard(H, size=None):
    """The heads of `H` that one shard's kernel sees under `_per_shard`: the
    model axis (of `size`; by default the ambient mesh's) splits them
    wherever it divides. A kernel whose tiling depends on the head count is
    chosen by this count, not by the global one."""
    if size is None:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return H
        from ..parallel.sharding import MODEL_AXIS
        size = mesh.shape.get(MODEL_AXIS, 1)
    return H // size if H % size == 0 else H


def _note_fallback(kernel, path, **shape):
    """`use_pallas` was asked for and these shapes do not tile, so the call
    gives way to `path`. Callers rely on that; it is counted in
    `pallas_fallback_total{kernel,path,shape}` (at trace time, so once per
    compiled program) and logged the first time each shape is seen."""
    from ..telemetry.logging import get_logger
    from ..telemetry.registry import get_registry
    counter = get_registry().counter(
        "pallas_fallback_total",
        "Pallas kernel calls that gave way to a pure-JAX path because the "
        "shapes do not tile")
    label = ",".join(f"{k}={v}" for k, v in shape.items())
    first = not counter.get(kernel=kernel, path=path, shape=label)
    counter.inc(1, kernel=kernel, path=path, shape=label)
    if first:
        get_logger().warning("pallas_fallback", kernel=kernel, path=path,
                             **shape)


def _note_decode_block(block_c, **shape):
    """The key block `_decode_block` chose for these shapes, as the gauge
    `flash_decode_block{C,H,D,itemsize}` beside `pallas_fallback_total`: set
    at trace time, so once per compiled program. With the server's
    `decode_kv_live_pct` it says what share of the cache a step reads."""
    from ..telemetry.registry import get_registry
    get_registry().gauge(
        "flash_decode_block",
        "Key-block length (cache positions) of the flash_decode kernel: a "
        "slot's blocks wholly past its length are not read").set(
            block_c, **shape)


def _mask_fold(s, km_ref):
    """Fold the [1, block_k] key-validity row (the BlockSpec index map
    already selected this key block) into the score tile — broadcasts over
    the q sublanes."""
    km = km_ref[0]                               # [1, block_k]
    return jnp.where(km > 0, s, NEG_INF)


def _causal_fold(s, qi, ki, q_off, k_off, block_q, block_k):
    qpos = q_off + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = k_off + ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(kpos > qpos, NEG_INF, s)


def _causal_keep(qi, ki, q_off, k_off, block_q, block_k):
    """Whether this (q block, k block) pair has any unmasked causal entry:
    skip blocks entirely above the diagonal (~half the grid) — they are fully
    masked and would pay both matmuls for nothing. With dynamic ring offsets
    this is a runtime predicate on the same inequality."""
    return k_off + ki * block_k <= q_off + (qi + 1) * block_q - 1


def _window_fold(s, qi, ki, block_q, block_k, window):
    """The sliding window's lower end: query i sees keys j with i - window <
    j (<= i: `_causal_fold`), `window` keys with its own."""
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(kpos <= qpos - window, NEG_INF, s)


def _window_keep(qi, ki, block_q, block_k, window):
    """Whether the key block's last position is inside the window of the
    query block's first row: the blocks wholly below the window are skipped
    as those above the diagonal are."""
    return (ki + 1) * block_k - 1 > qi * block_q - window


def _flash_kernel(*refs, scale, causal, block_q, block_k, nk, need_lse,
                  has_mask, has_offs, window=None):
    from jax.experimental import pallas as pl
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    offs_ref = next(it) if has_offs else None
    km_ref = next(it) if has_mask else None
    o_ref = next(it)
    lse_ref = next(it) if need_lse else None
    acc_ref, m_ref, l_ref = next(it), next(it), next(it)
    ki = pl.program_id(2)
    qi = pl.program_id(1)
    q_off = offs_ref[0] if has_offs else 0
    k_off = offs_ref[1] if has_offs else 0

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)        # [bq, d]
        k = k_ref[0].astype(jnp.float32)        # [bk, d]
        v = v_ref[0].astype(jnp.float32)        # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_fold(s, qi, ki, q_off, k_off, block_q, block_k)
        if window is not None:
            s = _window_fold(s, qi, ki, block_q, block_k, window)
        if has_mask:
            s = _mask_fold(s, km_ref)

        m_prev = m_ref[:, :1]                    # [bq, 1]
        l_prev = l_ref[:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                   # [bq, bk]
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if window is not None:      # causal, no offsets: `flash_attention`
        pl.when(_causal_keep(qi, ki, 0, 0, block_q, block_k)
                & _window_keep(qi, ki, block_q, block_k, window))(_accumulate)
    elif causal:
        pl.when(_causal_keep(qi, ki, q_off, k_off, block_q, block_k))(
            _accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _finish():
        # masked-out rows (fully-causal-masked early q rows never happen:
        # diagonal blocks always contribute) — guard l=0 anyway
        l = l_ref[:, :1]
        o_ref[0, ...] = (acc_ref[...] /
                         jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if need_lse:
            lse_ref[0, ...] = m_ref[...] + jnp.log(
                jnp.maximum(l_ref[...], 1e-30))


def _fold_heads(x):
    B, T, H, D = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(B * H, T, D)


def _mask_spec(H, block_k, kdim):
    """BlockSpec for the [B, 1, Tk] key mask: heads share one batch row via
    the b // H index map — the mask never replicates H× in HBM. `kdim` names
    which grid axis walks the key blocks (2 on forward/dq grids, 1 on the
    dk/dv grid)."""
    from jax.experimental import pallas as pl

    def index(b, i, j, H=H):
        kb = (i, j)[kdim - 1]
        return (b // H, 0, kb)
    return pl.BlockSpec((1, 1, block_k), index)


def _offs_smem_spec():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_forward(q, k, v, km, offs, scale, causal, block_q, block_k,
                   interpret, need_lse=False, window=None):
    """Returns (out [B,Tq,H,D], lse [BH,Tq,LANES] f32 | None).

    The kernel's name in the jaxpr and in a device trace is `flash_fwd`; the
    backward kernels are `flash_bwd_dq` and `flash_bwd_dkv`, the decode
    kernel `flash_decode` / `flash_decode_paged`, the cache append
    `kv_append`.

    km: optional [B, 1, Tk] f32 key-validity mask; offs: optional int32 [2]
    (global q, k position offsets for the causal mask — the ring path).
    The LSE residual is emitted (written to HBM) only when `need_lse` —
    inference-only calls skip that extra output-sized write."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    # fold heads into batch; kernel works on [BH, T, D]
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    nq = Tq // block_q
    nk = Tk // block_k
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, nk=nk,
                               need_lse=need_lse, has_mask=km is not None,
                               has_offs=offs is not None, window=window)
    o_spec = pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0))
    o_shape = jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype)
    lse_spec = pl.BlockSpec((1, block_q, LANES), lambda b, qi, ki: (b, qi, 0))
    lse_shape = jax.ShapeDtypeStruct((B * H, Tq, LANES), jnp.float32)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
    ]
    args = [qf, kf, vf]
    if offs is not None:
        in_specs.append(_offs_smem_spec())
        args.append(offs)
    if km is not None:
        in_specs.append(_mask_spec(H, block_k, kdim=2))
        args.append(km)
    res = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=in_specs,
        out_specs=[o_spec, lse_spec] if need_lse else [o_spec],
        out_shape=[o_shape, lse_shape] if need_lse else [o_shape],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),       # acc
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    out = res[0]
    lse = res[1] if need_lse else None
    return jnp.swapaxes(out.reshape(B, H, Tq, D), 1, 2), lse


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, nk, has_mask,
                   has_offs):
    from jax.experimental import pallas as pl
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref = next(it), next(it), next(it), next(it)
    lse_ref, delta_ref = next(it), next(it)
    offs_ref = next(it) if has_offs else None
    km_ref = next(it) if has_mask else None
    dq_ref = next(it)
    dq_acc = next(it)
    ki = pl.program_id(2)
    qi = pl.program_id(1)
    q_off = offs_ref[0] if has_offs else 0
    k_off = offs_ref[1] if has_offs else 0

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)        # [bq, d]
        lse = lse_ref[0][:, :1]                   # [bq, 1]
        delta = delta_ref[0][:, :1]               # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_fold(s, qi, ki, q_off, k_off, block_q, block_k)
        if has_mask:
            s = _mask_fold(s, km_ref)
        p = jnp.exp(s - lse)                      # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale             # [bq, bk]
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_keep(qi, ki, q_off, k_off, block_q, block_k))(
            _accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, ...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, nq, has_mask,
                    has_offs):
    from jax.experimental import pallas as pl
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref = next(it), next(it), next(it), next(it)
    lse_ref, delta_ref = next(it), next(it)
    offs_ref = next(it) if has_offs else None
    km_ref = next(it) if has_mask else None
    dk_ref, dv_ref = next(it), next(it)
    dk_acc, dv_acc = next(it), next(it)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    q_off = offs_ref[0] if has_offs else 0
    k_off = offs_ref[1] if has_offs else 0

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)        # [bq, d]
        lse = lse_ref[0][:, :1]                   # [bq, 1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_fold(s, qi, ki, q_off, k_off, block_q, block_k)
        if has_mask:
            s = _mask_fold(s, km_ref)
        p = jnp.exp(s - lse)                      # [bq, bk]
        # dV += Pᵀ·dO ; dK += dSᵀ·Q  (contract over the q rows)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        # skip q blocks strictly above the diagonal: every row there masks
        # this whole k block ((qi+1)*bq - 1 < ki*bk)
        pl.when(_causal_keep(qi, ki, q_off, k_off, block_q, block_k))(
            _accumulate)
    else:
        _accumulate()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, km, offs, scale, causal, block_q,
                    block_k, interpret, g_lse=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    nq, nk = Tq // block_q, Tk // block_k
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    dof = _fold_heads(g)
    # delta_i = Σ_d dO_id · O_id (− the LSE cotangent when the caller uses
    # the (out, lse) primal pair: ds = p·(dp − delta + g_lse) folds into the
    # same kernel as a delta shift), lane-broadcast like lse (module doc)
    delta = jnp.sum(dof.astype(jnp.float32) * _fold_heads(out).astype(jnp.float32),
                    axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.reshape(B * H, Tq)
    delta = jnp.broadcast_to(delta[..., None], (B * H, Tq, LANES))
    lse = jnp.broadcast_to(lse[..., None], (B * H, Tq, LANES))

    extra_args = []
    dq_extra_specs = []
    dkv_extra_specs = []
    if offs is not None:
        extra_args.append(offs)
        dq_extra_specs.append(_offs_smem_spec())
        dkv_extra_specs.append(_offs_smem_spec())
    if km is not None:
        extra_args.append(km)
        dq_extra_specs.append(_mask_spec(H, block_k, kdim=2))
        dkv_extra_specs.append(_mask_spec(H, block_k, kdim=1))
    has_mask, has_offs = km is not None, offs is not None

    lane_spec = pl.BlockSpec((1, block_q, LANES), lambda b, qi, ki: (b, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          has_mask=has_mask, has_offs=has_offs),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            lane_spec,
            lane_spec,
        ] + dq_extra_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lse, delta, *extra_args)

    qlane = pl.BlockSpec((1, block_q, LANES), lambda b, ki, qi: (b, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          has_mask=has_mask, has_offs=has_offs),
        grid=(B * H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, ki, qi: (b, qi, 0)),
            qlane,
            qlane,
        ] + dkv_extra_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, lse, delta, *extra_args)

    unfold = lambda x, T: jnp.swapaxes(x.reshape(B, H, T, D), 1, 2)
    return unfold(dq, Tq), unfold(dk, Tk), unfold(dv, Tk)


def _zero_cotangents(km, offs):
    """Cotangents for the non-differentiable mask/offset operands: float0
    for the int32 offsets (JAX's required cotangent type for integer
    primals), zeros for the float mask."""
    km_ct = None if km is None else jnp.zeros_like(km)
    offs_ct = None if offs is None else np.zeros(offs.shape, jax.dtypes.float0)
    return km_ct, offs_ct


# --------------------------------------------------------------------- plain
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, km, offs, scale, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, km, offs, scale, causal, block_q, block_k,
                          interpret)[0]


def _flash_fwd(q, k, v, km, offs, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, km, offs, scale, causal, block_q,
                              block_k, interpret, need_lse=True)
    return out, (q, k, v, km, offs, out, lse[..., 0])


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, km, offs, out, lse = res
    dq, dk, dv = _flash_backward(q, k, v, out, lse, g, km, offs, scale,
                                 causal, block_q, block_k, interpret)
    km_ct, offs_ct = _zero_cotangents(km, offs)
    return dq, dk, dv, km_ct, offs_ct


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------- (out, lse)
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_lse(q, k, v, km, offs, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, km, offs, scale, causal, block_q,
                              block_k, interpret, need_lse=True)
    B, Tq, H, _ = q.shape
    return out, lse[..., 0].reshape(B, H, Tq)


def _flash_lse_fwd(q, k, v, km, offs, scale, causal, block_q, block_k,
                   interpret):
    out, lse = _flash_lse(q, k, v, km, offs, scale, causal, block_q, block_k,
                          interpret)
    B, Tq, H, _ = q.shape
    return (out, lse), (q, k, v, km, offs, out, lse.reshape(B * H, Tq))


def _flash_lse_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, km, offs, out, lse = res
    g_out, g_lse = g
    dq, dk, dv = _flash_backward(q, k, v, out, lse, g_out, km, offs, scale,
                                 causal, block_q, block_k, interpret,
                                 g_lse=g_lse)
    km_ct, offs_ct = _zero_cotangents(km, offs)
    return dq, dk, dv, km_ct, offs_ct


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _fit_block(T, target, align):
    """Largest block <= target that tiles T and meets the Mosaic alignment,
    or None if no aligned divisor exists."""
    for b in range(min(target, T) - min(target, T) % align, 0, -align):
        if T % b == 0:
            return b
    return None


def _plan(Tq, Tk, D, block_q, block_k, interpret):
    """(block_q, block_k) the kernel can run with, or None => fall back.
    Mosaic requires tile-aligned blocks when compiling (sublane multiple of
    8, lane multiple of 128 on the [block_q, block_k] score tile); interpret
    mode (CPU tests) has no such constraint so small blocks stay allowed."""
    q_align, k_align = (1, 1) if interpret else (8, 128)
    bq = _fit_block(Tq, min(block_q, Tq), q_align)
    bk = _fit_block(Tk, min(block_k, Tk), k_align)
    if bq is None or bk is None or D % 8:
        return None
    return bq, bk


def _prep_mask(key_mask, B, Tk):
    """[B, 1, Tk] f32 kernel mask from any reference-style broadcastable
    key mask ((Tk,), (1, Tk), (B, Tk))."""
    km = jnp.broadcast_to(jnp.asarray(key_mask), (B, Tk))
    return km.astype(jnp.float32)[:, None, :]


def flash_attention(q, k, v, *, causal=False, scale=None, key_mask=None,
                    window=None, block_q=256, block_k=1024, interpret=None):
    """Pallas flash attention on [batch, time, heads, head_dim] tensors.

    Default blocks (256 query x 1024 key) were swept on a real v5e: they run
    the fwd+bwd ~1.4x FASTER than the materializing einsum reference at
    T=2048-4096 (and ~9x smaller compiled temp memory); the original 128x128
    tiling was ~2x slower than the reference because each kernel invocation
    did too little MXU work per grid step.

    key_mask: optional [batch, Tk] (or broadcastable) key-position validity —
    same semantics as attention_reference/blockwise_attention, folded into
    the score tiles of the forward and both backward kernels (packed/ragged
    batches keep the fast path).

    window: with `causal`, query i sees the `window` keys i - window < j <=
    i (its own among them). The key blocks wholly below a query block's
    window are skipped like those above the diagonal (their products, not
    their copies: on the chip, 4,096 positions x 32 heads of 128 under a
    window of 1,024, the key block of 1,024 takes 2.69 ms where the plain
    causal call takes 2.89, a block of 512 3.28 and one of 256 4.91 — PERF.md
    section 6, PR 47 —, so a window keeps the default block). FORWARD ONLY: the two
    backward kernels know no window, so a windowed call is not
    differentiable (a layer trains through the pure-JAX path).

    Falls back to the pure-JAX blockwise scan (O(T_block) memory) when the
    sequence doesn't tile into the requested blocks but a sane key-block
    divisor exists, and to the materializing reference only as a last
    resort; callers may use it unconditionally."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = float(1.0 / (D ** 0.5))
    if interpret is None:
        interpret = _interpret_default()
    if window is not None:
        assert causal, "a window is the lower end of a causal mask"
    plan = _plan(Tq, Tk, D, block_q, block_k, interpret)
    if plan is None:
        # prefer the O(T_block)-memory blockwise scan over the materializing
        # reference whenever a sane key-block divisor exists — long ragged
        # batches are exactly where the [Tq, Tk] score temp hurts
        from ..parallel.ring_attention import (attention_reference,
                                               blockwise_attention)
        blk = _fit_block(Tk, min(block_k, Tk), 1)
        blockwise = blk is not None and blk >= 8
        _note_fallback("flash_attention",
                       "blockwise" if blockwise else "reference",
                       Tq=Tq, Tk=Tk, D=D, interpret=interpret)
        if blockwise:
            return blockwise_attention(q, k, v, block_size=blk, causal=causal,
                                       scale=scale, key_mask=key_mask,
                                       window=window)
        return attention_reference(q, k, v, causal=causal, scale=scale,
                                   key_mask=key_mask, window=window)
    masks = () if key_mask is None else (_prep_mask(key_mask, B, Tk),)
    if window is not None:
        return _per_shard(
            lambda q, k, v, km=None: _flash_forward(
                q, k, v, km, None, scale, True, plan[0], plan[1], interpret,
                window=int(window))[0],
            (q, k, v) + masks, B, H)
    return _per_shard(
        lambda q, k, v, km=None: _flash(q, k, v, km, None, scale, causal,
                                        plan[0], plan[1], interpret),
        (q, k, v) + masks, B, H)


def flash_attention_lse(q, k, v, *, causal=False, scale=None, key_mask=None,
                        q_offset=None, k_offset=None, block_q=256,
                        block_k=1024, interpret=None):
    """Flash attention that ALSO returns the per-row log-sum-exp
    ([batch, heads, Tq] f32) so partial results over disjoint key shards can
    be merged exactly (parallel/ring_attention.py's per-ring-step update).

    q_offset/k_offset: dynamic global positions of q[0] / k[0] for the
    causal mask (traced scalars are fine — they ride to the kernel in SMEM).
    No shape fallback here: callers must check `can_flash(...)` first (the
    ring keeps its einsum block update for non-tiling shapes)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = float(1.0 / (D ** 0.5))
    if interpret is None:
        interpret = _interpret_default()
    plan = _plan(Tq, Tk, D, block_q, block_k, interpret)
    if plan is None:
        raise ValueError(
            f"flash_attention_lse: shapes (Tq={Tq}, Tk={Tk}, D={D}) don't "
            "tile; check can_flash() and use the blockwise path instead")
    km = None if key_mask is None else _prep_mask(key_mask, B, Tk)
    offs = None
    if q_offset is not None or k_offset is not None:
        offs = jnp.stack(
            [jnp.asarray(0 if q_offset is None else q_offset, jnp.int32),
             jnp.asarray(0 if k_offset is None else k_offset, jnp.int32)])
    return _flash_lse(q, k, v, km, offs, scale, causal, plan[0], plan[1],
                      interpret)


def _decode_reference(q, k, v, lengths, scale, window=None):
    """Masked single-query attention, materializing the [S, H, 1, C] score
    row — the fallback (and CPU-test) semantics flash_decode must match.
    A slot with lengths=0 degrades to the uniform average over the cache,
    same contract as the main kernel's fully-masked-row behavior; callers
    never read those slots. With a `window` a slot sees its `window` newest
    positions only."""
    S, C = k.shape[0], k.shape[1]
    G = q.shape[2] // k.shape[2]
    if G > 1:           # grouped heads: K/V head j serves query heads j*G..
        k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    at = jax.lax.broadcasted_iota(jnp.int32, (S, C), 1)
    lengths = jnp.asarray(lengths, jnp.int32)[:, None]
    valid = at < lengths
    if window is not None:
        valid &= at >= lengths - window
    s = jnp.einsum("sqhd,schd->shqc", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("shqc,schd->sqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# the decode kernel's K and V tiles: all heads of one slot's key block. Four
# of them (K and V, double-buffered) stay inside the default scoped VMEM;
# with the appending kernel's stage (at most two more, at a 128-position
# block) they need the limit raised, so that it compiles what the other does.
_DECODE_TILE_BYTES = 2 << 20
_DECODE_APPEND_VMEM_BYTES = 32 << 20


def _decode_block(C, H, D, itemsize, block_k, interpret):
    """Key-block length of the decode kernel — the largest divisor of the
    capacity whose [H, D, block] tile fits `_DECODE_TILE_BYTES` (and
    `block_k`) — or None => fall back. H counts the QUERY heads: with
    grouped heads the tile holds fewer K/V heads, but a block's cost is its
    arithmetic (PERF.md section 6, PR 30), which follows the query heads,
    and a shorter block lets more of a slot's capacity go unread. Compiled,
    positions lie on the lanes (a multiple of 128) and head_dim on the
    sublanes (a multiple of 8 for float32, of 16 for a packed bfloat16);
    interpret mode takes any divisor."""
    c_align, d_align = (1, 1) if interpret else (128, 32 // itemsize)
    if D % d_align:
        return None
    target = min(block_k, C, max(c_align,
                                 _DECODE_TILE_BYTES // (H * D * itemsize)))
    return _fit_block(C, target, c_align)


def _live_blocks(length, block_c, nk):
    """How many of a slot's nk key blocks hold a valid position:
    ceil(length / block_c). `_decode_kernel` neither copies nor computes on
    any block after them. A slot of length 0 keeps every block live: its
    documented result is the uniform average over the whole cache
    (`_decode_reference`)."""
    return jnp.where(length > 0, (length + block_c - 1) // block_c, nk)


def _decode_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
                   buf_ref, qc_ref, acc_ref, m_ref, l_ref, *, scale, block_c,
                   nk, slots, append=None):
    """One slot of decode attention on the cache as it lies in HBM. k_hbm /
    v_hbm are the whole [S, H, D, C] buffers, left in HBM; the kernel walks
    the slot's LIVE key blocks only (`_live_blocks` of its length, a
    scalar in SMEM) and copies each [H, D, block_c] tile — head_dim on the
    sublanes, cache positions on the lanes — into one of two VMEM buffers
    itself, so a block past the slot's length costs neither HBM traffic nor
    arithmetic. (A grid over the key blocks with an index map clamped to the
    last live block still fetches every block: Mosaic's pipeline re-copies
    an operand whose index map computes the same block again.)

    The copies are double-buffered across the whole call: each block's copy
    is started while the block before it is computed on, the first block of
    the NEXT slot under this slot's last, and always before the current
    block is waited for, so the DMA queue never runs dry. Which buffer the
    slot starts in crosses the grid step in `buf_ref` (SMEM), hence the
    grid's "arbitrary" semantics.

    With one query row per head the work is a matrix-vector product, so it
    runs on the VPU in float32: the scores are a sublane reduction of k * q
    (q as a [D, 1] column), the output a lane reduction of v * p, both per
    head, carried across key blocks by the online softmax in (m, l, acc).
    The validity mask is an iota against the slot's length.

    Grouped heads: q_ref holds Hq = G * H query heads against the tile's H
    K/V heads; K/V head h is loaded once and serves query heads h*G .. h*G +
    G - 1, each with its own (m, l, acc).

    `append` (`_decode_append_kernel`): the step's token is written by this
    kernel too. The slot's length counts it, so its position `length - 1`
    lies in the slot's LAST live block; that block is peeled off the loop,
    and once it is in VMEM the token's K and V go into their lane of the
    tiles and the lanes round it are copied back to the cache while the
    block is computed on (`_insert_token`): what `kv_append` writes, without
    its read of the tile and without its launch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    si = pl.program_id(0)
    H, D = k_buf.shape[1], k_buf.shape[2]
    Hq = q_ref.shape[1]
    G = Hq // H
    eye = (jax.lax.broadcasted_iota(jnp.int32, (D, D), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (D, D), 1))
    length = len_ref[si]
    live = _live_blocks(length, block_c, nk)

    def copies(slot, block, buf):
        at = pl.ds(pl.multiple_of(block * block_c, block_c), block_c)
        return [pltpu.make_async_copy(hbm.at[slot, :, :, at], vmem.at[buf],
                                      sem.at[i, buf])
                for i, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf)))]

    @pl.when(si == 0)
    def _first():
        buf_ref[0] = 0
        for copy in copies(0, 0, 0):
            copy.start()

    def column(h, carry):
        # the head's query row [1, D] turned onto the sublanes: [D, 1]
        row = q_ref[0, h].astype(jnp.float32)
        qc_ref[h] = jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)
        return carry
    jax.lax.fori_loop(0, Hq, column, None, unroll=True)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def block(j, buf, token=None):
        # what follows block j: the slot's next live block or the next
        # slot's first; after the last block of the last slot a copy nobody
        # reads (`_drain` waits for it), which keeps this one basic block
        more = j + 1 < live
        for copy in copies(jnp.where(more, si, jnp.minimum(si + 1, slots - 1)),
                           jnp.where(more, j + 1, 0), 1 - buf):
            copy.start()
        for copy in copies(si, j, buf):
            copy.wait()
        if token is not None:   # into its lane of the tiles, and home
            token(buf)
        kpos = j * block_c + jax.lax.broadcasted_iota(jnp.int32,
                                                      (1, block_c), 1)
        valid = kpos < length                         # [1, block_c]

        def head(h, carry):
            k = k_buf[buf, h].astype(jnp.float32)     # [D, block_c]
            v = None        # loaded after the first p, where it always was:
            for g in range(G):  # equal heads trace to the program they did
                qh = h if G == 1 else h * G + g       # the query head
                s = jnp.sum(k * qc_ref[qh], axis=0, keepdims=True) * scale
                s = jnp.where(valid, s, NEG_INF)      # [1, block_c]
                m_prev = m_ref[qh]                    # [1, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                if v is None:
                    v = v_buf[buf, h].astype(jnp.float32)  # [D, block_c]
                acc_ref[qh] = acc_ref[qh] * corr + jnp.sum(v * p, axis=1,
                                                           keepdims=True)
                l_ref[qh] = l_ref[qh] * corr + jnp.sum(p, axis=1,
                                                       keepdims=True)
                m_ref[qh] = m_new
            return carry
        # unrolled: the scheduler overlaps one head's reductions with the
        # next head's loads (0.74 -> 0.58 ms a call at 48 x 1024 x 16 x 64
        # float32)
        jax.lax.fori_loop(0, H, head, None, unroll=True)
        return 1 - buf

    if append is None:
        buf = jax.lax.fori_loop(0, live, block, buf_ref[0])
    else:
        buf = jax.lax.fori_loop(0, live - 1, block, buf_ref[0])
        buf = block(live - 1, buf, functools.partial(
            _insert_token, append, (k_buf, v_buf), si, length - 1, block_c,
            eye))
    buf_ref[0] = buf

    @pl.when(si == slots - 1)
    def _drain():
        for copy in copies(0, 0, buf):
            copy.wait()
        if append is not None:
            for write in _token_writes(append, si, length - 1):
                write.wait()

    def row(h, carry):
        # l >= 1 always: a fully masked slot sums exp(0) per position
        col = acc_ref[h] / l_ref[h]                   # [D, 1]
        o_ref[0, h] = jnp.sum(jnp.where(eye, col, 0.0), axis=0,
                              keepdims=True).astype(o_ref.dtype)
        return carry
    jax.lax.fori_loop(0, Hq, row, None, unroll=True)


def _decode_scratch(Hq, H, D, block_c, dtype):
    """`_decode_kernel`'s scratch, in the order of its arguments."""
    from jax.experimental.pallas import tpu as pltpu
    return [
        pltpu.VMEM((2, H, D, block_c), dtype),       # K tiles
        pltpu.VMEM((2, H, D, block_c), dtype),       # V tiles
        pltpu.SemaphoreType.DMA((2, 2)),             # (K | V, buffer)
        pltpu.SMEM((1,), jnp.int32),                 # next buffer
        pltpu.VMEM((Hq, D, 1), jnp.float32),         # q columns
        pltpu.VMEM((Hq, D, 1), jnp.float32),         # acc
        pltpu.VMEM((Hq, 1, 1), jnp.float32),         # running max
        pltpu.VMEM((Hq, 1, 1), jnp.float32),         # running sum
    ]


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _decode_call(q, k, v, lengths, scale, block_c, interpret, name):
    """q [S, 1, Hq, D], k/v [S, C, H, D], lengths [S] -> [S, 1, Hq, D]
    (Hq a multiple of H: grouped heads).

    Jitted, so the layers of one step program share ONE trace and one
    lowering of the kernel. With the head loop unrolled, tracing it layer by
    layer cost the opt350m server 4 s of every set-up (24 layers x 16
    heads), a warm compile cache or not."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, _, Hq, D = q.shape
    C, H = k.shape[1], k.shape[2]
    # The kernel's operand is [S, H, D, C]. For head_dim < 128 that IS the
    # cache buffer: the TPU lays a [S, C, H, D] array out with the positions
    # minor-most (minor-to-major {1,3,2,0}: a 64-wide minor axis would pad
    # every tile to 128 lanes), so this transpose compiles to a bitcast
    # (tests/test_tpu_compile.py holds it to that). For head_dim >= 128 the
    # buffer is row-major and the transpose is a copy: a decode step does not
    # come here with one (`_decode_rows_call`); the paged gather and the
    # counted fallback do.
    kt, vt = (jnp.transpose(x, (0, 2, 3, 1)) for x in (k, v))
    row = pl.BlockSpec((1, Hq, 1, D), lambda s, lens: (s, 0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_c=block_c,
                          nk=C // block_c, slots=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[row, in_hbm, in_hbm],
            out_specs=row,
            scratch_shapes=_decode_scratch(Hq, H, D, block_c, k.dtype)),
        out_shape=jax.ShapeDtypeStruct((S, Hq, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(lengths, q.reshape(S, Hq, 1, D), kt, vt)
    return out.reshape(S, 1, Hq, D)


def flash_decode(q, k, v, lengths, *, scale=None, use_pallas=True,
                 block_k=1024, interpret=None, _name="flash_decode"):
    """Decode-mode flash attention: ONE new query per cache slot against a
    fixed-shape slot-per-request KV cache.

    q: [slots, 1, heads, head_dim] — the current token's query (its k/v
    are in the cache at position lengths-1 already: this entry only reads
    the cache; `flash_decode_append` is the one that also writes them, and
    what a decode step calls);
    k, v: [slots, capacity, kv_heads, head_dim] — the cache; `heads` is a
    multiple of `kv_heads` (grouped-query attention: K/V head j is read
    once and serves query heads j*G .. j*G + G - 1);
    lengths: [slots] int32 — valid entries per slot (including the current
    token). Returns [slots, 1, heads, head_dim].

    A kernel of its own (`_decode_kernel`), not the training forward: the
    grid walks the slots, the kernel each slot's key blocks, and a tile is
    all heads of one key block, copied from the cache buffer in the layout
    it is stored in — no fold of heads, no copy of the cache in HBM
    (`_decode_call`). The per-slot
    validity mask is an iota compared with the slot's length, which rides
    to the kernel as a scalar (SMEM), so every decode step runs ONE
    executable regardless of how many tokens each co-batched request has
    generated (the zero-recompile contract of the decode engine). A key
    block wholly past a slot's length is neither read from HBM nor computed
    on (`_live_blocks`); for every length >= 1 the result is bit for
    bit what reading every block gives, and a slot of length 0 reads them
    all (the uniform average, which callers never read).
    Multiplies and accumulates in float32 whatever the cache's dtype. The
    key block follows from heads x head_dim, the dtype and the VMEM budget
    (`_decode_block`); `block_k` caps it. Falls back to the masked
    reference row when shapes don't tile or `use_pallas=False` (the two
    paths agree to f32 rounding)."""
    S, Tq, Hq, D = q.shape
    assert Tq == 1, f"flash_decode takes one query per slot, got Tq={Tq}"
    C, H = k.shape[1], k.shape[2]
    assert Hq % H == 0, f"{Hq} query heads over {H} K/V heads"
    if scale is None:
        scale = float(1.0 / (D ** 0.5))
    if interpret is None:
        interpret = _interpret_default()
    lengths = jnp.asarray(lengths, jnp.int32)
    if not use_pallas:
        return _decode_reference(q, k, v, lengths, scale)
    block_c = _decode_block(C, Hq, D, k.dtype.itemsize, block_k, interpret)
    if block_c is None:
        _note_fallback("flash_decode", "reference", C=C, D=D,
                       interpret=interpret)
        return _decode_reference(q, k, v, lengths, scale)
    _note_decode_block(block_c, C=C, H=H, D=D, itemsize=k.dtype.itemsize)
    return _per_shard(
        lambda q, k, v, lengths: _decode_call(q, k, v, lengths, scale,
                                              block_c, interpret, _name),
        (q, k, v, lengths), S, H)


def _append_reference(k, v, k_new, v_new, pos):
    """The append as XLA writes it: a per-slot `lax.dynamic_update_slice`
    vmapped over the slot axis — the semantics `kv_append` must match bit
    for bit, and its fallback. In place on a donated cache, but on the TPU a
    serial loop over the slots (48 strided updates of ~8 us each per buffer
    at 48 x 1024 x 16 x 64)."""
    def one(row, t, at):
        z = jnp.zeros((), at.dtype)
        return jax.lax.dynamic_update_slice(row, t, (at, z, z))
    append = jax.vmap(one)
    return append(k, k_new, pos), append(v, v_new, pos)


def _append_block(C, D, itemsize, interpret):
    """Lane block of the append kernel — the run of cache positions that
    holds a slot's append position — or None => fall back. The kernel writes
    the cache as the TPU stores it for head_dim < 128: positions on the
    lanes (a block of 128 that divides the capacity), head_dim on the
    sublanes (a multiple of 8 for float32, of 16 for a packed bfloat16).
    For head_dim >= 128 the buffer is row-major and a token's [H, D] rows
    are one run of tiles: the step's kernel for that layout writes them by
    one copy (`_decode_rows_kernel`); alone, XLA's update does. Interpret
    mode takes the largest divisor of the capacity up to 128."""
    if D >= LANES:
        return None
    if interpret:
        return _fit_block(C, LANES, 1)
    if C % LANES or D % (32 // itemsize):
        return None
    return LANES


def _with_token(old, row, hit, eye):
    """The [D, lanes] tile `old` with lane `hit` replaced by a token's
    [1, D] row. The row turns onto the sublanes as `_decode_kernel` turns q
    — but by a max over -inf, which hands every value through bit for bit (a
    sum would turn -0.0 into 0.0)."""
    col = jnp.max(jnp.where(eye, row.astype(jnp.float32), -jnp.inf), axis=1,
                  keepdims=True)                              # [D, 1]
    return jnp.where(hit, col, old.astype(jnp.float32)).astype(old.dtype)


def _append_kernel(pos_ref, kn_ref, vn_ref, k_ref, v_ref, ko_ref, vo_ref, *,
                   block_c):
    """One slot's append: k_ref/v_ref are the [1, H, D, block_c] tile of the
    cache that holds position pos[slot] (the BlockSpec's index map chose it
    from the prefetched scalar), ko_ref/vo_ref the same tile of the same
    buffer (aliased). Lane pos % block_c is replaced by the new token's
    values (`_with_token`), every other lane is written back as read."""
    from jax.experimental import pallas as pl
    H, D = k_ref.shape[1], k_ref.shape[2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (D, D), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (D, D), 1))
    hit = (jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1)
           == pos_ref[pl.program_id(0)] % block_c)

    def head(h, carry):
        for new_ref, old_ref, out_ref in ((kn_ref, k_ref, ko_ref),
                                          (vn_ref, v_ref, vo_ref)):
            out_ref[0, h] = _with_token(old_ref[0, h], new_ref[0, h], hit,
                                        eye)
        return carry
    jax.lax.fori_loop(0, H, head, None, unroll=True)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _append_call(k, v, k_new, v_new, pos, block_c, interpret):
    """k/v [S, C, H, D], k_new/v_new [S, 1, H, D], pos [S] -> (k, v) with
    k[s, pos[s]] = k_new[s, 0]. Jitted for the reason `_decode_call` is:
    the layers of a step share one trace and one lowering.

    The operand is the same [S, H, D, C] view of the cache `_decode_call`
    reads (a bitcast of a buffer stored positions-minor), the output is
    aliased onto it, and the only block of a slot that moves is the one
    its position lies in: 2 x H x D x 128 elements read and written a slot,
    whatever the capacity."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, C, H, D = k.shape
    kt, vt = (jnp.transpose(x, (0, 2, 3, 1)) for x in (k, v))
    tile = pl.BlockSpec((1, H, D, block_c),
                        lambda s, pos: (s, 0, 0, pos[s] // block_c))
    row = pl.BlockSpec((1, H, 1, D), lambda s, pos: (s, 0, 0, 0))
    slab = jax.ShapeDtypeStruct((S, H, D, C), k.dtype)
    nk, nv = pl.pallas_call(
        functools.partial(_append_kernel, block_c=block_c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[row, row, tile, tile],
            out_specs=[tile, tile]),
        out_shape=[slab, slab],
        # operands count from the prefetched scalar: 3 and 4 are the slabs
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kv_append",
    )(pos, k_new.reshape(S, H, 1, D), v_new.reshape(S, H, 1, D), kt, vt)
    return tuple(jnp.transpose(x, (0, 3, 1, 2)) for x in (nk, nv))


def kv_append(k, v, k_new, v_new, pos, *, use_pallas=True, interpret=None):
    """Append one token's K and V per slot to a slot-per-request KV cache,
    in place when the cache is donated.

    k, v: [slots, capacity, heads, head_dim] — the cache;
    k_new, v_new: [slots, 1, heads, head_dim] — the step's new token, in
    the cache's dtype; pos: [slots] int32 — where each slot appends, inside
    [0, capacity). Returns (k, v) with k[s, pos[s]] = k_new[s, 0] and
    v[s, pos[s]] = v_new[s, 0], every other element as it was.

    ONE kernel (`kv_append`) for all slots and for K and V (the decode
    step's own append where `flash_decode_append` cannot fold it into the
    decode kernel, and the fold's oracle): the grid walks the slots, a slot's
    block index follows from its position (a prefetched scalar), and the
    output is aliased onto the input, so of each slot only the 128-position
    block that holds the position is read and written back — on the buffer
    in the layout the TPU stores it in, as `flash_decode` reads it. Gives
    way to the vmapped `dynamic_update_slice` (`_append_reference`, the
    same bytes) when the shapes do not tile that way (`_append_block`) or
    `use_pallas=False`."""
    S, C, H, D = k.shape
    if interpret is None:
        interpret = _interpret_default()
    pos = jnp.asarray(pos, jnp.int32)
    if not use_pallas:
        return _append_reference(k, v, k_new, v_new, pos)
    block_c = _append_block(C, D, k.dtype.itemsize, interpret)
    if block_c is None:
        _note_fallback("kv_append", "dynamic_update_slice", C=C, D=D,
                       interpret=interpret)
        return _append_reference(k, v, k_new, v_new, pos)
    return _per_shard(
        lambda k, v, k_new, v_new, pos: _append_call(k, v, k_new, v_new, pos,
                                                     block_c, interpret),
        (k, v, k_new, v_new, pos), S, H)


def _token_writes(append, slot, pos):
    """The fused kernel's copies of a slot's staged tiles (K, V) to the
    `window` cache positions round `pos`; `append` as `_decode_kernel`
    takes it: (the token's refs, the aliased caches, semaphores, stage)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _, out_hbms, sem, stage = append
    window = stage.shape[-1]
    home = pl.ds(pl.multiple_of(pos // window * window, window), window)
    return [pltpu.make_async_copy(stage.at[i], hbm.at[slot, :, :, home],
                                  sem.at[i])
            for i, hbm in enumerate(out_hbms)]


def _insert_token(append, tiles, slot, pos, block_c, eye, buf):
    """The fused kernel's append, on the slot's last live block as it lies
    in buffer `buf` of the K and V `tiles`: the token's rows (`new_refs`,
    [1, H, 1, D]) go into lane pos % block_c of the tiles, where the block's
    arithmetic then finds them as it finds every other key, and the lanes
    round the position — all the cache can be written in: the [H, D, 128]
    tile `kv_append` writes — go to `stage` and from there, by one copy a
    buffer, to the aliased cache (`_token_writes`). The copies are waited
    for when the stage is needed again, a slot later (`_drain`: the last
    slot's), so they ride under a slot's worth of blocks."""
    from jax.experimental import pallas as pl
    new_refs, _, _, stage = append
    H, window = stage.shape[1], stage.shape[-1]
    hit = (jax.lax.broadcasted_iota(jnp.int32, (1, window), 1)
           == pos % window)
    lanes = pl.ds(pl.multiple_of(pos % block_c // window * window, window),
                  window)
    writes = _token_writes(append, slot, pos)

    @pl.when(slot > 0)
    def _staged():
        for write in writes:
            write.wait()

    def head(h, carry):
        for i, (new_ref, tile) in enumerate(zip(new_refs, tiles)):
            stage[i, h] = tile[buf, h, :, lanes] = _with_token(
                tile[buf, h, :, lanes], new_ref[0, h], hit, eye)
        return carry
    jax.lax.fori_loop(0, H, head, None, unroll=True)
    for write in writes:
        write.start()


def _decode_append_kernel(len_ref, q_ref, kn_ref, vn_ref, k_hbm, v_hbm, o_ref,
                          ko_hbm, vo_hbm, *scratch, **kw):
    """`_decode_kernel` with the step's token (kn_ref / vn_ref) and the
    cache a second time, as the outputs ko_hbm / vo_hbm aliased onto k_hbm /
    v_hbm; the last two scratches are the write copies' semaphores and
    their stage."""
    _decode_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, *scratch[:-2],
                   append=((kn_ref, vn_ref), (ko_hbm, vo_hbm), *scratch[-2:]),
                   **kw)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _decode_append_call(q, k, v, k_new, v_new, lengths, scale, block_c,
                        interpret):
    """`_decode_call` and `_append_call` as one kernel: q [S, 1, Hq, D],
    k/v [S, C, H, D], k_new/v_new [S, 1, H, D], lengths [S] (the appended
    token counted) -> (out [S, 1, Hq, D], k, v) with k[s, lengths[s] - 1] =
    k_new[s, 0]. Both slabs stay in HBM, in the [S, H, D, C] view both calls
    use (a bitcast of a buffer stored positions-minor), aliased onto the two
    slab outputs: the kernel reads the live blocks and writes 2 x H x D x 128
    elements a slot. In a trace it is `flash_decode`: the decode kernel of
    the step, whichever entry built it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, _, Hq, D = q.shape
    C, H = k.shape[1], k.shape[2]
    # compiled, the lanes of one tile; interpreted, what tiles the block
    window = _fit_block(block_c, LANES, 1 if interpret else LANES)
    kt, vt = (jnp.transpose(x, (0, 2, 3, 1)) for x in (k, v))
    row = pl.BlockSpec((1, Hq, 1, D), lambda s, lens: (s, 0, 0, 0))
    new = pl.BlockSpec((1, H, 1, D), lambda s, lens: (s, 0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    slab = jax.ShapeDtypeStruct((S, H, D, C), k.dtype)
    out, nk, nv = pl.pallas_call(
        functools.partial(_decode_append_kernel, scale=scale,
                          block_c=block_c, nk=C // block_c, slots=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[row, new, new, in_hbm, in_hbm],
            out_specs=[row, in_hbm, in_hbm],
            scratch_shapes=_decode_scratch(Hq, H, D, block_c, k.dtype) + [
                pltpu.SemaphoreType.DMA((2,)),           # K | V written back
                pltpu.VMEM((2, H, D, window), k.dtype),  # from this stage
            ]),
        out_shape=[jax.ShapeDtypeStruct((S, Hq, 1, D), q.dtype), slab, slab],
        # operands count from the prefetched scalar: 4 and 5 are the slabs
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_APPEND_VMEM_BYTES),
        interpret=interpret,
        name="flash_decode",
    )(lengths, q.reshape(S, Hq, 1, D), k_new.reshape(S, H, 1, D),
      v_new.reshape(S, H, 1, D), kt, vt)
    return (out.reshape(S, 1, Hq, D),
            *(jnp.transpose(x, (0, 3, 1, 2)) for x in (nk, nv)))


# the row-major decode kernel's key block, in cache positions: a slot's blocks
# past its length are not read, so a shorter block reads less; a tile is the
# block's positions x all K/V heads, `_DECODE_TILE_BYTES` at most
_ROWS_BLOCK_POSITIONS = 256
# the rows of an (8, 128) tile: what the row-major kernel's copies start and
# end on in HBM, bfloat16 (stored (8, 128)(2, 1)) and float32 alike
SUBLANES = 8


def _rows_block(C, H, D, itemsize, block_k, interpret, tiled=False):
    """Key-block length of the row-major decode kernel, or None => the
    cache is not one it reads (`flash_decode_append` then takes the other
    kernel or the two calls). H and D are a position's ROWS as the cache
    holds them — K/V heads of head_dim, or, packed (`packed_rows`, and
    `tiled_rows` of narrow heads), rows of 128 lanes with several heads
    side by side. D has to be a multiple of the lanes: the TPU then stores
    a [S, C, H, D] cache row-major, a position's [H, D] values in whole
    tiles, and — compiled — H has to fill a tile's 8 sublanes (packed or
    not: a [.., 8, 128] bfloat16 array lies in (8, 128)(2, 1) tiles), so
    that the [S, C * H, D] view the kernel copies from is the buffer
    itself; or, `tiled`, the leaf is DECLARED in whole tiles, [S, C * H //
    8, 8, D] with H = 1, 2 or 4 (`tiled_rows`: 8 // H positions a tile, and
    a token is written by reading its tile, replacing its rows and writing
    the tile back). The block is then a multiple of 128 positions: 256 for
    every leaf the layer declares over a capacity of 256 or more. Interpret
    mode takes any H and any divisor of the capacity."""
    if D % LANES or (not interpret and H % SUBLANES
                     and not (tiled and SUBLANES % H == 0)):
        return None
    target = min(block_k, C, _ROWS_BLOCK_POSITIONS,
                 max(1, _DECODE_TILE_BYTES // (H * D * itemsize)))
    return _fit_block(C, target, 1 if interpret else LANES)


def packed_rows(H, D, shards=1):
    """Rows of 128 lanes a cache position takes when its `H` K/V heads of
    `D` are PACKED, 128 // D of them side by side on a row, and the leaf is
    declared `[S, C, packed_rows, 128]` — or None where it is not to be so:
    D does not divide the lanes (or fills them), or the rows ONE shard of a
    model axis of `shards` holds (`_heads_per_shard`) are not whole (8, 128)
    tiles (8 heads of 64, half a tile, are packed by `tiled_rows`, into
    whole tiles). A `[S, C, H, D]` cache with D < 128 is stored
    positions-minor on the TPU; declared `[S, C, packed_rows, 128]` the
    same bytes are row-major, one tile a position for 16 heads of 64 in
    float32, which is what `_decode_rows_kernel` reads and writes a token
    into by one 4 KB copy. `[B, T, H, D] -> [B, T, H * D // 128, 128]` is
    the packing: a plain reshape."""
    if D >= LANES or LANES % D or \
            _heads_per_shard(H, shards) * D % (8 * LANES):
        return None
    return H * D // LANES


def tiled_rows(C, H, D, shards=1):
    """The leading rows `C * R // 8` of a cache of `C` positions of `H` K/V
    heads of `D` declared in WHOLE TILES, `[S, C * R // 8, 8, W]`, R rows of
    W lanes a position: the heads themselves where D is a multiple of the
    lanes (R = H, W = D); heads narrower than the lanes PACKED first, 128 //
    D side by side (R = H * D // 128, W = 128) — or None where it is not to
    be: the heads neither are lane rows nor pack into them, R fills a tile
    already or does not divide it, the positions are not whole tiles, or a
    model axis splits the heads (a tile then mixes the shards' rows). Fewer
    than 8 rows are half a tile a position or less, and a `[S, C, 4, 128]`
    array is not the `[S, C * 4, 128]` buffer the row-major kernel copies
    from; declared so, it is (row (c % 2) * 4 + r of tile c // 2 is row r of
    position c: a plain reshape of `[B, T, H, D]`). 4 heads of 128 and 8
    heads of 64 are the same leaf, `[S, C // 2, 8, 128]`."""
    if D < LANES and LANES % D == 0 and H * D % LANES == 0:
        H, D = H * D // LANES, LANES    # narrow heads: packed rows first
    if D % LANES or H >= SUBLANES or SUBLANES % H or shards != 1 \
            or C * H % SUBLANES:
        return None
    return C * H // SUBLANES


def _rows_dot(small, rows_ref, b, dims):
    """`small [M, .] . rows_ref[b]` of the row-major decode kernel on the
    MXU, float32 out; `dims` the contraction as `lax.dot_general` takes it.
    It multiplies in the cache's dtype: bfloat16 rows in one pass, float32
    rows at full float32 precision (Mosaic's default for them is a single
    bfloat16 pass, 2 x 10^-3 off at 48 x 1024 x 16 x 64; at full precision
    the kernel is as fast, bound by its copies: PERF.md section 6, PR 44)."""
    exact = jax.lax.Precision.HIGHEST if rows_ref.dtype == jnp.float32 \
        else None
    return jax.lax.dot_general(small.astype(rows_ref.dtype), rows_ref[b],
                               (dims, ((), ())), precision=exact,
                               preferred_element_type=jnp.float32)


def _decode_rows_kernel(len_ref, q_ref, kx_ref, vx_ref, kn_ref, vn_ref, k_hbm,
                        v_hbm, o_ref, ko_hbm, vo_hbm, k_buf, v_buf, sem, wsem,
                        buf_ref, bias_ref, acc_ref, m_ref, l_ref, *stage,
                        scale, block_c, slots, heads, group, ring):
    """One slot of decode attention on a ROW-MAJOR cache (a position's
    values in rows of a multiple of 128 lanes), the step's token appended on
    the way. k_hbm / v_hbm are the whole caches viewed [S, C * H, D] — row
    c * H + h is row h of position c, which is how a [S, C, H, D] array with
    H = 8 lies in HBM — and stay there; a key block is `block_c` positions =
    `block_c * H` consecutive rows, copied as they lie into one of two VMEM
    buffers (the scheme of `_decode_kernel`: the next copy started before
    the current one is waited for, the next slot's first block under this
    slot's last).

    A row is a K/V head of head_dim D, or `pack` heads of 128 // pack side
    by side on 128 lanes (`packed_rows`: the output's rows are then narrower
    than the cache's); `heads` counts
    the rows a position, `group` the query heads a row serves (the grouped
    heads' G times `pack`). Packed, a query head enters as a 128-lane row
    that is zero outside its own K/V head's lanes, so its product with a
    row is its product with that head; the accumulator keeps 128 lanes a
    query head, of which its own are picked once a slot, at the end.

    Positions x rows are on the sublanes and a row's values on the lanes, so
    both products run on the MXU with no relayout of a tile: the scores of
    ALL query heads against ALL rows of the block, `q [Hq, D] . rows
    [block_c * H, D]^T`, of which a query head keeps the columns of its own
    row (`bias_ref`: 0 there, NEG_INF elsewhere, built once a call), and
    `p [Hq, block_c * H] . rows` with p = 0 in the other rows' columns. The
    MXU's cost is the tiles of the block it has to load as weights, the same
    whether 8 or 64 query rows stream past them, so a row's block is read
    once and serves its whole group. Scores, softmax and accumulator are
    float32; the products multiply in the cache's dtype (`_rows_dot`: at
    full precision on float32 rows).

    The token: the slot holds `length` tokens, the last of them this step's,
    whose K and V rows (`kn_ref` / `vn_ref`, [1, H, D]) are not in the cache
    yet. They go there by one copy a buffer, straight from the operand's
    block to rows (length - 1) * H .. of the aliased cache (`ko_hbm` /
    `vo_hbm`), started first and waited for last. The blocks read are those
    of the length - 1 CACHED positions (one at least: an empty slot reads a
    block it masks whole), every column at or past the token's position
    masked; the token itself is the softmax's first term, from `kx_ref` /
    `vx_ref` (the token's rows repeated to the query heads, [1, Hq, D]), on
    the VPU in float32.

    Fewer rows a position than a tile's 8 (`heads` 1, 2 or 4: `stage` is
    there): a copy to HBM starts and ends on a tile, so the token's rows
    reach the cache with their tile — the 8 rows that hold them are read
    into `stage` (started first, under the blocks), the token's rows
    (`kn_ref` / `vn_ref` repeated to 8 rows, [1, 8, D]) replace theirs
    there, and the tile is written back; the write is waited for a slot
    later (the last slot's at the end), so it rides under that slot's
    blocks. The other positions of the tile are rewritten as read. The rows
    may be packed ones as well (8 heads of 64: `heads` 4, `pack` 2): the
    two are independent, one about a row's lanes, one about a tile's rows.

    `ring`: the cache is a RING of C positions (a sliding window's): the
    token at position length - 1 is written at (length - 1) % C and the slot
    attends to its min(length, C) newest tokens — every block once the ring
    has filled, each column but the token's own, whose place still holds
    the position that has just left the window."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    si = pl.program_id(0)
    rows = block_c * heads
    pos = len_ref[si] - 1
    live = jnp.maximum((pos + block_c - 1) // block_c, 1)
    at, full = pos, False
    if ring:
        C = k_hbm.shape[1] // heads
        at, full = pos % C, pos >= C
        live = jnp.where(full, C // block_c, live)

    def copies(slot, block, b):
        span = pl.ds(pl.multiple_of(block * rows, rows), rows)
        return [pltpu.make_async_copy(hbm.at[slot, span, :], vmem.at[b],
                                      sem.at[i, b])
                for i, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf)))]

    news, outs = (kn_ref, vn_ref), (ko_hbm, vo_hbm)
    sub = bool(stage)
    if sub:         # the token's rows with their tile, through `stage`
        stage, rsem = stage
        par = si % 2
        first = at * heads // SUBLANES * SUBLANES
        home = pl.ds(pl.multiple_of(first, SUBLANES), SUBLANES)
        reads = [pltpu.make_async_copy(hbm.at[si, home, :], stage.at[par, i],
                                       rsem.at[i])
                 for i, hbm in enumerate((k_hbm, v_hbm))]
        for read in reads:
            read.start()

        def writes(parity):
            return [pltpu.make_async_copy(stage.at[parity, i],
                                          hbm.at[si, home, :],
                                          wsem.at[parity, i])
                    for i, hbm in enumerate(outs)]
    else:
        home = pl.ds(pl.multiple_of(at * heads, heads), heads)
        direct = [pltpu.make_async_copy(new.at[0], hbm.at[si, home, :],
                                        wsem.at[i])
                  for i, (new, hbm) in enumerate(zip(news, outs))]
        for write in direct:
            write.start()

    @pl.when(si == 0)
    def _first():
        buf_ref[0] = 0
        for copy in copies(0, 0, 0):
            copy.start()
        col = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 0)
        bias_ref[...] = jnp.where(col % heads == row // group, 0.0, NEG_INF)

    q = q_ref[0]                                            # [Hq, D]
    m_ref[...] = jnp.sum(q.astype(jnp.float32)
                         * kx_ref[0].astype(jnp.float32), axis=1,
                         keepdims=True) * scale             # the token's score
    l_ref[...] = jnp.ones_like(l_ref)
    acc_ref[...] = vx_ref[0].astype(jnp.float32)

    def block(j, b):
        more = j + 1 < live
        for copy in copies(jnp.where(more, si, jnp.minimum(si + 1, slots - 1)),
                           jnp.where(more, j + 1, 0), 1 - b):
            copy.start()
        for copy in copies(si, j, b):
            copy.wait()
        s = _rows_dot(q, k_buf, b, ((1,), (1,))) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        own = (at - j * block_c) * heads    # the token's columns: from here
        cached = col < own
        if ring:    # and, the ring full, every column after them
            cached |= full & (col >= own + heads)
        s = jnp.where(cached, s + bias_ref[...], NEG_INF)   # [Hq, rows]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[...] = acc_ref[...] * corr + _rows_dot(
            p, v_buf, b, ((1,), (0,)))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        return 1 - b

    b = jax.lax.fori_loop(0, live, block, buf_ref[0])
    buf_ref[0] = b

    @pl.when(si == slots - 1)
    def _drain():
        for copy in copies(0, 0, b):
            copy.wait()

    out = acc_ref[...] / l_ref[...]                         # l >= 1
    D = o_ref.shape[-1]
    pack = out.shape[1] // D
    if pack > 1:    # a query head's own lanes of its 128
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        out = jnp.where(lane // D == row % group // (group // pack), out, 0.0)
        out = sum(out[:, i * D:(i + 1) * D] for i in range(pack))
    o_ref[0] = out.astype(o_ref.dtype)
    if not sub:
        for write in direct:
            write.wait()
        return
    for read in reads:
        read.wait()
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, 1), 0)
    mine = (row >= at * heads - first) & (row < at * heads - first + heads)
    for i, new in enumerate(news):      # exact through float32
        stage[par, i] = jnp.where(
            mine, new[0].astype(jnp.float32),
            stage[par, i].astype(jnp.float32)).astype(stage.dtype)
    for write in writes(par):
        write.start()

    @pl.when(si > 0)
    def _before():      # the slot before's, under this slot's blocks
        for write in writes(1 - par):
            write.wait()

    @pl.when(si == slots - 1)
    def _last():
        for write in writes(par):
            write.wait()


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _decode_rows_call(q, k, v, k_new, v_new, lengths, scale, block_c,
                      interpret, ring=False):
    """`_decode_append_call` for a row-major cache: q [S, 1, Hq, D], k/v [S,
    C, H, W] — W = D, or packed (`packed_rows`) H * W = K/V heads * D with W
    = 128 —, or, H of 1, 2 or 4 (`tiled_rows`: packed or not), [S, C * H //
    8, 8, W]; k_new/v_new [S, 1, H, W], lengths [S] (the token counted) ->
    (out [S, 1, Hq, D], k, v). The kernel's view of a cache is [S, C * H,
    W]: the same tiles in the same order, so the reshape is a bitcast
    (tests/test_tpu_compile.py holds it to that) and the two slab outputs
    are aliased onto the caches. `ring`: a sliding window's cache
    (`_decode_rows_kernel`). In a trace it is `flash_decode`, as the kernel
    it stands in for, or — a ring's — `flash_decode_window`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, _, Hq, D = q.shape
    H, W = k_new.shape[2:]
    total = k.shape[1] * k.shape[2]         # C * H rows a slot
    pack = W // D
    G = Hq // H                     # query heads a row
    rows = block_c * H
    sub = H < SUBLANES              # a token's rows go with their tile
    row = pl.BlockSpec((1, Hq, W), lambda s, lens: (s, 0, 0))
    new = pl.BlockSpec((1, SUBLANES if sub else H, W),
                       lambda s, lens: (s, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    slab = jax.ShapeDtypeStruct((S, total, W), k.dtype)
    kn, vn = k_new.reshape(S, H, W), v_new.reshape(S, H, W)
    q = q.reshape(S, Hq, D).astype(k.dtype)
    if pack > 1:    # zero outside the lanes of the query head's K/V head
        own = (jnp.arange(Hq) % G // (G // pack))[:, None] == jnp.arange(pack)
        q = jnp.where(own[None, :, :, None], q[:, :, None, :],
                      jnp.zeros((), q.dtype)).reshape(S, Hq, W)
    tile = (lambda x: jnp.tile(x, (1, SUBLANES // H, 1))) if sub \
        else (lambda x: x)
    out, nk, nv = pl.pallas_call(
        functools.partial(_decode_rows_kernel, scale=scale, block_c=block_c,
                          slots=S, heads=H, group=G, ring=ring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[row, row, row, new, new, in_hbm, in_hbm],
            out_specs=[pl.BlockSpec((1, Hq, D), lambda s, lens: (s, 0, 0)),
                       in_hbm, in_hbm],
            scratch_shapes=[
                pltpu.VMEM((2, rows, W), k.dtype),           # K tiles
                pltpu.VMEM((2, rows, W), k.dtype),           # V tiles
                pltpu.SemaphoreType.DMA((2, 2)),             # (K | V, buffer)
                # K | V written (through the stage: of either parity)
                pltpu.SemaphoreType.DMA((2, 2) if sub else (2,)),
                pltpu.SMEM((1,), jnp.int32),                 # next buffer
                pltpu.VMEM((Hq, rows), jnp.float32),         # own-row bias
                pltpu.VMEM((Hq, W), jnp.float32),            # acc
                pltpu.VMEM((Hq, 1), jnp.float32),            # running max
                pltpu.VMEM((Hq, 1), jnp.float32),            # running sum
            ] + ([pltpu.VMEM((2, 2, SUBLANES, W), k.dtype),  # the token's
                  pltpu.SemaphoreType.DMA((2,))]             # tile, read
                 if sub else [])),
        out_shape=[jax.ShapeDtypeStruct((S, Hq, D), q.dtype), slab, slab],
        # operands count from the prefetched scalar: 6 and 7 are the slabs
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_APPEND_VMEM_BYTES),
        interpret=interpret,
        name="flash_decode_window" if ring else "flash_decode",
    )(lengths, q, jnp.repeat(kn, G, axis=1), jnp.repeat(vn, G, axis=1),
      tile(kn), tile(vn), k.reshape(S, total, W), v.reshape(S, total, W))
    return (out.reshape(S, 1, Hq, D), nk.reshape(k.shape),
            nv.reshape(v.shape))


def flash_decode_append(q, k, v, k_new, v_new, pos, *, scale=None,
                        use_pallas=True, block_k=1024, interpret=None,
                        ring=False):
    """A decode step's attention layer in ONE kernel: append the step's
    token to the cache and attend to the cache with it.

    q: [slots, 1, heads, head_dim]; k, v: [slots, capacity, kv_heads,
    head_dim] — the cache, in place when donated — or PACKED, [slots,
    capacity, kv_heads * head_dim // 128, 128] (`packed_rows`), or in WHOLE
    TILES, [slots, capacity * rows // 8, 8, lanes] (`tiled_rows`: rows x
    lanes a position are kv_heads x head_dim or, heads narrower than 128
    packed as well, kv_heads * head_dim // 128 x 128); each is recognised
    by its shape against the token's; k_new, v_new: [slots, 1, kv_heads,
    head_dim] — the token, in the cache's dtype; pos: [slots] int32 — where
    each slot appends, inside [0, capacity): the slot then holds pos + 1
    tokens, so `flash_decode`'s length-0 contract does not exist here.
    Returns (out, k, v): the cache `kv_append` leaves and, on it, the rows
    `flash_decode(q, k, v, pos + 1)` gives, both bit for bit.

    `ring`: the cache is a sliding window's RING of `capacity` positions and
    `pos` any position >= 0: the token is written at pos % capacity and the
    slot attends to its min(pos + 1, capacity) newest tokens, itself among
    them. The row-major kernel is then named `flash_decode_window` in a
    trace: a window layer's call reads its ring and no more, and a reader
    that counts a full layer's bytes a `flash_decode` call must not see it.

    The kernel is `flash_decode`'s (`_decode_kernel`, and its name in a
    trace) with the append folded in: when a slot's last live block — the
    one that holds `pos` — is in VMEM, the token goes into its lane there
    and the 128 lanes round it are copied back to the cache under the
    block's arithmetic. The cache is written exactly where `kv_append`
    writes it; the append's own read of that tile, its launch and its grid
    are gone. A cache that is stored row-major — head_dim a multiple of 128,
    packed, or in whole tiles — takes the kernel that reads it so
    (`_decode_rows_call`, `_rows_block`: the same contract, the output to
    float32 rounding — its products run on the MXU in another order — and
    both slabs bit for bit).
    Gives way to the two calls, counted in
    `pallas_fallback_total{kernel="flash_decode",
    path="kv_append+flash_decode"}`, wherever neither kernel takes these
    shapes (`_append_block`, `_decode_block`, `_rows_block` — under a mesh
    the last is asked about the rows one shard holds, so 8 heads split over
    a model axis give way; a packed cache is unpacked for them and packed
    again, a copy of both slabs; a ring that is not row-major gives way
    too), and is the two references under `use_pallas=False`."""
    S, Tq, Hq, D = q.shape
    assert Tq == 1, f"flash_decode takes one query per slot, got Tq={Tq}"
    H = k_new.shape[2]
    assert Hq % H == 0, f"{Hq} query heads over {H} K/V heads"
    W = k.shape[3]
    R = H * D // W                      # rows a position
    C = k.shape[1] * k.shape[2] // R
    plain = k.shape[2:] == (H, D)
    # fewer rows a position than a tile's, declared in whole tiles
    tiled = not plain and R < SUBLANES == k.shape[2]
    assert plain or (W in (D, LANES) and R * W == H * D
                     and (tiled or k.shape[2] == R)), \
        f"a cache of {k.shape} for {H} K/V heads of {D}"
    if scale is None:
        scale = float(1.0 / (D ** 0.5))
    if interpret is None:
        interpret = _interpret_default()
    pos = jnp.asarray(pos, jnp.int32)
    size = k.dtype.itemsize
    block_c, rows = None, False
    if use_pallas and plain and not ring \
            and _append_block(C, D, size, interpret):
        block_c = _decode_block(C, Hq, D, size, block_k, interpret)
    elif use_pallas:
        # the rows kernel's view needs the SHARD's rows to fill a tile, or
        # the leaf to be declared in whole tiles
        block_c, rows = _rows_block(
            C, _heads_per_shard(R), W, size, block_k, interpret,
            tiled=tiled), True
    if block_c is not None:
        _note_decode_block(block_c, C=C, H=H, D=D, itemsize=size)
        k_new, v_new = (x.reshape(S, 1, R, W) for x in (k_new, v_new))

        def call(*a):
            if rows:
                return _decode_rows_call(*a, scale, block_c, interpret, ring)
            return _decode_append_call(*a, scale, block_c, interpret)
        # split by the cache's rows: packed, they divide where the heads do
        return _per_shard(call, (q, k, v, k_new, v_new, pos + 1), S,
                          k.shape[2])
    if use_pallas:
        _note_fallback("flash_decode", "kv_append+flash_decode", C=C, D=D,
                       interpret=interpret)
    rows = k.shape
    k, v = (x.reshape(S, C, H, D) for x in (k, v))
    with jax.named_scope("kv_append"):
        k, v = kv_append(k, v, k_new, v_new, pos % C if ring else pos,
                         use_pallas=use_pallas, interpret=interpret)
    out = flash_decode(q, k, v, jnp.minimum(pos + 1, C), scale=scale,
                       use_pallas=use_pallas, block_k=block_k,
                       interpret=interpret)
    return out, k.reshape(rows), v.reshape(rows)


def flash_decode_paged(q, k_pool, v_pool, block_table, lengths, *,
                       scale=None, use_pallas=True, block_k=1024,
                       interpret=None, window=None):
    """Decode attention through a paged KV pool (decode/paged.py).

    q:           [slots, 1, heads, head_dim] — current token's query;
    k_pool/v_pool: [num_blocks, block_size, heads, head_dim] — the shared
                 block pool (block 0 is the scratch block);
    block_table: [slots, max_blocks] int32 — logical block j of slot s
                 lives in pool block block_table[s, j] (0 = unallocated);
    lengths:     [slots] int32 — valid tokens per slot;
    window:      a slot sees its `window` newest tokens only (a sliding
                 window's layer keeps every block of the shared table, so
                 this is the masked row, `_decode_reference`, counted in
                 `pallas_fallback_total{kernel="flash_decode_paged"}`).

    Token t of a slot sits at (table[t // bs], t % bs), so gathering the
    slot's table row reconstructs its contiguous cache:
    ``pool[table]`` -> [slots, max_blocks, bs, H, D] -> reshape to
    [slots, max_blocks*bs, H, D], then the SAME masked decode attention as
    the slab path (`flash_decode` / `_decode_reference` — parity-tested
    token-for-token). Unallocated entries gather scratch garbage at
    positions >= length, which the length mask already excludes.

    The gather IS the paged indirection: XLA streams each slot's blocks
    from wherever they sit in the pool, and the bytes read per step equal
    the slab path's (table capacity x H x D), while the bytes RESIDENT
    shrink to blocks actually allocated — the capacity win paging buys.
    A Mosaic-native gather-inside-the-kernel (indexing block tiles from
    SMEM) is the rig follow-up; the fallback/masked-reference contract is
    identical either way."""
    S = q.shape[0]
    N, bs, H, D = k_pool.shape
    nb = block_table.shape[1]
    table = jnp.asarray(block_table, jnp.int32)
    k = jnp.take(k_pool, table, axis=0).reshape(S, nb * bs, H, D)
    v = jnp.take(v_pool, table, axis=0).reshape(S, nb * bs, H, D)
    if window is not None:
        if use_pallas:
            _note_fallback("flash_decode_paged", "reference_window",
                           C=nb * bs, D=D, window=window)
        return _decode_reference(
            q, k, v, lengths, float(1.0 / (D ** 0.5)) if scale is None
            else scale, window=window)
    return flash_decode(q, k, v, lengths, scale=scale, use_pallas=use_pallas,
                        block_k=block_k, interpret=interpret,
                        _name="flash_decode_paged")


def can_flash(Tq, Tk, D, *, block_q=256, block_k=1024, interpret=None):
    """True when the Pallas kernel can run these shapes (compiled-mode tile
    alignment on TPU; any divisor in interpret mode)."""
    if interpret is None:
        interpret = _interpret_default()
    return _plan(Tq, Tk, D, block_q, block_k, interpret) is not None
