"""Long-context attention: blockwise (flash-style) and ring attention.

NEW first-class capability with no reference counterpart (SURVEY.md §5
"Long-context / sequence parallelism: none" — the reference's long-sequence
story is truncated BPTT + masking only). Design follows the public ring
attention recipe (blockwise online-softmax accumulation + ppermute of K/V
around the ICI ring) so sequence length scales linearly with the number of
devices on the `seq` mesh axis.

Shapes: q/k/v are [batch, time, heads, head_dim] (BTHD).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .sharding import SEQ_AXIS

NEG_INF = -1e30


def attention_reference(q, k, v, *, causal=False, scale=None, key_mask=None,
                        window=None):
    """Plain softmax attention (the correctness oracle for the blockwise and
    ring paths). key_mask: optional [batch, time] validity of key positions.
    window: with `causal`, query i sees keys i - window < j <= i."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :] > 0, s, NEG_INF)
    if causal:
        qpos = jnp.arange(Tq)[:, None]
        kpos = jnp.arange(Tk)[None, :]
        bad = kpos > qpos
        if window is not None:
            bad = bad | (kpos <= qpos - window)
        s = jnp.where(bad[None, None], NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _causal_mask_fn(qpos, window=None):
    """Scores mask: key positions after the query's global position — and,
    with a `window`, those at or below position - window — get NEG_INF
    (shared by the blockwise scan and the ring body)."""
    def mask_fn(s, k_off):
        kpos = k_off + jnp.arange(s.shape[-1])
        bad = kpos[None, :] > qpos[:, None]               # Tq, Tb
        if window is not None:
            bad = bad | (kpos[None, :] <= qpos[:, None] - window)
        return jnp.where(bad[None, None], NEG_INF, s)
    return mask_fn


def _block_update(carry, kv, q, scale, mask_fn=None):
    """Online-softmax accumulation of one K/V block into (o, m, l).
    kv = (kb, vb, k_off[, km]): km is an optional [B, Tb] KEY-validity mask
    for this block. A fully-masked block is harmless: its scores are the
    finite NEG_INF, so once any later block contributes a real max, the
    exp(m - m_new) correction zeroes the bogus partials (and a row with NO
    valid key anywhere degrades to the same uniform average the reference
    softmax yields over all-NEG_INF scores)."""
    o, m, l = carry
    kb, vb, k_off = kv[:3]
    km = kv[3] if len(kv) > 3 else None
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kb) * scale      # B,H,Tq,Tb
    if mask_fn is not None:
        s = mask_fn(s, k_off)
    if km is not None:
        s = jnp.where(km[:, None, None, :] > 0, s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)                           # B,H,Tq
    m_new = jnp.maximum(m, m_blk)
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])                     # B,H,Tq,Tb
    l = l * corr + jnp.sum(p, axis=-1)
    o = o * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
    return (o, m_new, l), None


def blockwise_attention(q, k, v, *, block_size=256, causal=False, scale=None,
                        key_mask=None, window=None):
    """Single-device flash-style attention: scan over K/V blocks with online
    softmax — O(T_block) memory instead of O(T^2). Numerically identical to
    attention_reference, including its key_mask ([batch, time] key validity)
    semantics — masked sequences keep the memory-bounded path instead of
    falling back to the materializing reference."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    block_size = min(block_size, Tk)
    assert Tk % block_size == 0, "block_size must evenly divide the key length"
    n_blocks = Tk // block_size
    scale = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(q.dtype)

    kb = k.reshape(B, n_blocks, block_size, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, block_size, H, D).transpose(1, 0, 2, 3, 4)
    offs = jnp.arange(n_blocks) * block_size

    mask_fn = _causal_mask_fn(jnp.arange(Tq), window) if causal else None

    o0 = jnp.zeros((B, H, Tq, D), q.dtype)
    m0 = jnp.full((B, H, Tq), NEG_INF, q.dtype)
    l0 = jnp.zeros((B, H, Tq), q.dtype)
    if key_mask is not None:
        # accept the same broadcastable shapes the reference does ((1, Tk)
        # shared masks etc.) before carving into blocks
        key_mask = jnp.broadcast_to(jnp.asarray(key_mask), (B, Tk))
        kmb = key_mask.reshape(B, n_blocks, block_size).transpose(1, 0, 2)
        blocks = (kb, vb, offs, kmb)
    else:
        blocks = (kb, vb, offs)
    (o, m, l), _ = jax.lax.scan(
        functools.partial(_block_update, q=q, scale=scale, mask_fn=mask_fn),
        (o0, m0, l0), blocks)
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3)                      # back to BTHD


def _ring_attention_local(q, k, v, km=None, *, causal, scale, axis_name,
                          use_flash=False, block_q=256, block_k=1024):
    """Per-shard body under shard_map: each device owns a time-slice of
    q/k/v (and of the optional key mask, which rotates with K/V); queries
    accumulate online-softmax partials as K/V blocks move around the ring
    (ppermute over ICI).

    use_flash: run the Pallas flash kernel on each visiting shard (the
    shard's global key offset drives the causal mask in-kernel) and merge
    the per-shard (out, lse) partials by log-sum-exp — the [Tq, Tb] score
    block never materializes. The einsum `_block_update` stays as the
    fallback for shapes the kernel can't tile."""
    B, Tq, H, D = q.shape
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    if use_flash:
        # the kernel wants a hashable Python scalar, and jnp ops on
        # constants become tracers under the shard_map trace; a TRACED
        # caller-supplied scale can't feed the kernel — take the einsum
        # path for it instead of crashing
        try:
            scale = float(scale) if scale is not None \
                else 1.0 / float(D) ** 0.5
        except (TypeError, jax.errors.ConcretizationTypeError):
            use_flash = False
    if not use_flash:
        scale = scale if scale is not None \
            else 1.0 / jnp.sqrt(D).astype(q.dtype)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def rotate(kr, vr, kmr):
        kr = jax.lax.ppermute(kr, axis_name, perm)
        vr = jax.lax.ppermute(vr, axis_name, perm)
        if kmr is not None:
            kmr = jax.lax.ppermute(kmr, axis_name, perm)
        return kr, vr, kmr

    if use_flash:
        from ..kernels.flash_attention import (flash_attention,
                                               flash_attention_lse)

        if n == 1:
            # degenerate ring: one shard holds everything — the kernel
            # alone IS the answer; no LSE emission, no merge passes
            return flash_attention(q, k, v, causal=causal, scale=scale,
                                   key_mask=km, block_q=block_q,
                                   block_k=block_k)

        # accumulators derive from q so shard_map's varying-axis tracking
        # sees them as seq-varying; carry (normalized out, lse) in f32 and
        # fold each visiting shard in with the standard log-sum-exp merge
        o = (q * 0.0).astype(jnp.float32)                         # B,Tq,H,D
        lse = (q[..., 0].transpose(0, 2, 1) * 0.0).astype(
            jnp.float32) + NEG_INF                                # B,H,Tq

        def flash_body(r, state):
            o, lse, kr, vr, kmr = state
            src = (my - r) % n

            def visit():
                return flash_attention_lse(
                    q, kr, vr, causal=causal, scale=scale,
                    key_mask=kmr, q_offset=my * Tq if causal else None,
                    k_offset=src * Tq if causal else None,
                    block_q=block_q, block_k=block_k)

            if causal:
                # a strictly-future shard is fully masked: skip its kernel
                # (and its q/k/v DMAs) outright instead of streaming NEG_INF
                out_r, lse_r = jax.lax.cond(
                    src <= my, visit,
                    lambda: (jnp.zeros(q.shape, q.dtype),
                             jnp.full((B, H, Tq), NEG_INF, jnp.float32)))
            else:
                out_r, lse_r = visit()
            m_new = jnp.maximum(lse, lse_r)
            w_acc = jnp.exp(lse - m_new)
            w_r = jnp.exp(lse_r - m_new)
            tw = lambda w: w.transpose(0, 2, 1)[..., None]        # → B,Tq,H,1
            o = (o * tw(w_acc) + out_r.astype(jnp.float32) * tw(w_r)) \
                / tw(jnp.maximum(w_acc + w_r, 1e-30))
            lse = m_new + jnp.log(jnp.maximum(w_acc + w_r, 1e-30))
            kr, vr, kmr = rotate(kr, vr, kmr)
            return o, lse, kr, vr, kmr

        o, lse, _, _, _ = jax.lax.fori_loop(0, n, flash_body,
                                            (o, lse, k, v, km))
        return o.astype(q.dtype)

    # einsum fallback: the same online-softmax math, materializing one
    # [Tq, Tb] score block per ring step
    qt = q.transpose(0, 2, 1, 3)                       # B,H,Tq,D
    o = qt * 0.0
    m = qt[..., 0] * 0.0 + NEG_INF                     # B,H,Tq
    l = qt[..., 0] * 0.0
    mask_fn = _causal_mask_fn(my * Tq + jnp.arange(Tq)) if causal else None

    def body(r, state):
        o, m, l, kr, vr, kmr = state
        # kr/vr originated on device (my - r) mod n; the per-shard update is
        # the SAME online-softmax step the single-device blockwise path
        # scans with — a ring step is a blockwise step whose "block" is the
        # visiting shard and whose key offset is that shard's global start
        # (kmr is None — a static empty pytree node — on the unmasked path,
        # which therefore pays no mask select and no extra ppermute)
        src = (my - r) % n
        blk = (kr, vr, src * Tq) if kmr is None else (kr, vr, src * Tq, kmr)
        (o, m, l), _ = _block_update((o, m, l), blk, q, scale, mask_fn)
        kr, vr, kmr = rotate(kr, vr, kmr)
        return o, m, l, kr, vr, kmr

    o, m, l, _, _, _ = jax.lax.fori_loop(0, n, body, (o, m, l, k, v, km))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3)


def ring_attention(q, k, v, mesh, *, causal=False, scale=None,
                   axis_name=SEQ_AXIS, key_mask=None, use_flash=None,
                   block_q=256, block_k=1024):
    """Sequence-parallel attention over `mesh`'s `axis_name` ring: time is
    sharded across devices; peak memory per device is O(T/n) and the K/V
    transfer rides the ICI ring concurrently with compute. key_mask:
    optional [batch, time] key validity, sharded and rotated with K/V.

    use_flash (default: auto) runs the Pallas flash kernel on each visiting
    K/V shard — the per-step [Tq/n, Tk/n] score block stays in VMEM instead
    of materializing — falling back to the einsum block update when the
    per-shard shapes don't tile the kernel's blocks."""
    from ..kernels.flash_attention import can_flash
    n = mesh.shape[axis_name]
    B, T, H, D = q.shape
    if use_flash is None:
        use_flash = T % n == 0 and can_flash(T // n, T // n, D,
                                             block_q=block_q, block_k=block_k)
    spec = P(None, axis_name, None, None)
    sh = NamedSharding(mesh, spec)
    q = jax.device_put(q, sh)
    k = jax.device_put(k, sh)
    v = jax.device_put(v, sh)
    body = functools.partial(_ring_attention_local, causal=causal,
                             scale=scale, axis_name=axis_name,
                             use_flash=use_flash, block_q=block_q,
                             block_k=block_k)
    # pallas_call outputs carry no varying-mesh-axis metadata, so the flash
    # path opts out of shard_map's vma check (the einsum path keeps it)
    extra = {"check_vma": False} if use_flash else {}
    if key_mask is None:   # unmasked path: no mask traffic on the ring
        fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, **extra)
        return fn(q, k, v)
    mspec = P(None, axis_name)
    key_mask = jnp.broadcast_to(jnp.asarray(key_mask, q.dtype),
                                q.shape[:2])
    key_mask = jax.device_put(key_mask, NamedSharding(mesh, mspec))
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec, mspec),
                   out_specs=spec, **extra)
    return fn(q, k, v, key_mask)
