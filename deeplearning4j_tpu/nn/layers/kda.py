"""Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692 section 3): linear
attention whose state is updated by a delta rule under a per-channel decay
(conf: nn/conf/layers.py KimiDeltaAttentionLayer — NEW, no reference
counterpart). With x the layer's input at position t, H heads of D:

    (q, k, v, f, gate) = split(x W_in)      five H x D wide
    (q, k, v) = silu(causal depthwise conv_K(q | k | v))       no bias
    q <- q / |q|_2 * D^-1/2 ;  k <- k / |k|_2                  a head
    g = lower_bound * sigmoid(exp(A_log) (f + dt_bias))         in [lower_bound, 0]
    beta = beta_scale * sigmoid(x Wb)                           a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t ;   out = [RMSNorm_D(o_t) * sigmoid(gate)] Wo

The conf's variants: `gate_form="softplus"` makes g = -exp(A_log) softplus(f +
dt_bias), unbounded below (Kimi Linear's own; the bounded form above is
`ling3_flash`'s); `gate_rank=r` makes f and gate low rank, (x W_fa) W_fb and
(x W_ga) W_gb with W_in = [W_q | W_k | W_v | W_fa | W_ga] then [n_in, 3 H D
+ 2 r]; `beta_scale=2` lets beta reach 2 (a negative eigenvalue of I - beta
k k^T). Nothing of `kda_chunked` or `kda_step` changes with them: every
exponent is still a difference <= 0 — in `kda_chunked` each of the two
factors about a sub-block's boundary is one —, and the triangular system's
entries grow to 2 (tests/test_solar_hybrid.py holds both to the sequential
rule).

Mamba-2's state (nn/layers/mamba.py) decays by one scalar a head and is
added to; this one decays by a value a channel and is CORRECTED: the rank-1
update reads S^T k of the decayed state, so neither `ssd_chunked` nor
`ssm_step` expresses it. Three formulations of the same recurrence:

- `forward` (training, output(), and the decode prefill): `kda_chunked`, in
  chunks of `chunk_size` and, inside a chunk, sub-blocks of 16 positions,
  in `jax.numpy`; autodiff gives the backward. A masked position gets g = 0
  and beta = 0: the state passes through it unchanged.
- `decode_step`: one token a slot against the slot's state, in place
  (kernels.kda_step); the convs are a 4-tap product with the slot's tail.
- the sequential scan over positions is the reference's
  (benchmarks/reference/ling3_flash.py), which both are held against.

Decode state, per slot: `state` [H, D, D] (d_k major, d_v on the lanes) in
the accumulation dtype — float32 under bfloat16: the recurrence compounds
over every token of a session; `conv`, the last K - 1 raw inputs of the
three convs side by side, [K - 1, 3 H D] in the cache dtype. Neither grows
with the sequence and neither rewinds by a length reset
(`decode_rewindable = False`). The decay, the convs, the normalisations, the
chunked form (its contractions at `highest`) and the output norm's
statistics run in float32; the projections in the activations' dtype.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .base import (BaseLayerModule, CacheLeaf, apply_dropout,
                   note_cache_entry, register_impl)
from .convolution import rms_norm
from .recurrent import _acc_dtype
from ..weights import init_weights
from ..conf.inputs import InputType

_HIGHEST = lax.Precision.HIGHEST
_SUB = 16                # positions a sub-block of a chunk's scores
_L2_EPS = 1e-6


def _sub_block(Q):
    """Positions a sub-block of a chunk of Q: `_SUB`, or half of it where
    only that divides Q; a chunk no longer than `_SUB`, or one that neither
    divides, is one block."""
    if Q <= _SUB:
        return Q
    return next((B for B in (_SUB, _SUB // 2) if Q % B == 0), Q)


def kda_chunked(q, k, v, g, beta, chunk):
    """The delta rule over a whole sequence, chunk by chunk.

    q, k, v, g [b, t, H, D] (q and k normalised; g <= 0 the decay's log, 0
    at masked positions), beta [b, t, H] (0 at masked positions), one dtype
    (float32 in practice) -> o [b, t, H, D] and the state after the last
    position, [b, H, D, D]. Inside a chunk, with G_r the sum of g up to and
    including r and S_0 the state entering it:

        A_ij = beta_i sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])     j < i
        (I + A) U = beta * (V - (K * exp G) S_0)      unit lower-triangular
        o_r = S_0^T (q_r * exp G_r)
              + sum_{i <= r} (sum_d q_r[d] k_i[d] exp(G_r[d] - G_i[d])) u_i
        S_end = Diag(exp G_C) S_0 + sum_i (k_i * exp(G_C - G_i)) u_i^T

    Every exponent is formed as a difference <= 0 BEFORE it is
    exponentiated: a chunk at the gate's bound sums to hundreds below zero,
    where exp(G_i) / exp(G_j) is 0 / 0 in float32.

    The two score matrices (the sums over d above) are formed by sub-blocks
    of `_sub_block(Q)` positions. A block on the diagonal is the sum as
    written, one exponent an (i, j, d). A block below it, rows i >= r and
    columns j < r with r the first position of its block row, is a matrix
    product about that boundary:

        exp(G_i - G_j) = exp(G_i - G_r) exp(G_r - G_j)             j < r <= i
        sum_d = (k_i * exp(G_i - G_r)) . (k_j * exp(G_r - G_j))

    BOTH factors' exponents are differences <= 0, since g <= 0 and j < r <=
    i: neither overflows, and one underflows only where the product does. A
    block above the diagonal is zero and is never formed. The triangular
    system is solved over the same sub-blocks, by forward substitution: a
    block row's right-hand side less A's blocks left of the diagonal times
    the rows of U already found, then that block's own unit-triangular
    solve (XLA inverts a triangular block on the TPU at a cost that grows
    faster than its size squared: four of 16 cost a sixth of one of 64,
    PERF.md section 6, PR 45)."""
    b, T, H, D = q.shape
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:                 # g = 0, beta = 0: padding leaves the state alone
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nc = (T + pad) // Q
    chunks = lambda a: jnp.moveaxis(a.reshape((b, nc, Q) + a.shape[2:]), 1, 0)
    B = _sub_block(Q)
    n = Q // B
    blocks = lambda a: a.reshape((b, n, B) + a.shape[2:])
    upto = jnp.tril(jnp.ones((B, B), bool))[:, :, None, None]    # j <= i

    def scores(qc, kc, G):
        """kk, qk [b, H, Q, Q]: sum_d k_i k_j exp(G_i - G_j) and the same
        with q_i, at j <= i; zero above the diagonal."""
        Gb, kb = blocks(G), blocks(kc)
        rows = jnp.stack([kb, blocks(qc)])               # [2, b, n, B, H, D]
        diff = Gb[:, :, :, None] - Gb[:, :, None, :]     # [b, n, i, j, H, D]
        kE = kb[:, :, None] * jnp.exp(jnp.where(upto, diff, -jnp.inf))
        on = jnp.moveaxis(jnp.sum(rows[:, :, :, :, None] * kE, axis=-1),
                          -1, 2)                         # [2, b, H, n, i, j]
        rows = rows * jnp.exp(Gb - Gb[:, :, :1])         # exp(G_i - G_r)
        out = []
        for a in range(n):
            r = a * B
            row = []
            if a:
                row.append(jnp.einsum(
                    "sbihd,bjhd->sbhij", rows[:, :, a],
                    kc[:, :r] * jnp.exp(G[:, r:r + 1] - G[:, :r]),
                    precision=_HIGHEST))
            row.append(on[:, :, :, a])
            if r + B < Q:
                row.append(jnp.zeros((2, b, H, B, Q - r - B), G.dtype))
            out.append(jnp.concatenate(row, axis=-1))
        return jnp.concatenate(out, axis=-2)

    def solve(A, rhs):
        """U of (I + A) U = rhs, A [b, H, Q, Q] read strictly below its
        diagonal, rhs [b, H, Q, D]: forward substitution over the block
        rows, a B x B unit-triangular solve each."""
        U = []
        for a in range(n):
            at = slice(a * B, (a + 1) * B)
            r = rhs[:, :, at]
            if a:
                r = r - jnp.einsum("bhij,bhjv->bhiv", A[:, :, at, :a * B],
                                   jnp.concatenate(U, axis=2),
                                   precision=_HIGHEST)
            U.append(jax.scipy.linalg.solve_triangular(
                A[:, :, at, at], r, lower=True, unit_diagonal=True))
        return jnp.concatenate(U, axis=2)

    def one(S0, c):
        qc, kc, vc, gc, bc = c                               # [b, Q, H, ..]
        G = jnp.cumsum(gc, axis=1)
        from_start = jnp.exp(G)                              # [b, Q, H, D]
        kk, qk = scores(qc, kc, G)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bihd,bhdv->bihv", kc * from_start, S0, precision=_HIGHEST))
        U = solve(jnp.moveaxis(bc, 1, 2)[..., None] * kk,
                  jnp.moveaxis(rhs, 1, 2))                   # [b, H, Q, D]
        o = jnp.einsum("bihd,bhdv->bihv", qc * from_start, S0,
                       precision=_HIGHEST) \
            + jnp.einsum("bhij,bhjv->bihv", qk, U, precision=_HIGHEST)
        to_end = jnp.exp(G[:, -1:] - G)                      # [b, Q, H, D]
        S = from_start[:, -1][..., None] * S0 + jnp.einsum(
            "bihd,bhiv->bhdv", kc * to_end, U, precision=_HIGHEST)
        return S, o

    last, o = lax.scan(one, jnp.zeros((b, H, D, D), q.dtype),
                       tuple(chunks(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, T + pad, H, D)
    return o[:, :T], last


@register_impl("KimiDeltaAttentionLayer")
class KimiDeltaAttentionLayerModule(BaseLayerModule):
    decode_rewindable = False

    def dims(self):
        """(H, D, K, H * D)."""
        c = self.conf
        H, D = int(c.n_heads), int(c.head_dim)
        return H, D, int(c.d_conv), H * D

    def init(self, rng, input_type, dtype=jnp.float32):
        """A uniform in [1, 16], dt_bias 0, the norm 1, the convs uniform
        +- 1/sqrt(K) (Mamba2Layer's conventions)."""
        c = self.conf
        H, D, K, HD = self.dims()
        n_in, n_out = int(c.n_in), int(c.n_out)
        r = getattr(c, "gate_rank", None)
        k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
        mk = lambda k, i, o: init_weights(k, (i, o), c.weight_init, fan_in=i,
                                          fan_out=o, distribution=c.dist,
                                          dtype=dtype)
        params = {
            "W_in": mk(k1, n_in, 3 * HD + 2 * (int(r) if r else HD)),
            "Wb": mk(k2, n_in, H),
            "conv_W": (jax.random.uniform(k3, (K, 3 * HD), jnp.float32, -1.0,
                                          1.0) / np.sqrt(K)).astype(dtype),
            "dt_bias": jnp.zeros((HD,), dtype),
            "A_log": jnp.log(jax.random.uniform(k4, (H,), jnp.float32,
                                                1.0, 16.0)).astype(dtype),
            "norm": jnp.ones((D,), dtype),
            "Wo": mk(k5, HD, n_out),
        }
        if r:
            for i, name in enumerate(("W_fb", "W_gb")):
                params[name] = mk(jax.random.fold_in(rng, 6 + i), int(r), HD)
        return params, {}, InputType.recurrent(n_out)

    # -- the pieces the legs share ---------------------------------------------
    def _project(self, params, u):
        """u [.., f] -> the convs' raw input [.., 3 H D], f and gate
        [.., H D] (through their second factors where they are low rank),
        beta [.., H] (float32)."""
        HD = self.dims()[3]
        r = getattr(self.conf, "gate_rank", None)
        w = int(r) if r else HD
        qkv, f, gate = jnp.split(u @ params["W_in"], [3 * HD, 3 * HD + w],
                                 axis=-1)
        if r:
            f, gate = f @ params["W_fb"], gate @ params["W_gb"]
        acc = _acc_dtype(u.dtype)
        beta = jax.nn.sigmoid((u @ params["Wb"]).astype(acc))
        return qkv, f, gate, float(getattr(self.conf, "beta_scale", 1.0)) \
            * beta

    def _rule_inputs(self, params, conv, f):
        """The convs' output and the raw f -> q, k (normalised), v, g
        [.., H, D], all in the conv's dtype (float32; float64 under x64)."""
        H, D, _, _ = self.dims()
        acc = conv.dtype
        heads = lambda a: a.reshape(a.shape[:-1] + (H, D))
        q, k, v = (heads(a) for a in jnp.split(conv, 3, axis=-1))
        l2 = lambda a: a * lax.rsqrt(jnp.sum(jnp.square(a), axis=-1,
                                             keepdims=True) + _L2_EPS)
        A = jnp.exp(params["A_log"].astype(acc))[:, None]
        f = heads(f.astype(acc) + params["dt_bias"].astype(acc))
        form = getattr(self.conf, "gate_form", "bounded")
        if form == "softplus":
            g = -A * jax.nn.softplus(f)
        elif form == "bounded":
            g = float(self.conf.gate_lower_bound) * jax.nn.sigmoid(A * f)
        else:
            raise ValueError(f"gate_form {form!r}: 'bounded' or 'softplus'")
        return l2(q) * D ** -0.5, l2(k), v, g

    def _finish(self, params, o, gate, out_dtype):
        """Per-head norm, sigmoid gate, output projection."""
        HD = self.dims()[3]
        y = rms_norm(o, params["norm"], self.conf.eps) * jax.nn.sigmoid(
            gate.astype(o.dtype)).reshape(o.shape)
        y = y.reshape(y.shape[:-2] + (HD,))
        return self.activation_fn()(y.astype(out_dtype) @ params["Wo"])

    # -- forward ---------------------------------------------------------------
    def forward(self, params, state, u, *, train=False, rng=None, mask=None,
                return_state=False):
        """return_state: also (the state after the last unmasked position
        [b, H, D, D], the convs' raw inputs [b, t, 3 H D])."""
        c = self.conf
        K = int(c.d_conv)
        u = apply_dropout(u, c.dropout, train, rng)
        acc = _acc_dtype(u.dtype)
        qkv, f, gate, beta = self._project(params, u)
        T = u.shape[1]
        with jax.named_scope("kda_conv"):
            w = params["conv_W"].astype(acc)
            xp = jnp.pad(qkv.astype(acc), ((0, 0), (K - 1, 0), (0, 0)))
            conv = jax.nn.silu(sum(xp[:, k:k + T] * w[k] for k in range(K)))
        with jax.named_scope("kda_chunk"):
            q, k, v, g = self._rule_inputs(params, conv, f)
            if mask is not None:
                m = mask.astype(acc)[:, :, None]
                g, beta = g * m[..., None], beta * m
            o, last = kda_chunked(q, k, v, g, beta, c.chunk_size)
        out = self._finish(params, o, gate, u.dtype)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        if return_state:
            return out, state, mask, (last, qkv)
        return out, state, mask

    # -- decode ----------------------------------------------------------------
    def decode_unsupported(self):
        return None

    def decode_entry(self, geom):
        H, D, K, HD = self.dims()
        return note_cache_entry(geom, "state", {
            "state": CacheLeaf((geom.slots, H, D, D), _acc_dtype(geom.dtype),
                               1),
            "conv": CacheLeaf((geom.slots, K - 1, 3 * HD), geom.dtype, 2)})

    def decode_prefill(self, params, state, u, entry, ctx):
        """Both rows of the slot are overwritten whole: a reused slot
        carries nothing over. The conv tail is the raw q | k | v at
        positions length - K + 1 .. length - 1, zeros before position 0."""
        K = int(self.conf.d_conv)
        y, _, _, (last, qkv) = self.forward(params, state, u, mask=ctx.mask,
                                            return_state=True)
        z = jnp.zeros((), ctx.slot.dtype)
        xp = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
        zl = jnp.zeros((), ctx.length.dtype)
        tail = lax.dynamic_slice(xp, (zl, ctx.length, zl),
                                 (1, K - 1, xp.shape[2]))
        return y, {
            "state": lax.dynamic_update_slice(
                entry["state"], last.astype(entry["state"].dtype),
                (ctx.slot, z, z, z)),
            "conv": lax.dynamic_update_slice(
                entry["conv"], tail.astype(entry["conv"].dtype),
                (ctx.slot, z, z))}

    def decode_step(self, params, state, u, entry, ctx):
        from ...kernels import kda_step
        acc = entry["state"].dtype
        qkv, f, gate, beta = self._project(params, u[:, 0])     # [S, ..]
        with jax.named_scope("kda_conv"):
            window = jnp.concatenate(
                [entry["conv"], qkv[:, None].astype(entry["conv"].dtype)],
                axis=1)                                     # [S, K, 3 H D]
            conv = jax.nn.silu(jnp.sum(
                window.astype(acc) * params["conv_W"].astype(acc), axis=1))
        with jax.named_scope("kda_step"):
            q, k, v, g = self._rule_inputs(params, conv, f)
            new, o = kda_step(
                entry["state"], jnp.exp(g), k, q, beta, v,
                use_pallas=getattr(self.conf, "use_pallas", False))
        out = self._finish(params, o, gate, u.dtype)
        return out[:, None], {"state": new, "conv": window[:, 1:]}
