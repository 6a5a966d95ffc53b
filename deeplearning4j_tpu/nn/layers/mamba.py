"""Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060): a selective state-space
layer with one scalar decay a head (conf: nn/conf/layers.py Mamba2Layer — NEW,
no reference counterpart).

    (z, xBC, dt) = split(u W_in)            [d_inner | d_inner + 2N | H]
    xBC = silu(causal depthwise conv_K(xBC) + b)
    (x, B, C) = split(xBC)                  [H x P | N | N]   (one group)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)            (a head)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t             S in [N, H x P]
    y_t = S_t C_t + D x_t
    out = RMSNorm(y * silu(z)) W_out                          (gate, then norm)

Three formulations of the same recurrence:

- `forward` (training, output(), and the decode prefill): the chunked SSD
  form (`ssd_chunked`) — inside a chunk the quadratic form against the decay
  matrix exp(segment sums), between chunks the state handed on by a scan —
  in `jax.numpy`; autodiff gives the backward. A masked position gets dt = 0:
  decay 1, contribution 0, so the state passes through it unchanged.
- `decode_step`: one token a slot against the slot's state, in place
  (kernels.ssm_step); the conv is a 4-tap product with the slot's tail.
- the sequential scan over positions is the reference's
  (benchmarks/reference/granite4_h_micro.py), which both are held against.

Decode state, per slot: `ssm` [N, H x P] in the accumulation dtype (float32
under bfloat16: the recurrence compounds over every token of a session),
laid out state-index major so the channels lie on the TPU's lanes; `conv`,
the last K - 1 inputs of the conv, [K - 1, d_inner + 2N] in the cache dtype.
Neither grows with the sequence and neither rewinds by a length reset
(`decode_rewindable = False`). The decays, softplus, the conv, the scan and
the gated norm's statistics run in float32; the two projections in the
activations' dtype.
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


def ssd_chunked(x, dt, A, B, C, chunk):
    """The recurrence over a whole sequence, chunk by chunk.

    x [b, t, H, P], dt [b, t, H] (0 at masked positions), A [H] (negative),
    B, C [b, t, N], one dtype (float32 in practice) -> y [b, t, H, P] without
    the skip term, and the state after the last position, [b, N, H * P].
    The contractions run at `highest`: they are small beside the layer's
    projections, and the decays they carry are what the state's accuracy
    rests on."""
    b, T, H, P = x.shape
    N = B.shape[-1]
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:                     # dt = 0: the padding leaves the state alone
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (T + pad) // Q
    xd = (x * dt[..., None]).reshape(b, nc, Q, H, P)
    Bc, Cc = B.reshape(b, nc, Q, N), C.reshape(b, nc, Q, N)
    cum = jnp.cumsum((dt * A).reshape(b, nc, Q, H), axis=2)
    # inside a chunk: y_i = sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) xd_j
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]       # [b,c,i,j,H]
    tril = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(tril, seg, -jnp.inf))
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc, precision=_HIGHEST)
    y = jnp.einsum("bcijh,bcjhp->bcihp", cb[..., None] * decay, xd,
                   precision=_HIGHEST)
    # what a chunk adds to the state, and what it leaves of the state before
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)                 # [b,c,Q,H]
    added = jnp.einsum("bcjn,bcjhp->bcnhp", Bc, xd * to_end[..., None],
                       precision=_HIGHEST)
    kept = jnp.exp(cum[:, :, -1, :])                          # [b,c,H]

    def hand_on(S, chunk_):
        add, keep = chunk_
        return keep[:, None, :, None] * S + add, S

    last, before = lax.scan(
        hand_on, jnp.zeros((b, N, H, P), x.dtype),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(kept, 1, 0)))
    y = y + jnp.einsum("bcin,bcnhp->bcihp", Cc, jnp.moveaxis(before, 0, 1),
                       precision=_HIGHEST) * jnp.exp(cum)[..., None]
    return y.reshape(b, T + pad, H, P)[:, :T], last.reshape(b, N, H * P)


@register_impl("Mamba2Layer")
class Mamba2LayerModule(BaseLayerModule):
    decode_rewindable = False

    def dims(self):
        """(H, P, N, K, d_inner, conv channels)."""
        c = self.conf
        H, P, N, K = int(c.n_heads), int(c.head_dim), int(c.d_state), \
            int(c.d_conv)
        return H, P, N, K, H * P, H * P + 2 * N

    def init(self, rng, input_type, dtype=jnp.float32):
        """A uniform in [1, 16], dt_bias the inverse softplus of a dt drawn
        log-uniform in [0.001, 0.1], D and the norm 1, the conv uniform
        +- 1/sqrt(K) (the reference implementation's defaults)."""
        c = self.conf
        H, P, N, K, di, cd = self.dims()
        n_in, n_out = int(c.n_in), int(c.n_out)
        k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
        mk = lambda k, i, o: init_weights(k, (i, o), c.weight_init, fan_in=i,
                                          fan_out=o, distribution=c.dist,
                                          dtype=dtype)
        dt = jnp.exp(jax.random.uniform(k4, (H,), jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        params = {
            "W_in": mk(k1, n_in, 2 * di + 2 * N + H),
            "conv_W": (jax.random.uniform(k2, (K, cd), jnp.float32, -1.0, 1.0)
                       / np.sqrt(K)).astype(dtype),
            "conv_b": jnp.zeros((cd,), dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(k5, (H,), jnp.float32,
                                                1.0, 16.0)).astype(dtype),
            "D": jnp.ones((H,), dtype),
            "norm": jnp.ones((di,), dtype),
            "W_out": mk(k3, di, n_out),
        }
        return params, {}, InputType.recurrent(n_out)

    # -- the pieces the three legs share ---------------------------------------
    def _project(self, params, u):
        """u [.., f] -> z [.., d_inner], xBC [.., conv channels], dt [.., H]."""
        _, _, _, _, di, cd = self.dims()
        return jnp.split(u @ params["W_in"], [di, di + cd], axis=-1)

    def _ssm_inputs(self, params, conv, dt):
        """The conv's output and the raw dt -> x [.., H, P], B, C [.., N],
        dt [.., H] after softplus, A [H], all float32 (float64 under x64)."""
        H, P, N, _, di, _ = self.dims()
        acc = conv.dtype
        x, B, C = jnp.split(conv, [di, di + N], axis=-1)
        dt = jax.nn.softplus(dt.astype(acc) + params["dt_bias"].astype(acc))
        A = -jnp.exp(params["A_log"].astype(acc))
        return x.reshape(x.shape[:-1] + (H, P)), B, C, dt, A

    def _finish(self, params, y, x, z, out_dtype):
        """Skip term, gate, norm over all of d_inner, output projection."""
        di = self.dims()[4]
        y = y + params["D"].astype(y.dtype)[:, None] * x
        y = y.reshape(y.shape[:-2] + (di,))
        y = rms_norm(y * jax.nn.silu(z.astype(y.dtype)), params["norm"],
                     self.conf.eps)
        return self.activation_fn()(y.astype(out_dtype) @ params["W_out"])

    # -- forward ---------------------------------------------------------------
    def forward(self, params, state, u, *, train=False, rng=None, mask=None,
                return_state=False):
        """return_state: also (the state after the last unmasked position
        [b, N, H*P], the conv's raw inputs [b, t, channels])."""
        c = self.conf
        K = int(c.d_conv)
        u = apply_dropout(u, c.dropout, train, rng)
        acc = _acc_dtype(u.dtype)
        z, xbc, dt = self._project(params, u)
        T = u.shape[1]
        with jax.named_scope("ssm_conv"):
            w = params["conv_W"].astype(acc)
            xp = jnp.pad(xbc.astype(acc), ((0, 0), (K - 1, 0), (0, 0)))
            conv = jax.nn.silu(sum(xp[:, k:k + T] * w[k] for k in range(K))
                               + params["conv_b"].astype(acc))
        with jax.named_scope("ssm_scan"):
            x, B, C, dt, A = self._ssm_inputs(params, conv, dt)
            if mask is not None:
                dt = dt * mask.astype(acc)[:, :, None]
            y, last = ssd_chunked(x, dt, A, B, C, c.chunk_size)
        out = self._finish(params, y, x, z, u.dtype)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        if return_state:
            return out, state, mask, (last, xbc)
        return out, state, mask

    # -- decode ----------------------------------------------------------------
    def decode_unsupported(self):
        return None

    def decode_entry(self, geom):
        _, _, N, K, di, cd = self.dims()
        return note_cache_entry(geom, "state", {
            "ssm": CacheLeaf((geom.slots, N, di), _acc_dtype(geom.dtype), 2),
            "conv": CacheLeaf((geom.slots, K - 1, cd), geom.dtype, 2)})

    def decode_prefill(self, params, state, u, entry, ctx):
        """Both rows of the slot are overwritten whole: a reused slot
        carries nothing over. The conv tail is the raw xBC at positions
        length - K + 1 .. length - 1, zeros before position 0."""
        K = int(self.conf.d_conv)
        y, _, _, (last, xbc) = self.forward(params, state, u, mask=ctx.mask,
                                            return_state=True)
        z = jnp.zeros((), ctx.slot.dtype)
        xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        zl = jnp.zeros((), ctx.length.dtype)
        tail = lax.dynamic_slice(xp, (zl, ctx.length, zl),
                                 (1, K - 1, xp.shape[2]))
        return y, {
            "ssm": lax.dynamic_update_slice(
                entry["ssm"], last.astype(entry["ssm"].dtype),
                (ctx.slot, z, z)),
            "conv": lax.dynamic_update_slice(
                entry["conv"], tail.astype(entry["conv"].dtype),
                (ctx.slot, z, z))}

    def decode_step(self, params, state, u, entry, ctx):
        from ...kernels import ssm_step
        P = int(self.conf.head_dim)
        acc = entry["ssm"].dtype
        z, xbc, dt = self._project(params, u[:, 0])         # [S, ..]
        with jax.named_scope("ssm_conv"):
            window = jnp.concatenate(
                [entry["conv"], xbc[:, None].astype(entry["conv"].dtype)],
                axis=1)                                     # [S, K, channels]
            conv = jax.nn.silu(
                jnp.sum(window.astype(acc) * params["conv_W"].astype(acc),
                        axis=1) + params["conv_b"].astype(acc))
        with jax.named_scope("ssm_step"):
            x, B, C, dt, A = self._ssm_inputs(params, conv, dt)
            S = x.shape[0]
            new, y = ssm_step(
                entry["ssm"], jnp.repeat(jnp.exp(dt * A), P, axis=1),
                (x * dt[..., None]).reshape(S, -1), B, C,
                use_pallas=getattr(self.conf, "use_pallas", False))
        out = self._finish(params, y.reshape(x.shape), x, z, u.dtype)
        return out[:, None], {"ssm": new, "conv": window[:, 1:]}
