"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), causal, with
rotary positions and, as options, compressed queries, YaRN and a head-wise
output gate (conf: nn/conf/layers.py LatentAttentionLayer — NEW, no
reference counterpart). With x the layer's input at position t, H heads,
latent R, rotary Dr, nope Dn, value Dv:

    [c | k_pe] = x Wkv_a ;  c <- RMSNorm_R(c) ;  k_pe <- RoPE(k_pe)    shared
    [q_nope | q_pe]_h = x Wq ;  q_pe <- RoPE(q_pe)
        with `q_lora_rank`:  = RMSNorm(x Wq_a) Wq_b     (scope `mla_queries`)
    [k_nope | v]_h = c Wkv_b
    p_h = causal softmax((q_nope_h . k_nope_h + q_pe_h . k_pe) (Dn + Dr)^-1/2)
        with `rope_yarn`: the scores times m(mscale_all_dim)^2
    out = [(sum p_h v_h) * sigmoid(x Wgate)_h] Wo       (`output_gate`)

RoPE turns adjacent pairs (2i, 2i + 1) by position * theta^(-2i / Dr) — with
`rope_yarn` by YaRN's blended frequencies (`recurrent.rope_frequencies`), cos
and sin times m(mscale) / m(mscale_all_dim) —, in float32; the positions are
0.. for a sequence and a prefill, `ctx.pos` for a decode step, `ctx.start`..
for a verify window.

Two formulations in one layer:

- the PLAIN form above (`forward`, the decode prefill, verify): keys and
  values are made from the latent for the whole sequence, 192-wide scores
  against 128-wide values. While the `[H, tq, tk]` float32 scores are small
  (`PLAIN_SCORE_BYTES`) they are formed whole (`attend_plain`); beyond that
  the layer attends BLOCKWISE: a sequence from position 0 through
  kernels.mla_prefill (scope `mla_prefill`), a verify window a query block
  at a time in `jax.numpy`.
- the ABSORBED form (`decode_step`): q'_h = q_nope_h W_UK,h^T (R wide), the
  scores are [q'_h | q_pe_h] against the cached row, the mix of the rows'
  latents goes through W_UV,h afterwards — so a token's row is read ONCE for
  all heads (kernels.mla_decode) and never expanded to keys and values.

Decode state: `latent` [slots, capacity, width], the row [c | k_pe] a token
in the cache dtype, `width` = R + Dr rounded up to whole lane tiles (576 ->
640: the TPU's tiled layout pads the row to that anyway, and the decode
kernel's copies must start and end on a tile); the padding is zero and the
query's is too. One leaf for all heads, so nothing splits over a serving
mesh's model axis (`model_axis=None`). Rolling back is a length reset:
stale rows are masked by the length (`decode_rewindable`). Under a paged
engine the rows stay a slab (a block table for latent rows is not written).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .base import (BaseLayerModule, CacheLeaf, apply_dropout,
                   note_cache_entry, register_impl)
from .convolution import rms_norm
from .recurrent import rope_frequencies
from ..weights import init_weights
from ..conf.inputs import InputType

# the plain form's [b, H, tq, tk] float32 scores are formed whole up to this
# size — 32 heads at 1,024 x 1,024, the largest the `ling3_flash` cell forms,
# whose programs stay as they were measured — and blockwise beyond
PLAIN_SCORE_BYTES = 128 << 20


def yarn_factors(yarn):
    """(the factor on cos and sin, the factor on the scores) of a conf's
    `rope_yarn`, as DeepSeek-V3 computes them: m(mscale) / m(mscale_all_dim)
    and m(mscale_all_dim)^2 — 1 without `mscale_all_dim` —, m(s) = 0.1 s
    ln(factor) + 1."""
    factor = float(yarn["factor"])
    m = lambda s: 0.1 * float(s) * math.log(factor) + 1.0 if factor > 1 \
        else 1.0
    all_dim = yarn.get("mscale_all_dim", 0)
    return m(yarn.get("mscale", 1)) / m(all_dim), \
        m(all_dim) ** 2 if all_dim else 1.0


def rope(x, pos, theta, freq=None, factor=1.0):
    """x [.., t, (heads,) Dr] turned at positions pos [.., t]: adjacent
    pairs (2i, 2i + 1) by pos * theta^(-2i / Dr) — or by pos * freq_i, cos
    and sin times `factor`, where a table is given (YaRN). float32 inside."""
    half = x.shape[-1] // 2
    if freq is None:
        freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(freq)
    ang = ang.reshape(pos.shape + (1,) * (x.ndim - pos.ndim - 1) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


@register_impl("LatentAttentionLayer")
class LatentAttentionLayerModule(BaseLayerModule):

    def dims(self):
        """(H, R, Dn, Dr, Dv)."""
        c = self.conf
        return (int(c.n_heads), int(c.kv_lora_rank), int(c.qk_nope_head_dim),
                int(c.qk_rope_head_dim), int(c.v_head_dim))

    @property
    def row_width(self):
        from ...kernels.flash_attention import LANES
        _, R, _, Dr, _ = self.dims()
        return -(-(R + Dr) // LANES) * LANES

    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        H, R, Dn, Dr, Dv = self.dims()
        n_in, n_out = int(c.n_in), int(c.n_out)
        k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
        mk = lambda k, i, o: init_weights(k, (i, o), c.weight_init, fan_in=i,
                                          fan_out=o, distribution=c.dist,
                                          dtype=dtype)
        params = {"Wkv_a": mk(k2, n_in, R + Dr),
                  "kv_norm": jnp.ones((R,), dtype),
                  "Wkv_b": mk(k3, R, H * (Dn + Dv)),
                  "Wo": mk(k5, H * Dv, n_out)}
        Rq = getattr(c, "q_lora_rank", None)
        if Rq:
            params.update(Wq_a=mk(k1, n_in, int(Rq)),
                          q_norm=jnp.ones((int(Rq),), dtype),
                          Wq_b=mk(jax.random.fold_in(k1, 1), int(Rq),
                                  H * (Dn + Dr)))
        else:
            params["Wq"] = mk(k1, n_in, H * (Dn + Dr))
        if getattr(c, "output_gate", True):
            params["Wgate"] = mk(k4, n_in, H)
        return params, {}, InputType.recurrent(n_out)

    # -- the pieces the legs share ---------------------------------------------
    def latent(self, params, x, pos):
        """x [.., f] at positions pos [..] -> the normed latent [.., R] and
        the turned rotary key [.., Dr], in x's dtype."""
        c = self.conf
        R = self.dims()[1]
        lat, k_pe = jnp.split(x @ params["Wkv_a"], [R], axis=-1)
        return rms_norm(lat, params["kv_norm"], c.eps), self.turn(k_pe, pos)

    def turn(self, x, pos):
        """The rotary turn of this layer: plain at `rope_theta`, or YaRN's
        table and its factor on cos and sin."""
        c = self.conf
        yarn = getattr(c, "rope_yarn", None)
        if not yarn:
            return rope(x, pos, c.rope_theta)
        freq, _ = rope_frequencies(x.shape[-1], c.rope_theta, yarn)
        return rope(x, pos, c.rope_theta, freq, yarn_factors(yarn)[0])

    def queries(self, params, x, pos):
        """-> q_nope [.., H, Dn], q_pe [.., H, Dr] turned."""
        H, _, Dn, Dr, _ = self.dims()
        if "Wq_a" in params:
            with jax.named_scope("mla_queries"):
                q = rms_norm(x @ params["Wq_a"], params["q_norm"],
                             self.conf.eps) @ params["Wq_b"]
        else:
            q = x @ params["Wq"]
        q = q.reshape(x.shape[:-1] + (H, Dn + Dr))
        return q[..., :Dn], self.turn(q[..., Dn:], pos)

    def up(self, params):
        """Wkv_b as (W_UK [R, H, Dn], W_UV [R, H, Dv])."""
        H, R, Dn, _, Dv = self.dims()
        w = params["Wkv_b"].reshape(R, H, Dn + Dv)
        return w[..., :Dn], w[..., Dn:]

    def scale(self):
        _, _, Dn, Dr, _ = self.dims()
        yarn = getattr(self.conf, "rope_yarn", None)
        return float(Dn + Dr) ** -0.5 * (yarn_factors(yarn)[1] if yarn
                                         else 1.0)

    def attend_plain(self, params, q_nope, q_pe, lat, k_pe, q_pos, valid):
        """Queries [b, tq, H, ..] at positions q_pos [b, tq] against the
        keys and values made from lat [b, tk, R] / k_pe [b, tk, Dr] at
        positions 0.. tk - 1; valid [b, tk] or None masks keys. float32
        scores; -> [b, tq, H, Dv]."""
        W_uk, W_uv = self.up(params)
        k_nope = jnp.einsum("bkr,rhn->bkhn", lat, W_uk)
        v = jnp.einsum("bkr,rhv->bkhv", lat, W_uv)
        s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                          preferred_element_type=jnp.float32)) * self.scale()
        keep = jnp.arange(lat.shape[1])[None, None, :] <= q_pos[:, :, None]
        if valid is not None:
            keep = keep & (valid[:, None, :] > 0)
        p = jax.nn.softmax(jnp.where(keep[:, None], s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhv->bqhv", p.astype(v.dtype), v)

    def attend(self, params, q_nope, q_pe, lat, k_pe, q_pos, valid,
               from_zero):
        """`attend_plain` while its scores are small, else the same
        attention blockwise: a whole sequence from position 0 (`from_zero`:
        q_pos is 0 .. tk - 1) through kernels.mla_prefill, any other queries
        a block at a time in `jax.numpy`."""
        from ...kernels.mla_prefill import mla_attend_blockwise, mla_prefill
        H = self.dims()[0]
        (b, tq), tk = q_pos.shape, lat.shape[1]
        if 4 * b * H * tq * tk <= PLAIN_SCORE_BYTES:
            with jax.named_scope("mla_attention"):
                return self.attend_plain(params, q_nope, q_pe, lat, k_pe,
                                         q_pos, valid)
        W_uk, W_uv = self.up(params)
        with jax.named_scope("mla_prefill"):
            k_nope = jnp.einsum("bkr,rhn->bkhn", lat, W_uk)
            v = jnp.einsum("bkr,rhv->bkhv", lat, W_uv)
            if from_zero:
                return mla_prefill(
                    q_nope, q_pe, k_nope, k_pe, v, scale=self.scale(),
                    key_mask=valid,
                    use_pallas=getattr(self.conf, "use_pallas", False))
            return mla_attend_blockwise(q_nope, q_pe, k_nope, k_pe, v, q_pos,
                                        valid, self.scale())

    def finish(self, params, o, x, mask):
        """Head-wise sigmoid gate (where the layer has one), output
        projection, mask zeroing."""
        if "Wgate" in params:
            gate = jax.nn.sigmoid((x @ params["Wgate"]).astype(jnp.float32))
            o = o * gate[..., None].astype(o.dtype)
        y = o.reshape(o.shape[:-2] + (-1,))
        y = self.activation_fn()(y.astype(x.dtype) @ params["Wo"])
        return y if mask is None else y * mask[:, :, None].astype(y.dtype)

    def _sequence(self, params, x, mask):
        """The plain form over [b, t, f] from position 0: (y, the latent
        [b, t, R], the turned rotary key [b, t, Dr])."""
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        lat, k_pe = self.latent(params, x, pos)
        q_nope, q_pe = self.queries(params, x, pos)
        o = self.attend(params, q_nope, q_pe, lat, k_pe, pos, mask, True)
        return self.finish(params, o, x, mask), lat, k_pe

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = apply_dropout(x, self.conf.dropout, train, rng)
        return self._sequence(params, x, mask)[0], state, mask

    # -- decode ----------------------------------------------------------------
    def decode_unsupported(self):
        return None

    def decode_entry(self, geom):
        return note_cache_entry(geom, "kv", {"latent": CacheLeaf(
            (geom.slots, geom.capacity, self.row_width), geom.dtype, None)})

    def _row(self, dtype, *parts):
        """[parts.. | zeros] up to `row_width`: what the cache holds of a
        token, and a query laid against it."""
        row = jnp.concatenate(parts, axis=-1)
        pad = self.row_width - row.shape[-1]
        return jnp.pad(row, ((0, 0),) * (row.ndim - 1) + ((0, pad),)
                       ).astype(dtype)

    def decode_prefill(self, params, state, x, entry, ctx):
        y, lat, k_pe = self._sequence(params, x, ctx.mask)
        z = jnp.zeros((), ctx.slot.dtype)
        with jax.named_scope("latent_append"):
            return y, {"latent": lax.dynamic_update_slice(
                entry["latent"], self._row(entry["latent"].dtype, lat, k_pe),
                (ctx.slot, z, z))}

    def decode_step(self, params, state, x, entry, ctx):
        from ...kernels import latent_append, mla_decode
        use_pallas = getattr(self.conf, "use_pallas", False)
        R = self.dims()[1]
        xt = x[:, 0]                                        # [S, f]
        lat, k_pe = self.latent(params, xt, ctx.pos)
        with jax.named_scope("latent_append"):
            cache = latent_append(
                entry["latent"], self._row(entry["latent"].dtype, lat, k_pe),
                ctx.pos, use_pallas=use_pallas)
        q_nope, q_pe = self.queries(params, xt, ctx.pos)    # [S, H, ..]
        W_uk, W_uv = self.up(params)
        with jax.named_scope("mla_attention"):
            q = self._row(cache.dtype, jnp.einsum(
                "shn,rhn->shr", q_nope, W_uk) * self.scale(),
                q_pe * self.scale())
            mix = mla_decode(q, cache, ctx.kv_valid, rank=R,
                             use_pallas=use_pallas)
            o = jnp.einsum("shr,rhv->shv", mix.astype(xt.dtype), W_uv)
        return self.finish(params, o[:, None], x, None), {"latent": cache}

    def decode_verify(self, params, state, x, entry, ctx):
        """A [1, W, f] window at position ctx.start of slot ctx.slot: its
        rows are written, then the plain form against the slot's rows."""
        R, Dr = self.dims()[1], self.dims()[3]
        slot = ctx.slot
        start = jnp.asarray(ctx.start, slot.dtype)
        pos = start + jnp.arange(x.shape[1], dtype=slot.dtype)[None]
        lat, k_pe = self.latent(params, x, pos)
        z = jnp.zeros((), slot.dtype)
        cache = lax.dynamic_update_slice(
            entry["latent"], self._row(entry["latent"].dtype, lat, k_pe),
            (slot, start, z))
        rows = lax.dynamic_index_in_dim(cache, slot, 0, keepdims=True)
        q_nope, q_pe = self.queries(params, x, pos)
        o = self.attend(params, q_nope, q_pe, rows[..., :R],
                        rows[..., R:R + Dr], pos, None, False)
        return self.finish(params, o, x, None), {"latent": cache}
