"""Recurrent layers: GravesLSTM (peepholes), LSTM, GravesBidirectionalLSTM.

Reference counterparts: nn/layers/recurrent/GravesLSTM.java,
GravesBidirectionalLSTM.java, and the shared math in LSTMHelpers.java (501 LoC;
forward :58, per-timestep Java gemm loop :172-174).

TPU-first: the per-timestep loop is a lax.scan whose body is ONE fused
[x_t, h_prev] @ W_combined gemm hitting the MXU, with gate nonlinearities fused
by XLA — versus the reference's 4 separate gemms + elementwise ops per step.
Sequence layout [batch, time, features]; scan runs over time with batch-major
carries. Masking: masked steps carry state through and emit zeros (matching the
reference's mask semantics for variable-length series).

Streaming inference (rnnTimeStep, reference MultiLayerNetwork.java ~:2100) is
supported via explicit carry in/out: forward(..., initial_state=..., return_state=True).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .base import (BaseLayerModule, CacheLeaf, apply_dropout,
                   note_cache_entry, register_impl)
from ..activations import get_activation
from ..weights import init_weights
from ..conf.inputs import InputType

# Gate order in the fused 4*n_out dimension: [input, forget, output, cell-candidate]
I, F, O, G = 0, 1, 2, 3


def _init_lstm_params(rng, n_in, n_out, conf, dtype, peephole):
    k1, k2, k3 = jax.random.split(rng, 3)
    params = {
        # W: input->gates [n_in, 4*n_out]; RW: recurrent [n_out, 4*n_out]
        "W": init_weights(k1, (n_in, 4 * n_out), conf.weight_init,
                          fan_in=n_in, fan_out=n_out, distribution=conf.dist, dtype=dtype),
        "RW": init_weights(k2, (n_out, 4 * n_out), conf.weight_init,
                           fan_in=n_out, fan_out=n_out, distribution=conf.dist, dtype=dtype),
        "b": jnp.zeros((4 * n_out,), dtype).at[F * n_out:(F + 1) * n_out].set(
            conf.forget_gate_bias_init),
    }
    if peephole:
        # peephole weights for input/forget (on c_prev) and output (on c_new)
        params["P"] = init_weights(k3, (3 * n_out,), "uniform", fan_in=n_out,
                                   fan_out=n_out, dtype=dtype)
    return params


def _acc_dtype(dt):
    """Any sub-32-bit float compute (bf16, and f16 with its 65504 max) gets
    f32 accumulation: the scan's gate arithmetic and cell state, and the
    carry rows a decode cache keeps."""
    return (jnp.float32 if jnp.issubdtype(dt, jnp.floating)
            and jnp.finfo(dt).bits < 32 else dt)


def _lstm_scan(params, x, h0, c0, gate_act, cell_act, peephole, mask=None, reverse=False):
    """x: [b,t,n_in] -> outputs [b,t,n_out], final (h,c).

    Mixed precision: under bf16 compute the GEMMs run bf16 on the MXU and
    all gate arithmetic plus the CELL state accumulate in f32 — a bf16 cell
    carry drifts over the sequence (c_new = f*c + i*g compounds rounding
    every step; the reference's tuned LSTM keeps full-precision state for
    the same reason, LSTMHelpers.java). The HIDDEN carry stays in the
    compute dtype: h is fully re-derived from c each step (h = o*tanh(c),
    nothing compounds), and keeping it bf16 feeds the recurrent gemm
    without a per-step cast. Final carries return in the accumulation dtype
    so TBPTT windows see ONE stable carry dtype (no per-window retrace, no
    bf16 quantization of the cell state at window boundaries)."""
    n_out = params["RW"].shape[0]
    gate_fn = get_activation(gate_act)
    act_fn = get_activation(cell_act)
    W, RW, b = params["W"], params["RW"], params["b"]
    P = params.get("P")
    out_dt = x.dtype
    acc_dt = _acc_dtype(out_dt)
    if P is not None:
        P = P.astype(acc_dt)

    def step(carry, inputs):
        h_prev, c_prev = carry            # out_dt, acc_dt
        if mask is not None:
            xz_t, m_t = inputs
        else:
            xz_t, m_t = inputs, None
        # the input projection was hoisted out of the scan (one [b*t, n_in]
        # gemm instead of t small ones — the MXU-friendly schedule); only the
        # recurrent gemm stays sequential (out_dt on the MXU, f32 out)
        z = xz_t.astype(acc_dt) + (h_prev @ RW).astype(acc_dt)
        zi, zf, zo, zg = (z[:, I * n_out:(I + 1) * n_out], z[:, F * n_out:(F + 1) * n_out],
                          z[:, O * n_out:(O + 1) * n_out], z[:, G * n_out:(G + 1) * n_out])
        if P is not None:
            zi = zi + P[:n_out] * c_prev
            zf = zf + P[n_out:2 * n_out] * c_prev
        i_g = gate_fn(zi)
        f_g = gate_fn(zf)
        g = act_fn(zg)
        c_new = f_g * c_prev + i_g * g
        if P is not None:
            zo = zo + P[2 * n_out:] * c_new
        o_g = gate_fn(zo)
        h_new = (o_g * act_fn(c_new)).astype(out_dt)
        if m_t is not None:
            m = m_t[:, None]
            h_out = h_new * m.astype(out_dt)
            h_new = jnp.where(m > 0, h_new, h_prev)
            c_new = jnp.where(m > 0, c_new, c_prev)
        else:
            h_out = h_new
        return (h_new, c_new), h_out

    xz_all = x @ W + b                # [b, t, 4n] single batched gemm
    xs = jnp.swapaxes(xz_all, 0, 1)   # [t, b, 4n]
    seq = (xs, jnp.swapaxes(mask, 0, 1)) if mask is not None else xs
    (h_f, c_f), outs = lax.scan(step, (h0.astype(out_dt), c0.astype(acc_dt)),
                                seq, reverse=reverse)
    return jnp.swapaxes(outs, 0, 1), (h_f.astype(acc_dt), c_f)


class _BaseLSTMModule(BaseLayerModule):
    peephole = True

    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        params = _init_lstm_params(rng, int(c.n_in), int(c.n_out), c, dtype, self.peephole)
        return params, {}, InputType.recurrent(int(c.n_out))

    def init_carry(self, batch, dtype=jnp.float32):
        n_out = int(self.conf.n_out)
        return (jnp.zeros((batch, n_out), dtype), jnp.zeros((batch, n_out), dtype))

    def forward(self, params, state, x, *, train=False, rng=None, mask=None,
                initial_state=None, return_state=False):
        c = self.conf
        x = apply_dropout(x, c.dropout, train, rng)
        h0, c0 = initial_state if initial_state is not None else self.init_carry(
            x.shape[0], x.dtype)
        outs, final = _lstm_scan(params, x, h0, c0, c.gate_activation, c.activation,
                                 self.peephole, mask)
        if return_state:
            return outs, state, mask, final
        return outs, state, mask

    # -- decode: each slot's (h, c) carry in a [slots, n_out] row. A carry
    # cannot be rolled back by a length reset, so verify refuses the plan
    # and a speculative draft snapshots the rows instead
    decode_rewindable = False

    def decode_unsupported(self):
        return None

    def decode_entry(self, geom):
        leaf = CacheLeaf((geom.slots, int(self.conf.n_out)),
                         _acc_dtype(geom.dtype), 1)
        return note_cache_entry(geom, "state", {"h": leaf, "c": leaf})

    def decode_prefill(self, params, state, x, entry, ctx):
        # masked steps carry state through (the scan's contract), so the
        # final carry equals the state after `length` real steps
        y, _, _, (hf, cf) = self.forward(params, state, x, mask=ctx.mask,
                                         return_state=True)
        at = (ctx.slot, jnp.zeros((), ctx.slot.dtype))
        return y, {
            "h": lax.dynamic_update_slice(
                entry["h"], hf.astype(entry["h"].dtype), at),
            "c": lax.dynamic_update_slice(
                entry["c"], cf.astype(entry["c"].dtype), at)}

    def decode_step(self, params, state, x, entry, ctx):
        y, _, _, (hf, cf) = self.forward(
            params, state, x, initial_state=(entry["h"], entry["c"]),
            return_state=True)
        return y, {"h": hf.astype(entry["h"].dtype),
                   "c": cf.astype(entry["c"].dtype)}


@register_impl("GravesLSTM")
class GravesLSTMModule(_BaseLSTMModule):
    peephole = True


@register_impl("LSTM")
class LSTMModule(_BaseLSTMModule):
    peephole = False


@register_impl("GravesBidirectionalLSTM")
class GravesBidirectionalLSTMModule(BaseLayerModule):
    """Two independent peephole LSTMs over forward/reversed time; outputs are
    summed (reference: nn/layers/recurrent/GravesBidirectionalLSTM.java sums
    forward and backward activations into n_out)."""

    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        kf, kb = jax.random.split(rng)
        params = {
            "fwd": _init_lstm_params(kf, int(c.n_in), int(c.n_out), c, dtype, True),
            "bwd": _init_lstm_params(kb, int(c.n_in), int(c.n_out), c, dtype, True),
        }
        return params, {}, InputType.recurrent(int(c.n_out))

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        c = self.conf
        x = apply_dropout(x, c.dropout, train, rng)
        b = x.shape[0]
        n_out = int(c.n_out)
        zeros = (jnp.zeros((b, n_out), x.dtype), jnp.zeros((b, n_out), x.dtype))
        out_f, _ = _lstm_scan(params["fwd"], x, *zeros, c.gate_activation,
                              c.activation, True, mask, reverse=False)
        out_b, _ = _lstm_scan(params["bwd"], x, *zeros, c.gate_activation,
                              c.activation, True, mask, reverse=True)
        return out_f + out_b, state, mask

    def decode_unsupported(self):
        return ("bidirectional recurrence needs future tokens and cannot "
                "stream")


def _paged_append_seq(pool, t, row):
    """Scatter a [L, H, Dh] token sequence into the [N, bs, H, Dh] block
    pool along `row` (the slot's table row): the L positions reshape into
    L/bs chunks of one block each, landing at the row's physical block ids.
    Pad chunks of a prefill bucket address block 0 (scratch) — over-length
    writes land where nobody reads instead of needing in-trace bounds
    checks."""
    bs = pool.shape[1]
    L = t.shape[0]
    chunks = -(-L // bs)
    pad = chunks * bs - L
    if pad:
        t = jnp.pad(t, ((0, pad), (0, 0), (0, 0)))
    tc = t.reshape(chunks, bs, t.shape[1], t.shape[2])
    return pool.at[row[:chunks]].set(tc.astype(pool.dtype))


def rope_frequencies(head_dim, theta, yarn=None):
    """(the head_dim / 2 rotary frequencies as float32 numpy, the factor on
    cos and sin). Plain: f_j = theta^(-2j / head_dim), factor 1. YaRN
    (`yarn`: the conf's `rope_yarn`), as `transformers` computes it: e_j =
    theta^(-2j / head_dim), n_j = e_j / factor; c(r) = head_dim ln(original
    / (2 pi r)) / (2 ln theta); low = floor(c(beta_fast)), high =
    ceil(c(beta_slow)), both clipped to [0, head_dim - 1]; ramp_j = clip((j
    - low) / (high - low), 0, 1); f_j = n_j ramp_j + e_j (1 - ramp_j); the
    factor is `attention_factor` (1 where the caller has its own:
    nn/layers/mla.py)."""
    half = head_dim // 2
    e = float(theta) ** (-np.arange(half, dtype=np.float64) / half)
    if not yarn:
        return e.astype(np.float32), 1.0
    low, high = yarn_ramp(head_dim, theta, yarn)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    f = e / float(yarn["factor"]) * ramp + e * (1 - ramp)
    return f.astype(np.float32), float(yarn.get("attention_factor", 1.0))


def yarn_ramp(head_dim, theta, yarn):
    """(low, high): the channels between which YaRN's frequencies go from
    theta's own to theta's / factor."""
    def c(rotations):
        return head_dim * np.log(
            yarn["original_max_position_embeddings"]
            / (rotations * 2 * np.pi)) / (2 * np.log(theta))
    return (max(int(np.floor(c(yarn["beta_fast"]))), 0),
            min(int(np.ceil(c(yarn["beta_slow"]))), head_dim - 1))


def rope_half(x, pos, freq, factor=1.0):
    """x [b, t, heads, Dh] turned at positions pos [b or 1, t], HF's
    `rotate_half` pairing: channel j with j + Dh / 2 by pos * freq_j; cos
    and sin times `factor`. Angles, cos and sin float32; back in x's
    dtype."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[..., None, None] * jnp.asarray(freq)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor   # [b, t, 1, half]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _repeat_kv(q, k, v):
    """k, v [b, t, Hkv, Dh] repeated to q's heads (K/V head j serves query
    heads j*G .. j*G + G - 1); as they are when the counts agree."""
    G = q.shape[2] // k.shape[2]
    if G == 1:
        return k, v
    return jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)


def _verify_attend(q, k, v, start):
    """[1, W, H, Dh] window queries vs one slot's full [1, C, H, Dh] cache
    row, causal against GLOBAL positions: query i (at position start+i)
    sees keys [0, start+i]. Cache entries beyond start+W hold stale garbage
    from longer rolled-back windows — causally masked, so rollback never has
    to zero them. W is tiny (K+1 draft tokens), so the [H, W, C] score tile
    is reference-einsum territory; a Mosaic flash variant with a query
    offset is the rig follow-up."""
    W, C = q.shape[1], k.shape[1]
    k, v = _repeat_kv(q, k, v)
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
    qpos = start + jnp.arange(W, dtype=jnp.int32)
    kpos = jnp.arange(C, dtype=jnp.int32)
    mask = kpos[None, :] <= qpos[:, None]                # [W, C]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


@register_impl("SelfAttentionLayer")
class SelfAttentionLayerModule(BaseLayerModule):
    """Multi-head self-attention [b,t,f] -> [b,t,n_out] (NEW capability, no
    reference counterpart). QKV + output projections around flash-style
    blockwise attention; a key mask folds the sequence mask into the scores
    and zeroes masked outputs (same convention as the LSTM scan). For
    sequence-parallel long-context attention call
    parallel.ring_attention.ring_attention on the projections directly.

    Decode state (causal layers only): K and V of every token so far, as a
    slab `[slots, capacity, H, Dh]` or, paged, a block pool `[num_blocks,
    block_size, H, Dh]` the slots share through the engine's block table
    (decode/paged.py); the head axis splits over a serving mesh's model
    axis. Prefill writes the prompt's K/V into the slot's rows in one
    update; pad positions write garbage beyond `length` that the length
    mask hides from every later step. A step appends every slot's token
    and attends in ONE kernel a layer
    (kernels.flash_attention.flash_decode_append: the decode kernel puts
    the token into the slot's last live block as it reads it and writes the
    128 positions round it back, in place), on the cache buffer in the
    layout the device stores it in: no instruction of the step copies a
    slab or loops over the slots (tests/test_tpu_compile.py). Where that
    layout is row-major (head_dim a multiple of 128) the same entry point
    runs the kernel that reads it so: a block's rows as they lie, all K/V
    heads at once on the MXU, the token's row written by one copy. The paged
    step scatters into (table[pos // bs], pos % bs) and gathers the slot's
    blocks back (flash_decode_paged), token for token the slab.
    Rolling back is a length reset: stale rows are causally masked.

    `head_dim` sets a head's width apart from n_out / n_heads (Wq, Wgate [n_in,
    H Dh], Wk, Wv [n_in, Hkv Dh], Wo [H Dh, n_out]). `output_gate`: the
    context is multiplied by sigmoid(x Wgate), x the layer's input, before Wo
    (scope `attention_gate`; the sigmoid in float32) in every leg.

    `rope_theta` (with `rope_yarn`): q and k are turned by their positions
    (`rope_half`, scope `rope`, float32 inside) — 0.. in a sequence and a
    prefill, `ctx.pos` in a step, `ctx.start`.. in a verify window — so the
    cache holds turned keys. `window`: position i sees keys i - window < j <=
    i. A windowed layer's slab entry is a RING of min(window, capacity)
    positions a slot, position p at p % ring: a prefill writes the prompt's
    last `ring` real positions there (`ctx.length`: a bucket's pad positions
    never overwrite a live one), a step writes at pos % ring and attends to
    the min(pos + 1, ring) newest (`flash_decode_append(ring=True)`, named
    `flash_decode_window` in a trace; scope `attention_window`). What a ring
    has overwritten cannot be rewound, so a windowed layer is not
    `decode_rewindable`. Paged, it keeps the shared table's every block and
    masks (`flash_decode_paged(window=)`). Fewer than 8 K/V heads of a
    multiple of 128 — or narrower heads that pack to fewer than 8 rows of
    128 lanes a position, 8 heads of 64 — are declared in whole (8, 128)
    tiles (`kernels.flash_attention.tiled_rows`), which is what lets the
    row-major kernel take them."""

    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        n_in, n_out, H = int(c.n_in), int(c.n_out), int(c.n_heads)
        assert getattr(c, "head_dim", None) or n_out % H == 0, \
            "n_heads must evenly divide n_out"
        assert H % self.kv_heads == 0, "n_kv_heads must evenly divide n_heads"
        n_q, n_kv = self.head_dim * H, self.head_dim * self.kv_heads
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        mk = lambda k, i, o: init_weights(k, (i, o), c.weight_init, fan_in=i,
                                          fan_out=o, distribution=c.dist,
                                          dtype=dtype)
        params = {
            "Wq": mk(k1, n_in, n_q), "Wk": mk(k2, n_in, n_kv),
            "Wv": mk(k3, n_in, n_kv), "Wo": mk(k4, n_q, n_out),
            "b": jnp.full((n_out,), c.bias_init or 0.0, dtype),
        }
        if getattr(c, "output_gate", False):
            params["Wgate"] = mk(jax.random.fold_in(rng, 5), n_in, n_q)
        return params, {}, InputType.recurrent(n_out)

    @property
    def head_dim(self):
        c = self.conf
        return int(getattr(c, "head_dim", None)
                   or int(c.n_out) // int(c.n_heads))

    @property
    def kv_heads(self):
        """K/V heads: the query heads unless the conf groups them."""
        c = self.conf
        return int(getattr(c, "n_kv_heads", None) or c.n_heads)

    def project_qkv(self, params, x, pos=None):
        """[b,t,f] -> q [b,t,H,Dh] and k, v [b,t,Hkv,Dh]. Split out of
        forward so the decode legs run the SAME projections when they append
        a token's k/v to a KV-cache slot. An explicit `score_scale` rides on
        q (q * score_scale * sqrt(Dh)), so every kernel behind keeps its
        1 / sqrt(Dh). With `rope_theta`, q and k are turned at `pos` [b or
        1, t] (default: 0 .. t - 1)."""
        c = self.conf
        B, T, _ = x.shape
        H, Hkv, Dh = int(c.n_heads), self.kv_heads, self.head_dim
        q = (x @ params["Wq"]).reshape(B, T, H, Dh)
        k = (x @ params["Wk"]).reshape(B, T, Hkv, Dh)
        v = (x @ params["Wv"]).reshape(B, T, Hkv, Dh)
        scale = getattr(c, "score_scale", None)
        if scale is not None:
            q = q * jnp.asarray(float(scale) * float(np.sqrt(Dh)), q.dtype)
        theta = getattr(c, "rope_theta", None)
        if theta is not None:
            with jax.named_scope("rope"):
                if pos is None:
                    pos = jnp.arange(T, dtype=jnp.int32)[None]
                turn = rope_frequencies(Dh, theta,
                                        getattr(c, "rope_yarn", None))
                q, k = rope_half(q, pos, *turn), rope_half(k, pos, *turn)
        return q, k, v

    @property
    def window(self):
        w = getattr(self.conf, "window", None)
        return None if w is None else int(w)

    @property
    def decode_rewindable(self):
        """A ring cannot be rewound past what it has overwritten."""
        return self.window is None

    def _attention_scope(self):
        return jax.named_scope("attention" if self.window is None
                               else "attention_window")

    def attend(self, q, k, v, mask):
        """The kernel dispatch (shared by forward and the decode prefill).
        Grouped K/V heads are repeated to the query heads here: a sequence's
        K and V are small beside the cache, where the decode kernel reads
        each K/V head once for its group."""
        from ...parallel.ring_attention import attention_reference, \
            blockwise_attention
        c = self.conf
        T = q.shape[1]
        k, v = _repeat_kv(q, k, v)
        window = {} if self.window is None else {"window": self.window}
        if getattr(c, "use_pallas", False):
            from ...kernels import flash_attention
            # block_size tunes the QUERY tile only; the key tile keeps the
            # kernel's swept default (1024) — forcing both to block_size
            # starved the MXU (256x256 measured ~1.7x slower than 256x1024
            # at T=4096 on a real v5e). Key masks fold into the kernel's
            # score tiles (fwd + both bwd), so ragged/packed batches keep
            # the fast path; untileable shapes fall back inside the call
            return flash_attention(q, k, v, causal=c.causal,
                                   block_q=int(c.block_size), key_mask=mask,
                                   **window)
        if T % min(int(c.block_size), T) == 0:
            return blockwise_attention(q, k, v, block_size=int(c.block_size),
                                       causal=c.causal, key_mask=mask,
                                       **window)
        return attention_reference(q, k, v, causal=c.causal, key_mask=mask,
                                   **window)

    def finish(self, params, out, mask, x):
        """Output gate (from the layer's input x), output projection,
        activation and mask zeroing on the attention context [b,t,H,Dh]
        (shared by forward and the decode legs)."""
        c = self.conf
        B, T = out.shape[0], out.shape[1]
        out = out.reshape(B, T, -1)
        if getattr(c, "output_gate", False):
            with jax.named_scope("attention_gate"):
                acc = _acc_dtype(x.dtype)
                out = (out.astype(acc) * jax.nn.sigmoid(
                    (x @ params["Wgate"]).astype(acc))).astype(x.dtype)
        out = out @ params["Wo"] + params["b"]
        out = self.activation_fn()(out)
        if mask is not None:
            out = out * mask[:, :, None]  # zero masked steps like the LSTM scan
        return out

    # -- decode ---------------------------------------------------------------
    def decode_unsupported(self):
        if not getattr(self.conf, "causal", False):
            return ("non-causal attention attends to future positions and "
                    "cannot decode incrementally")
        return None

    def decode_entry(self, geom):
        from ...kernels.flash_attention import (LANES, SUBLANES,
                                                packed_rows, tiled_rows)
        H, Dh = self.kv_heads, self.head_dim
        if geom.paged:
            shape = (geom.num_blocks, geom.block_size, H, Dh)
            return note_cache_entry(geom, "kv", self._leaves(shape, geom))
        # a windowed layer keeps a ring, a position at its remainder: of its
        # window, or of the capacity where a slot cannot outgrow the window
        # (a ring that never wraps; still the ring's kind, kernel name and
        # `decode_rewindable`: `self.window is not None` decides all four)
        ring = self.window is not None
        C = min(self.window, geom.capacity) if ring else geom.capacity
        # a slab the step's kernel reads: heads narrower than a lane row
        # packed side by side where one shard's then fill whole tiles, fewer
        # lane rows a position than a tile's 8 (heads of whole rows, or
        # narrower ones packed) declared in whole tiles
        kernels = getattr(self.conf, "use_pallas", False)
        rows = kernels and packed_rows(H, Dh, geom.model_shards)
        tiles = kernels and tiled_rows(C, H, Dh, geom.model_shards)
        shape = (geom.slots, *((C, rows, LANES) if rows else
                               (tiles, SUBLANES, max(Dh, LANES)) if tiles
                               else (C, H, Dh)))
        return note_cache_entry(geom, "window" if ring else "kv",
                                self._leaves(shape, geom))

    @staticmethod
    def _leaves(shape, geom):
        leaf = CacheLeaf(shape, geom.dtype, 2)
        return {"k": leaf, "v": leaf}

    def _positions(self, leaf):
        """Positions a slot of a slab leaf holds, whatever its rows' shape."""
        return leaf.shape[1] * leaf.shape[2] * leaf.shape[3] \
            // (self.kv_heads * self.head_dim)

    @staticmethod
    def _as_stored(t, leaf):
        """A sequence's K or V [b, t, H, Dh] in the leaf's dtype and in the
        shape of its rows (packed: `[b, t, H Dh / 128, 128]`; in whole tiles:
        `[b, t H / 8, 8, Dh]`, or, packed as well, `[b, t H Dh / 1024, 8,
        128]`: a plain reshape every way)."""
        return t.astype(leaf.dtype).reshape(t.shape[0], -1, *leaf.shape[2:])

    def _as_ring(self, t, length, ring):
        """The [1, L, H, Dh] rows of a prompt as the ring holds them after
        it: position p at p % ring, of the `length` real positions the last
        `ring`. A bucket no longer than the ring is its own start; a longer
        one's last `ring` real rows are cut out and turned to their places
        (a prompt shorter than the ring in a longer bucket keeps its rows
        where they are: the cut starts at 0)."""
        L = t.shape[1]
        if L <= ring:
            return t
        start = jnp.clip(length - ring, 0, L - ring)
        rows = lax.dynamic_slice_in_dim(t, start, ring, axis=1)
        return jnp.roll(rows, start % ring, axis=1)

    def decode_prefill(self, params, state, x, entry, ctx):
        q, k, v = self.project_qkv(params, x)                 # [1, L, H, Dh]
        with self._attention_scope():
            out = self.attend(q, k, v, ctx.mask)
        y = self.finish(params, out, ctx.mask, x)
        with jax.named_scope("kv_append"):
            if ctx.table is not None:
                return y, {
                    "k": _paged_append_seq(entry["k"], k[0], ctx.row),
                    "v": _paged_append_seq(entry["v"], v[0], ctx.row)}
            if self.window is not None:
                ring = self._positions(entry["k"])
                k, v = (self._as_ring(t, ctx.length, ring) for t in (k, v))
            # match the traced slot's index dtype under x64
            z = jnp.zeros((), ctx.slot.dtype)
            at = (ctx.slot, z, z, z)
            return y, {
                "k": lax.dynamic_update_slice(
                    entry["k"], self._as_stored(k, entry["k"]), at),
                "v": lax.dynamic_update_slice(
                    entry["v"], self._as_stored(v, entry["v"]), at)}

    def decode_step(self, params, state, x, entry, ctx):
        from ...kernels import flash_decode_append, flash_decode_paged
        q, kt, vt = self.project_qkv(params, x, ctx.pos[:, None])
        use_pallas = getattr(self.conf, "use_pallas", False)   # [S, 1, H, Dh]
        if ctx.table is not None:
            with jax.named_scope("kv_append"):
                nk = entry["k"].at[ctx.blk, ctx.off].set(
                    kt[:, 0].astype(entry["k"].dtype))
                nv = entry["v"].at[ctx.blk, ctx.off].set(
                    vt[:, 0].astype(entry["v"].dtype))
            with self._attention_scope():
                out = flash_decode_paged(q, nk, nv, ctx.table, ctx.kv_valid,
                                         use_pallas=use_pallas,
                                         window=self.window)
        else:
            # the slot then holds ctx.kv_valid = ctx.pos + 1 tokens, a
            # window's ring the newest of them
            with self._attention_scope():
                out, nk, nv = flash_decode_append(
                    q, entry["k"], entry["v"], kt.astype(entry["k"].dtype),
                    vt.astype(entry["v"].dtype), ctx.pos,
                    use_pallas=use_pallas, ring=self.window is not None)
        return self.finish(params, out.astype(x.dtype), None, x), \
            {"k": nk, "v": nv}

    def decode_verify(self, params, state, x, entry, ctx):
        """Slab layout only (the engine's verify() refuses the paged one),
        and no windowed layer (`decode_rewindable`)."""
        W = x.shape[1]
        start = jnp.asarray(ctx.start, ctx.slot.dtype)
        q, k, v = self.project_qkv(
            params, x, (start + jnp.arange(W, dtype=start.dtype))[None])
        slot = ctx.slot                                       # [1, W, H, Dh]
        z = jnp.zeros((), slot.dtype)

        def write(leaf, t):
            if leaf.shape[1] == self._positions(leaf):
                # a position an index: plain, or packed
                return lax.dynamic_update_slice(
                    leaf, self._as_stored(t, leaf), (slot, start, z, z))
            # whole tiles: a window may start inside one, so by the rows
            lanes = leaf.shape[3]
            R = t.shape[2] * t.shape[3] // lanes    # rows a position
            rows = leaf.reshape(leaf.shape[0], -1, lanes)
            return lax.dynamic_update_slice(
                rows, t.astype(leaf.dtype).reshape(1, W * R, lanes),
                (slot, start * R, z)).reshape(leaf.shape)
        nk, nv = write(entry["k"], k), write(entry["v"], v)
        # the one slot's row, as heads (a packed or tiled row unpacked)
        krow, vrow = (
            lax.dynamic_index_in_dim(n, slot, 0, keepdims=True).reshape(
                1, -1, *k.shape[2:]) for n in (nk, nv))
        out = _verify_attend(q, krow, vrow, ctx.start)
        return self.finish(params, out.astype(x.dtype), None, x), \
            {"k": nk, "v": nv}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        c = self.conf
        attn_rng = None
        attn_drop = getattr(c, "attention_dropout", 0.0) or 0.0
        if rng is not None and attn_drop > 0:
            rng, attn_rng = jax.random.split(rng)
        x = apply_dropout(x, c.dropout, train, rng)
        q, k, v = self.project_qkv(params, x)
        out = self.attend(q, k, v, mask)
        out = apply_dropout(out, attn_drop, train, attn_rng)
        return self.finish(params, out, mask, x), state, mask
