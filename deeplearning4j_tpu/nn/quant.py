"""Quantization codecs — the designated quant/dequant module (ROADMAP item 3).

State a step reads and writes is device memory held and bytes moved every
step, whatever bounds the step: this module is about holding and moving
fewer bytes. ZeRO-1 (parallel/zero.py) already removed the *redundant*
optimizer-state pool; this module removes precision from the two pools that
remain — moment precision for training and weight precision for serving —
the same reduced-precision-primitives direction the cuDNN paper
(PAPERS.md [1]) takes for inference.

Two codecs live here, and ONLY here (graftlint GL014 `quant-silent-widening`
flags float32 widening of quantized leaves anywhere else):

1. `MomentCodec` — bf16 / 8-bit block-wise optimizer moments. The 8-bit
   format is block-wise fp8-e4m3 codes with one POWER-OF-TWO scale per
   block (chosen by `frexp`/`ldexp` bit manipulation so `absmax/scale`
   lands in [128, 256), clipped to ±240). Two deliberate choices:

   - LOG-SPACED codes, not linear int8: Adam's second moment spans many
     orders of magnitude *within* a block, and a linear absmax grid rounds
     the small entries to zero — `update = m_hat/(sqrt(0)+eps)` then
     divides by eps and the run detonates (measured: a linear-int8 variant
     blew a toy MLP 15 units of weight in 10 steps). e4m3's binades keep
     ~6% relative error down to absmax/2^17, which second moments tolerate
     and first moments don't notice.
   - EXACT round-trips: pow2 scales make `codes * scale` an exact float op
     and re-encoding a decoded block reproduces the same scale and codes
     bit-for-bit. That idempotence is what makes the round-trip safe
     without stochastic rounding: conversion chains — checkpoint → restore
     → re-shard → re-shard — never compound quantization error, they
     replay it. (Stochastic-rounding codecs deliberately randomize the
     round, so each hop would drift; here only *training steps* move the
     moments.)

   Codecs operate on the FLAT zero-padded vectors of the ZeRO flatten-pad
   layout (parallel/zero.py), with blocks anchored at offset 0 — so the
   same canonical values re-encode to identical codes at ANY shard count
   (the zero padding beyond the real data quantizes to zero regardless of
   how much of it a given shard count appends).

2. `WeightQuant` — per-channel symmetric int8 weight quantization for the
   serving path. Eligible leaves (floating, ndim >= 2, weight-named) are
   replaced IN the param tree by their int8 codes; scales ride on the
   WeightQuant object and the dequant (`codes * scale`, broadcast over the
   last/output-channel axis) is traced INTO the jitted inference
   executables, so HBM holds and reads the narrow weights and the widening
   happens in-register on the way into the matmul. The float originals are
   kept as a host-side numpy backup (`restore_params`) so serializers write
   f32 zips and a failed parity gate can undo the quantization.

`quantize_model_weights` is the deploy-time entry: quantize + accuracy
parity gate in one move — breach restores the f32 weights and raises
`QuantParityError`, so a deploy can never silently ship a model whose int8
outputs diverged.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp


MOMENT_DTYPES = ("f32", "bf16", "q8")

# blocks scale so absmax/scale lands in [128, 256); codes clip to +-240 so
# fp8 rounding can never cross the 256 binade boundary (which would flip
# the re-derived scale and break bitwise idempotence)
_Q_EXP = 8
_Q_CLIP = 240.0
_Q_MAX = 127.0     # int8 weight-quant ceiling (per-channel serving codes)


def _pow2_scale(absmax):
    """The power of two with absmax/scale in [128, 256) — exact via
    frexp/ldexp bit manipulation (no libm log2 rounding), so re-encoding a
    decoded block reproduces the identical scale. absmax == 0 -> scale 1."""
    _, e = jnp.frexp(absmax)                 # absmax = m * 2^e, m in [.5, 1)
    scale = jnp.ldexp(jnp.ones_like(absmax), e - _Q_EXP)
    return jnp.where(absmax > 0, scale, jnp.ones_like(absmax))


class MomentCodec:
    """bf16 / blockwise-int8 codec for the flat padded moment vectors of the
    ZeRO layout. One instance per ZeroUpdater; `dtype` in ("bf16", "q8")."""

    def __init__(self, dtype, n_shards=1, block=128):
        if dtype not in ("bf16", "q8"):
            raise ValueError(f"moment dtype {dtype!r} not in ('bf16', 'q8')")
        self.dtype = dtype
        self.n = max(1, int(n_shards))
        self.block = int(block)
        # q8 codes pad to a multiple of block*n so both the codes and the
        # per-block scales divide the data axis evenly
        self.granule = self.block * self.n

    # ------------------------------------------------------------ encode
    def encode(self, v):
        """f32 flat [L] (L a multiple of n_shards) -> stored representation:
        bf16 [L], or {"qcodes": fp8-e4m3 [L2], "qscale": f32 [L2/block]}
        with L2 = L rounded up to the granule (extra tail is zeros)."""
        if self.dtype == "bf16":
            return v.astype(jnp.bfloat16)
        L = v.shape[0]
        L2 = -(-L // self.granule) * self.granule
        if L2 > L:
            v = jnp.pad(v, (0, L2 - L))
        b = v.reshape(-1, self.block)
        scale = _pow2_scale(jnp.max(jnp.abs(b), axis=1)).astype(jnp.float32)
        q = jnp.clip(b / scale[:, None], -_Q_CLIP, _Q_CLIP)
        return {"qcodes": q.astype(jnp.float8_e4m3fn).reshape(-1),
                "qscale": scale}

    # ------------------------------------------------------------ decode
    def decode(self, enc, length):
        """Stored representation -> f32 flat [length]. Exact: fp8 code *
        pow2 scale never rounds, so decode(encode(decode(x))) == decode(x)."""
        if self.dtype == "bf16":
            return enc.astype(jnp.float32)
        q = enc["qcodes"].reshape(-1, self.block).astype(jnp.float32)
        v = (q * enc["qscale"][:, None]).reshape(-1)
        return v[:length]

    def is_encoded(self, leaf):
        """True for nodes this codec produced (pytree traversal stop)."""
        if self.dtype == "bf16":
            return (hasattr(leaf, "dtype") and getattr(leaf, "ndim", 0) == 1
                    and leaf.dtype == jnp.bfloat16)
        return isinstance(leaf, dict) and "qcodes" in leaf


# ---------------------------------------------------------------------------
# int8 weight quantization (serving)
# ---------------------------------------------------------------------------

# param keys that are NOT weights: biases, norm stats/affine, center-loss
# centers (mirrors network._is_weight_key)
_NON_WEIGHT_KEYS = ("gamma", "beta", "centers", "mean", "var")


def _is_quantizable_weight(key, leaf):
    return (hasattr(leaf, "ndim") and leaf.ndim >= 2
            and hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and not str(key).endswith("b")
            and str(key) not in _NON_WEIGHT_KEYS)


def quantize_weight(w):
    """Per-channel symmetric int8: one exact-absmax scale per OUTPUT channel
    (the last axis — dense [in, out], conv HWIO, LSTM [in, 4H] columns).
    Returns (codes int8, scale f32 [n_out])."""
    red = tuple(range(w.ndim - 1))
    absmax = jnp.max(jnp.abs(w), axis=red)
    scale = jnp.where(absmax > 0, absmax / _Q_MAX,
                      jnp.ones_like(absmax)).astype(jnp.float32)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -_Q_MAX, _Q_MAX)
    return q.astype(jnp.int8), scale


def dequantize_weight(codes, scale):
    """Traced into the inference executable: the int8 codes are the
    HBM-resident operand; the widening multiply fuses into the consumer."""
    return codes.astype(scale.dtype) * scale


class WeightQuant:
    """Scales + host-side f32 backup for a weight-quantized param tree.

    `build` replaces eligible leaves of the (two-level {layer: {name: arr}})
    param tree with int8 codes; `dequant` is the traceable inverse the
    inference executables fuse (scales are closure constants — a few floats
    per channel); `restore_params` rebuilds the f32 tree from the backup
    (serializers write f32 zips; a failed parity gate un-quantizes)."""

    def __init__(self, scales, backup, dtype="int8"):
        self.scales = scales       # {layer: {name: f32 [n_out]}}
        self.backup = backup       # {layer: {name: host np f32 array}}
        self.dtype = dtype

    @staticmethod
    def build(params, dtype="int8"):
        if dtype != "int8":
            raise ValueError(f"weight quant dtype {dtype!r} != 'int8'")
        scales, backup, out = {}, {}, {}
        for lk, sub in params.items():
            new_sub = dict(sub)
            for k, leaf in sub.items():
                if not _is_quantizable_weight(k, leaf):
                    continue
                codes, scale = quantize_weight(leaf)
                scales.setdefault(lk, {})[k] = scale
                backup.setdefault(lk, {})[k] = np.asarray(leaf)
                new_sub[k] = codes
            out[lk] = new_sub
        if not scales:
            raise ValueError("no quantizable weight leaves found")
        return WeightQuant(scales, backup, dtype), out

    def dequant(self, params):
        """Traceable: int8 code leaves -> widened weights; everything else
        passes through untouched."""
        out = {}
        for lk, sub in params.items():
            lscales = self.scales.get(lk)
            if not lscales:
                out[lk] = sub
                continue
            out[lk] = {k: (dequantize_weight(v, lscales[k])
                           if k in lscales else v)
                       for k, v in sub.items()}
        return out

    def restore_params(self, params):
        out = {}
        for lk, sub in params.items():
            lback = self.backup.get(lk, {})
            out[lk] = {k: (jnp.asarray(lback[k]) if k in lback else v)
                       for k, v in sub.items()}
        return out


# ---------------------------------------------------------------------------
# deploy-time parity gate
# ---------------------------------------------------------------------------


class QuantParityError(RuntimeError):
    """int8 outputs diverged from f32 beyond the gate; the model was
    restored to f32 before raising."""

    def __init__(self, report):
        super().__init__(f"quantization parity gate breached: {report}")
        self.report = report


@dataclass
class QuantGate:
    """Accuracy-parity thresholds for a quantized deploy: classification
    heads must agree on >= `min_top1_agreement` of the parity rows AND the
    worst output delta must stay under `max_rel_delta` of the f32 output
    range."""
    max_rel_delta: float = 0.1
    min_top1_agreement: float = 0.97


def parity_report(ref, quant):
    """Compare f32 vs quantized outputs: max |delta| relative to the f32
    output range, plus top-1 agreement when the output looks like a
    distribution over classes (last dim > 1)."""
    ref = np.asarray(ref, np.float64)
    quant = np.asarray(quant, np.float64)
    span = float(max(np.max(np.abs(ref)), 1e-9))
    max_rel = float(np.max(np.abs(ref - quant))) / span
    top1 = None
    if ref.ndim >= 2 and ref.shape[-1] > 1:
        top1 = float(np.mean(np.argmax(ref, -1) == np.argmax(quant, -1)))
    return {"max_rel_delta": round(max_rel, 6),
            "top1_agreement": None if top1 is None else round(top1, 6)}


def quantize_model_weights(model, dtype="int8", parity_inputs=None,
                           gate=None):
    """Quantize `model`'s weights for serving, gated on accuracy parity.

    With `parity_inputs`, the f32 outputs are snapshotted first, the model
    is quantized, and the quantized outputs must pass `gate` — a breach
    restores the f32 weights and raises QuantParityError, so the caller's
    deploy fails with the model unchanged. Without parity inputs the
    quantization is applied ungated (callers measuring accuracy end-to-end
    on a held-out set of their own). Returns the parity report."""
    gate = gate if gate is not None else QuantGate()
    if parity_inputs is None:
        model.quantize_weights(dtype)
        return {"gated": False, "dtype": dtype}
    x = np.asarray(parity_inputs)
    ref = np.asarray(model.output(x))
    model.quantize_weights(dtype)
    quant = np.asarray(model.output(x))
    report = parity_report(ref, quant)
    report.update(gated=True, dtype=dtype, rows=int(x.shape[0]))
    breach = report["max_rel_delta"] > gate.max_rel_delta or (
        report["top1_agreement"] is not None
        and report["top1_agreement"] < gate.min_top1_agreement)
    if breach:
        model.dequantize_weights()
        raise QuantParityError(report)
    return report


def synthetic_parity_inputs(model, batch=16, seed=0):
    """A deterministic standard-normal parity batch shaped from the model's
    configured input type, or None when the conf carries no input shape
    (the caller must then supply explicit parity inputs)."""
    t = getattr(model.conf, "input_type", None)
    if t is None:
        types = getattr(model.conf, "input_types", None)
        t = types[0] if types else None
    if t is None:
        return None
    rng = np.random.default_rng(seed)
    kind = getattr(t, "kind", None)
    if kind == "ff":
        shape = (batch, t.size)
    elif kind == "recurrent":
        shape = (batch, int(getattr(t, "timesteps", None) or 16), t.size)
    elif kind in ("cnn", "cnn_flat"):
        shape = (batch, t.height, t.width, t.channels)
    else:
        return None
    return rng.normal(size=shape).astype(np.float32)
