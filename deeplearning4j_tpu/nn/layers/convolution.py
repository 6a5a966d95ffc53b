"""Convolution family runtime: Conv2D, Subsampling (pooling), ZeroPadding,
LocalResponseNormalization, BatchNormalization, GlobalPooling.

Reference counterparts: nn/layers/convolution/ConvolutionLayer.java (im2col+gemm
path :265-310, cuDNN helper hook :71), subsampling/SubsamplingLayer.java,
normalization/{BatchNormalization,LocalResponseNormalization}.java,
pooling/GlobalPoolingLayer.java.

TPU-first: the reference's helper SPI (cuDNN vs Java path) collapses into a
single XLA lowering — lax.conv_general_dilated / lax.reduce_window ARE the
accelerated path, tiled onto the MXU by the compiler. Layout NHWC; kernels HWIO.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .base import BaseLayerModule, register_impl, apply_dropout
from ..weights import init_weights
from ..conf.inputs import InputType, RecurrentInputType, ConvolutionalInputType


def _conv_padding(conf, kernel=None):
    if conf.convolution_mode == "same":
        return "SAME"
    p = conf.padding
    return ((int(p[0]), int(p[0])), (int(p[1]), int(p[1])))


@register_impl("ConvolutionLayer")
class ConvolutionLayerModule(BaseLayerModule):
    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        kh, kw = int(c.kernel_size[0]), int(c.kernel_size[1])
        n_in, n_out = int(c.n_in), int(c.n_out)
        fan_in = n_in * kh * kw
        fan_out = n_out * kh * kw
        params = {
            "W": init_weights(rng, (kh, kw, n_in, n_out), c.weight_init,
                              fan_in=fan_in, fan_out=fan_out, distribution=c.dist,
                              dtype=dtype),
        }
        if getattr(c, "has_bias", True):
            params["b"] = jnp.full((n_out,), c.bias_init or 0.0, dtype)
        return params, {}, c.get_output_type(input_type)

    def preoutput(self, params, x):
        c = self.conf
        z = lax.conv_general_dilated(
            x, params["W"],
            window_strides=tuple(int(s) for s in c.stride),
            padding=_conv_padding(c),
            rhs_dilation=tuple(int(d) for d in getattr(c, "dilation", (1, 1))),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if "b" in params:
            z = z + params["b"]
        return z

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = apply_dropout(x, self.conf.dropout, train, rng)
        return self.activation_fn()(self.preoutput(params, x)), state, mask


@register_impl("SubsamplingLayer")
class SubsamplingLayerModule(BaseLayerModule):
    def init(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, self.conf.get_output_type(input_type)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        c = self.conf
        kh, kw = int(c.kernel_size[0]), int(c.kernel_size[1])
        sh, sw = int(c.stride[0]), int(c.stride[1])
        if c.convolution_mode == "same":
            pad = "SAME"
        else:
            ph, pw = int(c.padding[0]), int(c.padding[1])
            pad = ((0, 0), (ph, ph), (pw, pw), (0, 0))
        window = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        pt = c.pooling_type
        if pt == "max":
            init_val = -jnp.inf
            y = lax.reduce_window(x, init_val, lax.max, window, strides, pad)
        elif pt in ("avg", "sum"):
            y = lax.reduce_window(x, 0.0, lax.add, window, strides, pad)
            if pt == "avg":
                y = y / (kh * kw)
        elif pt == "pnorm":
            p = float(c.pnorm)
            y = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides, pad) ** (1.0 / p)
        else:
            raise ValueError(f"Unknown pooling type {pt}")
        return y, state, mask


@register_impl("ZeroPaddingLayer")
class ZeroPaddingLayerModule(BaseLayerModule):
    def init(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, self.conf.get_output_type(input_type)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        c = self.conf
        y = jnp.pad(x, ((0, 0), (c.pad_top, c.pad_bottom),
                        (c.pad_left, c.pad_right), (0, 0)))
        return y, state, mask


@register_impl("LocalResponseNormalization")
class LocalResponseNormalizationModule(BaseLayerModule):
    """Cross-channel LRN on NHWC; the reduce_window over the channel axis fuses
    into one XLA kernel (reference runtime:
    nn/layers/normalization/LocalResponseNormalization.java, cuDNN helper
    deeplearning4j-cuda/.../CudnnLocalResponseNormalizationHelper.java)."""

    def init(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        c = self.conf
        n = int(c.n)
        half = n // 2
        sq = x * x
        win = lax.reduce_window(sq, 0.0, lax.add, (1, 1, 1, n), (1, 1, 1, 1),
                                ((0, 0), (0, 0), (0, 0), (half, n - 1 - half)))
        denom = (c.k + c.alpha * win) ** c.beta
        return x / denom, state, mask


@register_impl("LayerNormalization")
class LayerNormalizationModule(BaseLayerModule):
    """Layer norm over the last axis (stateless; NEW — the reference's 2017
    layer set has no LayerNormalization). Per-position mean/variance keep
    transformer activations stable regardless of batch composition."""
    positionwise = True

    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        n = int(c.n_in)
        params = {"gamma": jnp.ones((n,), dtype),
                  "beta": jnp.zeros((n,), dtype)}
        return params, {}, input_type

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        c = self.conf
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * lax.rsqrt(var + c.eps)
        y = y * params["gamma"] + params["beta"]
        return self.activation_fn()(y), state, mask


@register_impl("RMSNormalization")
class RMSNormalizationModule(BaseLayerModule):
    """x * rsqrt(mean(x^2) + eps) * gamma over the last axis; the mean of
    squares in float32, the result in the activations' dtype."""
    positionwise = True

    def init(self, rng, input_type, dtype=jnp.float32):
        return {"gamma": jnp.ones((int(self.conf.n_in),), dtype)}, {}, \
            input_type

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        y = rms_norm(x, params["gamma"], self.conf.eps)
        return self.activation_fn()(y), state, mask


def rms_norm(x, gamma, eps):
    """The RMS norm itself, shared with the Mamba-2 mixer's gated norm."""
    acc = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(acc)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(acc)).astype(x.dtype)


@register_impl("BatchNormalization")
class BatchNormalizationModule(BaseLayerModule):
    """Batch normalization over the channel (last) axis for NHWC or the feature
    axis for [b,f] (reference runtime: nn/layers/normalization/BatchNormalization.java:55,
    cuDNN helper CudnnBatchNormalizationHelper.java). Running stats live in
    layer state and are updated functionally inside the compiled train step."""

    def init(self, rng, input_type, dtype=jnp.float32):
        c = self.conf
        n = int(c.n_in)
        params = {}
        if not c.lock_gamma_beta:
            params["gamma"] = jnp.full((n,), c.gamma, dtype)
            params["beta"] = jnp.full((n,), c.beta, dtype)
        state = {"mean": jnp.zeros((n,), dtype), "var": jnp.ones((n,), dtype)}
        return params, state, input_type

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        c = self.conf
        axes = tuple(range(x.ndim - 1))  # all but channel/feature
        # statistics ACCUMULATE in the state dtype (f32 under bf16 mixed
        # precision — bf16 accumulation loses the small batch-to-batch deltas
        # the running stats depend on), but the per-element normalization
        # stays in the input dtype so the channel-sized scale/shift fuses into
        # the surrounding bf16 elementwise chain without f32 HBM traffic
        in_dt = x.dtype
        stat_dt = state["mean"].dtype
        if train:
            mean = jnp.mean(x, axis=axes, dtype=stat_dt)
            if in_dt == stat_dt:
                # full-precision path: two-pass variance (gradient-check exact)
                var = jnp.mean(jnp.square(x - mean), axis=axes, dtype=stat_dt)
            else:
                # mixed-precision path: one-pass shifted variance
                # E[(x−μ₀)²] − (E[x]−μ₀)² so both reductions fuse into a
                # single read of the bf16 activation (the two-pass form
                # re-reads x; ~40 ms/step across ResNet-50's 53 BN layers).
                # μ₀ is the mean of a strided subsample of THIS batch — it
                # lands within O(std/√n_sub) of the true mean, so the shifted
                # second moment has the same magnitude as the variance itself
                # and f32 rounding stays relative to var (a μ₀ far from the
                # data — e.g. the running mean at step 0, zeros — degenerates
                # to E[x²]−E[x]² and cancels catastrophically when
                # |mean| >> std). The subsample is a slice, so its reduction
                # reads a fraction of x and fuses alongside the main pass.
                sub = x[(slice(None),) + tuple(
                    slice(None, None, max(1, x.shape[a] // 8))
                    for a in range(1, x.ndim - 1))]
                mu0 = lax.stop_gradient(
                    jnp.mean(sub, axis=tuple(range(sub.ndim - 1)),
                             dtype=stat_dt))
                d = x.astype(stat_dt) - mu0
                ex2c = jnp.mean(jnp.square(d), axis=axes, dtype=stat_dt)
                var = jnp.maximum(ex2c - jnp.square(mean - mu0), 0.0)
            decay = c.decay
            new_state = {
                "mean": decay * state["mean"] + (1 - decay) * mean,
                "var": decay * state["var"] + (1 - decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = lax.rsqrt(var + c.eps)          # f32, channel-sized
        if "gamma" in params:
            scale = params["gamma"].astype(stat_dt) * inv
            shift = params["beta"].astype(stat_dt) - mean * scale
        else:
            scale = c.gamma * inv
            shift = c.beta - mean * scale
        y = x * scale.astype(in_dt) + shift.astype(in_dt)
        return self.activation_fn()(y), new_state, mask


@register_impl("GlobalPoolingLayer")
class GlobalPoolingLayerModule(BaseLayerModule):
    """Mask-aware global pooling over time ([b,t,f] -> [b,f]) or space
    ([b,h,w,c] -> [b,c]) (reference: nn/layers/pooling/GlobalPoolingLayer.java,
    masked reductions via util/MaskedReductionUtil.java)."""

    def init(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, self.conf.get_output_type(input_type)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        c = self.conf
        pt = c.pooling_type
        if x.ndim == 3:  # [b, t, f] with optional mask [b, t]
            if mask is not None:
                m = mask[:, :, None].astype(x.dtype)
                if pt == "max":
                    y = jnp.max(jnp.where(m > 0, x, -jnp.inf), axis=1)
                elif pt == "sum":
                    y = jnp.sum(x * m, axis=1)
                elif pt == "avg":
                    y = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
                elif pt == "pnorm":
                    p = float(c.pnorm)
                    y = jnp.sum((jnp.abs(x) * m) ** p, axis=1) ** (1.0 / p)
                else:
                    raise ValueError(pt)
                return y, state, None
            axis = (1,)
        elif x.ndim == 4:  # [b, h, w, c]
            axis = (1, 2)
        else:
            raise ValueError(f"GlobalPooling expects rank-3 or rank-4 input, got {x.shape}")
        if pt == "max":
            y = jnp.max(x, axis=axis)
        elif pt == "avg":
            y = jnp.mean(x, axis=axis)
        elif pt == "sum":
            y = jnp.sum(x, axis=axis)
        elif pt == "pnorm":
            p = float(c.pnorm)
            y = jnp.sum(jnp.abs(x) ** p, axis=axis) ** (1.0 / p)
        else:
            raise ValueError(pt)
        return y, state, None
