"""Layer configuration classes (the builder-DSL vocabulary).

Capability parity with reference nn/conf/layers/* (25 config classes; see
SURVEY.md §2.1). Each config is a serializable dataclass; hyperparameters left
as None inherit the global values set on the NeuralNetConfiguration builder
(reference behavior: per-layer override of global hyperparams,
nn/conf/NeuralNetConfiguration.java:484 Builder).

Runtime semantics live in deeplearning4j_tpu/nn/layers/* — configs only carry
hyperparameters and shape logic (get_output_type / infer n_in), mirroring the
reference's config/impl split.
"""
from __future__ import annotations

from dataclasses import dataclass, field, asdict, fields as dc_fields

from .inputs import (InputType, FeedForwardInputType, RecurrentInputType,
                     ConvolutionalInputType, ConvolutionalFlatInputType)

_LAYER_REGISTRY: dict = {}


def register_layer_conf(cls):
    _LAYER_REGISTRY[cls.__name__] = cls
    return cls


def layer_conf_from_dict(d):
    d = dict(d)
    cls = _LAYER_REGISTRY[d.pop("type")]
    kw = {}
    names = {f.name for f in dc_fields(cls)}
    for k, v in d.items():
        if k in names:
            kw[k] = v
    obj = cls(**kw)
    if "updater" in d and d["updater"] is not None and isinstance(d["updater"], dict):
        from ..updaters import updater_from_dict
        obj.updater = updater_from_dict(d["updater"])
    return obj


# Global hyperparameters a layer can override (reference: NeuralNetConfiguration
# Builder fields cloned into each layer conf).
_INHERITED = ("activation", "weight_init", "bias_init", "l1", "l2", "l1_bias",
              "l2_bias", "dropout", "updater", "gradient_normalization",
              "gradient_normalization_threshold", "dist")


@dataclass
class BaseLayerConf:
    name: str | None = None
    activation: str | None = None
    weight_init: str | None = None
    bias_init: float | None = None
    dist: dict | None = None
    l1: float | None = None
    l2: float | None = None
    l1_bias: float | None = None
    l2_bias: float | None = None
    dropout: float | None = None
    updater: object | None = None
    gradient_normalization: str | None = None
    gradient_normalization_threshold: float | None = None

    def apply_global_defaults(self, g: dict):
        for k in _INHERITED:
            if getattr(self, k, None) is None and g.get(k) is not None:
                setattr(self, k, g[k])
        if self.activation is None:
            self.activation = "sigmoid"
        if self.weight_init is None:
            self.weight_init = "xavier"
        if self.bias_init is None:
            self.bias_init = 0.0
        for k in ("l1", "l2", "l1_bias", "l2_bias"):
            if getattr(self, k) is None:
                setattr(self, k, 0.0)
        if self.dropout is None:
            self.dropout = 0.0

    # ---- shape logic ------------------------------------------------------
    def get_output_type(self, input_type):
        raise NotImplementedError

    def set_n_in(self, input_type):
        """Infer n_in from the incoming InputType when unset."""
        if hasattr(self, "n_in") and getattr(self, "n_in", None) in (None, 0):
            self.n_in = input_type.flat_size()

    # ---- serde ------------------------------------------------------------
    def to_dict(self):
        d = {}
        for f in dc_fields(self):
            v = getattr(self, f.name)
            if v is None:
                continue
            if hasattr(v, "to_dict"):
                v = v.to_dict()
            d[f.name] = v
        d["type"] = type(self).__name__
        return d


@dataclass
class FeedForwardLayerConf(BaseLayerConf):
    n_in: int | None = None
    n_out: int | None = None

    def get_output_type(self, input_type):
        return InputType.feed_forward(self.n_out)


@register_layer_conf
@dataclass
class DenseLayer(FeedForwardLayerConf):
    """Fully connected layer (reference: nn/conf/layers/DenseLayer.java).
    On [b, t, f] input it applies per-timestep (time-distributed; one batched
    gemm) and stays recurrent — beyond the reference, which demands
    RnnToFeedForward wrapping."""

    def get_output_type(self, input_type):
        if isinstance(input_type, RecurrentInputType):
            return InputType.recurrent(self.n_out)
        return InputType.feed_forward(self.n_out)


@register_layer_conf
@dataclass
class OutputLayer(FeedForwardLayerConf):
    """Output layer with integrated loss (reference: nn/conf/layers/OutputLayer.java)."""
    loss: str = "MCXENT"


@register_layer_conf
@dataclass
class RnnOutputLayer(FeedForwardLayerConf):
    """Per-timestep output layer for sequences [b,t,f]
    (reference: nn/conf/layers/RnnOutputLayer.java)."""
    loss: str = "MCXENT"

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out)


@register_layer_conf
@dataclass
class LMHeadLayer(RnnOutputLayer):
    """Per-timestep output layer of a language model whose head is the
    embedding: probabilities = softmax(x W^T / logits_scaling), no bias. W is
    stored [n_out, n_in] — the layout of the [vocab, d_model] embedding
    matrix, so a tied model places ONE buffer under both leaves (the graph
    keeps a leaf per layer; training them tied is not wired). The logits and
    the softmax are float32 whatever the parameters' dtype: among 100k
    near-flat probabilities a bfloat16 tie would decide the greedy pick."""
    logits_scaling: float = 1.0


@register_layer_conf
@dataclass
class GatedDenseLayer(FeedForwardLayerConf):
    """Gated feed-forward block (GLU family; SwiGLU with the default
    activation): (g, u) = split(x W_in), out = (activation(g) * u) W_out,
    no biases. n_hidden is the width of g and of u, so W_in is [n_in,
    2 * n_hidden] and W_out [n_hidden, n_out]. Time-distributed on
    [b, t, f] like DenseLayer."""
    n_hidden: int | None = None

    def apply_global_defaults(self, g):
        explicit = self.activation
        super().apply_global_defaults(g)
        if explicit is None:
            self.activation = "swish"

    def get_output_type(self, input_type):
        if isinstance(input_type, RecurrentInputType):
            return InputType.recurrent(self.n_out)
        return InputType.feed_forward(self.n_out)


@register_layer_conf
@dataclass
class LossLayer(BaseLayerConf):
    """Parameterless loss layer (reference: nn/conf/layers/LossLayer.java)."""
    loss: str = "MSE"
    n_in: int | None = None
    n_out: int | None = None

    def get_output_type(self, input_type):
        return input_type


@register_layer_conf
@dataclass
class CenterLossOutputLayer(FeedForwardLayerConf):
    """Output layer + center loss on penultimate features
    (reference: nn/conf/layers/CenterLossOutputLayer.java,
    nn/layers/training/CenterLossOutputLayer.java)."""
    loss: str = "MCXENT"
    alpha: float = 0.05
    lambda_: float = 2e-4


@register_layer_conf
@dataclass
class EmbeddingLayer(FeedForwardLayerConf):
    """Index -> vector lookup (reference: nn/conf/layers/EmbeddingLayer.java).
    Input: integer indices [b] or one-hot [b, n_in]."""
    has_bias: bool = True


@register_layer_conf
@dataclass
class ConvolutionLayer(FeedForwardLayerConf):
    """2-D convolution, NHWC (reference: nn/conf/layers/ConvolutionLayer.java;
    runtime im2col path at nn/layers/convolution/ConvolutionLayer.java:265-310 is
    replaced by a single XLA conv_general_dilated that maps onto the MXU)."""
    kernel_size: tuple = (5, 5)
    stride: tuple = (1, 1)
    padding: tuple = (0, 0)
    convolution_mode: str = "truncate"  # truncate | same | strict
    dilation: tuple = (1, 1)
    has_bias: bool = True

    def set_n_in(self, input_type):
        if self.n_in in (None, 0) and isinstance(input_type, (ConvolutionalInputType, ConvolutionalFlatInputType)):
            self.n_in = input_type.channels

    def get_output_type(self, input_type):
        h, w = input_type.height, input_type.width
        oh, ow = conv_output_size(h, w, self.kernel_size, self.stride, self.padding,
                                  self.convolution_mode, self.dilation)
        return InputType.convolutional(oh, ow, self.n_out)


@dataclass
class _NoActivationConf(BaseLayerConf):
    """Layers with no activation of their own ignore the global activation."""

    def apply_global_defaults(self, g):
        explicit = self.activation
        super().apply_global_defaults(g)
        if explicit is None:
            self.activation = "identity"


@register_layer_conf
@dataclass
class SubsamplingLayer(_NoActivationConf):
    """Spatial pooling (reference: nn/conf/layers/SubsamplingLayer.java)."""
    pooling_type: str = "max"  # max | avg | sum | pnorm
    kernel_size: tuple = (2, 2)
    stride: tuple = (2, 2)
    padding: tuple = (0, 0)
    convolution_mode: str = "truncate"
    pnorm: int = 2

    def get_output_type(self, input_type):
        h, w = input_type.height, input_type.width
        oh, ow = conv_output_size(h, w, self.kernel_size, self.stride, self.padding,
                                  self.convolution_mode)
        return InputType.convolutional(oh, ow, input_type.channels)


def _norm_set_n_in(self, input_type):
    """Shared n_in inference for the normalization confs: channel count for
    CNN activations, feature size otherwise; n_out mirrors n_in."""
    if self.n_in in (None, 0):
        if isinstance(input_type, ConvolutionalInputType):
            self.n_in = input_type.channels
        else:
            self.n_in = input_type.flat_size()
    self.n_out = self.n_in


@register_layer_conf
@dataclass
class LayerNormalization(BaseLayerConf):
    """Layer norm over the feature (last) axis — NEW capability beyond the
    reference's 2017 layer set (no LayerNormalization.java exists at v0.7.3);
    added because the transformer family (zoo.transformer_lm) needs it.
    Stateless (no running statistics), works on [b,f], [b,t,f], [b,h,w,c]."""
    n_in: int | None = None
    n_out: int | None = None
    eps: float = 1e-5

    def apply_global_defaults(self, g):
        explicit = self.activation
        super().apply_global_defaults(g)
        if explicit is None:
            self.activation = "identity"

    set_n_in = _norm_set_n_in

    def get_output_type(self, input_type):
        return input_type


@register_layer_conf
@dataclass
class RMSNormalization(_NoActivationConf):
    """Root-mean-square norm over the feature (last) axis, x * rsqrt(mean(x^2)
    + eps) * gamma: no mean, no bias (Zhang & Sennrich 2019) — the norm of
    the pre-norm decoder blocks (zoo.granite_hybrid_lm). The statistics are
    taken in float32 whatever the activations' dtype."""
    n_in: int | None = None
    n_out: int | None = None
    eps: float = 1e-5

    set_n_in = _norm_set_n_in

    def get_output_type(self, input_type):
        return input_type


@register_layer_conf
@dataclass
class BatchNormalization(BaseLayerConf):
    """Batch norm over feature/channel axis (reference:
    nn/conf/layers/BatchNormalization.java, runtime
    nn/layers/normalization/BatchNormalization.java:55)."""
    n_in: int | None = None
    n_out: int | None = None
    decay: float = 0.9
    eps: float = 1e-5
    gamma: float = 1.0
    beta: float = 0.0
    lock_gamma_beta: bool = False

    def apply_global_defaults(self, g):
        explicit = self.activation
        super().apply_global_defaults(g)
        if explicit is None:
            self.activation = "identity"

    set_n_in = _norm_set_n_in

    def get_output_type(self, input_type):
        return input_type


@register_layer_conf
@dataclass
class LocalResponseNormalization(_NoActivationConf):
    """Cross-channel LRN (reference: nn/conf/layers/LocalResponseNormalization.java)."""
    k: float = 2.0
    n: float = 5.0
    alpha: float = 1e-4
    beta: float = 0.75

    def get_output_type(self, input_type):
        return input_type


@dataclass
class BaseRecurrentConf(FeedForwardLayerConf):
    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out)


@register_layer_conf
@dataclass
class SelfAttentionLayer(BaseRecurrentConf):
    """Multi-head self-attention over a sequence [b,t,f] — NEW capability with
    no reference counterpart (SURVEY.md §5: the reference has no attention).
    Runs flash-style blockwise attention on one device; the sequence-parallel
    long-context variant is parallel.ring_attention.ring_attention, applied to
    the same Q/K/V projections. use_pallas=True routes the unmasked forward
    through the hand-tiled Pallas kernel (kernels/flash_attention.py;
    interpret mode on CPU, Mosaic on TPU). Grouped K/V heads (`n_kv_heads`),
    a head width of its own (`head_dim`: H x head_dim need not be n_out) and
    a sigmoid output gate from the layer's input (`output_gate`) are options,
    as are rotary positions on q and k (`rope_theta`, and YaRN's parameters
    as `rope_yarn`) and a sliding `window`. Causal layers decode from a K/V
    cache [slots, capacity, kv_heads, head_dim] — a windowed one from a ring
    of `window` positions —: one kernel a step whichever way the TPU stores
    it (row-major from head_dim 128 on and, with `use_pallas`, wherever
    narrower heads pack into whole tiles; positions-minor elsewhere:
    nn/layers/recurrent.py `decode_entry`)."""
    n_heads: int = 4
    causal: bool = False
    block_size: int = 256
    use_pallas: bool = False
    # dropout on the attention OUTPUT (post-softmax·V, pre-Wo) — the layer's
    # inherited `dropout` drops the INPUT like every reference layer
    attention_dropout: float = 0.0
    # grouped-query attention: K/V heads (None: as many as query heads);
    # each serves n_heads // n_kv_heads query heads, and the decode cache
    # holds only these
    n_kv_heads: int | None = None
    # what the scores are multiplied by (None: 1 / sqrt(head_dim))
    score_scale: float | None = None
    # a head's width (None: n_out // n_heads); given, the projections are
    # [n_in, n_heads * head_dim] and Wo [n_heads * head_dim, n_out]
    head_dim: int | None = None
    # gated attention (arXiv:2505.06708, the SDPA-output form): the context
    # is multiplied elementwise by sigmoid(x Wgate), x the layer's input,
    # before Wo
    output_gate: bool = False
    # rotary positions on q and k, HF's `rotate_half` pairing (channel j
    # with j + head_dim / 2), angles, cos and sin in float32 (None: the
    # layer adds no positions)
    rope_theta: float | None = None
    # YaRN (arXiv:2309.00071) as `transformers` computes it, given as data:
    # {"factor", "original_max_position_embeddings", "beta_fast",
    # "beta_slow", "attention_factor"} — the frequencies blended between
    # theta's and theta's / factor over the ramp [low, high], cos and sin
    # times attention_factor (None: plain rotary)
    rope_yarn: dict | None = None
    # sliding window (causal layers): position i sees the `window` keys
    # i - window < j <= i, its own among them (None: the whole context)
    window: int | None = None


@register_layer_conf
@dataclass
class Mamba2Layer(_NoActivationConf, BaseRecurrentConf):
    """Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060): a selective
    state-space layer with a scalar decay a head, [b,t,f] -> [b,t,n_out].
    d_inner = n_heads * head_dim; one group (B and C are shared by all
    heads). Runtime: nn/layers/mamba.py — chunked SSD for sequences, a
    per-token recurrence on a fixed-size state for decode."""
    n_heads: int = 8
    head_dim: int = 64
    d_state: int = 128
    d_conv: int = 4
    chunk_size: int = 256
    eps: float = 1e-5
    use_pallas: bool = False


@register_layer_conf
@dataclass
class KimiDeltaAttentionLayer(_NoActivationConf, BaseRecurrentConf):
    """Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692 section 3):
    linear attention whose per-head state S [head_dim, head_dim] is updated
    by a delta rule under a per-channel decay, [b,t,f] -> [b,t,n_out]; q, k
    and v each pass a causal depthwise conv of `d_conv` taps and SiLU, q and
    k are L2-normalised a head, the output a per-head RMS norm times a
    sigmoid gate. Three published variants are options here:

    - the decay's log g: `gate_form` "bounded" is `gate_lower_bound` *
      sigmoid(exp(A_log) (f + dt_bias)) in [gate_lower_bound, 0]
      (`ling3_flash`: `kda_safe_gate`); "softplus" is Kimi Linear's own,
      -exp(A_log) * softplus(f + dt_bias), unbounded below (`solar_open2`);
    - `gate_rank`: None projects the decay gate's f and the output gate
      full rank (two [n_in, H D] blocks of `W_in`); a rank r makes each the
      product of [n_in, r] (side by side in `W_in`, which is then [n_in,
      3 H D + 2 r]) and its own [r, H D] (`W_fb`, `W_gb`): Kimi Linear's
      f_a/f_b and g_a/g_b;
    - `beta_scale`: beta = beta_scale * sigmoid(x Wb); 2 lets I - beta k
      k^T have a negative eigenvalue (`allow_neg_eigval`).

    Runtime: nn/layers/kda.py — the chunked form for sequences, a per-token
    step on the fixed-size state for decode (kernels/kda_step.py: any head
    count, walked in groups of 2 MB of state)."""
    n_heads: int = 4
    head_dim: int = 32
    d_conv: int = 4
    chunk_size: int = 64
    gate_lower_bound: float = -5.0
    eps: float = 1e-6
    use_pallas: bool = False
    gate_form: str = "bounded"          # | "softplus"
    gate_rank: int | None = None
    beta_scale: float = 1.0


@register_layer_conf
@dataclass
class LatentAttentionLayer(_NoActivationConf, BaseRecurrentConf):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), causal:
    keys and values are up-projections of ONE `kv_lora_rank`-wide normed
    latent a token, beside one `qk_rope_head_dim`-wide rotary key shared by
    all heads, so the decode cache holds kv_lora_rank + qk_rope_head_dim
    values a token whatever the head count. Rotary positions on adjacent
    pairs. Full-rank queries, or (`q_lora_rank`) queries compressed through
    a normed latent of their own; plain rotary, or YaRN's frequencies
    (`rope_yarn`) with the softmax scale times mscale^2; a sigmoid gate a
    head on the output, or none (`output_gate`). Runtime: nn/layers/mla.py —
    the plain form for sequences and prefill (blockwise once the scores
    would be large: kernels/mla_prefill.py), the absorbed form (scores
    against the cached row) for a decode step."""
    n_heads: int = 4
    kv_lora_rank: int = 64
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    rope_theta: float = 10000.0
    eps: float = 1e-6
    use_pallas: bool = False
    # compressed queries (DeepSeek-V2 section 2.1.2): q = RMSNorm(x Wq_a)
    # Wq_b, the latent `q_lora_rank` wide (None: one full-rank Wq)
    q_lora_rank: int | None = None
    # YaRN (arXiv:2309.00071) as DeepSeek-V3 computes it, given as data:
    # {"factor", "original_max_position_embeddings", "beta_fast",
    # "beta_slow", "mscale", "mscale_all_dim"} — the rotary frequencies
    # blended between theta's and theta's / factor over the ramp [low,
    # high]; cos and sin times m(mscale) / m(mscale_all_dim) and the scores
    # times m(mscale_all_dim)^2, m(s) = 0.1 s ln(factor) + 1 (None: plain)
    rope_yarn: dict | None = None
    # the head-wise sigmoid gate sigmoid(x Wgate) on the context before Wo
    output_gate: bool = True


@register_layer_conf
@dataclass
class GravesLSTM(BaseRecurrentConf):
    """LSTM with peephole connections (reference: nn/conf/layers/GravesLSTM.java,
    runtime nn/layers/recurrent/LSTMHelpers.java — the per-timestep Java gemm
    loop at :172-174 becomes one lax.scan whose body is a single fused gemm)."""
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"


@register_layer_conf
@dataclass
class LSTM(BaseRecurrentConf):
    """LSTM without peepholes (cuDNN-compatible formulation)."""
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"


@register_layer_conf
@dataclass
class GravesBidirectionalLSTM(BaseRecurrentConf):
    """Bidirectional peephole LSTM (reference:
    nn/conf/layers/GravesBidirectionalLSTM.java). Output = concat(fwd, bwd) so
    output size is 2*n_out? No — reference sums into n_out via separate
    directions each of size n_out and adds; here we follow the reference:
    forward and backward nets each produce n_out and outputs are summed."""
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"


@register_layer_conf
@dataclass
class ActivationLayer(BaseLayerConf):
    """Applies an activation only (reference: nn/conf/layers/ActivationLayer.java)."""

    def get_output_type(self, input_type):
        return input_type


@register_layer_conf
@dataclass
class DropoutLayer(_NoActivationConf):
    """Dropout as its own layer (reference: nn/conf/layers/DropoutLayer.java)."""

    def get_output_type(self, input_type):
        return input_type


@register_layer_conf
@dataclass
class MixtureOfExpertsLayer(FeedForwardLayerConf):
    """Routed mixture-of-experts feed-forward block — NEW capability beyond
    the reference (no MoE exists at v0.7.3; SURVEY.md §2.4 lists expert
    parallelism as absent upstream). Router: the `top_k` largest of
    `n_experts` logits (computed in float32), gates their softmax; out =
    sum over the chosen experts of gate * expert(x). Only the chosen (token,
    expert) pairs are computed: rows sorted by expert through a grouped
    product (kernels/expert_gmm.py), no capacity, no dropped pair.

    An expert is a 2-layer ReLU FFN with biases of width `hidden_mult *
    n_out` or, with `gated`, (silu(a) * b) W2 with (a, b) = split(x W1) of
    width `n_hidden`, no biases. The layer may be ONE CHIP'S SHARE of a
    deployment: it routes over all `n_experts`, holds experts
    `first_expert .. first_expert + experts_held - 1` (default: all) and
    returns their part of the sum, the gates as published (not renormalised
    over the held). Expert weights are expert-major [held, ...]: sharding
    axis 0 over a mesh axis is expert parallelism. Works on [b, f] and
    time-distributed [b, t, f].

    Three regimes of the grouped product meet here. Few large groups
    (`granite4_h_small`: 18 held experts at 4.4 rows a decode step), MANY
    SMALL GROUPS (`ling3_flash`: 64 held of 512 under group-limited sigmoid
    routing, 2 rows each at the mean of a 128-slot step): every non-empty
    group still costs one whole row tile (16 rows of bfloat16), so the
    grouped operand is mostly padding there: 6.8 rows computed a pair
    against 3.6 (kernels/expert_gmm.py `row_tile` has the count); and
    between them `solar_open2`: 40 held of 320 in one group, 4.8 rows each
    at the mean of a 192-slot step, experts of 31.5 MB that under a
    balanced router all but always get a row (99 % touched a step; at the
    benchmark's seeded weights the loads are uneven and 93-95 % are,
    counted from the reference's router: PERF.md section 5)."""
    n_experts: int = 4
    hidden_mult: int = 2
    top_k: int = 2
    gated: bool = False
    n_hidden: int | None = None     # default: hidden_mult * n_out
    experts_held: int | None = None
    first_expert: int = 0
    use_pallas: bool = False        # the gated form's grouped-product kernel
    # "softmax": gates are the softmax over the chosen logits. "sigmoid"
    # (DeepSeek-V3 `noaux_tc`): scores s = sigmoid(logits); the choice is by
    # s + b with b a selection-only bias leaf (`expert_bias`); a group's
    # score is the sum of its two largest s + b, the `topk_groups` best of
    # `n_groups` equal groups stay, `top_k` experts are taken inside them;
    # gates = routed_scaling * s / sum of the chosen s (no b in the gates)
    score_function: str = "softmax"
    n_groups: int = 1
    topk_groups: int | None = None  # default: all groups
    routed_scaling: float = 1.0

    def get_output_type(self, input_type):
        if isinstance(input_type, RecurrentInputType):
            return InputType.recurrent(self.n_out)
        return InputType.feed_forward(self.n_out)


@register_layer_conf
@dataclass
class GlobalPoolingLayer(_NoActivationConf):
    """Pool over time (rnn) or space (cnn) to fixed-size vectors
    (reference: nn/conf/layers/GlobalPoolingLayer.java, runtime
    nn/layers/pooling/GlobalPoolingLayer.java). Mask-aware."""
    pooling_type: str = "max"  # max | avg | sum | pnorm
    pnorm: int = 2
    collapse_dimensions: bool = True

    def get_output_type(self, input_type):
        if isinstance(input_type, RecurrentInputType):
            return InputType.feed_forward(input_type.size)
        if isinstance(input_type, ConvolutionalInputType):
            return InputType.feed_forward(input_type.channels)
        return input_type


@register_layer_conf
@dataclass
class ZeroPaddingLayer(_NoActivationConf):
    """Spatial zero padding (reference: nn/conf/layers/ZeroPaddingLayer.java)."""
    pad_top: int = 0
    pad_bottom: int = 0
    pad_left: int = 0
    pad_right: int = 0

    def get_output_type(self, input_type):
        return InputType.convolutional(input_type.height + self.pad_top + self.pad_bottom,
                                       input_type.width + self.pad_left + self.pad_right,
                                       input_type.channels)


@register_layer_conf
@dataclass
class AutoEncoder(FeedForwardLayerConf):
    """Denoising autoencoder (reference: nn/conf/layers/AutoEncoder.java,
    runtime nn/layers/feedforward/autoencoder/AutoEncoder.java).
    Pretrain layer: reconstruction via tied decoder params."""
    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss: str = "MSE"


@register_layer_conf
@dataclass
class RBM(FeedForwardLayerConf):
    """Restricted Boltzmann machine trained by contrastive divergence
    (reference: nn/conf/layers/RBM.java, runtime
    nn/layers/feedforward/rbm/RBM.java)."""
    visible_unit: str = "binary"   # binary | gaussian
    hidden_unit: str = "binary"    # binary | rectified | gaussian | softmax
    k: int = 1
    sparsity: float = 0.0
    loss: str = "MSE"


@register_layer_conf
@dataclass
class VariationalAutoencoder(FeedForwardLayerConf):
    """VAE (reference: nn/conf/layers/variational/VariationalAutoencoder.java,
    runtime nn/layers/variational/VariationalAutoencoder.java, 1063 LoC).
    n_out = latent size. Supervised use: forward = encoder mean (matches the
    reference where the VAE acts as a feedforward layer outputting z-mean)."""
    encoder_layer_sizes: tuple = (100,)
    decoder_layer_sizes: tuple = (100,)
    reconstruction_distribution: str = "gaussian"  # gaussian | bernoulli
    pzx_activation: str = "identity"
    num_samples: int = 1


# ---------------------------------------------------------------------------


def conv_output_size(h, w, kernel, stride, padding, mode="truncate", dilation=(1, 1)):
    kh = kernel[0] + (kernel[0] - 1) * (dilation[0] - 1)
    kw = kernel[1] + (kernel[1] - 1) * (dilation[1] - 1)
    if mode == "same":
        return ((h + stride[0] - 1) // stride[0], (w + stride[1] - 1) // stride[1])
    oh = (h + 2 * padding[0] - kh) // stride[0] + 1
    ow = (w + 2 * padding[1] - kw) // stride[1] + 1
    if mode == "strict" and ((h + 2 * padding[0] - kh) % stride[0] != 0 or
                             (w + 2 * padding[1] - kw) % stride[1] != 0):
        raise ValueError("ConvolutionMode.Strict: input size does not tile exactly "
                         f"(h={h}, w={w}, kernel={kernel}, stride={stride}, padding={padding})")
    return oh, ow
