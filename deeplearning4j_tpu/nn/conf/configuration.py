"""NeuralNetConfiguration builder DSL -> MultiLayerConfiguration.

Capability parity with reference nn/conf/NeuralNetConfiguration.java (Builder at
:484), nn/conf/MultiLayerConfiguration.java (setInputType at :412 drives
automatic preprocessor insertion + nIn inference). JSON round-trip of configs is
the serialization contract (reference stores `configuration.json` inside model
zips, util/ModelSerializer.java:94); unlike the reference's Jackson classpath
scan (registerSubtypes :376), subtypes live in an explicit registry.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field

from . import layers as L
from .inputs import (InputType, FeedForwardInputType, RecurrentInputType,
                     ConvolutionalInputType, ConvolutionalFlatInputType)
from .preprocessors import (CnnToFeedForwardPreProcessor, CnnToRnnPreProcessor,
                            FeedForwardToCnnPreProcessor, FeedForwardToRnnPreProcessor,
                            RnnToCnnPreProcessor, RnnToFeedForwardPreProcessor,
                            preprocessor_from_dict)
from ..updaters import Sgd, updater_from_dict


class BackpropType:
    STANDARD = "standard"
    TRUNCATED_BPTT = "truncated_bptt"


class OptimizationAlgorithm:
    STOCHASTIC_GRADIENT_DESCENT = "sgd"
    LINE_GRADIENT_DESCENT = "line_gradient_descent"
    CONJUGATE_GRADIENT = "conjugate_gradient"
    LBFGS = "lbfgs"


def expected_input_kind(conf):
    """Which InputType family a layer consumes: 'ff' | 'cnn' | 'recurrent' | 'any'."""
    if isinstance(conf, (L.ConvolutionLayer, L.SubsamplingLayer, L.ZeroPaddingLayer,
                         L.LocalResponseNormalization)):
        return "cnn"
    if isinstance(conf, (L.BaseRecurrentConf, L.RnnOutputLayer)):
        # GravesLSTM/LSTM/GravesBidirectionalLSTM/SelfAttentionLayer all
        # consume [b, t, f]
        return "recurrent"
    if isinstance(conf, (L.ActivationLayer, L.DropoutLayer, L.LossLayer,
                         L.GlobalPoolingLayer, L.BatchNormalization,
                         L.LayerNormalization, L.RMSNormalization)):
        return "any"
    if type(conf) in (L.DenseLayer, L.GatedDenseLayer,
                      L.MixtureOfExpertsLayer):
        # Dense is time-distributed on [b, t, f] (no RnnToFeedForward needed)
        # and self-flattens rank-4 CNN input; only cnn_flat still reshapes
        return "any"
    return "ff"


def default_preprocessor(prev_type, conf):
    """Auto preprocessor between layer families (reference:
    InputType-driven insertion in MultiLayerConfiguration.Builder.setInputType +
    per-InputType getPreProcessorForInputType)."""
    want = expected_input_kind(conf)
    kind = prev_type.kind
    if want == "any" or want == kind or (want == "ff" and kind == "ff"):
        if kind == "cnn_flat" and want == "cnn":
            return FeedForwardToCnnPreProcessor(prev_type.height, prev_type.width, prev_type.channels)
        return None
    if kind in ("cnn",):
        if want == "ff":
            return CnnToFeedForwardPreProcessor(prev_type.height, prev_type.width, prev_type.channels)
        if want == "recurrent":
            return CnnToRnnPreProcessor(prev_type.height, prev_type.width, prev_type.channels)
    if kind == "cnn_flat":
        if want == "cnn":
            return FeedForwardToCnnPreProcessor(prev_type.height, prev_type.width, prev_type.channels)
        if want == "ff":
            return None
        if want == "recurrent":
            return FeedForwardToRnnPreProcessor()
    if kind == "ff":
        if want == "cnn":
            raise ValueError("Cannot infer CNN dims from feed-forward input; "
                             "use InputType.convolutional_flat or an explicit "
                             "FeedForwardToCnnPreProcessor")
        if want == "recurrent":
            return FeedForwardToRnnPreProcessor()
    if kind == "recurrent":
        if want == "ff":
            return RnnToFeedForwardPreProcessor()
        if want == "cnn":
            raise ValueError("RnnToCnn requires explicit dims; add RnnToCnnPreProcessor manually")
    return None


def type_after_preprocessor(prev_type, pre):
    return pre.output_type(prev_type) if pre is not None else (
        InputType.feed_forward(prev_type.flat_size())
        if prev_type.kind == "cnn_flat" else prev_type)


@dataclass
class MultiLayerConfiguration:
    layers: list = field(default_factory=list)
    input_preprocessors: dict = field(default_factory=dict)
    input_type: object = None
    backprop_type: str = BackpropType.STANDARD
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    seed: int = 12345
    dtype: str = "float32"
    # compute (activation/matmul) dtype for mixed precision; None = same as
    # dtype. "bfloat16" keeps f32 master params + BN stats + loss while the
    # MXU-bound forward/backward runs in bf16 (TPU-native mixed precision —
    # the reference's analog is the fp16 cuDNN bypass, ConvolutionLayer.java:158)
    compute_dtype: object = None
    # rematerialization (gradient checkpointing): recompute activations in
    # the backward instead of storing them (jax.checkpoint over the
    # forward; modes in nn/remat.py). None = off; "convs_and_dots" saves
    # conv+matmul outputs and recomputes the elementwise/BN chains (the
    # recommended memory dial: ResNet-50 measured −24% temp for −22%
    # throughput, PERF.md §3); "dots" saves matmul outputs only (convs
    # recompute too); "dots_no_batch" the jax variant thereof; "full"
    # saves only inputs. The reference has no analog (its workspace memory
    # manager reuses buffers but never recomputes).
    remat: object = None
    optimization_algo: str = OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT
    max_num_line_search_iterations: int = 5
    pretrain: bool = False
    backprop: bool = True

    # ---- serde (the checkpoint `configuration.json` contract) -------------
    def to_dict(self):
        return {
            "format": "deeplearning4j-tpu/MultiLayerConfiguration",
            "version": 1,
            "layers": [l.to_dict() for l in self.layers],
            "input_preprocessors": {str(k): v.to_dict() for k, v in self.input_preprocessors.items()},
            "input_type": self.input_type.to_dict() if self.input_type else None,
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "seed": self.seed,
            "dtype": self.dtype,
            "compute_dtype": self.compute_dtype,
            "remat": self.remat,
            "optimization_algo": self.optimization_algo,
            "max_num_line_search_iterations": self.max_num_line_search_iterations,
            "pretrain": self.pretrain,
            "backprop": self.backprop,
        }

    def to_json(self):
        return json.dumps(self.to_dict(), indent=2)

    @staticmethod
    def from_dict(d):
        conf = MultiLayerConfiguration()
        conf.layers = [L.layer_conf_from_dict(ld) for ld in d["layers"]]
        conf.input_preprocessors = {int(k): preprocessor_from_dict(v)
                                    for k, v in d.get("input_preprocessors", {}).items()}
        it = d.get("input_type")
        conf.input_type = InputType.from_dict(it) if it else None
        for k in ("backprop_type", "tbptt_fwd_length", "tbptt_back_length", "seed",
                  "dtype", "compute_dtype", "remat", "optimization_algo",
                  "max_num_line_search_iterations", "pretrain", "backprop"):
            if k in d:
                setattr(conf, k, d[k])
        return conf

    @staticmethod
    def from_json(s):
        return MultiLayerConfiguration.from_dict(json.loads(s))


class ListBuilder:
    """The `.list()` stage of the DSL (reference:
    NeuralNetConfiguration.ListBuilder)."""

    def __init__(self, global_conf):
        self._global = global_conf
        self._layers = []
        self._preprocessors = {}
        self._input_type = None
        self._backprop_type = BackpropType.STANDARD
        self._tbptt_fwd = 20
        self._tbptt_back = 20
        self._pretrain = False
        self._backprop = True

    def layer(self, index_or_conf, conf=None):
        """Accepts .layer(conf) or .layer(i, conf) like the reference."""
        if conf is None:
            self._layers.append(index_or_conf)
        else:
            idx = int(index_or_conf)
            while len(self._layers) <= idx:
                self._layers.append(None)
            self._layers[idx] = conf
        return self

    def input_preprocessor(self, index, pre):
        self._preprocessors[int(index)] = pre
        return self

    def set_input_type(self, input_type):
        self._input_type = input_type
        return self

    input_type = set_input_type

    def backprop_type(self, bptype):
        self._backprop_type = bptype
        return self

    def tbptt_fwd_length(self, n):
        self._tbptt_fwd = int(n)
        return self

    def tbptt_back_length(self, n):
        self._tbptt_back = int(n)
        return self

    def pretrain(self, flag):
        self._pretrain = bool(flag)
        return self

    def backprop(self, flag):
        self._backprop = bool(flag)
        return self

    def build(self):
        g = self._global
        conf = MultiLayerConfiguration(
            layers=list(self._layers),
            input_preprocessors=dict(self._preprocessors),
            input_type=self._input_type,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            seed=g.get("seed", 12345),
            dtype=g.get("dtype", "float32"),
            compute_dtype=g.get("compute_dtype"),
            remat=g.get("remat"),
            optimization_algo=g.get("optimization_algo",
                                    OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT),
            max_num_line_search_iterations=g.get("max_num_line_search_iterations", 5),
            pretrain=self._pretrain,
            backprop=self._backprop,
        )
        for i, lc in enumerate(conf.layers):
            if lc is None:
                raise ValueError(f"Layer {i} was never set")
            lc.apply_global_defaults(g)
            if lc.updater is None:
                lc.updater = g.get("updater") or Sgd(learning_rate=g.get("learning_rate", 0.1))
        # shape inference + auto preprocessors
        cur = conf.input_type
        if cur is not None:
            for i, lc in enumerate(conf.layers):
                pre = conf.input_preprocessors.get(i)
                if pre is None:
                    pre = default_preprocessor(cur, lc)
                    if pre is not None:
                        conf.input_preprocessors[i] = pre
                cur = type_after_preprocessor(cur, pre)
                lc.set_n_in(cur)
                cur = lc.get_output_type(cur)
        return conf


class NeuralNetConfigurationBuilder:
    """Global-hyperparameter stage of the DSL (reference: Builder :484)."""

    def __init__(self):
        self._g = {}

    def seed(self, s):
        self._g["seed"] = int(s)
        return self

    def activation(self, a):
        self._g["activation"] = a
        return self

    def weight_init(self, w):
        self._g["weight_init"] = w
        return self

    def dist(self, d):
        self._g["dist"] = d
        self._g["weight_init"] = "distribution"
        return self

    def bias_init(self, b):
        self._g["bias_init"] = float(b)
        return self

    def l1(self, v):
        self._g["l1"] = float(v)
        return self

    def l2(self, v):
        self._g["l2"] = float(v)
        return self

    def l1_bias(self, v):
        self._g["l1_bias"] = float(v)
        return self

    def l2_bias(self, v):
        self._g["l2_bias"] = float(v)
        return self

    def dropout(self, v):
        self._g["dropout"] = float(v)
        return self

    def learning_rate(self, v):
        self._g["learning_rate"] = float(v)
        if "updater" in self._g and self._g["updater"] is not None:
            self._g["updater"].learning_rate = float(v)
        return self

    def updater(self, u):
        if "learning_rate" in self._g and u is not None:
            # .learning_rate() set before .updater(): honor it unless the
            # updater carries an explicit non-default lr
            pass
        self._g["updater"] = u
        return self

    def optimization_algo(self, algo):
        self._g["optimization_algo"] = algo
        return self

    def max_num_line_search_iterations(self, n):
        self._g["max_num_line_search_iterations"] = int(n)
        return self

    def gradient_normalization(self, mode, threshold=1.0):
        self._g["gradient_normalization"] = mode
        self._g["gradient_normalization_threshold"] = float(threshold)
        return self

    def dtype(self, dt):
        self._g["dtype"] = str(dt)
        return self

    def remat(self, mode):
        """Rematerialization: None / "convs_and_dots" (recommended memory
        dial) / "dots" / "dots_no_batch" / "full" — see
        MultiLayerConfiguration.remat and nn/remat.py."""
        self._g["remat"] = mode
        return self

    def compute_dtype(self, dt):
        """Mixed precision: run forward/backward math in `dt` (e.g. "bfloat16")
        while parameters, optimizer state, BatchNorm statistics, and the loss
        stay in `dtype`."""
        self._g["compute_dtype"] = None if dt is None else str(dt)
        return self

    def regularization(self, flag):
        # reference has a use-regularization toggle; here l1/l2=0 mean off.
        return self

    def mini_batch(self, flag):
        return self

    def list(self):
        return ListBuilder(dict(self._g))

    def graph_builder(self):
        from .graph_configuration import GraphBuilder
        return GraphBuilder(dict(self._g))


class NeuralNetConfiguration:
    @staticmethod
    def builder():
        return NeuralNetConfigurationBuilder()
