"""ComputationGraph configuration: GraphBuilder DSL + graph vertices.

Capability parity with reference nn/conf/ComputationGraphConfiguration.java
(GraphBuilder :406, addLayer :517, addInputs :553) and the vertex configs in
nn/conf/graph/: ElementWiseVertex, MergeVertex, SubsetVertex, StackVertex,
UnstackVertex, ScaleVertex, L2NormalizeVertex, L2Vertex, PreprocessorVertex,
LayerVertex, plus rnn/{LastTimeStepVertex, DuplicateToTimeSeriesVertex}.

Vertices are pure functions over lists of input arrays — they trace into the
same XLA computation as the layers.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field

import jax.numpy as jnp

from . import layers as L
from .inputs import InputType
from .configuration import (BackpropType, OptimizationAlgorithm, default_preprocessor,
                            type_after_preprocessor)
from .preprocessors import preprocessor_from_dict
from ..updaters import Sgd

_VERTEX_REGISTRY: dict = {}


def register_vertex(cls):
    _VERTEX_REGISTRY[cls.__name__] = cls
    return cls


def vertex_from_dict(d):
    d = dict(d)
    cls = _VERTEX_REGISTRY[d.pop("type")]
    return cls(**d)


class BaseVertexConf:
    """Non-layer DAG node (reference: nn/conf/graph/GraphVertex.java)."""

    #: a per-position map over its inputs: the decode engine may stream it
    positionwise = False

    def n_params(self):
        return 0

    def apply(self, inputs, masks=None):
        raise NotImplementedError

    def output_type(self, input_types):
        raise NotImplementedError

    def output_mask(self, masks):
        for m in (masks or []):
            if m is not None:
                return m
        return None

    def to_dict(self):
        d = dict(self.__dict__)
        d["type"] = type(self).__name__
        return d


@register_vertex
class ElementWiseVertex(BaseVertexConf):
    """Add/Subtract/Product/Average/Max of equal-shaped inputs
    (reference: nn/conf/graph/ElementWiseVertex.java)."""
    positionwise = True

    def __init__(self, op="add"):
        self.op = op

    def apply(self, inputs, masks=None):
        op = self.op
        if op == "add":
            out = inputs[0]
            for x in inputs[1:]:
                out = out + x
            return out
        if op == "subtract":
            return inputs[0] - inputs[1]
        if op == "product":
            out = inputs[0]
            for x in inputs[1:]:
                out = out * x
            return out
        if op == "average":
            return sum(inputs) / len(inputs)
        if op == "max":
            out = inputs[0]
            for x in inputs[1:]:
                out = jnp.maximum(out, x)
            return out
        raise ValueError(f"Unknown elementwise op {self.op}")

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
class MergeVertex(BaseVertexConf):
    """Concatenate along the feature/channel (last) axis
    (reference: nn/conf/graph/MergeVertex.java)."""
    positionwise = True

    def __init__(self):
        pass

    def apply(self, inputs, masks=None):
        return jnp.concatenate(inputs, axis=-1)

    def output_type(self, input_types):
        t0 = input_types[0]
        if t0.kind == "ff":
            return InputType.feed_forward(sum(t.size for t in input_types))
        if t0.kind == "recurrent":
            return InputType.recurrent(sum(t.size for t in input_types))
        if t0.kind == "cnn":
            return InputType.convolutional(t0.height, t0.width,
                                           sum(t.channels for t in input_types))
        return t0


@register_vertex
class SubsetVertex(BaseVertexConf):
    """Select feature range [from, to] inclusive (reference:
    nn/conf/graph/SubsetVertex.java)."""

    def __init__(self, from_index, to_index):
        self.from_index = int(from_index)
        self.to_index = int(to_index)

    def apply(self, inputs, masks=None):
        return inputs[0][..., self.from_index:self.to_index + 1]

    def output_type(self, input_types):
        n = self.to_index - self.from_index + 1
        t = input_types[0]
        if t.kind == "recurrent":
            return InputType.recurrent(n)
        return InputType.feed_forward(n)


@register_vertex
class StackVertex(BaseVertexConf):
    """Stack inputs along the batch axis (reference: nn/conf/graph/StackVertex.java)."""

    def __init__(self):
        pass

    def apply(self, inputs, masks=None):
        return jnp.concatenate(inputs, axis=0)

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
class UnstackVertex(BaseVertexConf):
    """Take the i-th of n equal batch slices (reference:
    nn/conf/graph/UnstackVertex.java)."""

    def __init__(self, from_index, stack_size):
        self.from_index = int(from_index)
        self.stack_size = int(stack_size)

    def apply(self, inputs, masks=None):
        x = inputs[0]
        n = x.shape[0] // self.stack_size
        return x[self.from_index * n:(self.from_index + 1) * n]

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
class ScaleVertex(BaseVertexConf):
    """Multiply by a fixed scalar (reference: nn/conf/graph/ScaleVertex.java):
    an embedding or residual multiplier in a decoder block."""
    positionwise = True

    def __init__(self, scale_factor=1.0):
        self.scale_factor = float(scale_factor)

    def apply(self, inputs, masks=None):
        return inputs[0] * self.scale_factor

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
class L2NormalizeVertex(BaseVertexConf):
    """x / ||x||_2 over the feature axis (reference:
    nn/conf/graph/L2NormalizeVertex.java)."""

    def __init__(self, eps=1e-8):
        self.eps = float(eps)

    def apply(self, inputs, masks=None):
        x = inputs[0]
        n = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + self.eps)
        return x / n

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
class L2Vertex(BaseVertexConf):
    """Pairwise L2 distance between two inputs -> [b, 1]
    (reference: nn/conf/graph/L2Vertex.java)."""

    def __init__(self, eps=1e-8):
        self.eps = float(eps)

    def apply(self, inputs, masks=None):
        a, b = inputs[0], inputs[1]
        d = jnp.sqrt(jnp.sum((a - b) ** 2, axis=-1, keepdims=True) + self.eps)
        return d

    def output_type(self, input_types):
        return InputType.feed_forward(1)


@register_vertex
class PreprocessorVertex(BaseVertexConf):
    """Wraps an InputPreProcessor as a standalone vertex (reference:
    nn/conf/graph/PreprocessorVertex.java)."""

    def __init__(self, preprocessor=None):
        self.preprocessor = preprocessor if not isinstance(preprocessor, dict) \
            else preprocessor_from_dict(preprocessor)

    def apply(self, inputs, masks=None):
        m = masks[0] if masks else None
        return self.preprocessor(inputs[0], m)

    def output_type(self, input_types):
        return self.preprocessor.output_type(input_types[0])

    def to_dict(self):
        return {"type": "PreprocessorVertex",
                "preprocessor": self.preprocessor.to_dict()}


@register_vertex
class LastTimeStepVertex(BaseVertexConf):
    """[b,t,f] -> [b,f] taking the last unmasked step (reference:
    nn/conf/graph/rnn/LastTimeStepVertex.java)."""

    def __init__(self, mask_input=None):
        self.mask_input = mask_input

    def apply(self, inputs, masks=None):
        x = inputs[0]
        m = masks[0] if masks and masks[0] is not None else None
        if m is None:
            return x[:, -1]
        idx = jnp.maximum(jnp.sum(m > 0, axis=1).astype(jnp.int32) - 1, 0)
        return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]

    def output_type(self, input_types):
        return InputType.feed_forward(input_types[0].size)

    def output_mask(self, masks):
        return None


@register_vertex
class DuplicateToTimeSeriesVertex(BaseVertexConf):
    """[b,f] -> [b,t,f] broadcast over the timesteps of a reference input
    (reference: nn/conf/graph/rnn/DuplicateToTimeSeriesVertex.java)."""

    def __init__(self, reference_input=None):
        self.reference_input = reference_input
        self._timesteps = None  # bound at runtime by the graph

    def apply(self, inputs, masks=None, timesteps=None):
        x = inputs[0]
        t = timesteps if timesteps is not None else self._timesteps
        return jnp.broadcast_to(x[:, None, :], (x.shape[0], t, x.shape[-1]))

    def output_type(self, input_types):
        return InputType.recurrent(input_types[0].flat_size())

    def to_dict(self):
        return {"type": "DuplicateToTimeSeriesVertex",
                "reference_input": self.reference_input}


# ---------------------------------------------------------------------------


@dataclass
class GraphVertexSpec:
    name: str
    kind: str                       # "input" | "layer" | "vertex"
    layer_conf: object = None       # for kind == "layer"
    vertex_conf: object = None      # for kind == "vertex"
    inputs: list = field(default_factory=list)
    preprocessor: object = None     # optional InputPreProcessor before a layer


@dataclass
class ComputationGraphConfiguration:
    vertices: dict = field(default_factory=dict)     # name -> GraphVertexSpec
    network_inputs: list = field(default_factory=list)
    network_outputs: list = field(default_factory=list)
    input_types: list = None
    backprop_type: str = BackpropType.STANDARD
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    seed: int = 12345
    dtype: str = "float32"
    compute_dtype: object = None   # mixed precision (see MultiLayerConfiguration)
    remat: object = None           # rematerialization (see MultiLayerConfiguration)
    optimization_algo: str = "sgd"
    max_num_line_search_iterations: int = 5
    topological_order: list = None

    def topo_sort(self):
        """Kahn's algorithm (reference: ComputationGraph.topologicalSortOrder :850)."""
        if self.topological_order is not None:
            return self.topological_order
        indeg = {n: len(s.inputs) for n, s in self.vertices.items()}
        out_edges = {n: [] for n in self.vertices}
        for n, s in self.vertices.items():
            for i in s.inputs:
                out_edges[i].append(n)
        queue = [n for n, d in indeg.items() if d == 0]
        order = []
        while queue:
            n = queue.pop(0)
            order.append(n)
            for m in out_edges[n]:
                indeg[m] -= 1
                if indeg[m] == 0:
                    queue.append(m)
        if len(order) != len(self.vertices):
            raise ValueError("Graph has a cycle")
        self.topological_order = order
        return order

    def to_dict(self):
        verts = {}
        for n, s in self.vertices.items():
            verts[n] = {
                "kind": s.kind,
                "inputs": s.inputs,
                "layer_conf": s.layer_conf.to_dict() if s.layer_conf else None,
                "vertex_conf": s.vertex_conf.to_dict() if s.vertex_conf else None,
                "preprocessor": s.preprocessor.to_dict() if s.preprocessor else None,
            }
        return {
            "format": "deeplearning4j-tpu/ComputationGraphConfiguration",
            "version": 1,
            "vertices": verts,
            "network_inputs": self.network_inputs,
            "network_outputs": self.network_outputs,
            "input_types": [t.to_dict() for t in self.input_types] if self.input_types else None,
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "seed": self.seed,
            "dtype": self.dtype,
            "compute_dtype": self.compute_dtype,
            "remat": self.remat,
            "optimization_algo": self.optimization_algo,
            "max_num_line_search_iterations": self.max_num_line_search_iterations,
        }

    def to_json(self):
        return json.dumps(self.to_dict(), indent=2)

    @staticmethod
    def from_dict(d):
        conf = ComputationGraphConfiguration()
        for n, sd in d["vertices"].items():
            conf.vertices[n] = GraphVertexSpec(
                name=n, kind=sd["kind"],
                layer_conf=L.layer_conf_from_dict(sd["layer_conf"]) if sd.get("layer_conf") else None,
                vertex_conf=vertex_from_dict(sd["vertex_conf"]) if sd.get("vertex_conf") else None,
                inputs=list(sd.get("inputs", [])),
                preprocessor=preprocessor_from_dict(sd["preprocessor"]) if sd.get("preprocessor") else None)
        conf.network_inputs = list(d["network_inputs"])
        conf.network_outputs = list(d["network_outputs"])
        if d.get("input_types"):
            conf.input_types = [InputType.from_dict(t) for t in d["input_types"]]
        for k in ("backprop_type", "tbptt_fwd_length", "tbptt_back_length", "seed",
                  "dtype", "compute_dtype", "remat", "optimization_algo",
                  "max_num_line_search_iterations"):
            if k in d:
                setattr(conf, k, d[k])
        return conf

    @staticmethod
    def from_json(s):
        return ComputationGraphConfiguration.from_dict(json.loads(s))


class GraphBuilder:
    """(reference: ComputationGraphConfiguration.GraphBuilder :406)"""

    def __init__(self, global_conf):
        self._global = global_conf
        self._conf = ComputationGraphConfiguration(
            seed=global_conf.get("seed", 12345),
            dtype=global_conf.get("dtype", "float32"),
            compute_dtype=global_conf.get("compute_dtype"),
            remat=global_conf.get("remat"),
            optimization_algo=global_conf.get("optimization_algo", "sgd"),
            max_num_line_search_iterations=global_conf.get(
                "max_num_line_search_iterations", 5))

    def add_inputs(self, *names):
        for n in names:
            self._conf.network_inputs.append(n)
            self._conf.vertices[n] = GraphVertexSpec(name=n, kind="input")
        return self

    def add_layer(self, name, layer_conf, *inputs, preprocessor=None):
        self._conf.vertices[name] = GraphVertexSpec(
            name=name, kind="layer", layer_conf=layer_conf, inputs=list(inputs),
            preprocessor=preprocessor)
        return self

    def add_vertex(self, name, vertex_conf, *inputs):
        self._conf.vertices[name] = GraphVertexSpec(
            name=name, kind="vertex", vertex_conf=vertex_conf, inputs=list(inputs))
        return self

    def set_outputs(self, *names):
        self._conf.network_outputs = list(names)
        return self

    def set_input_types(self, *types):
        self._conf.input_types = list(types)
        return self

    def backprop_type(self, t):
        self._conf.backprop_type = t
        return self

    def tbptt_fwd_length(self, n):
        self._conf.tbptt_fwd_length = int(n)
        return self

    def tbptt_back_length(self, n):
        self._conf.tbptt_back_length = int(n)
        return self

    def build(self):
        conf = self._conf
        g = self._global
        order = conf.topo_sort()
        # finalize layer confs + shape inference
        types = {}
        if conf.input_types:
            for name, t in zip(conf.network_inputs, conf.input_types):
                types[name] = t
        for name in order:
            spec = conf.vertices[name]
            if spec.kind == "input":
                continue
            in_types = [types.get(i) for i in spec.inputs]
            if spec.kind == "layer":
                lc = spec.layer_conf
                lc.apply_global_defaults(g)
                if lc.updater is None:
                    lc.updater = g.get("updater") or Sgd(learning_rate=g.get("learning_rate", 0.1))
                t = in_types[0]
                if t is not None:
                    if spec.preprocessor is None:
                        spec.preprocessor = default_preprocessor(t, lc)
                    t = type_after_preprocessor(t, spec.preprocessor)
                    lc.set_n_in(t)
                    types[name] = lc.get_output_type(t)
            else:
                if all(t is not None for t in in_types):
                    types[name] = spec.vertex_conf.output_type(in_types)
        return conf
