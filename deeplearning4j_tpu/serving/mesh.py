"""Mesh-sharded serving: one dispatch, all chips (ROADMAP item 1).

Every serving path used to bind one replica to one chip: the batcher's
coalesced pow2 batch dispatched to a single-device executable, N-1 chips
idle, and no model larger than one chip's HBM could serve at all. This
module applies the GSPMD recipe that already powers the ZeRO training path
(arXiv 2004.13336: express placement once, let XLA partition the
executable) to *inference*:

- **Replica-parallel dispatch** — `MeshDispatcher.output` places the
  coalesced batch with `NamedSharding(mesh, P("data", ...))` before calling
  the model's jitted `output()`, so ONE executable call answers the wave
  with the rows split across the mesh's data axis. The batcher is
  untouched: the dispatcher sits where the model object used to be (the
  registry wraps models through its adapter hook) and pads the batch up to
  a data-axis multiple, slicing the pad rows back off the result.
- **Tensor-parallel serving** — `place_params` resolves
  `ShardingRules` specs through `parallel.sharding.match_partition_rules`
  (the fmengine regex idiom) and `device_put`s every weight leaf under its
  spec, so `output()`, `feed_forward`, `score` and the decode executables
  all compile with the weights spanning chips. This composes with int8
  serving weights (nn/quant.py): the placed leaves ARE the narrow codes,
  so capacity multiplies — ~n_model x 3.7x over one chip's f32 footprint.
- **Sharded decode** — the DecodeEngine asks the model for its
  `mesh_context` and places the KV cache `[slots, capacity, H, Dh]` with
  the head axis over the mesh's model axis (`cache_sharding`), so
  /generate serves models whose cache would OOM one chip. The step/prefill
  executables pin the cache's out_shardings, preserving both donation and
  the zero-steady-state-recompile invariant (GL011).

Fleet semantics: a mesh group is ONE ServingServer and therefore ONE
`ReplicaHandle` in the FleetFrontend — one breaker, one health probe, one
canary-cohort member; eject-all-or-none. The server's /healthz carries
`mesh_chips` so the fleet/autoscaler planes can *display* chip counts
while all replica accounting (min/max/step policy, never-empty guard,
replicas_down) keeps counting groups.
"""
from __future__ import annotations

import threading

import numpy as np
import jax
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import (DATA_AXIS, MODEL_AXIS, ShardingRules,
                                 even_sharding, make_mesh,
                                 match_partition_rules, spec_shards)
from ..telemetry.trace import get_tracer
from ..util.time_source import monotonic_s


class MeshServingConfig:
    """Shape of the serving mesh. JSON-friendly (`from_spec`) so launchers
    can pass it through `server_opts` to subprocess replicas.

    rules: None (replicate weights — pure replica-parallel dispatch),
    "tensor_parallel" (ShardingRules.tensor_parallel_dense: W output dims
    over the model axis), or a ShardingRules instance."""

    def __init__(self, n_data=None, n_model=1, rules=None):
        self.n_data = n_data
        self.n_model = int(n_model)
        self.rules = rules

    @staticmethod
    def from_spec(spec):
        """None -> None; True -> all-devices data axis; int -> that many
        model-axis chips; dict -> explicit fields."""
        if spec is None:
            return None
        if isinstance(spec, MeshServingConfig):
            return spec
        if spec is True:
            return MeshServingConfig()
        if isinstance(spec, int):
            return MeshServingConfig(n_model=spec,
                                     rules="tensor_parallel" if spec > 1
                                     else None)
        if isinstance(spec, dict):
            return MeshServingConfig(n_data=spec.get("n_data"),
                                     n_model=spec.get("n_model", 1),
                                     rules=spec.get("rules"))
        raise TypeError(f"cannot build a mesh config from {spec!r}")

    def resolve_rules(self):
        if self.rules is None:
            return ShardingRules()           # replicate every leaf
        if isinstance(self.rules, ShardingRules):
            return self.rules
        name = str(self.rules)
        if name in ("tensor_parallel", "tensor_parallel_dense"):
            return ShardingRules.tensor_parallel_dense()
        if name in ("none", "replicated", "data_parallel"):
            return ShardingRules()
        raise ValueError(f"unknown sharding rules {self.rules!r}")

    def to_dict(self):
        rules = self.rules
        if isinstance(rules, ShardingRules):
            rules = "tensor_parallel"        # best JSON approximation
        return {"n_data": self.n_data, "n_model": self.n_model,
                "rules": rules}


class MeshContext:
    """One serving mesh shared by every wrapped model on a server: owns the
    Mesh (built by parallel.make_mesh — parallel/ owns mesh construction),
    the resolved ShardingRules, and the per-ndim batch shardings."""

    def __init__(self, config=None, devices=None, tracer=None):
        self.config = MeshServingConfig.from_spec(config) \
            or MeshServingConfig()
        devices = list(devices) if devices is not None else jax.devices()
        n_model = max(1, int(self.config.n_model))
        n_data = self.config.n_data
        if n_data is None:
            n_data = max(1, len(devices) // n_model)
        self.mesh = make_mesh(n_data=int(n_data), n_model=n_model,
                              devices=devices[:int(n_data) * n_model])
        self.rules = self.config.resolve_rules()
        self.tracer = tracer if tracer is not None else get_tracer()
        # live cost attribution (telemetry/cost.py): the owning server
        # attaches its ExecutableCostRegistry here so mesh-routed dispatch
        # wall time lands in the sampled dispatch_ms histogram
        self.cost_registry = None
        self.dispatches = 0                  # mesh-routed batch dispatches
        self._batch_shardings = {}           # ndim -> NamedSharding
        self._lock = threading.Lock()
        # ONE partitioned execution in flight per mesh: concurrent launches
        # from different host threads (the batcher's /predict dispatch and
        # the decode loop's step) interleave their collectives' rendezvous
        # participants and deadlock XLA's CPU runtime — and on real chips
        # they'd serialize anyway, since each wave already spans every
        # device. Both planes take this lock around the executable call.
        self.run_lock = threading.Lock()

    # ---- topology ----------------------------------------------------------
    @property
    def data_size(self):
        return int(self.mesh.shape[DATA_AXIS])

    @property
    def model_size(self):
        return int(self.mesh.shape[MODEL_AXIS])

    @property
    def chips(self):
        return int(np.prod(list(self.mesh.shape.values())))

    def describe(self):
        return {"chips": self.chips, "data": self.data_size,
                "model": self.model_size,
                "rules": self.config.to_dict()["rules"]}

    # ---- placement ---------------------------------------------------------
    def batch_sharding(self, ndim):
        """NamedSharding splitting the leading (batch) axis over the data
        axis; everything else replicated."""
        with self._lock:
            s = self._batch_shardings.get(ndim)
            if s is None:
                spec = P(*([DATA_AXIS] + [None] * (ndim - 1)))
                s = self._batch_shardings[ndim] = \
                    even_sharding(self.mesh, spec, (self.data_size,) * ndim)
        return s

    def param_shardings(self, params):
        """match_partition_rules specs -> NamedShardings, degrading any
        leaf whose partitioned dim doesn't divide its mesh axis to
        replicated (a head count of 6 on a model axis of 4 must replicate,
        not fail the deploy)."""
        specs = match_partition_rules(self.rules, params)
        return jax.tree_util.tree_map(
            lambda leaf, spec: even_sharding(self.mesh, spec, leaf.shape),
            params, specs)

    def place_params(self, model):
        """device_put the model's params (and states) under their resolved
        specs — int8 code leaves included, so TP capacity composes with the
        weight diet. Idempotent per params object."""
        shardings = self.param_shardings(model.params)
        model.params = jax.tree_util.tree_map(jax.device_put, model.params,
                                              shardings)
        if getattr(model, "states", None):
            model.states = jax.device_put(
                model.states, even_sharding(self.mesh, P(), ()))
        return model.params

    def cache_sharding(self, shape, model_axis=None):
        """Decode-cache leaf sharding: `model_axis`, the axis the layer that
        keeps the leaf declared (attention K/V: heads; recurrent carries:
        features), partitions over the model axis; None replicates. An
        uneven dim degrades to replicated (even_sharding)."""
        spec = P() if model_axis is None else P(
            *[MODEL_AXIS if i == model_axis else None
              for i in range(len(shape))])
        return even_sharding(self.mesh, spec, shape)

    # ---- wrapping ----------------------------------------------------------
    def wrap(self, model):
        """Model -> MeshDispatcher (identity for an already-wrapped model).
        The registry applies this through its adapter hook, so every
        registered/loaded version serves mesh-dispatched."""
        if getattr(model, "mesh_inner", None) is not None:
            return model
        return MeshDispatcher(model, self)


class MeshDispatcher:
    """Stands in for the model at the batcher/registry/engine seam: the
    batcher hands it the coalesced pow2 batch, it places rows across the
    mesh data axis and calls the wrapped model's jitted `output()` — one
    executable call, all chips. Everything else (`params`, `score`,
    `feed_forward`, `quantize_weights`, decode's `_dequant_params`, ...)
    delegates to the wrapped model, whose params this dispatcher keeps
    placed under the context's ShardingRules (re-placing when the params
    object changes, e.g. after an int8 quantize/dequantize)."""

    def __init__(self, model, context):
        self.mesh_inner = model
        self.mesh_context = context
        self._placed_params = None      # identity of the last placed tree
        self._place_lock = threading.Lock()

    def __getattr__(self, name):
        inner = self.__dict__.get("mesh_inner")
        if inner is None or name.startswith("__"):
            raise AttributeError(name)
        return getattr(inner, name)

    # ---- placement ---------------------------------------------------------
    def ensure_placed(self):
        """Place (or re-place) the wrapped model's params on the mesh. The
        identity check makes this free in steady state and catches every
        path that swaps the params object (init, quantize, dequantize)."""
        inner = self.mesh_inner
        if inner.params is None:
            inner.init()
        with self._place_lock:
            if self._placed_params is not inner.params:
                self.mesh_context.place_params(inner)
                self._placed_params = inner.params
        return self

    def param_shard_bytes(self):
        """(per_chip_bytes, total_bytes) of the placed params — the
        capacity claim as a measurement: a TP-placed model's per-chip
        footprint is what must fit HBM, not the global tree."""
        self.ensure_placed()
        total = per = 0
        for leaf in jax.tree_util.tree_leaves(self.mesh_inner.params):
            nbytes = int(leaf.size) * leaf.dtype.itemsize
            total += nbytes
            shards = spec_shards(self.mesh_context.mesh,
                                 getattr(leaf, "sharding").spec) \
                if hasattr(leaf, "sharding") else 1
            per += nbytes // max(1, shards)
        return per, total

    # ---- the mesh dispatch -------------------------------------------------
    def output(self, x, mask=None, **kw):
        """Replica-parallel dispatch: pad the coalesced batch up to a
        data-axis multiple (pow2 buckets stay pow2 — the zero-recompile
        bucket discipline is preserved, small buckets just share the
        data-sized executable), place rows over the data axis, run the ONE
        jitted forward, slice the pad rows back off."""
        ctx = self.mesh_context
        self.ensure_placed()
        x = np.asarray(x)
        rows = int(x.shape[0])
        pad = (-rows) % ctx.data_size
        if pad:
            x = np.concatenate(
                [x, np.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
            if mask is not None:
                mask = np.asarray(mask)
                mask = np.concatenate(
                    [mask, np.zeros((pad,) + mask.shape[1:], mask.dtype)],
                    axis=0)
        cr = ctx.cost_registry
        sampled = cr is not None and cr.dispatch_due("mesh_dispatch")
        t0 = monotonic_s() if sampled else 0.0
        # per-axis dispatch span: the chips answering this wave, by axis
        with ctx.tracer.span("mesh_dispatch", chips=ctx.chips,
                             axis_data=ctx.data_size,
                             axis_model=ctx.model_size,
                             rows=rows, padded_rows=rows + pad):
            xb = jax.device_put(x, ctx.batch_sharding(x.ndim))
            if mask is not None:
                mb = np.asarray(mask)
                kw["mask"] = jax.device_put(mb, ctx.batch_sharding(mb.ndim))
            # run_lock + block: one partitioned wave in flight per mesh
            # (see MeshContext.run_lock — concurrent launches deadlock the
            # CPU collectives, and on real chips they'd serialize anyway)
            # set_mesh: the Pallas kernels read the ambient mesh and run
            # per shard (kernels/flash_attention._per_shard) — GSPMD cannot
            # partition a Mosaic kernel
            with ctx.run_lock, jax.set_mesh(ctx.mesh):
                out = self.mesh_inner.output(xb, **kw)
                jax.block_until_ready(out)
        if sampled:
            cr.observe_dispatch("mesh_dispatch",
                                (monotonic_s() - t0) * 1000.0)
        ctx.dispatches += 1
        if pad:
            if isinstance(out, (list, tuple)):
                return type(out)(o[:rows] for o in out)
            return out[:rows]
        return out
