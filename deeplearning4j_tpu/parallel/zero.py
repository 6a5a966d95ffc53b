"""ZeRO-1: updater state and the parameter update sharded over the data axis.

PAPERS.md, "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arXiv 2004.13336), applied to this stack (ROADMAP item 4): in plain
data-parallel SPMD every replica holds the FULL optimizer state
(momentum/adam moments — for Adam, 2x the parameter bytes) and redundantly
computes the identical full parameter update. Those redundant state bytes
are device memory every replica holds, and reads and writes every step, for
nothing: the largest pool a data-parallel run can give back.

The transform here:
  reduce-scatter(grads) -> per-shard optax update (1/N of the state resident
  per device) -> all-gather the updates back into the replicated params.

Mechanically, `ZeroUpdater.wrap` turns the model's per-layer optax
transforms into a ZeRO-1 `GradientTransformation`: each included layer's
params/grads are flattened per-param to 1-D, zero-padded to a multiple of
the shard count (uneven sizes — a [3] bias over 8 shards — just pad), and
`with_sharding_constraint`-ed to `P(axis)`; the inner (elementwise) optax
transform then runs on 1/N-sized shards and its state LIVES sharded between
steps, while the returned updates are unflattened under a replicated
constraint (GSPMD inserts the all-gather). Because the result is still an
optax `GradientTransformation` driven through `model._tx`, every train path
— the std jitted step, the scanned multistep executable, both TBPTT paths,
`ShardedTrainer`/`ParallelWrapper` — picks it up without touching step code,
and donation keeps aliasing (state leaves keep identical shapes/dtypes
across the step).

Layer inclusion follows the trainer's first-match `ShardingRules`: a layer
whose params are replicated under the rules (the data-parallel default)
zero-shards; a layer carrying a tensor-parallel spec keeps its ordinary
per-layer update (its moments already shard over the model axis).

Checkpoints stay topology-independent: `to_canonical`/`from_canonical`
convert between the sharded flat layout and the canonical per-param layout
the serializers store, so a run checkpointed at N=8 resumes at N=4 (or
unsharded) bit-for-bit — the resharding-on-replica-count-change contract.

Low-bit moments (ROADMAP item 3, the bytes diet): `moment_dtype="bf16"|"q8"`
stores the flat moment shards through nn.quant.MomentCodec — bf16 halves
them, 8-bit block-wise absmax cuts them ~3.9x (codes + one pow2 scale per
128-element block, both sharded over the axis). The codec rides INSIDE this
layout: the stored state leaves keep fixed shapes/dtypes across steps (the
traced update decodes, runs the layer's own optax transform in f32, and
re-encodes), so donation still aliases and no train path retraces. The
canonical checkpoint layout is UNCHANGED — to_canonical decodes to the
same per-param f32 state every serializer already stores, from_canonical
re-encodes for the target updater — and because the codec's round-trip is
exact-idempotent (pow2 scales), conversion chains (8 -> 4 -> 8, elastic
shrink/grow) replay the codes bit-for-bit instead of compounding error.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from .sharding import DATA_AXIS, _param_paths


def _pad_len(size, n):
    """size rounded up to a multiple of n (the padded flat length)."""
    return -(-int(size) // n) * n


def _dict_path(path):
    """Only the DictKey components of a tree path, joined — the param-key
    path of a moment leaf inside an optax state (namedtuple attrs and chain
    indices carry no param identity)."""
    return "/".join(str(k.key) for k in path
                    if isinstance(k, jax.tree_util.DictKey))


def _leaf_device_bytes(leaf):
    """Bytes `leaf` holds per device: sharded leaves count their shard
    shape, replicated/unplaced leaves count in full."""
    if not hasattr(leaf, "shape") or not hasattr(leaf, "dtype"):
        return 0
    sh = getattr(leaf, "sharding", None)
    shape = (sh.shard_shape(leaf.shape)
             if sh is not None and hasattr(sh, "shard_shape")
             else leaf.shape)
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize


def per_device_bytes(tree):
    """Bytes of `tree` RESIDENT PER DEVICE: sharded leaves count their shard
    shape, replicated/unplaced leaves count in full. This is the number the
    ZeRO claim is about — what each chip's HBM actually holds."""
    return int(sum(_leaf_device_bytes(leaf)
                   for leaf in jax.tree_util.tree_leaves(tree)))


def moment_bytes(tree):
    """Per-device bytes of the >= 1-D optimizer-state leaves — the moment
    pool the bytes diet targets (flat shards, q8 codes AND their per-block
    scales); scalar schedule counts are excluded. Reported as the
    `opt_moment_bytes_per_device` gauge."""
    return int(sum(_leaf_device_bytes(leaf)
                   for leaf in jax.tree_util.tree_leaves(tree)
                   if getattr(leaf, "ndim", 0) >= 1))


class ZeroUpdater:
    """ZeRO-1 sharded-update factory for one mesh axis.

    One instance per trainer; `wrap(transforms, params)` produces the
    GradientTransformation the model installs as `_tx`
    (`network.set_update_sharding`), and the canonical<->sharded state
    converters keep checkpoints replica-count-independent.
    """

    def __init__(self, mesh, axis=DATA_AXIS, rules=None, moment_dtype=None,
                 block=128):
        self.mesh = mesh
        self.axis = axis
        self.n_shards = int(mesh.shape[axis])
        self.rules = rules
        self.shard = NamedSharding(mesh, P(axis))
        self.replicated = NamedSharding(mesh, P())
        # low-bit moments: "bf16" / "q8" store the flat shards through the
        # MomentCodec (nn/quant.py); None/"f32" keeps full precision
        self.moment_dtype = ("f32" if moment_dtype in (None, "f32")
                             else str(moment_dtype))
        self.codec = None
        if self.moment_dtype != "f32":
            from ..nn.quant import MomentCodec
            self.codec = MomentCodec(self.moment_dtype,
                                     n_shards=self.n_shards, block=block)

    # ------------------------------------------------------- moment codec
    def _encode_state(self, st, layer_params):
        """Flat f32 moment leaves of one layer's optax state -> the stored
        low-bit representation (identity without a codec). Only leaves that
        ARE flat padded moments encode — matched by the same padded-length
        test to_canonical uses — so schedule counts/hyperparams stay put."""
        if self.codec is None:
            return st
        n = self.n_shards
        pmap = _param_paths(layer_params)

        def conv(path, leaf, pmap=pmap):
            w = pmap.get(_dict_path(path))
            if (w is not None and getattr(leaf, "ndim", 0) == 1
                    and hasattr(leaf, "dtype")
                    and jnp.issubdtype(leaf.dtype, jnp.floating)
                    and leaf.dtype != jnp.bfloat16
                    and leaf.shape[0] == _pad_len(w.size, n)):
                return self.codec.encode(leaf)
            return leaf
        return jax.tree_util.tree_map_with_path(conv, st)

    def _decode_state(self, st, layer_params):
        """Stored low-bit moments -> flat f32 (identity without a codec);
        traced at the top of the update so the optax math runs full
        precision on 1/N-sized shards."""
        if self.codec is None:
            return st
        n = self.n_shards
        pmap = _param_paths(layer_params)

        def conv(path, leaf, pmap=pmap):
            if self.codec.is_encoded(leaf):
                w = pmap.get(_dict_path(path))
                if w is not None:
                    return self.codec.decode(leaf, _pad_len(w.size, n))
            return leaf
        return jax.tree_util.tree_map_with_path(
            conv, st, is_leaf=self.codec.is_encoded)

    # ------------------------------------------------------------ inclusion
    def included(self, layer_key, layer_params):
        """A layer zero-shards iff every param is replicated under the
        trainer's ShardingRules (first match wins, like param placement);
        tensor-parallel layers keep their ordinary per-layer update."""
        if self.rules is None:
            return True
        for path, leaf in _param_paths(layer_params,
                                       f"{layer_key}/").items():
            spec = self.rules.spec_for(path, getattr(leaf, "ndim", 0))
            if tuple(spec) != ():
                return False
        return True

    def _inclusion(self, params):
        return {k: self.included(k, params[k]) for k in params}

    # ------------------------------------------------------------ transform
    def wrap(self, transforms, params):
        """Per-layer optax transforms -> one ZeRO-1 GradientTransformation.

        Inside the (traced) update: flatten-pad each included layer's grads
        and params to `P(axis)`-constrained 1-D shards, run the layer's own
        transform on the shards (identical math — every updater in
        nn/updaters.py is elementwise over its params, and each layer keeps
        its own schedule count), constrain the new state to stay sharded,
        and unflatten the updates under a replicated constraint so GSPMD
        all-gathers exactly once per layer."""
        incl = self._inclusion(params)
        n = self.n_shards
        shard, repl = self.shard, self.replicated
        constrain = jax.lax.with_sharding_constraint

        def flat(w):
            v = w.reshape((-1,))
            pad = _pad_len(v.shape[0], n) - v.shape[0]
            if pad:
                v = jnp.pad(v, (0, pad))
            return constrain(v, shard)

        def unflat(v, ref):
            return constrain(v[:ref.size].reshape(ref.shape), repl)

        def keep_sharded(st):
            return jax.tree_util.tree_map(
                lambda l: constrain(l, shard)
                if getattr(l, "ndim", 0) >= 1 else l, st)

        def init(ps):
            state = {}
            for k, sub in ps.items():
                if incl[k]:
                    state[k] = self._encode_state(
                        transforms[k].init(jax.tree_util.tree_map(flat, sub)),
                        sub)
                else:
                    state[k] = transforms[k].init(sub)
            return self.place_opt_state(state, ps)

        def update(grads, state, ps=None):
            if ps is None:
                raise ValueError(
                    "ZeRO-1 update requires params (flatten/unflatten "
                    "needs their shapes)")
            # iterate grads, not transforms: per_layer_transform's partial-
            # update contract (PipelineTrainer updates one stage's layers at
            # a time with single-layer dicts) must survive the ZeRO wrap
            ups, new_state = {}, {}
            for k, g in grads.items():
                tx = transforms[k]
                if not incl[k]:
                    ups[k], new_state[k] = tx.update(g, state[k], ps[k])
                    continue
                gf = jax.tree_util.tree_map(flat, g)
                pf = jax.tree_util.tree_map(flat, ps[k])
                # low-bit moments decode to f32 shards for the layer's own
                # optax math, then re-encode for storage — all inside the
                # traced step, so the STORED leaves keep fixed shapes/dtypes
                # (donation aliases; zero retraces)
                uf, st = tx.update(gf, self._decode_state(state[k], ps[k]),
                                   pf)
                new_state[k] = keep_sharded(self._encode_state(st, ps[k]))
                ups[k] = jax.tree_util.tree_map(unflat, uf, ps[k])
            return ups, new_state

        return optax.GradientTransformation(init, update)

    # ------------------------------------------------------------ placement
    def place_opt_state(self, opt_state, params, pshard=None, repl=None):
        """Eager device placement for a ZeRO opt_state: flat moment leaves
        of included layers go on the shard sharding, scalars replicate;
        excluded (tensor-parallel) layers mirror their param shardings via
        the ordinary opt_state_shardings path."""
        from .sharding import opt_state_shardings
        repl = repl if repl is not None else self.replicated
        incl = self._inclusion(params)
        out = {}
        for k, st in opt_state.items():
            if incl[k]:
                out[k] = jax.tree_util.tree_map(
                    lambda l: jax.device_put(
                        l, self.shard if getattr(l, "ndim", 0) >= 1
                        else repl) if hasattr(l, "shape") else l, st)
            else:
                sub_shard = {k: pshard[k]} if pshard is not None else \
                    {k: jax.tree_util.tree_map(lambda _: repl, params[k])}
                sh = opt_state_shardings({k: st}, {k: params[k]},
                                         sub_shard, repl)
                out[k] = jax.tree_util.tree_map(
                    lambda l, s: jax.device_put(l, s)
                    if hasattr(l, "shape") else l, {k: st}, sh)[k]
        return out

    # --------------------------------------------------------- checkpoints
    def to_canonical(self, opt_state, params):
        """Sharded flat layout -> the canonical per-param layout every
        serializer stores (identical treedef to the unsharded
        per_layer_transform state, so plain restores and replica-count
        changes both just work). Gathers the moments — checkpoint-time
        only."""
        incl = self._inclusion(params)
        n = self.n_shards
        out = {}
        for k, st in opt_state.items():
            if not incl[k]:
                out[k] = st
                continue
            pmap = _param_paths(params[k])

            def conv(path, leaf, pmap=pmap):
                w = pmap.get(_dict_path(path))
                if w is None:
                    return leaf
                if self.codec is not None and self.codec.is_encoded(leaf):
                    v = self.codec.decode(leaf, _pad_len(w.size, n))
                    return v[:w.size].reshape(w.shape)
                if (getattr(leaf, "ndim", 0) == 1
                        and leaf.shape[0] == _pad_len(w.size, n)):
                    return jnp.asarray(leaf)[:w.size].reshape(w.shape)
                return leaf
            out[k] = jax.tree_util.tree_map_with_path(
                conv, st,
                is_leaf=self.codec.is_encoded if self.codec else None)
        return out

    def from_canonical(self, opt_state, params):
        """Canonical per-param layout -> sharded flat layout for THIS mesh
        (the resume half: a checkpoint written at any replica count — or
        never sharded at all — re-shards for the current axis size)."""
        incl = self._inclusion(params)
        n = self.n_shards
        out = {}
        for k, st in opt_state.items():
            if not incl[k]:
                out[k] = st
                continue
            pmap = _param_paths(params[k])

            def conv(path, leaf, pmap=pmap):
                w = pmap.get(_dict_path(path))
                if (w is not None and hasattr(leaf, "shape")
                        and tuple(leaf.shape) == tuple(w.shape)):
                    v = jnp.asarray(leaf).reshape((-1,))
                    pad = _pad_len(v.shape[0], n) - v.shape[0]
                    if pad:
                        v = jnp.pad(v, (0, pad))
                    if self.codec is not None and \
                            jnp.issubdtype(v.dtype, jnp.floating):
                        # device_put over the encoded pytree: codes AND
                        # per-block scales both shard over the axis
                        return jax.device_put(
                            self.codec.encode(jnp.asarray(v, jnp.float32)),
                            self.shard)
                    return jax.device_put(v, self.shard)
                return leaf
            out[k] = jax.tree_util.tree_map_with_path(conv, st)
        return out
