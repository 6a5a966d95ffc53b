"""ComputationGraph: arbitrary-DAG model with multi-input/multi-output.

Reference: nn/graph/ComputationGraph.java (2276 LoC; init :267,
topologicalSortOrder :850, fit :671/:740, calcBackpropGradients :1175,
rnnTimeStep :1789) and the vertex runtime nn/graph/vertex/GraphVertex.java.

TPU-first: vertices are pure functions evaluated in topological order inside
one traced computation; forward+backward+updater compile to a single XLA
program per step, exactly like MultiLayerNetwork.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import optax

from ..conf.graph_configuration import (ComputationGraphConfiguration,
                                        DuplicateToTimeSeriesVertex)
from ..conf.configuration import BackpropType
from ..layers.base import create_layer
from ..layers import (feedforward, convolution, recurrent, mamba, kda, mla, misc,  # noqa: F401
                      variational)
from ..multistep import MultiStepTrainable, _step_leaf
from ...telemetry.xla import timed_first_call
from ..updaters import apply_gradient_normalization
from ...optimize.listeners import resolve_listeners


class ComputationGraph(MultiStepTrainable):
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.order = conf.topo_sort()
        self.layers = {}
        for name in self.order:
            spec = conf.vertices[name]
            if spec.kind == "layer":
                self.layers[name] = create_layer(spec.layer_conf)
        self.params = None
        self.states = None
        self.opt_state = None
        self._tx = None
        self.listeners = []
        self.iteration_count = 0
        self.epoch_count = 0
        self._score_dev = float("nan")
        self._dtype = jnp.dtype(conf.dtype)
        self._rng = jax.random.PRNGKey(conf.seed)
        self._jit_cache = {}
        self._rnn_state = {}
        self._ingest = None         # device-side ingest fused into the step
        self._zero = None           # ZeRO-1 sharded update (parallel/zero.py)
        self._wq = None             # int8 serving weights (nn/quant.py)

    @property
    def score_value(self):
        """Most recent minibatch score; kept on device by the train step and
        synced to host lazily on first read (mirrors MultiLayerNetwork)."""
        s = self._score_dev
        if not isinstance(s, float):
            s = float(s)
            self._score_dev = s
        return s

    @score_value.setter
    def score_value(self, v):
        self._score_dev = v

    # ------------------------------------------------------------------ init
    def init(self, params=None):
        conf = self.conf
        rng = jax.random.PRNGKey(conf.seed)
        self.params, self.states = {}, {}
        types = {}
        if conf.input_types:
            for name, t in zip(conf.network_inputs, conf.input_types):
                types[name] = t
        for name in self.order:
            spec = conf.vertices[name]
            if spec.kind == "input":
                continue
            if spec.kind == "layer":
                rng, sub = jax.random.split(rng)
                t = types.get(spec.inputs[0])
                if t is not None and spec.preprocessor is not None:
                    t = spec.preprocessor.output_type(t)
                elif t is not None and t.kind == "cnn_flat":
                    from ..conf.inputs import InputType
                    t = InputType.feed_forward(t.flat_size())
                p, s, out_t = self.layers[name].init(sub, t, self._dtype)
                self.params[name] = p
                self.states[name] = s
                types[name] = out_t
            else:
                in_types = [types.get(i) for i in spec.inputs]
                if all(t is not None for t in in_types):
                    types[name] = spec.vertex_conf.output_type(in_types)
        if params is not None:
            self.params = jax.tree_util.tree_map(jnp.asarray, params)
        self._build_updater()
        return self

    def _build_updater(self, init_state=True):
        from ..updaters import layer_transform, per_layer_transform
        transforms = {name: layer_transform(self.conf.vertices[name].layer_conf)
                      for name in self.params}
        if self._zero is not None:
            self._tx = self._zero.wrap(transforms, self.params)
        else:
            self._tx = per_layer_transform(transforms)
        if init_state:
            self.opt_state = self._tx.init(self.params)

    # -------------------------------------------------------------- forward
    def _forward(self, params, states, inputs, *, train, rng, masks=None,
                 initial_carries=None):
        """inputs: list of arrays aligned with network_inputs. Returns
        (activations dict, new_states, out_masks dict, carries)."""
        conf = self.conf
        acts, out_masks = {}, {}
        new_states = dict(states)
        carries = {}
        in_masks = masks or [None] * len(conf.network_inputs)
        timesteps = None
        for name, x, m in zip(conf.network_inputs, inputs, in_masks):
            acts[name] = x
            out_masks[name] = m
            if hasattr(x, "ndim") and x.ndim == 3:
                timesteps = x.shape[1]
        for name in self.order:
            spec = conf.vertices[name]
            if spec.kind == "input":
                continue
            xs = [acts[i] for i in spec.inputs]
            ms = [out_masks.get(i) for i in spec.inputs]
            # the vertex's name on its operations, in the lowered text and
            # in a device trace (backward: transpose(jvp(<name>)))
            with jax.named_scope(name):
                if spec.kind == "layer":
                    x, m = xs[0], ms[0]
                    if rng is not None:
                        rng, pre_rng, sub = jax.random.split(rng, 3)
                    else:
                        pre_rng = sub = None
                    if spec.preprocessor is not None:
                        x = spec.preprocessor(x, m, rng=pre_rng)
                        m = spec.preprocessor.feed_forward_mask(m) if m is not None else None
                    kwargs = {}
                    if initial_carries is not None and name in initial_carries:
                        kwargs = {"initial_state": initial_carries[name], "return_state": True}
                    out = self.layers[name].forward(params[name], states[name], x,
                                                    train=train, rng=sub, mask=m, **kwargs)
                    if len(out) == 4:
                        y, s, m, fin = out
                        carries[name] = fin
                    else:
                        y, s, m = out
                    new_states[name] = s
                    acts[name] = y
                    out_masks[name] = m
                else:
                    vc = spec.vertex_conf
                    if isinstance(vc, DuplicateToTimeSeriesVertex):
                        ref = vc.reference_input
                        t = acts[ref].shape[1] if ref in acts and acts[ref].ndim == 3 else timesteps
                        acts[name] = vc.apply(xs, ms, timesteps=t)
                    else:
                        acts[name] = vc.apply(xs, ms)
                    out_masks[name] = vc.output_mask(ms)
        return acts, new_states, out_masks, carries

    # ------------------------------------------------------- mixed precision
    def _compute_dtype(self):
        cd = getattr(self.conf, "compute_dtype", None)
        if cd is None or jnp.dtype(cd) == self._dtype:
            return None
        return jnp.dtype(cd)

    def _cast_for_compute(self, params, inputs):
        """bf16 compute for all non-output layers; output layers keep the
        param dtype so their loss math runs in full precision (mirrors
        MultiLayerNetwork._cast_for_compute)."""
        cd = self._compute_dtype()
        if cd is None:
            return params, inputs
        outs = set(self.conf.network_outputs)
        # uint8 = image pixels (exact in bf16, rescaled on-chip); wider ints
        # (embedding ids) must not be cast — ids > 256 don't fit bf16
        cast = lambda a: a.astype(cd) \
            if hasattr(a, "dtype") and (jnp.issubdtype(a.dtype, jnp.floating)
                                        or a.dtype == jnp.uint8) else a
        params = {k: (v if k in outs else jax.tree_util.tree_map(cast, v))
                  for k, v in params.items()}
        inputs = [cast(x) for x in inputs]
        return params, inputs

    # ---------------------------------------------------------------- loss
    def _loss(self, params, states, inputs, labels, *, train, rng, masks=None,
              label_masks=None, initial_carries=None):
        conf = self.conf
        params, inputs = self._cast_for_compute(params, inputs)
        if rng is not None:
            rng, fwd_rng = jax.random.split(rng)
        else:
            fwd_rng = None
        # run everything except output layers' score; output layer forward is
        # replaced by its integrated loss on the features feeding it. Under
        # conf.remat the forward recomputes (policy-chosen) activations in
        # the backward instead of storing them (nn/remat.py) — training only
        def fwd_fn(p, s, xx, rr, mm, ic):
            return self._forward(p, s, xx, train=train, rng=rr, masks=mm,
                                 initial_carries=ic)
        from ..remat import maybe_checkpoint
        fwd_fn = maybe_checkpoint(
            fwd_fn, getattr(conf, "remat", None) if train else None)
        acts, new_states, out_masks, carries = fwd_fn(
            params, states, inputs, fwd_rng, masks, initial_carries)
        total = 0.0
        lm = label_masks or [None] * len(conf.network_outputs)
        for out_name, y, mlab in zip(conf.network_outputs, labels, lm):
            spec = conf.vertices[out_name]
            layer = self.layers[out_name]
            if not layer.is_output_layer():
                raise ValueError(f"Network output '{out_name}' is not an output layer")
            with jax.named_scope(out_name):        # as _forward names it
                feats = acts[spec.inputs[0]]
                if spec.preprocessor is not None:
                    if rng is not None:
                        rng, pre_rng = jax.random.split(rng)
                    else:
                        pre_rng = None
                    feats = spec.preprocessor(
                        feats, out_masks.get(spec.inputs[0]), rng=pre_rng)
                if self._compute_dtype() is not None:
                    # loss math in full precision
                    feats = feats.astype(self._dtype)
                mask = mlab if mlab is not None \
                    else out_masks.get(spec.inputs[0])
                if isinstance(layer, feedforward.CenterLossOutputLayerModule):
                    total = total + layer.score(
                        params[out_name], feats, y, mask, train, rng,
                        state=states[out_name])
                    new_states[out_name] = layer.update_centers(
                        states[out_name], feats, y)
                else:
                    total = total + layer.score(params[out_name], feats, y,
                                                mask, train, rng)
        total = total + self._reg_score(params)
        return total, (new_states, carries)

    def _reg_score(self, params):
        total = 0.0
        for name, p in params.items():
            lc = self.conf.vertices[name].layer_conf
            l1, l2 = lc.l1 or 0.0, lc.l2 or 0.0
            l1b, l2b = lc.l1_bias or 0.0, lc.l2_bias or 0.0
            if not (l1 or l2 or l1b or l2b):
                continue
            for k, v in p.items():
                is_w = not (k.endswith("b") or k in ("gamma", "beta", "centers"))
                if is_w:
                    if l1:
                        total = total + l1 * jnp.sum(jnp.abs(v))
                    if l2:
                        total = total + 0.5 * l2 * jnp.sum(v ** 2)
                else:
                    if l1b:
                        total = total + l1b * jnp.sum(jnp.abs(v))
                    if l2b:
                        total = total + 0.5 * l2b * jnp.sum(v ** 2)
        return total

    def _normalize_grads(self, grads):
        out = {}
        for name, g in grads.items():
            lc = self.conf.vertices[name].layer_conf
            if lc.gradient_normalization and g:
                g = apply_gradient_normalization(g, lc.gradient_normalization,
                                                 lc.gradient_normalization_threshold or 1.0)
            out[name] = g
        return out

    # ------------------------------------------------------- device ingest
    def set_ingest(self, ingest):
        """Fuse a device-side ingest transform into the jitted train step
        (mirrors MultiLayerNetwork.set_ingest): `apply_features` runs on the
        FIRST network input, `apply_labels` on the FIRST label — the
        single-input/single-output shape every ingest workload here has.
        Training paths only; output()/score() keep consuming preprocessed
        tensors. Clears the jit cache so executables re-trace with the
        ingest ops fused."""
        self._ingest = ingest
        self._jit_cache.clear()
        return self

    def _apply_ingest(self, inputs, labels):
        ing = self._ingest
        if ing is None:
            return inputs, labels
        inputs = [ing.apply_features(inputs[0])] + list(inputs[1:])
        out = []
        for i, l in enumerate(labels):
            y = ing.apply_labels(l) if i == 0 else l
            # restore the non-ingest _prep_batch cast for EVERY label head,
            # not just the ingested one
            if y.dtype != self._dtype:
                y = y.astype(self._dtype)
            out.append(y)
        return inputs, out

    # ---------------------------------------------------------------- train
    def _make_train_step(self, tbptt=False):
        tx = self._tx

        def train_step(params, opt_state, states, rng, inputs, labels, masks,
                       label_masks, carries):
            inputs, labels = self._apply_ingest(inputs, labels)

            def loss_fn(p):
                return self._loss(p, states, inputs, labels, train=True, rng=rng,
                                  masks=masks, label_masks=label_masks,
                                  initial_carries=carries if tbptt else None)
            (score, (new_states, out_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = self._normalize_grads(grads)
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, new_states, score, out_carries

        # tbptt donates the recurrent carries too (arg 8): out_carries
        # aliases the incoming h/c buffers across windows instead of fresh
        # [B, H] allocations (see MultiLayerNetwork._make_train_step); std
        # passes carries=None — zero leaves, donation is a no-op there
        donate = (0, 1, 2, 8) if tbptt else (0, 1, 2)
        return jax.jit(train_step, donate_argnums=donate)

    def _get_train_step(self, key="std"):
        """One cached jitted step per mode; jit itself retraces per input
        structure (mask presence etc.), so no structure-derived keys needed.
        timed_first_call routes the compile through the jit accounting and
        the cost registry (telemetry/cost.py) like the MLN train steps."""
        if key not in self._jit_cache:
            self._jit_cache[key] = timed_first_call(
                self._make_train_step(tbptt=(key == "tbptt")),
                f"graph_train_step:{key}")
        return self._jit_cache[key]

    def fit(self, data, labels=None, epochs=1, steps_per_execution=1,
            prefetch=None, ingest=None):
        """Accepts MultiDataSet / DataSet / iterator thereof / (x, y)
        (reference: fit(DataSetIterator) :671, fit(MultiDataSet) :740).

        steps_per_execution=K compiles K optimizer steps into ONE executable
        (lax.scan with donated carry, nn/multistep.py) — one host dispatch
        per K minibatches; listeners fire on a K-step cadence.

        prefetch=K wraps the source in an etl.DevicePrefetcher (K-deep
        device buffer: batch N+1's h2d DMA overlaps batch N's compute);
        ingest=DeviceIngest(...) fuses device-side decode/cast/one-hot into
        the compiled step (= set_ingest), so prefetch ships narrow raw bytes
        — mirrors MultiLayerNetwork.fit."""
        from ...datasets.dataset import DataSet, MultiDataSet
        from ...datasets.iterator.base import (as_iterator, DataSetIterator,
                                               ListDataSetIterator)
        if ingest is not None:
            self.set_ingest(ingest)
        if labels is not None:
            data = MultiDataSet(data, labels)
        if isinstance(data, (DataSet, MultiDataSet)):
            items = [data]
        elif isinstance(data, DataSetIterator):
            items = data
        elif isinstance(data, (list, tuple)):
            items = list(data)
        else:
            items = as_iterator(data)
        wrapped = None
        if prefetch:
            from ...etl.prefetch import DevicePrefetcher
            if isinstance(items, list):
                items = ListDataSetIterator(items)
            items = wrapped = DevicePrefetcher(items,
                                               queue_size=int(prefetch))
        K = max(1, int(steps_per_execution))
        try:
            for _ in range(epochs):
                for listener in self.listeners:
                    listener.on_epoch_start(self)
                if hasattr(items, "reset"):
                    items.reset()
                if K > 1:
                    self._fit_grouped(items, K)
                else:
                    for ds in items:
                        self.fit_batch(ds)
                for listener in self.listeners:
                    listener.on_epoch_end(self)
                self.epoch_count += 1
        except BaseException:
            if wrapped is not None:
                try:
                    wrapped.close()
                except Exception:
                    pass           # don't mask the primary training error
            raise
        if wrapped is not None:
            wrapped.close()        # stop the fit-owned prefetch thread
        return self

    def _prep_batch(self, ds, keep_chunks=False):
        """(inputs, labels, masks, lmasks) lists of device arrays — the
        per-step leaves both fit_batch and the scanned path consume."""
        from ...datasets.dataset import DataSet, MultiDataSet
        if isinstance(ds, DataSet):
            ds = MultiDataSet([ds.features], [ds.labels],
                              None if ds.features_mask is None else [ds.features_mask],
                              None if ds.labels_mask is None else [ds.labels_mask])
        inputs = [_step_leaf(f, keep_chunks) for f in ds.features]
        # with a fused ingest, labels ship raw/narrow (e.g. int class ids)
        # and the one-hot expansion happens inside the compiled step
        labels = [jnp.asarray(l) for l in ds.labels] if self._ingest is not None \
            else [jnp.asarray(l, self._dtype) for l in ds.labels]
        masks = None if ds.features_masks is None else \
            [None if m is None else jnp.asarray(m, self._dtype) for m in ds.features_masks]
        lmasks = None if ds.labels_masks is None else \
            [None if m is None else jnp.asarray(m, self._dtype) for m in ds.labels_masks]
        return inputs, labels, masks, lmasks

    def _scan_loss(self, p, states, inputs, labels, rng, masks, lmasks):
        inputs, labels = self._apply_ingest(inputs, labels)
        score, (new_states, _) = self._loss(p, states, inputs, labels,
                                            train=True, rng=rng, masks=masks,
                                            label_masks=lmasks)
        return score, new_states

    def _multi_step_mode(self, prepped):
        from ..conf.configuration import OptimizationAlgorithm
        inputs = prepped[0]
        if self.conf.optimization_algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            return None
        T = max((x.shape[1] for x in inputs
                 if hasattr(x, "ndim") and x.ndim == 3), default=0)
        if (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                and T > self.conf.tbptt_fwd_length):
            return None  # graph TBPTT groups run per-batch
        return None if self._listeners_need_gradients() else "std"

    def fit_batch(self, ds):
        if self.params is None:
            self.init()
        self._check_trainable()     # int8 serving weights can't train
        inputs, labels, masks, lmasks = self._prep_batch(ds)
        self._rng, step_rng = jax.random.split(self._rng)
        from ..conf.configuration import OptimizationAlgorithm
        if self.conf.optimization_algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            # flat solvers (reference: Solver.java:55); cached per model
            if getattr(self, "_flat_solver", None) is None:
                from ...optimize.solvers import make_solver
                self._flat_solver = make_solver(
                    self.conf.optimization_algo, self,
                    line_search_iterations=self.conf.max_num_line_search_iterations)
            self._flat_solver.optimize(inputs, labels, masks, lmasks)
        else:
            T = max((x.shape[1] for x in inputs
                     if hasattr(x, "ndim") and x.ndim == 3), default=0)
            if (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                    and T > self.conf.tbptt_fwd_length):
                self._fit_tbptt(inputs, labels, masks, lmasks, step_rng, T)
            else:
                step = self._get_train_step("std")
                self.params, self.opt_state, self.states, score, _ = step(
                    self.params, self.opt_state, self.states, step_rng, inputs,
                    labels, masks, lmasks, None)
                self.score_value = score  # device scalar; syncs lazily on read
        self.iteration_count += 1
        for listener in self.listeners:
            if hasattr(listener, "record_batch_size"):
                listener.record_batch_size(inputs[0].shape[0])
            listener.iteration_done(self, self.iteration_count)

    def _fit_tbptt(self, inputs, labels, masks, lmasks, rng, T):
        """Truncated BPTT over the graph (reference: ComputationGraph TBPTT via
        doTruncatedBPTT in ComputationGraph.java): slide a tbptt_fwd_length
        window over every time-distributed (3D) input/label, carrying recurrent
        layer state (stop-gradient) across windows; non-temporal inputs are
        passed whole to every window."""
        L = self.conf.tbptt_fwd_length
        batch = inputs[0].shape[0]
        carries = self._zero_carries(batch)
        step = self._get_train_step("tbptt")
        scores = []
        for start in range(0, T, L):
            end = min(start + L, T)
            xw = [x[:, start:end] if x.ndim == 3 and x.shape[1] == T else x
                  for x in inputs]
            yw = [y[:, start:end] if y.ndim == 3 and y.shape[1] == T else y
                  for y in labels]
            mw = None if masks is None else \
                [None if m is None else
                 (m[:, start:end] if m.ndim >= 2 and m.shape[1] == T else m)
                 for m in masks]
            lmw = None if lmasks is None else \
                [None if m is None else
                 (m[:, start:end] if m.ndim >= 2 and m.shape[1] == T else m)
                 for m in lmasks]
            rng, sub = jax.random.split(rng)
            # gradient truncation at window edges is inherent: each window's
            # value_and_grad differentiates params only; carries enter the next
            # step as concrete (non-differentiated) arguments
            self.params, self.opt_state, self.states, score, carries = step(
                self.params, self.opt_state, self.states, sub, xw, yw, mw, lmw,
                carries)
            scores.append(score)
        self.score_value = jnp.mean(jnp.stack(scores))

    # ------------------------------------------------------------ inference
    def output(self, *inputs, train=False, mask=None):
        """(reference: ComputationGraph.output / outputSingle). `mask` is a
        [batch, time] validity mask for the FIRST network input (the
        serving batcher's padded+masked length buckets)."""
        if self.params is None:
            self.init()
        inputs = [jnp.asarray(x) for x in inputs]
        masked = mask is not None
        key = ("output", len(inputs), masked)
        if key not in self._jit_cache:
            def fwd(params, states, xs, mm):
                # int8 serving weights: codes are the executable's operands;
                # the traced dequant fuses into the consumers (nn/quant.py)
                params = self._dequant_params(params)
                params, xs = self._cast_for_compute(params, xs)
                masks = None if mm is None else [mm] + [None] * (len(xs) - 1)
                acts, _, _, _ = self._forward(params, states, xs, train=False,
                                              rng=None, masks=masks)
                return [acts[o].astype(self._dtype) for o in self.conf.network_outputs]
            self._jit_cache[key] = timed_first_call(
                jax.jit(fwd),
                f"graph_output:inputs={len(inputs)},mask={masked}")
        outs = self._jit_cache[key](
            self.params, self.states, inputs,
            None if mask is None else jnp.asarray(mask, self._dtype))
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *inputs, train=False):
        acts, _, _, _ = self._forward(self._dequant_params(self.params),
                                      self.states,
                                      [jnp.asarray(x) for x in inputs],
                                      train=train, rng=None)
        return acts

    def score(self, ds):
        from ...datasets.dataset import DataSet, MultiDataSet
        if isinstance(ds, DataSet):
            ds = MultiDataSet([ds.features], [ds.labels])
        inputs = [jnp.asarray(f) for f in ds.features]
        labels = [jnp.asarray(l, self._dtype) for l in ds.labels]
        s, _ = self._loss(self._dequant_params(self.params), self.states,
                          inputs, labels, train=False, rng=None)
        return float(s)

    def compute_gradient_and_score(self, inputs, labels, masks=None, label_masks=None):
        inputs = [jnp.asarray(x) for x in (inputs if isinstance(inputs, (list, tuple)) else [inputs])]
        labels = [jnp.asarray(y) for y in (labels if isinstance(labels, (list, tuple)) else [labels])]

        def loss_fn(p):
            s, _ = self._loss(p, self.states, inputs, labels, train=False, rng=None,
                              masks=masks, label_masks=label_masks)
            return s
        score, grads = jax.value_and_grad(loss_fn)(self.params)
        return grads, float(score)

    # ------------------------------------------------------- rnn streaming
    def rnn_time_step(self, *inputs):
        """(reference: rnnTimeStep :1789)"""
        inputs = [jnp.asarray(x) for x in inputs]
        squeeze = inputs[0].ndim == 2
        if squeeze:
            inputs = [x[:, None, :] if x.ndim == 2 else x for x in inputs]
        batch = inputs[0].shape[0]
        carries = self._rnn_state or self._zero_carries(batch)
        acts, _, _, new_carries = self._forward(
            self._dequant_params(self.params), self.states, inputs,
            train=False, rng=None, initial_carries=carries)
        self._rnn_state = new_carries
        outs = [acts[o] for o in self.conf.network_outputs]
        if squeeze:
            outs = [o[:, -1] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self):
        self._rnn_state = {}

    # generate() — greedy KV-cache decode — lives on MultiStepTrainable
    # (shared with MultiLayerNetwork, like set_update_sharding)

    def _zero_carries(self, batch):
        carries = {}
        for name, layer in self.layers.items():
            if hasattr(layer, "init_carry"):
                carries[name] = layer.init_carry(batch, self._dtype)
        return carries

    def clone(self):
        """Deep copy (params/states/score); mirrors MultiLayerNetwork.clone —
        required by the early-stopping InMemoryModelSaver."""
        net = ComputationGraph(self.conf)
        if self.params is not None:
            net.init(params=jax.tree_util.tree_map(jnp.array, self.params))
            net.states = jax.tree_util.tree_map(jnp.array, self.states)
        return net

    # -------------------------------------------------------------- params
    def param_table(self):
        out = {}
        for name, p in self.params.items():
            for k, v in p.items():
                out[f"{name}_{k}"] = v
        return out

    def num_params(self):
        return sum(int(x.size) for x in jax.tree_util.tree_leaves(self.params))

    def get_flat_params(self):
        leaves = []
        for name in sorted(self.params.keys()):
            p = self.params[name]
            for k in sorted(p.keys()):
                leaves.append(np.asarray(p[k]).ravel())
        if not leaves:
            return np.zeros((0,), np.float32)
        return np.concatenate(leaves)

    def set_flat_params(self, flat):
        flat = np.asarray(flat)
        off = 0
        for name in sorted(self.params.keys()):
            p = self.params[name]
            for k in sorted(p.keys()):
                n = int(np.prod(p[k].shape)) if p[k].shape else 1
                p[k] = jnp.asarray(flat[off:off + n].reshape(p[k].shape), p[k].dtype)
                off += n
        return self

    def set_listeners(self, *listeners):
        self.listeners = resolve_listeners(listeners)
        return self

    def evaluate(self, iterator, top_n=1):
        """top_n > 1 also tracks top-N accuracy (reference:
        MultiLayerNetwork.evaluate(iter, labels, topN))."""
        from ...eval.evaluation import Evaluation
        from ...datasets.iterator.base import as_iterator
        e = Evaluation(top_n=top_n)
        it = as_iterator(iterator)
        it.reset()
        for ds in it:
            out = self.output(ds.features)
            e.eval(np.asarray(ds.labels), np.asarray(out),
                   None if ds.labels_mask is None else np.asarray(ds.labels_mask))
        return e
