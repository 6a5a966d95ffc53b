"""MultiLayerNetwork: the sequential-stack model and #1 user entry point.

Reference: nn/multilayer/MultiLayerNetwork.java (2444 LoC; init :385,
fit(DataSetIterator) :902, computeGradientAndScore :1729, backprop :973,
output :1462, feedForwardToLayer :692, pretrain :164, doTruncatedBPTT :1064,
rnnTimeStep ~:2100, score(DataSet) :1629).

TPU-first redesign: instead of a Java per-layer interpreter loop calling
hand-written backpropGradient per layer, the ENTIRE minibatch step —
forward, loss, backward (autodiff), gradient normalization, updater
(optax: LR schedule + momentum/adam state), parameter update, batch-norm
running-stat update — traces into ONE jit-compiled XLA computation with donated
parameter/optimizer buffers (the functional analog of the reference's in-place
flattened param view, Model.setParamsViewArray nn/api/Model.java:123).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
import optax

from ..conf.configuration import MultiLayerConfiguration, BackpropType
from ..layers.base import create_layer
from ..layers import (feedforward, convolution, recurrent, mamba, kda, mla, misc,  # noqa: F401 (register impls)
                      variational)
from ..multistep import MultiStepTrainable, _step_leaf
from ..updaters import apply_gradient_normalization
from ...optimize.listeners import resolve_listeners
from ...telemetry.trace import get_tracer
from ...telemetry.xla import timed_first_call


def _is_weight_key(k):
    return not (k.endswith("b") or k in ("gamma", "beta", "centers", "mean", "var"))


class MultiLayerNetwork(MultiStepTrainable):
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = [create_layer(lc) for lc in conf.layers]
        # jax.named_scope of each layer in the traced forward: the names a
        # device trace and the lowered text show for its operations
        self._scopes = [lc.name or f"layer{i}"
                        for i, lc in enumerate(conf.layers)]
        self.params = None          # {"0": {...}, "1": {...}}
        self.states = None          # non-trainable per-layer state
        self.opt_state = None
        self._tx = None
        self.listeners = []
        self.iteration_count = 0
        self.epoch_count = 0
        self._score_dev = float("nan")
        self.last_gradients = None   # most recent step's gradients (StatsListener)
        self._dtype = jnp.dtype(conf.dtype)
        self._rng = jax.random.PRNGKey(conf.seed)
        self._rnn_state = {}        # streaming inference carries per layer idx
        self._jit_cache = {}
        self._ingest = None         # device-side ingest fused into the step
        self._zero = None           # ZeRO-1 sharded update (parallel/zero.py)
        self._wq = None             # int8 serving weights (nn/quant.py)

    @property
    def score_value(self):
        """Most recent minibatch score. The train step leaves the score ON
        DEVICE (a host readback through the TPU runtime costs orders of
        magnitude more than the step itself); the device→host sync happens
        lazily here, only when something actually reads the score."""
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
        """Initialize parameters (reference: MultiLayerNetwork.init :385)."""
        conf = self.conf
        rng = jax.random.PRNGKey(conf.seed)
        self.params, self.states = {}, {}
        cur_type = conf.input_type
        for i, layer in enumerate(self.layers):
            rng, sub = jax.random.split(rng)
            pre = conf.input_preprocessors.get(i)
            if cur_type is not None and pre is not None:
                cur_type = pre.output_type(cur_type)
            elif cur_type is not None and cur_type.kind == "cnn_flat":
                from ..conf.inputs import InputType
                cur_type = InputType.feed_forward(cur_type.flat_size())
            p, s, out_type = layer.init(sub, cur_type, self._dtype)
            self.params[str(i)] = p
            self.states[str(i)] = s
            cur_type = out_type
        if params is not None:
            self.set_params(params)
        self._build_updater()
        return self

    def _build_updater(self, init_state=True):
        """Per-layer optax transforms (each layer may override the updater —
        reference: LayerUpdater per layer, UpdaterCreator). With a ZeRO-1
        updater installed (set_update_sharding), the per-layer transforms
        wrap into the sharded-update transform instead."""
        from ..updaters import layer_transform, per_layer_transform
        transforms = {str(i): layer_transform(lc)
                      for i, lc in enumerate(self.conf.layers)}
        if self._zero is not None:
            self._tx = self._zero.wrap(transforms, self.params)
        else:
            self._tx = per_layer_transform(transforms)
        if init_state:
            self.opt_state = self._tx.init(self.params)

    # -------------------------------------------------------------- forward
    def _apply_preprocessor(self, i, x, mask, rng=None):
        pre = self.conf.input_preprocessors.get(i)
        if pre is not None:
            x = pre(x, mask, rng=rng)
            mask = pre.feed_forward_mask(mask) if mask is not None else None
        return x, mask

    def _forward(self, params, states, x, *, train, rng, mask=None, to_layer=None,
                 initial_carries=None, collect=False):
        """Run layers [0, to_layer); returns (activations, new_states, mask,
        final_carries, collected)."""
        n = len(self.layers) if to_layer is None else to_layer
        new_states = dict(states)
        carries = {}
        collected = []
        cur_mask = mask
        for i in range(n):
            layer = self.layers[i]
            if rng is not None:
                rng, pre_rng, sub = jax.random.split(rng, 3)
            else:
                pre_rng = sub = None
            kwargs = {}
            if initial_carries is not None and str(i) in initial_carries:
                kwargs = {"initial_state": initial_carries[str(i)], "return_state": True}
            with jax.named_scope(self._scopes[i]):
                x, cur_mask = self._apply_preprocessor(i, x, cur_mask,
                                                       rng=pre_rng)
                out = layer.forward(params[str(i)], states[str(i)], x,
                                    train=train, rng=sub, mask=cur_mask,
                                    **kwargs)
            if len(out) == 4:
                x, new_s, cur_mask, final = out
                carries[str(i)] = final
            else:
                x, new_s, cur_mask = out
            new_states[str(i)] = new_s
            if collect:
                collected.append(x)
        return x, new_states, cur_mask, carries, collected

    # ------------------------------------------------------- mixed precision
    def _compute_dtype(self):
        """Mixed-precision compute dtype, or None when compute == param dtype."""
        cd = getattr(self.conf, "compute_dtype", None)
        if cd is None or jnp.dtype(cd) == self._dtype:
            return None
        return jnp.dtype(cd)

    @staticmethod
    def _cast_floats(tree, dt):
        return jax.tree_util.tree_map(
            lambda a: a.astype(dt)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating) else a,
            tree)

    def _cast_for_compute(self, params, x, *, keep_f32=()):
        """Cast params + input to the compute dtype for the MXU-bound layers;
        layers named in keep_f32 (the output/loss layers) keep the param dtype
        so softmax/cross-entropy run in full precision. BatchNorm statistics
        stay f32 inside the layer itself (layers/convolution.py)."""
        cd = self._compute_dtype()
        if cd is None:
            return params, x
        params = {k: (v if k in keep_f32 else self._cast_floats(v, cd))
                  for k, v in params.items()}
        if hasattr(x, "dtype") and (jnp.issubdtype(x.dtype, jnp.floating)
                                    or x.dtype == jnp.uint8):
            # uint8 covers the image-pixels-on-the-wire path: values 0..255
            # are exact in bf16 (ImageScalerPreProcessor rescales on-chip).
            # Wider integer inputs (embedding token ids) must NOT be cast —
            # ids > 256 are not representable in bf16.
            x = x.astype(cd)
        return params, x

    # ------------------------------------------------------------- loss/score
    def _loss(self, params, states, x, y, *, train, rng, mask=None, label_mask=None,
              initial_carries=None):
        out_idx = len(self.layers) - 1
        params, x = self._cast_for_compute(params, x, keep_f32=(str(out_idx),))
        if rng is not None:
            rng, fwd_rng, pre_rng = jax.random.split(rng, 3)
        else:
            fwd_rng = pre_rng = None
        # conf.remat recomputes (policy-chosen) activations in the backward
        # instead of storing them (nn/remat.py) — training only

        def fwd_fn(p, s, xx, rr, mm, ic):
            return self._forward(p, s, xx, train=train, rng=rr, mask=mm,
                                 to_layer=out_idx, initial_carries=ic)
        from ..remat import maybe_checkpoint
        fwd_fn = maybe_checkpoint(
            fwd_fn, getattr(self.conf, "remat", None) if train else None)
        feats, new_states, cur_mask, carries, _ = fwd_fn(
            params, states, x, fwd_rng, mask, initial_carries)
        out_layer = self.layers[out_idx]
        if not out_layer.is_output_layer():
            raise ValueError("Last layer is not an output/loss layer")
        with jax.named_scope(self._scopes[out_idx]):   # as _forward names it
            feats, cur_mask = self._apply_preprocessor(out_idx, feats,
                                                       cur_mask, rng=pre_rng)
            if self._compute_dtype() is not None:
                # loss math in full precision
                feats = feats.astype(self._dtype)
            lm = label_mask if label_mask is not None else cur_mask
            if isinstance(out_layer, feedforward.CenterLossOutputLayerModule):
                score = out_layer.score(
                    params[str(out_idx)], feats, y, lm, train, rng,
                    state=states[str(out_idx)])
                new_states[str(out_idx)] = out_layer.update_centers(
                    states[str(out_idx)], feats, y)
            else:
                score = out_layer.score(params[str(out_idx)], feats, y, lm,
                                        train, rng)
        score = score + self._reg_score(params)
        return score, (new_states, carries)

    def _reg_score(self, params):
        """L1/L2 terms (reference: BaseLayer.calcL1/calcL2 added into score)."""
        total = 0.0
        for i, lc in enumerate(self.conf.layers):
            l1 = lc.l1 or 0.0
            l2 = lc.l2 or 0.0
            l1b = lc.l1_bias or 0.0
            l2b = lc.l2_bias or 0.0
            if l1 == 0 and l2 == 0 and l1b == 0 and l2b == 0:
                continue
            for k, p in params[str(i)].items():
                if _is_weight_key(k):
                    if l1:
                        total = total + l1 * jnp.sum(jnp.abs(p))
                    if l2:
                        total = total + 0.5 * l2 * jnp.sum(p ** 2)
                else:
                    if l1b:
                        total = total + l1b * jnp.sum(jnp.abs(p))
                    if l2b:
                        total = total + 0.5 * l2b * jnp.sum(p ** 2)
        return total

    def _normalize_grads(self, grads):
        out = {}
        for i, lc in enumerate(self.conf.layers):
            g = grads[str(i)]
            if lc.gradient_normalization and g:
                g = apply_gradient_normalization(
                    g, lc.gradient_normalization,
                    lc.gradient_normalization_threshold or 1.0)
            out[str(i)] = g
        return out

    # ------------------------------------------------------- device ingest
    def set_ingest(self, ingest):
        """Fuse a device-side ingest transform (etl.device_transform
        .DeviceIngest, or any object with traceable `apply_features` /
        `apply_labels`) into the jitted TRAIN step: batches then ship as raw
        narrow arrays (uint8/int codes) and decode/cast/normalize/one-hot
        run as the first fused XLA ops of the step — one executable, no
        extra dispatch, 4x+ fewer host-link bytes. Training paths only
        (fit/fit_batch/scanned multistep); output()/score()/solvers keep
        consuming preprocessed tensors. Clears the jit cache so every
        executable re-traces with the ingest ops fused."""
        self._ingest = ingest
        self._jit_cache.clear()
        return self

    def _apply_ingest(self, x, y):
        """Traced at the top of every train step. Post-ingest casts replay
        the non-ingest `_prep_batch` semantics on device: signed-int inputs
        (embedding ids) pass through, everything else lands on the param
        dtype; labels always land on the param dtype."""
        ing = self._ingest
        if ing is None:
            return x, y
        x = ing.apply_features(x)
        if not jnp.issubdtype(x.dtype, jnp.signedinteger) \
                and x.dtype != self._dtype:
            x = x.astype(self._dtype)
        y = ing.apply_labels(y)
        if y.dtype != self._dtype:
            y = y.astype(self._dtype)
        return x, y

    # ---------------------------------------------------------------- train
    def _make_train_step(self, tbptt=False):
        tx = self._tx

        def train_step(params, opt_state, states, rng, x, y, mask, label_mask, carries):
            x, y = self._apply_ingest(x, y)

            def loss_fn(p):
                return self._loss(p, states, x, y, train=True, rng=rng, mask=mask,
                                  label_mask=label_mask,
                                  initial_carries=carries if tbptt else None)
            (score, (new_states, out_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = self._normalize_grads(grads)
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, new_states, score, out_carries, grads

        # tbptt also donates the LSTM carries (arg 8): out_carries aliases
        # the incoming h/c buffers instead of allocating 2*layers fresh
        # [B, H] arrays per window — the non-scanned sibling of the
        # multi_tbptt carry donation: bytes the step need not allocate
        # and copy are time it need not spend. The std step passes
        # carries=None (zero pytree leaves), so donating it there is a no-op.
        donate = (0, 1, 2, 8) if tbptt else (0, 1, 2)
        return jax.jit(train_step, donate_argnums=donate)

    def _get_train_step(self, key):
        if key not in self._jit_cache:
            # first call compiles the XLA executable; timed_first_call
            # attributes that cost to jit_compiles_total in the telemetry
            # registry (the Julia-TPU paper's compile-vs-run accounting)
            self._jit_cache[key] = timed_first_call(
                self._make_train_step(tbptt="tbptt" in key),
                f"train_step:{key}")
        return self._jit_cache[key]

    def fit(self, data, labels=None, epochs=1, steps_per_execution=1,
            prefetch=None, ingest=None):
        """Train. `data` may be a DataSetIterator-like (including an
        etl.ParallelPipelineExecutor), a DataSet, or (x, y) arrays
        (reference: fit(DataSetIterator) :902 and fit(INDArray,INDArray)).

        steps_per_execution=K compiles K optimizer steps into ONE executable
        (lax.scan with donated carry — see nn/multistep.py): one host
        dispatch per K minibatches instead of the reference's per-minibatch
        loop (StochasticGradientDescent.java:51-72). Listeners then fire on
        a K-step cadence; ragged tails and incompatible groups (TBPTT
        windowing, non-SGD solvers, mismatched shapes) fall back to
        per-batch steps.

        prefetch=K wraps the iterator in an etl.DevicePrefetcher with a
        K-deep buffer (2 = double, 3 = triple buffering): batch N+1's
        host->device transfer overlaps batch N's compute, so the jit step
        traces arrays that are already device-resident.

        ingest=DeviceIngest(...) (equivalent to set_ingest beforehand) fuses
        device-side decode/cast/normalize/one-hot into the SAME compiled
        step, so prefetch transfers narrow raw bytes and the first fused
        XLA ops do the widening on-chip."""
        from ...datasets.dataset import DataSet
        from ...datasets.iterator.base import as_iterator
        if ingest is not None:
            self.set_ingest(ingest)
        if labels is not None:
            data = DataSet(data, labels)
        it = as_iterator(data)
        wrapped = None
        if prefetch:
            from ...etl.prefetch import DevicePrefetcher
            it = wrapped = DevicePrefetcher(it, queue_size=int(prefetch))
        K = max(1, int(steps_per_execution))
        tracer = get_tracer()          # no-op span per epoch when disabled
        try:
            for _ in range(epochs):
                with tracer.span("epoch", epoch=self.epoch_count):
                    for listener in self.listeners:
                        listener.on_epoch_start(self)
                    it.reset()
                    if K > 1:
                        self._fit_grouped(it, K)
                    else:
                        for ds in it:
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
        """(x, y, mask, lmask) as device arrays — the per-step leaves both
        fit_batch and the scanned multi-step path consume. With an ingest
        fused (`set_ingest`) the arrays stay RAW/NARROW — the widening cast
        happens inside the compiled step, not here."""
        if self._ingest is not None:
            x = _step_leaf(ds.features, keep_chunks)
            y = jnp.asarray(ds.labels)
            mask = None if ds.features_mask is None else jnp.asarray(ds.features_mask, self._dtype)
            lmask = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask, self._dtype)
            return x, y, mask, lmask
        x = _step_leaf(ds.features, keep_chunks, self._dtype) \
            if not str(ds.features.dtype).startswith("int") else _step_leaf(ds.features, keep_chunks)
        y = jnp.asarray(ds.labels, self._dtype)
        mask = None if ds.features_mask is None else jnp.asarray(ds.features_mask, self._dtype)
        lmask = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask, self._dtype)
        return x, y, mask, lmask

    def _scan_loss(self, p, states, x, y, rng, mask, lmask):
        x, y = self._apply_ingest(x, y)
        score, (new_states, _) = self._loss(p, states, x, y, train=True,
                                            rng=rng, mask=mask,
                                            label_mask=lmask)
        return score, new_states

    def _multi_step_mode(self, prepped):
        from ..conf.configuration import OptimizationAlgorithm
        x = prepped[0]
        if self.conf.optimization_algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            return None
        if self._listeners_need_gradients():
            return None
        if (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                and x.ndim == 3 and x.shape[1] > self.conf.tbptt_fwd_length):
            # windows scan only when they tile the sequence exactly
            return "tbptt" if x.shape[1] % self.conf.tbptt_fwd_length == 0 \
                else None
        return "std"

    def _prepare_tbptt(self, prepped):
        """Flatten K TBPTT batches into one [K*W, ...] window scan: every
        batch contributes W = T/L windows, a `first` flag resets the carried
        recurrent state at batch boundaries, and an rng table replays
        EXACTLY the splits K fit_batch calls would draw (one step key per
        batch, one sub-key per window), advancing self._rng identically."""
        L = self.conf.tbptt_fwd_length
        T = prepped[0][0].shape[1]
        W = T // L
        K = len(prepped)

        def win(a, dims3):
            # [B, T, ...] -> [W, B, L, ...]; non-temporal arrays replicate
            if a is None:
                return None
            if a.ndim in dims3 and a.shape[1] == T:
                parts = [a[:, w * L:(w + 1) * L] for w in range(W)]
                return jnp.stack(parts)
            return jnp.stack([a] * W)

        stacked = []
        for (x, y, mask, lmask) in prepped:
            stacked.append((win(x, (3,)), win(y, (3,)), win(mask, (2, 3)),
                            win(lmask, (2, 3))))
        # [K, W, ...] -> [K*W, ...]
        flat = jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a), *stacked)
        firsts = jnp.tile(jnp.arange(W) == 0, K)              # [K*W]

        @jax.jit
        def rng_table(r):
            def outer(r, _):
                r, step = jax.random.split(r)

                def inner(s, _):
                    s, sub = jax.random.split(s)
                    return s, sub
                _, subs = jax.lax.scan(inner, step, None, length=W)
                return r, subs
            r, tab = jax.lax.scan(outer, r, None, length=K)
            return r, tab.reshape((K * W,) + tab.shape[2:])

        self._rng, rngs = rng_table(self._rng)
        return "tbptt", (flat + (firsts, rngs)), K

    def _run_prepared_tbptt(self, stacked, K):
        tx = self._tx
        if "multi_tbptt" not in self._jit_cache:
            def multi_tbptt(params, opt_state, states, carries, stacked):
                def body(carry, batch):
                    params, opt_state, states, carries = carry
                    x, y, mask, lmask, first, sub = batch
                    x, y = self._apply_ingest(x, y)
                    carries = jax.tree_util.tree_map(
                        lambda c: jnp.where(first, jnp.zeros_like(c), c),
                        carries)

                    def loss_fn(p):
                        return self._loss(p, states, x, y, train=True,
                                          rng=sub, mask=mask,
                                          label_mask=lmask,
                                          initial_carries=carries)
                    (score, (new_states, new_carries)), grads = \
                        jax.value_and_grad(loss_fn, has_aux=True)(params)
                    grads = self._normalize_grads(grads)
                    with jax.named_scope("optimizer"):
                        updates, opt_state = tx.update(grads, opt_state,
                                                       params)
                        params = optax.apply_updates(params, updates)
                    return (params, opt_state, new_states, new_carries), score

                # final carries ARE an output: the donated carry buffers can
                # alias them, so donation sticks instead of warning "Some
                # donated buffers were not usable" (and the scan allocates
                # no second set of [B, H] carries per execution)
                (params, opt_state, states, carries), scores = jax.lax.scan(
                    body, (params, opt_state, states, carries), stacked)
                return params, opt_state, states, carries, scores
            self._jit_cache["multi_tbptt"] = timed_first_call(
                jax.jit(multi_tbptt, donate_argnums=(0, 1, 2, 3)),
                "train_step:multi_tbptt")
        B = jax.tree_util.tree_leaves(stacked)[0].shape[1]
        carries = self._zero_carries(B, self._dtype)
        (self.params, self.opt_state, self.states, _,
         win_scores) = self._jit_cache["multi_tbptt"](
            self.params, self.opt_state, self.states, carries, stacked)
        # per-batch score = mean over that batch's windows (singles parity)
        return win_scores.reshape(K, -1).mean(axis=1)

    def fit_batch(self, ds):
        """One minibatch step — one XLA computation on device."""
        if self.params is None:
            self.init()
        self._check_trainable()        # int8 serving weights can't train
        tracer = get_tracer()          # no-op spans when tracing is off
        with tracer.span("iteration", iteration=self.iteration_count):
            x, y, mask, lmask = self._prep_batch(ds)
            self._rng, step_rng = jax.random.split(self._rng)

            from ..conf.configuration import OptimizationAlgorithm
            if self.conf.optimization_algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
                # second-order / line-search solvers work on the flattened param
                # vector (reference: Solver.java:55 factory on OptimizationAlgorithm);
                # one solver instance per model so its compiled fns are reused
                if getattr(self, "_flat_solver", None) is None:
                    from ...optimize.solvers import make_solver
                    self._flat_solver = make_solver(
                        self.conf.optimization_algo, self,
                        line_search_iterations=self.conf.max_num_line_search_iterations)
                with tracer.span("solver_step"):
                    self._flat_solver.optimize(x, y, mask, lmask)
            elif (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT and x.ndim == 3
                    and x.shape[1] > self.conf.tbptt_fwd_length):
                self._fit_tbptt(x, y, mask, lmask, step_rng)
            else:
                step = self._get_train_step("std")
                with tracer.span("jit_step", rows=int(x.shape[0])):
                    (self.params, self.opt_state, self.states, score, _,
                     self.last_gradients) = step(
                        self.params, self.opt_state, self.states, step_rng,
                        x, y, mask, lmask, None)
                self.score_value = score  # device scalar; syncs lazily on read
            self.iteration_count += 1
            for listener in self.listeners:
                if hasattr(listener, "record_batch_size"):
                    listener.record_batch_size(x.shape[0])
                listener.iteration_done(self, self.iteration_count)
        if not any(getattr(l, "wants_gradients", False) for l in self.listeners):
            # don't pin a params-sized gradient pytree on device between steps
            self.last_gradients = None

    def _fit_tbptt(self, x, y, mask, lmask, rng):
        """Truncated BPTT (reference: doTruncatedBPTT :1064): slide a window of
        tbptt_fwd_length over time, carrying recurrent state (stop-gradient)
        across windows."""
        T = x.shape[1]
        L = self.conf.tbptt_fwd_length
        carries = self._zero_carries(x.shape[0], x.dtype)
        step = self._get_train_step("tbptt")
        scores = []
        for start in range(0, T, L):
            end = min(start + L, T)
            xw = x[:, start:end]
            yw = y[:, start:end] if y.ndim == 3 else y
            mw = mask[:, start:end] if mask is not None else None
            lmw = lmask[:, start:end] if lmask is not None else None
            rng, sub = jax.random.split(rng)
            # gradient truncation at window edges is inherent: each window's
            # value_and_grad differentiates params only; carries enter the next
            # step as concrete (non-differentiated) arguments
            with get_tracer().span("jit_step", window_start=start):
                (self.params, self.opt_state, self.states, score, carries,
                 self.last_gradients) = step(
                    self.params, self.opt_state, self.states, sub, xw, yw,
                    mw, lmw, carries)
            scores.append(score)
        # mean stays on device; syncs lazily when score_value is read
        self.score_value = jnp.mean(jnp.stack(scores))

    def _zero_carries(self, batch, dtype):
        carries = {}
        for i, layer in enumerate(self.layers):
            if hasattr(layer, "init_carry"):
                carries[str(i)] = layer.init_carry(batch, dtype)
        return carries

    # ------------------------------------------------------------ inference
    def output(self, x, train=False, mask=None):
        """Full forward pass (reference: output :1462). Jitted per input shape.
        train=True uses train-mode semantics (batch statistics for BN); dropout
        stays off because no rng is threaded through inference. `mask`
        ([batch, time] validity for 3-D sequence inputs) flows to every layer
        like in training — the serving batcher's padded+masked length buckets
        ride through here."""
        if self.params is None:
            self.init()
        x = jnp.asarray(x)
        masked = mask is not None
        key = ("output", bool(train), masked)
        if key not in self._jit_cache:
            is_train = bool(train)

            def fwd(params, states, xx, mm):
                # int8 serving weights: the executable's params inputs ARE
                # the narrow codes; this traced dequant fuses the widening
                # into the consumers (nn/quant.py)
                params = self._dequant_params(params)
                params, xx = self._cast_for_compute(
                    params, xx, keep_f32=(str(len(self.layers) - 1),))
                out, _, _, _, _ = self._forward(params, states, xx,
                                                train=is_train, rng=None,
                                                mask=mm)
                return out.astype(self._dtype)
            self._jit_cache[key] = timed_first_call(
                jax.jit(fwd), f"output:train={bool(train)},mask={masked}")
        return self._jit_cache[key](
            self.params, self.states, x,
            None if mask is None else jnp.asarray(mask, self._dtype))

    def feed_forward(self, x, train=False):
        """Per-layer activations list (reference: feedForward)."""
        x = jnp.asarray(x)
        _, _, _, _, acts = self._forward(self._dequant_params(self.params),
                                         self.states, x, train=train,
                                         rng=None, collect=True)
        return acts

    def feed_forward_to_layer(self, layer_idx, x, train=False):
        """(reference: feedForwardToLayer :692) — activations up to and
        including layer_idx."""
        x = jnp.asarray(x)
        out, _, _, _, _ = self._forward(self._dequant_params(self.params),
                                        self.states, x, train=train,
                                        rng=None, to_layer=layer_idx + 1)
        return out

    def score(self, ds_or_x, labels=None, train=False):
        """Mean loss on data (reference: score(DataSet) :1629)."""
        if labels is not None:
            x, y, mask, lmask = ds_or_x, labels, None, None
        else:
            x, y = ds_or_x.features, ds_or_x.labels
            mask = ds_or_x.features_mask
            lmask = ds_or_x.labels_mask
        s, _ = self._loss(self._dequant_params(self.params), self.states,
                          jnp.asarray(x), jnp.asarray(y),
                          train=train, rng=None,
                          mask=None if mask is None else jnp.asarray(mask),
                          label_mask=None if lmask is None else jnp.asarray(lmask))
        return float(s)

    def compute_gradient_and_score(self, x, y, mask=None, label_mask=None):
        """(reference: computeGradientAndScore :1729) — used by gradient checks."""
        def loss_fn(p):
            s, _ = self._loss(p, self.states, jnp.asarray(x), jnp.asarray(y),
                              train=False, rng=None,
                              mask=None if mask is None else jnp.asarray(mask),
                              label_mask=None if label_mask is None else jnp.asarray(label_mask))
            return s
        score, grads = jax.value_and_grad(loss_fn)(self.params)
        return grads, float(score)

    # ------------------------------------------------------- rnn streaming
    def rnn_time_step(self, x):
        """Stateful streaming inference (reference: rnnTimeStep ~:2100):
        feeds one or more timesteps, keeps hidden state between calls."""
        x = jnp.asarray(x)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        carries = self._rnn_state or self._zero_carries(x.shape[0], self._dtype)
        out, _, _, new_carries, _ = self._forward(
            self._dequant_params(self.params), self.states, x, train=False,
            rng=None, initial_carries=carries)
        self._rnn_state = new_carries
        return out[:, -1] if squeeze and out.ndim == 3 else out

    def rnn_clear_previous_state(self):
        self._rnn_state = {}

    def rnn_get_previous_state(self, layer_idx):
        return self._rnn_state.get(str(layer_idx))

    def rnn_set_previous_state(self, layer_idx, state):
        self._rnn_state[str(layer_idx)] = state

    # generate() — greedy KV-cache decode — lives on MultiStepTrainable
    # (shared with ComputationGraph, like set_update_sharding)

    # ------------------------------------------------------------ pretrain
    def pretrain(self, data, epochs=1):
        """Greedy layerwise unsupervised pretraining for AE/RBM/VAE layers
        (reference: pretrain :164)."""
        for i, layer in enumerate(self.layers):
            if layer.is_pretrainable():
                self.pretrain_layer(i, data, epochs)
        return self

    def pretrain_layer(self, idx, data, epochs=1):
        from ...datasets.iterator.base import as_iterator
        layer = self.layers[idx]
        if not layer.is_pretrainable():
            return self
        lc = self.conf.layers[idx]
        tx = lc.updater.to_optax()
        lp = self.params[str(idx)]
        opt_state = tx.init(lp)

        def pstep(lp, opt_state, rng, feats):
            def loss_fn(p):
                return layer.pretrain_loss(p, feats, rng)
            loss, grads = jax.value_and_grad(loss_fn)(lp)
            updates, opt_state = tx.update(grads, opt_state, lp)
            return optax.apply_updates(lp, updates), opt_state, loss
        # the layer params + updater state rebind every call, so their
        # buffers alias in place instead of a fresh allocation per batch
        # (GL010 — same contract as the main train steps)
        pstep = jax.jit(pstep, donate_argnums=(0, 1))

        it = as_iterator(data)
        for _ in range(epochs):
            it.reset()
            for ds in it:
                x = jnp.asarray(ds.features, self._dtype)
                full = dict(self.params)
                full[str(idx)] = lp
                feats, _, _, _, _ = self._forward(full, self.states, x, train=False,
                                                  rng=None, to_layer=idx)
                feats, _ = self._apply_preprocessor(idx, feats, None)
                self._rng, sub = jax.random.split(self._rng)
                lp, opt_state, loss = pstep(lp, opt_state, sub, feats)
                self.score_value = loss  # device scalar; syncs lazily on read
        self.params[str(idx)] = lp
        return self

    # -------------------------------------------------------------- params
    def param_table(self):
        """{(layer, name): array} (reference: Model.paramTable)."""
        out = {}
        for i, p in self.params.items():
            for k, v in p.items():
                out[f"{i}_{k}"] = v
        return out

    def num_params(self):
        return sum(int(x.size) for x in jax.tree_util.tree_leaves(self.params))

    def get_flat_params(self):
        """Flattened param vector in deterministic (layer, name) order —
        the analog of the reference's flattened view (Model.params())."""
        leaves = []
        for i in range(len(self.layers)):
            p = self.params[str(i)]
            for k in sorted(p.keys()):
                leaves.append(np.asarray(p[k]).ravel())
        if not leaves:
            return np.zeros((0,), np.float32)
        return np.concatenate(leaves)

    def set_flat_params(self, flat):
        flat = np.asarray(flat)
        off = 0
        for i in range(len(self.layers)):
            p = self.params[str(i)]
            for k in sorted(p.keys()):
                n = int(np.prod(p[k].shape)) if p[k].shape else 1
                p[k] = jnp.asarray(flat[off:off + n].reshape(p[k].shape), p[k].dtype)
                off += n
        return self

    def set_params(self, params):
        self.params = jax.tree_util.tree_map(jnp.asarray, params)
        return self

    def set_listeners(self, *listeners):
        self.listeners = resolve_listeners(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    # ------------------------------------------------------------ evaluate
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

    def clone(self):
        net = MultiLayerNetwork(self.conf)
        if self.params is not None:
            net.init(params=jax.tree_util.tree_map(jnp.array, self.params))
            net.states = jax.tree_util.tree_map(jnp.array, self.states)
        return net

    def summary(self):
        lines = ["idx | layer | params"]
        for i, (lc, layer) in enumerate(zip(self.conf.layers, self.layers)):
            n = sum(int(x.size) for x in jax.tree_util.tree_leaves(self.params[str(i)])) \
                if self.params else 0
            lines.append(f"{i} | {type(lc).__name__} | {n}")
        lines.append(f"total params: {self.num_params() if self.params else 0}")
        return "\n".join(lines)
