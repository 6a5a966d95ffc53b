"""steps_per_execution: K optimizer steps inside ONE compiled executable.

The reference's training loop is a Java per-minibatch host loop
(optimize/solvers/StochasticGradientDescent.java:51-72 — fetch batch, one
gradient step, repeat), which SURVEY §7 marks as the thing to compile away:
every step pays a host dispatch, and for a small model that dispatch, not
the model, is what K separate steps time.

This mixin rolls the loop INSIDE the executable: `lax.scan` over K
pre-staged device batches with the (params, opt_state, states, rng) carry
donated, so training pays ONE dispatch per K steps and the whole chain —
forward, backward, updater, BN stat update, rng split — stays on device.
Semantics are identical to K fit_batch calls: the rng chain splits the same
way, per-layer states thread sequentially, and scores come back per step.

TBPTT batches scan too (MultiLayerNetwork): each batch's windows flatten
into the scan with a per-window carry that resets at batch boundaries, and
a precomputed rng table replays exactly the splits the per-batch path would
have drawn. Configs the scan can't honor (non-SGD solvers, ragged TBPTT
windows, gradient-hungry listeners, mismatched shapes within a group) fall
back to per-batch steps.

Each class provides (keep_chunks: leave a prefetcher's RowChunks unjoined):
  _prep_batch(ds, keep_chunks=False) -> per-step pytree (masks may be None)
  _scan_loss(p, states, x, y, rng, mask, lmask) -> (score, new_states)
  _multi_step_mode(prepped) -> "std" | "tbptt" | None

Listeners fire once per execution with the advanced iteration count — a
well-defined K-step cadence; per-step scores stay available on device as
`last_scores`.

Each execution after an epoch's first is one `fit_execution` phase
(telemetry/trace.py `Tracer.phase`), first pull of its group to end of its
listeners; its folded parts account for it (>= 95 % of the span), each with
a `<name>_ms` histogram on the default registry: `fit_next_batch` (the K
pulls from the iterator; a DevicePrefetcher's `etl_consumer_wait` nests in
it: time the input path did not keep up), `fit_execution_wait` (below),
`fit_prepare` (stacking the K batches into a plan), `fit_dispatch` (the
jitted call until it RETURNS) and `fit_listeners`. An epoch's first
execution has the same parts but no span, and a call that compiles runs
outside every phase. ONE EXECUTION IN FLIGHT (PR 40): before it stacks the
plan of execution N + 1 the loop waits, in `fit_execution_wait`, until
N - 1 has finished (`last_scores` it holds turn ready), so N + 1 is queued
while N runs and nothing further: the running plan, the queued one and one
pulled group are alive, whatever the host's lead. No knob: a device that
outruns the host never makes it wait. `fit_executions_ahead_total{ahead}`,
counted just before each dispatch that does not compile, says whether the
device was still fed: "1" the execution before has not finished (asked, not
waited for), "0" the device had drained: the starvation signal.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from ..telemetry.registry import get_registry
from ..telemetry.trace import get_tracer


def _fit_phase(name, help):
    """A folded phase of fit(steps_per_execution=K), with its `<name>_ms`
    histogram on the default registry (where etl_consumer_wait_ms lives)."""
    return get_tracer().phase(
        name, histogram=get_registry().histogram(name + "_ms", help),
        fold=True)


class MultiStepTrainable:
    last_scores = None      # [K] device array of the newest execution

    def set_update_sharding(self, zero):
        """Install (or with None, remove) a ZeRO-1 sharded update
        (parallel.zero.ZeroUpdater): updater state and the parameter update
        partition over the mesh's data axis — reduce-scatter grads,
        per-shard optax update, all-gather fresh params into the forward
        (arXiv 2004.13336; ROADMAP item 4). Existing updater state carries
        over exactly (canonical<->sharded conversion), so enabling,
        resuming from a checkpoint, or changing replica count never resets
        momentum. Clears the jit cache so every train path — including the
        scanned multi-step executables this mixin owns — re-traces with the
        sharded update fused. Shared by MultiLayerNetwork and
        ComputationGraph (each contributes its own _build_updater)."""
        old = self._zero
        if old is not None and self.opt_state is not None:
            self.opt_state = old.to_canonical(self.opt_state, self.params)
        self._zero = zero
        if self.params is not None:
            self._build_updater(init_state=False)
            if zero is not None and self.opt_state is not None:
                self.opt_state = zero.from_canonical(self.opt_state,
                                                     self.params)
        self._jit_cache.clear()
        return self

    # ------------------------------------------------- int8 serving weights
    def quantize_weights(self, dtype="int8"):
        """Per-channel symmetric int8 weight quantization for SERVING
        (nn/quant.py, ROADMAP item 3): eligible weight leaves (floating,
        ndim >= 2) are replaced in `self.params` by their int8 codes, and
        every inference executable — output(), the decode engine's
        step/prefill, rnn_time_step — traces a fused dequant
        (`codes * per-channel scale`) on the way into the matmul, so HBM
        holds and reads ~4x fewer weight bytes. The f32 originals are kept
        as a host-side numpy backup (`dequantize_weights` restores them;
        serializers write f32 zips). Training paths refuse a quantized
        model. Shared by MultiLayerNetwork and ComputationGraph."""
        if getattr(self, "_wq", None) is not None:
            return self
        if self.params is None:
            self.init()
        from .quant import WeightQuant
        self._wq, self.params = WeightQuant.build(self.params, dtype=dtype)
        self._jit_cache.clear()
        return self

    def dequantize_weights(self):
        """Undo quantize_weights from the host-side f32 backup (used when a
        deploy-time parity gate breaches)."""
        wq = getattr(self, "_wq", None)
        if wq is None:
            return self
        self.params = wq.restore_params(self.params)
        self._wq = None
        self._jit_cache.clear()
        return self

    def _dequant_params(self, params):
        """Traced at the top of every inference executable: int8 code
        leaves widen through their per-channel scales (closure constants);
        identity for unquantized models."""
        wq = getattr(self, "_wq", None)
        return params if wq is None else wq.dequant(params)

    def _check_trainable(self):
        if getattr(self, "_wq", None) is not None:
            raise RuntimeError(
                "weights are int8-quantized (serving-only); call "
                "dequantize_weights() before training")

    def generate(self, prompt_ids, max_new_tokens=20, stop_id=None,
                 max_len=None, sampler=None):
        """KV-cache autoregressive decode (decode/engine.py): feeds
        `prompt_ids` (token ids; one-hot happens inside the compiled
        prefill), then emits up to `max_new_tokens` ids one fixed-shape
        decode step at a time — greedy by default, token-for-token identical
        to re-running `output` on the growing sequence, without the O(T²)
        re-forward. `sampler` (a decode.SamplerConfig) switches to seeded
        temperature/top-k/top-p sampling; the params ride as array operands
        of the SAME executable, so swinging them between calls never
        recompiles. The engine (and its compiled executables) is cached on
        the model; pass `max_len` to size the cache (default: prompt + new
        tokens, rounded up). Shared by MultiLayerNetwork and
        ComputationGraph (single-input/single-output sequence graphs;
        anything without per-token semantics raises
        decode.DecodeUnsupported)."""
        from ..decode.engine import DecodeEngine, bucket_for_len
        n = len(list(prompt_ids))
        need = n + int(max_new_tokens) + 1
        eng = getattr(self, "_decode_engine", None)
        if eng is None or eng.capacity < need or eng.model is not self:
            cap = int(max_len) if max_len is not None \
                else bucket_for_len(need, 1 << 30)
            eng = self._decode_engine = DecodeEngine(self, slots=1,
                                                     max_len=cap)
        return eng.generate(prompt_ids, max_new_tokens, stop_id=stop_id,
                            sampler=sampler)

    def _make_multi_step(self):
        tx = self._tx

        def multi_step(params, opt_state, states, rng, stacked):
            def body(carry, batch):
                params, opt_state, states, rng = carry
                x, y, mask, lmask = batch
                rng, step_rng = jax.random.split(rng)
                (score, new_states), grads = jax.value_and_grad(
                    self._scan_loss, has_aux=True)(
                        params, states, x, y, step_rng, mask, lmask)
                grads = self._normalize_grads(grads)
                with jax.named_scope("optimizer"):
                    updates, opt_state = tx.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                return (params, opt_state, new_states, rng), score

            (params, opt_state, states, rng), scores = jax.lax.scan(
                body, (params, opt_state, states, rng), stacked)
            return params, opt_state, states, rng, scores

        # the batch stack is NOT donated: callers may reuse prepared groups
        return jax.jit(multi_step, donate_argnums=(0, 1, 2, 3))

    def prepare_steps(self, group):
        """Stack a list of same-shaped DataSets into one device-resident
        execution plan for `fit_prepared`, or None when this group can't
        scan. The plan is reusable: its batch leaves are never donated
        (re-running a TBPTT plan replays the same rng table; the std plan
        draws fresh rngs from the carried chain). A std plan is built by
        ONE device program (`_stack_steps`), which also joins features
        that a DevicePrefetcher handed over in row chunks."""
        if self.params is None:
            self.init()
        self._check_trainable()
        # decide eligibility from the FIRST batch alone before paying the
        # host->device transfer for the whole group — an ineligible config
        # would otherwise re-prep (and re-transfer) every batch in the
        # fit_batch fallback
        first = self._prep_batch(group[0], keep_chunks=True)
        mode = self._multi_step_mode(first)
        if mode is None:
            return None
        prepped = [first] + [self._prep_batch(ds, keep_chunks=True)
                             for ds in group[1:]]
        try:
            if mode == "std":
                return "std", _stack_steps(prepped), len(group)
            return self._prepare_tbptt(jax.tree_util.tree_map(
                jnp.asarray, prepped, is_leaf=_is_chunks))  # MLN-only
        except ValueError:
            return None  # shape or mask-structure mismatch within the group

    def fit_prepared(self, prepared):
        """Run one compiled multi-step execution over a `prepare_steps`
        plan."""
        mode, stacked, K = prepared
        if mode == "std":
            args = (self.params, self.opt_state, self.states, self._rng,
                    stacked)
            fn = self._jit_cache.get("multi")
            if fn is None:
                # the compiling call stays with timed_first_call's
                # accounting and OUTSIDE any phase: see _fit_grouped
                from ..telemetry.xla import timed_first_call
                fn = self._jit_cache["multi"] = timed_first_call(
                    self._make_multi_step(), "multi_step:std")
                out = fn(*args)
            else:
                prev = self.last_scores
                get_registry().counter(
                    "fit_executions_ahead_total",
                    "Multi-step executions dispatched while the one before "
                    "was still running (ahead=1) or onto a drained device "
                    "(ahead=0)").inc(1, ahead="1" if prev is not None
                                     and not prev.is_ready() else "0")
                with _fit_phase("fit_dispatch", "The multi-step "
                                "executable's call until it returns (not "
                                "until ready), ms"):
                    out = fn(*args)
            (self.params, self.opt_state, self.states, self._rng,
             scores) = out
        else:
            scores = self._run_prepared_tbptt(stacked, K)
        self.last_scores = scores          # [K] device array
        self.score_value = scores[-1]      # device scalar; syncs lazily
        self.iteration_count += int(K)
        B = jax.tree_util.tree_leaves(stacked)[0].shape[1]
        with _fit_phase("fit_listeners", "Listener callbacks of one "
                        "multi-step execution, ms"):
            for listener in self.listeners:
                if hasattr(listener, "record_batch_size"):
                    listener.record_batch_size(int(K) * int(B))
                listener.iteration_done(self, self.iteration_count)
        return self

    # `last_scores` of the two newest executions this loop dispatched,
    # older first: what the one-in-flight rule of _fit_grouped waits on
    _in_flight = ()

    def _fit_grouped(self, it, K, prepare=None, run=None, fallback=None):
        """One epoch: full groups of K go through the compiled scan; ragged
        tails and incompatible groups fall back to per-batch steps. The
        prepare/run/fallback hooks default to this model's own methods;
        ShardedTrainer reuses the same accumulation loop with its sharded
        prepare and mesh-scoped run — and with it the rule that exactly one
        execution is queued behind the running one (module docstring)."""
        prepare = prepare or self.prepare_steps
        run = run or (lambda prepared, group: self.fit_prepared(prepared))
        fallback = fallback or self.fit_batch
        it = iter(it)
        # An epoch's first execution may trace, lower and compile: it keeps
        # its compile accounting (timed_first_call) and stays out of the
        # fit_execution span and of fit_dispatch_ms. (PR 24 kept it out of
        # every `with` block because each one cost the lowering 3-4 s on the
        # v5e host; the cause was where the caller's frames ended on
        # CPython's chunked data stack, which timed_first_call now makes
        # irrelevant: util/stack_room.py.)
        warm = False

        def pull():
            group = []
            with _fit_phase("fit_next_batch", "Pulling one group of K "
                            "batches from the iterator, ms") as nb:
                for ds in it:
                    group.append(ds)
                    if len(group) == K:
                        break
                if not group:
                    nb.cancel()         # the iterator had ended
            return group

        def execute():
            """One group: pull, prepare, run. (group, plan): the plan None
            where the group is ragged or cannot scan."""
            group = pull()
            if len(group) < K:
                return group, None
            if len(self._in_flight) == 2:
                # N - 1 and N are out: N + 1 is neither stacked nor
                # dispatched before N - 1 has finished. Nothing else paces
                # a host that is handed batches faster than the device
                # runs them; a device that keeps up never waits here
                with _fit_phase("fit_execution_wait", "Waiting, before "
                                "execution N + 1 is stacked, until N - 1 "
                                "has finished, ms"):
                    jax.block_until_ready(self._in_flight[0])
            with _fit_phase("fit_prepare", "Stacking one group of K device "
                            "batches into an execution plan, ms") as prep:
                prepared = prepare(group)
                if prepared is None:
                    prep.cancel()
            if prepared is not None:
                run(prepared, group)
                self._in_flight = (self._in_flight
                                   + (self.last_scores,))[-2:]
            return group, prepared

        while True:
            if warm:
                with get_tracer().phase("fit_execution", steps=K) as ex:
                    group, prepared = execute()
                    if prepared is None:
                        ex.cancel()
            else:
                group, prepared = execute()
            warm = prepared is not None
            if prepared is None:
                for ds in group:
                    fallback(ds)
                if len(group) < K:
                    return
            # the K batches and their stacked plan are let go before the
            # next group is pulled, not when its plan replaces them
            del group, prepared

    def _listeners_need_gradients(self):
        return any(getattr(l, "wants_gradients", False) for l in self.listeners)

    def _prepare_tbptt(self, prepped):
        return None  # ComputationGraph: TBPTT groups fall back to fit_batch


def _is_chunks(a):
    from ..etl.prefetch import RowChunks
    return isinstance(a, RowChunks)


def _step_leaf(a, keep_chunks, dtype=None):
    """One array of a batch as a per-step leaf on the device. A `RowChunks`
    (what a DevicePrefetcher with transfer_streams hands over) stays in its
    chunks for `prepare_steps`, which joins them inside the stack's
    program; everyone else gets the joined array."""
    if keep_chunks and _is_chunks(a) and dtype in (None, a.dtype):
        return a
    return jnp.asarray(a, dtype)


@jax.jit
def _stack_steps(prepped):
    """K per-step pytrees -> one with a leading [K] axis, in ONE device
    program: leaf by leaf the stack, and where a leaf arrives as RowChunks
    the chunks' join inside it (a join of its own is a program more a
    batch, queued behind the running execution with a second copy of the
    batch alive until it runs)."""
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *prepped,
                                  is_leaf=_is_chunks)
