"""Dynamic micro-batcher: coalesce concurrent requests into padded
power-of-two batches so steady-state serving never recompiles.

Why buckets: `model.output` is jitted, and XLA compiles one executable per
input shape — serving raw request sizes (1, 3, 7, ...) would recompile on
every odd shape (cf. the fixed-primitive batching argument in PAPERS.md).
Padding the coalesced batch's leading dim up to the next power of two bounds
the executable set to log2(max_batch_size)+1 per feature signature; the pad
rows are zeros and are sliced off before results are returned, and each
caller's rows are bitwise-identical to a direct `model.output` call on the
same executable family.

One batcher thread owns dispatch: it takes a coalesced batch from the
AdmissionQueue (bounded wait `max_latency_ms` after the first request),
reads ONE `(version, model)` snapshot from the registry — so a hot-swap can
never mix versions within a batch — runs the jitted forward, splits the
output back to per-request futures, and records metrics.
"""
from __future__ import annotations

import inspect
import threading

import numpy as np

from ..telemetry.trace import get_tracer
from ..util.time_source import monotonic_s


def bucket_for(rows):
    """Smallest power of two >= rows."""
    b = 1
    while b < rows:
        b <<= 1
    return b


class DynamicBatcher:
    def __init__(self, registry, queue, metrics, max_batch_size=32,
                 max_latency_ms=5.0, tracer=None, compile_tracker=None,
                 cost_registry=None):
        self.registry = registry
        self.queue = queue
        self.metrics = metrics
        self.max_batch_size = bucket_for(int(max_batch_size))
        self.max_latency_ms = float(max_latency_ms)
        self.observed = set()         # (signature, bucket) pairs dispatched
        self._obs_lock = threading.Lock()
        self._mask_ok = {}            # id(model) -> (model, takes-mask bool)
        self._thread = None
        # telemetry: spans per dispatch (parented under the originating
        # request's propagated context) + XLA compile accounting — the first
        # dispatch of an unobserved (signature, bucket) IS the compile
        self.tracer = tracer if tracer is not None else get_tracer()
        self.compile_tracker = compile_tracker
        # live cost attribution (telemetry/cost.py): first dispatch of a
        # bucket captures the executable's XLA costs; every dispatch feeds
        # the sampled dispatch_ms histogram
        self.cost_registry = cost_registry

    # ---- lifecycle --------------------------------------------------------
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return self            # one batcher thread owns dispatch
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serving-batcher")
        self._thread.start()
        return self

    def _run(self):
        while True:
            batch = self.queue.take_batch(self.max_batch_size,
                                          self.max_latency_ms / 1000.0)
            if batch is None:          # queue closed and fully drained
                break
            try:
                self._dispatch(batch)
            except Exception as e:     # last-resort: the loop must survive
                self.metrics.errors.add(len(batch))
                for r in batch:
                    r.fail(e)          # real cause, not a generic wrapper

    def join(self, timeout=None):
        """Wait until the queue is drained and the batcher thread exited.
        The thread exits only after take_batch returns None (closed + empty),
        so a plain join IS the drained barrier — bounded by `timeout` once,
        not twice."""
        if self._thread is not None:
            self._thread.join(timeout)

    # ---- dispatch ---------------------------------------------------------
    def _dispatch(self, batch):
        # drop requests already completed elsewhere (client cancel, chunk
        # sibling failure): dispatching them would burn compute and count
        # rows the caller will never receive
        batch = [r for r in batch if not r.future.done()]
        if not batch:
            return
        if batch[0].seq_bucket:
            try:
                model = self.registry.active_entry().model
            except Exception:
                model = None     # no model: the failure path below reports
            if model is not None and not self._accepts_mask(model):
                # duck-typed model whose output() takes no mask: demote to
                # legacy per-length dispatches (no cross-length coalescing)
                # instead of failing 100% of its 3-D requests on a
                # TypeError — previously-working custom models keep working
                for r in batch:
                    r.seq_bucket = False
                groups = {}
                for r in batch:
                    groups.setdefault(r.timesteps, []).append(r)
                for group in groups.values():
                    self._dispatch(group)
                return
        taken_at = monotonic_s()
        tracer = self.tracer
        # ONE batch span per coalesced dispatch, root of its OWN trace: the
        # N request traces attach by span LINKS (exported as Chrome-trace
        # flow events), not parent edges — the old shape parented the batch
        # under the first request only, so coalesced followers could not be
        # attributed to the batch that served them
        batch_span = tracer.start_span("batch", n_requests=len(batch))
        # queue-wait spans, recorded retroactively from the timestamps the
        # queue already stamps — each parented under its own request context
        # and linked BOTH ways to the batch span
        for r in batch:
            batch_span.add_link(r.trace_ctx)
            tracer.record_span(
                "admission", r.enqueued_at, taken_at, parent=r.trace_ctx,
                rows=r.rows, batch_span_id=batch_span.span_id,
                batch_trace_id=batch_span.trace_id).add_link(batch_span)
        # everything up to the split is inside the try: a failure (no model
        # deployed, bad input, model error) must fail THIS batch's futures,
        # never escape and kill the batcher thread
        dispatch_span = None
        try:
            # ONE registry snapshot per batch: model + the version-owned
            # preprocessing (a zip's normalizer) can never mix across a swap
            entry = self.registry.active_entry()
            version, model = entry.version, entry.model
            seq = batch[0].seq_bucket     # signature-homogeneous batch
            rows = sum(r.rows for r in batch)
            bucket = bucket_for(rows)
            mask = None
            if seq:
                # padded+masked sequence-length bucketing: pad every request
                # along time up to ONE power-of-two length bucket and ship a
                # [rows, len_bucket] validity mask, so requests of DIFFERENT
                # prompt lengths share a batch AND a compiled executable —
                # the executable set is bounded by (batch buckets) x (length
                # buckets), not by the lengths clients happen to send
                len_bucket = bucket_for(max(r.timesteps for r in batch))
                parts, mparts = [], []
                for r in batch:
                    t = r.timesteps
                    xr = r.x
                    if t < len_bucket:
                        pad = np.zeros(
                            (xr.shape[0], len_bucket - t) + xr.shape[2:],
                            dtype=xr.dtype)
                        xr = np.concatenate([xr, pad], axis=1)
                    parts.append(xr)
                    mr = np.zeros((xr.shape[0], len_bucket), np.float32)
                    mr[:, :t] = 1.0
                    mparts.append(mr)
                x = parts[0] if len(parts) == 1 else \
                    np.concatenate(parts, axis=0)
                mask = mparts[0] if len(mparts) == 1 else \
                    np.concatenate(mparts, axis=0)
                self.metrics.record_seq_bucket(len_bucket)
            else:
                x = batch[0].x if len(batch) == 1 else \
                    np.concatenate([r.x for r in batch], axis=0)
            if bucket > rows:
                pad = np.zeros((bucket - rows,) + x.shape[1:], dtype=x.dtype)
                x = np.concatenate([x, pad], axis=0)
                if mask is not None:    # pad rows: every position invalid
                    mask = np.concatenate(
                        [mask, np.zeros((bucket - rows, mask.shape[1]),
                                        np.float32)], axis=0)
            if entry.transform is not None:
                # shape-preserving (normalizers are per-element affine); the
                # normalizer's own float32 output dtype flows through —
                # casting back to the request dtype would truncate z-scores
                # to garbage for integer-typed requests. Runs ON DEVICE when
                # the version's normalizer lowers (etl.device_transform):
                # the raw request bytes cross the link once and the widening
                # affine is an XLA op, not a host NumPy pass
                x = entry.transform_features_device(x)
            # observed/compile-accounting key = the POST-transform batch the
            # model actually sees: warmup() replays these, so a hot-swapped
            # version compiles the executable dispatch will really use (a
            # raw-request key would warm an executable serving never runs
            # whenever the transform changes the dtype). Seq batches key on
            # (batch bucket, length bucket) — warm-up replays the mask too
            if mask is not None:
                key = (("seq",) + (tuple(x.shape[2:]), str(x.dtype)),
                       bucket, x.shape[1])
            else:
                key = ((tuple(x.shape[1:]), str(x.dtype)), bucket)
            with self._obs_lock:
                first_dispatch = key not in self.observed
            dispatch_span = tracer.start_span(
                "dispatch", parent=batch_span, bucket=bucket, rows=rows,
                compiled=first_dispatch)
            t0 = monotonic_s()
            out = np.asarray(model.output(x) if mask is None
                             else model.output(x, mask=mask))
            dispatch_ms = (monotonic_s() - t0) * 1000.0
            if entry.transform is not None:
                # regression models fitted with fit_labels=True predict in
                # normalized label space; un-normalize so clients receive
                # real-unit values (no-op for feature-only normalizers)
                out = np.asarray(entry.revert_outputs(out))
            dispatch_span.set_attribute("version", version).end()
        except Exception as e:
            self.metrics.errors.add(len(batch))
            if dispatch_span is not None:
                # a failed model dispatch is exactly the span an operator
                # wants to see in /trace — finish it instead of dropping it
                dispatch_span.set_attribute("error", type(e).__name__).end()
            batch_span.set_attribute("error", type(e).__name__).end()
            for r in batch:
                r.fail(e)
            return
        # record AFTER success: a malformed request (e.g. wrong feature
        # count) must not poison every future deploy/rollback warm-up
        with self._obs_lock:
            self.observed.add(key)
        if first_dispatch and self.compile_tracker is not None:
            # first dispatch of a new bucket = XLA compile + one execution;
            # attributed as the compile cost (the Julia-TPU paper's proxy)
            self.compile_tracker.record(dispatch_ms, bucket=bucket,
                                        phase="serve")
        if self.cost_registry is not None:
            label = self._cost_label(bucket, mask, x)
            if first_dispatch:
                self._capture_cost(model, x, mask, bucket, version, label)
            self.cost_registry.record_dispatch(label, dispatch_ms)
        self.registry.count_served(version, rows)
        self.metrics.record_batch(
            bucket, sum(1 for r in batch if r.count_as_request), rows)
        now = monotonic_s()
        batch_span.set_attribute("bucket", bucket).end(now)
        offset = 0
        for r in batch:
            pred = out[offset:offset + r.rows]
            if seq and pred.ndim >= 3 and pred.shape[1] == x.shape[1]:
                # time-distributed ([rows, T, out]) output: hand back only
                # the request's own (unpadded) timesteps; pooled 2-D outputs
                # pass through whole (ndim check keeps an n_out that happens
                # to equal the length bucket from being mis-sliced)
                pred = pred[:, :r.timesteps]
            r.complete({"prediction": pred, "version": version})
            # exemplar: the request's own trace id rides with its latency
            # observation (batcher thread has no current span of its own)
            self.metrics.record_latency(
                (now - r.enqueued_at) * 1000.0,
                trace_id=getattr(r.trace_ctx, "trace_id", None))
            offset += r.rows

    def _accepts_mask(self, model):
        """Whether model.output takes a `mask` kwarg (both nn network types
        do; duck-typed stand-ins may not). Cached per model object, bounded
        — the (model, flag) tuple pins the object so a recycled id() can
        never serve a stale answer."""
        key = id(model)
        hit = self._mask_ok.get(key)
        if hit is not None and hit[0] is model:
            return hit[1]
        try:
            params = inspect.signature(model.output).parameters
            ok = "mask" in params or any(
                p.kind == inspect.Parameter.VAR_KEYWORD
                for p in params.values())
        except (TypeError, ValueError):
            ok = False
        self._mask_ok[key] = (model, ok)
        while len(self._mask_ok) > 8:     # a handful of live versions
            self._mask_ok.pop(next(iter(self._mask_ok)))
        return ok

    # ---- cost attribution (telemetry/cost.py) ------------------------------
    @staticmethod
    def _cost_label(bucket, mask, x):
        """Stable per-executable label (no version: a hot-swap re-captures
        the SAME series, which is what makes deploy byte deltas visible)."""
        if mask is not None:
            return f"serve:b{bucket}xL{x.shape[1]}"
        return f"serve:b{bucket}"

    def _capture_cost(self, model, x, mask, bucket, version, label):
        """Attribute this bucket's executable: re-lower the model's jitted
        output from abstract shapes (dispatch cache untouched — the
        zero-recompile invariant holds) and record flops/bytes per padded
        sample. Duck-typed against both nn network `_jit_cache` layouts; a
        model without one (exotic stand-in) is simply not attributed."""
        try:
            import jax
            from ..telemetry.cost import abstractify
            cache = getattr(model, "_jit_cache", None)
            if cache is None:
                return
            rows = int(x.shape[0])
            ctx = getattr(model, "mesh_context", None)
            if ctx is not None:
                # MeshDispatcher pads rows to a data-axis multiple before
                # the inner executable sees them — lower the shape that
                # actually compiled, not one XLA never ran
                rows += (-rows) % ctx.data_size
            xa = jax.ShapeDtypeStruct(
                (rows,) + tuple(x.shape[1:]),
                jax.dtypes.canonicalize_dtype(x.dtype))
            ma = None
            if mask is not None:
                mdt = getattr(model, "_dtype", None)
                ma = jax.ShapeDtypeStruct(
                    (rows,) + tuple(mask.shape[1:]),
                    jax.dtypes.canonicalize_dtype(
                        mdt if mdt is not None else mask.dtype))
            pa = abstractify(model.params)
            st = abstractify(model.states)
            masked = mask is not None
            fn = cache.get(("output", False, masked))     # MultiLayerNetwork
            args = (pa, st, xa, ma)
            if fn is None:
                fn = cache.get(("output", 1, masked))     # ComputationGraph
                args = (pa, st, [xa], ma)
            if fn is None:
                return
            self.cost_registry.capture(label, fn, args, family="serve",
                                       samples=bucket, version=version)
        except Exception:
            # attribution is observability, never a dispatch failure — but a
            # seam without a cost row is counted, not silent
            self.cost_registry.capture_errors.inc(1, executable=label)

    def reset_observed(self):
        """Forget recorded (signature, bucket) pairs — used when the serving
        model's input contract changes and the old shapes no longer apply."""
        with self._obs_lock:
            self.observed.clear()

    # ---- warm-up (used by registry deploy/rollback) ------------------------
    def warmup(self, model, version=None):
        """Compile `model`'s executables for every (signature, bucket) this
        batcher has dispatched, so a hot-swapped version is never cold —
        seq batches replay their (batch bucket, length bucket) pair WITH a
        mask, the executable dispatch really uses. Warm-up compiles are real
        XLA compiles and are accounted as such (labeled phase="warmup"),
        keeping deploy cost visible. Each warmed bucket is also re-captured
        in the cost registry under `version`, which is what arms the
        deploy-time bytes-regression gauge (a quantized->f32 fallback shows
        up HERE, before traffic does)."""
        with self._obs_lock:
            observed = sorted(self.observed,
                              key=lambda sb: (str(sb[0]), sb[1]))
        for key in observed:
            if len(key) == 3:            # (("seq", feat, dtype), bucket, L)
                (_, feat, dtype), bucket, L = key
                zeros = np.zeros((bucket, L) + tuple(feat), dtype=dtype)
                mask = np.ones((bucket, L), np.float32)
                call = lambda: np.asarray(model.output(zeros, mask=mask))
            else:
                (shape, dtype), bucket = key
                zeros = np.zeros((bucket,) + tuple(shape), dtype=dtype)
                mask = None
                call = lambda: np.asarray(model.output(zeros))
            with self.tracer.span("warmup_compile", bucket=bucket):
                t0 = monotonic_s()
                call()                   # block until compiled + run
                if self.compile_tracker is not None:
                    self.compile_tracker.record(
                        (monotonic_s() - t0) * 1000.0, bucket=bucket,
                        phase="warmup")
            if self.cost_registry is not None:
                self._capture_cost(model, zeros, mask, bucket, version,
                                   self._cost_label(bucket, mask, zeros))
