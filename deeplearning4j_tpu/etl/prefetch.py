"""DevicePrefetcher: double/triple-buffered, optionally SHARDED device_put
ahead of the consuming train step — with a NARROW-WIRE ingest mode.

Keeping the TPU fed across the host/device boundary is the canonical input
bottleneck (the Julia-to-TPU paper's compile/transfer accounting, PAPERS.md),
and what data-parallel training actually consumes is a *per-replica sharded*
batch (the cross-replica sharding paper, arXiv:2004.13336). This iterator
stages batch N+1's host->device DMA while the device computes batch N:

- plain mode: `jax.device_put` to one device (the existing
  datasets.iterator.DevicePrefetchIterator behavior, with telemetry);
- sharded mode (`mesh=`): each array is placed with the data-axis
  NamedSharding from parallel/sharding.batch_sharding, so `network.fit` /
  ShardedTrainer / ParallelWrapper receive already-resident, already-sharded
  arrays and GSPMD inserts no resharding copy. Batches whose leading dim
  does not divide the data axis fall back to an unsharded put (the trainer's
  wrap-padding then handles them).

Ingest mode (fewer bytes over the host link, and no widening cast on the
worker's thread):

- `transfer_dtype=np.uint8` narrows the FEATURE arrays on the host before
  the DMA (4x fewer wire bytes than float32 for image pixels); pair it with
  a fused `network.set_ingest` / `device_transform` so the widening cast
  runs on-chip, where it is one fused XLA op instead of link bytes.
- `device_transform=fn` applies a traceable/jitted fn (e.g.
  `DeviceIngest.jit_apply_features`) to each feature array AFTER placement —
  in sharded mode the input already carries the data-axis NamedSharding, so
  GSPMD keeps the transform sharded. Prefer fusing into the train step via
  `network.set_ingest` (ONE executable); this hook is for consumers that
  can't fuse (evaluation, custom loops).
- `transfer_streams=S` splits each large feature array into S row chunks
  `device_put` concurrently: where per-transfer latency (not wire
  bandwidth) bounds throughput, parallel chunked DMA raises sustained h2d.
  On a v5e host it does (PR 39's chip run: 38.5 MB in eight chunks 7.7-8.2
  ms against 38-42 ms whole, under a running program or not). Joining the
  chunks takes a device program, which waits its turn behind whatever the
  device is running (485 ms behind a 502 ms train step, PR 39): the worker
  runs none. It hands the chunks over as they are, a `RowChunks` in the
  array's place, and whoever uses them joins them: `jnp.asarray` /
  `np.asarray` of one give the joined array, and
  `fit(steps_per_execution=K)` joins a group's chunks inside the one
  program that stacks its plan (nn/multistep.py `prepare_steps`; PR 40).
  Plain/device placement only; sharded placement keeps whole-array puts.

Telemetry: `etl_h2d_bytes_total` counts the bytes that ACTUALLY cross the
link (post-narrowing). Every leg of a batch is a `Tracer.phase`
(telemetry/trace.py): a profiler annotation `dl4j:<leg>` on the thread that
ran it, so a profiler session shows it on the device's clock, and the
histogram `<leg>_ms{pipeline=<name>}`, tracer on or off. On the worker's
thread: `etl_h2d` (the puts and the fence behind them — "DMA done": a whole
array's put, or a chunked array's parts; no device program is in it, so
the leg reads what the bytes need whatever the device is running),
`etl_device_transform` (only with a `device_transform`: a chunked array's
join, the transform and their fence, which does wait for a device program
and so behind a running step), `etl_producer_blocked` (`put` into a full queue:
the healthy state, the worker is ahead). On the consumer's thread:
`etl_consumer_wait` (`next()` blocked on an empty queue), the histogram and
`etl_queue_depth` shared with the pipeline executor. With the tracer on,
every batch also records ONE `ingest` ring span whose `transfer_ms` /
`transform_ms` attributes are the same clock reads, so `/trace` shows where
ingest time goes.

What the wait says depends on the consumer. One that waits for its step
before it pulls again (`fit_batch`, an evaluation loop) starves the device
exactly while it waits: `etl_consumer_wait_ms` ~ 0 means the device never
starves. `fit(steps_per_execution=K)` keeps one execution queued behind the
running one and waits for the device in a phase of its own
(`fit_execution_wait`, nn/multistep.py), with its next group already
pulled: there too the wait in `next()` is time the worker did not keep up,
and the starvation signal is `fit_executions_ahead_total{ahead="0"}`. A
producer error is re-raised exactly once, from next()/has_next() or — if
the consumer already stopped pulling — from reset()/close().
"""
from __future__ import annotations

import queue
import threading

import jax
import numpy as np

from ..datasets.dataset import DataSet, MultiDataSet
from ..datasets.iterator.base import DataSetIterator
from ..telemetry.registry import get_registry
from ..telemetry.trace import get_tracer


class RowChunks:
    """One array on one device, as the row chunks it crossed the link in,
    not joined yet. What `DevicePrefetcher(transfer_streams=S)` hands over
    in a large array's place: joining takes a device program, and that is
    the consumer's to run — `jnp.asarray` / `np.asarray` of this give the
    joined array, and a jitted function takes it as a pytree of its parts
    (how `prepare_steps` joins and stacks a group in one program)."""

    def __init__(self, parts):
        self.parts = tuple(parts)

    @property
    def shape(self):
        return (sum(p.shape[0] for p in self.parts),) + self.parts[0].shape[1:]

    @property
    def dtype(self):
        return self.parts[0].dtype

    @property
    def ndim(self):
        return self.parts[0].ndim

    def __jax_array__(self):
        return jax.numpy.concatenate(self.parts, axis=0)

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.__jax_array__(), dtype)


jax.tree_util.register_pytree_node(
    RowChunks, lambda c: (c.parts, None), lambda _, parts: RowChunks(parts))


class DevicePrefetcher(DataSetIterator):
    _SENTINEL = object()

    def __init__(self, underlying, queue_size=2, device=None, mesh=None,
                 sharding=None, registry=None, name="prefetch",
                 transfer_dtype=None, device_transform=None,
                 transfer_streams=1, tracer=None):
        if sum(x is not None for x in (device, mesh, sharding)) > 1:
            raise ValueError("pass at most one of device/mesh/sharding")
        self.underlying = underlying
        self.queue_size = max(1, int(queue_size))
        self.device = device
        self.mesh = mesh
        self.sharding = sharding
        self.name = str(name)
        self._labels = {"pipeline": self.name}
        self.transfer_dtype = transfer_dtype
        self.device_transform = device_transform
        self.transfer_streams = max(1, int(transfer_streams))
        self._pool = None           # lazy ThreadPoolExecutor for streams > 1
        reg = registry if registry is not None else get_registry()
        self.tracer = tracer if tracer is not None else get_tracer()
        self._m_wait = reg.histogram(
            "etl_consumer_wait_ms",
            "Time the consumer blocked waiting for the next ETL batch")
        self._m_h2d = reg.histogram(
            "etl_h2d_ms", "One batch's host->device puts and the fence "
            "behind them, ms")
        self._m_transform = reg.histogram(
            "etl_device_transform_ms",
            "One batch's device_transform (and the join of a chunked "
            "array before it) until ready, ms")
        self._m_blocked = reg.histogram(
            "etl_producer_blocked_ms", "Time the prefetch worker held a "
            "staged batch before the queue took it (full queue: the "
            "worker is ahead), ms")
        self._m_depth = reg.gauge(
            "etl_queue_depth", "Chunks queued inside ETL pipelines")
        self._m_bytes = reg.counter(
            "etl_h2d_bytes_total",
            "Bytes transferred host->device by ETL prefetchers "
            "(post-narrowing: what actually crossed the link)")
        self._error_raised = False
        self._start()

    # ---- placement ---------------------------------------------------------
    def _placement_for(self, a):
        if self.sharding is not None:
            return self.sharding
        if self.mesh is not None:
            from ..parallel.sharding import DATA_AXIS, batch_sharding
            n = self.mesh.shape[DATA_AXIS]
            if a.shape and a.shape[0] % n == 0:
                return batch_sharding(self.mesh, max(a.ndim, 1))
            return None             # non-divisible batch: unsharded put
        return self.device

    def _transfer(self, a, narrow):
        """One host array -> device, returning (on_device, host_bytes):
        a `jax.Array`, or a `RowChunks` of them. Features narrow to
        `transfer_dtype` BEFORE the DMA; large plain-mode arrays split into
        `transfer_streams` concurrent chunk puts (latency hiding on links
        where per-transfer cost, not bandwidth, binds) and stay in chunks:
        their join is a device program, which would hold this thread
        behind whatever program the device runs."""
        a = np.asarray(a)
        if narrow and self.transfer_dtype is not None:
            a = np.asarray(a, self.transfer_dtype)
        placement = self._placement_for(a)
        chunkable = (self.transfer_streams > 1
                     and self.sharding is None and self.mesh is None
                     and a.ndim >= 1 and a.shape[0] >= self.transfer_streams
                     and a.nbytes >= (1 << 20))
        if not chunkable:
            return jax.device_put(a, placement), a.nbytes
        chunks = np.array_split(a, self.transfer_streams)
        futs = [self._pool.submit(jax.device_put, c, placement)
                for c in chunks]
        return RowChunks(f.result() for f in futs), a.nbytes

    def _leg(self, name, histogram):
        """One leg of a batch as a phase: `dl4j:<name>` on the calling
        thread + `histogram{pipeline}`; the ring keeps `ingest` instead."""
        return self.tracer.phase(name, histogram=histogram, fold=True,
                                 labels=self._labels)

    def _put(self, ds):
        nbytes = 0

        def put(a, narrow=False):
            nonlocal nbytes
            if a is None:
                return None
            dev, n = self._transfer(a, narrow)
            nbytes += n
            return dev
        with self._leg("etl_h2d", self._m_h2d) as h2d:
            if isinstance(ds, MultiDataSet):
                out = MultiDataSet(
                    [put(f, narrow=True) for f in ds.features],
                    [put(l) for l in ds.labels],
                    None if ds.features_masks is None else
                    [None if m is None else put(m)
                     for m in ds.features_masks],
                    None if ds.labels_masks is None else
                    [None if m is None else put(m) for m in ds.labels_masks])
                feats = out.features
            else:
                out = DataSet(put(ds.features, narrow=True), put(ds.labels),
                              put(ds.features_mask), put(ds.labels_mask))
                feats = [out.features]
            # fence inside the leg: device_put is async, and the leg must
            # mean "DMA done", not "DMA enqueued" (this blocks only the
            # prefetch worker — the consumer keeps computing). A RowChunks
            # is a pytree of its parts: the puts are all there is to fence
            jax.block_until_ready([f for f in feats if f is not None])
        end, transform_ms = h2d.end_mono, 0.0
        if self.device_transform is not None:
            def tf(f):              # a chunked array joined, then transformed
                return self.device_transform(jax.numpy.asarray(f))
            with self._leg("etl_device_transform", self._m_transform) as dt:
                if isinstance(out, MultiDataSet):
                    out = MultiDataSet([tf(f) for f in out.features],
                                       out.labels, out.features_masks,
                                       out.labels_masks)
                else:
                    out = DataSet(tf(out.features), out.labels,
                                  out.features_mask, out.labels_mask)
                jax.block_until_ready(out.features)
            end, transform_ms = dt.end_mono, dt.duration_ms
        self._m_bytes.inc(nbytes, pipeline=self.name)
        self.tracer.record_span(
            "ingest", h2d.start_mono, end, pipeline=self.name, bytes=nbytes,
            transfer_ms=round(h2d.duration_ms, 3),
            transform_ms=round(transform_ms, 3))
        return out

    # ---- worker ------------------------------------------------------------
    def _start(self):
        self._queue = queue.Queue(maxsize=self.queue_size)
        self._error = None
        self._error_raised = False
        self._stop = threading.Event()
        stop, q = self._stop, self._queue
        if self.transfer_streams > 1 and self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                max_workers=self.transfer_streams,
                thread_name_prefix=f"{self.name}-h2d")

        def worker():
            try:
                while not stop.is_set() and self.underlying.has_next():
                    item = self._put(self.underlying.next())
                    with self._leg("etl_producer_blocked", self._m_blocked):
                        while not stop.is_set():
                            try:
                                q.put(item, timeout=0.1)
                                break
                            except queue.Full:
                                continue
            except Exception as e:
                self._error = e
            finally:
                while True:     # the sentinel must land or the consumer hangs
                    try:
                        q.put(self._SENTINEL, timeout=0.1)
                        break
                    except queue.Full:
                        if stop.is_set():
                            break

        self._thread = threading.Thread(target=worker, daemon=True,
                                        name=f"{self.name}-device")
        self._thread.start()
        self._peek = None
        self._done = False
        self._consumed = False
        self._pending_error = None
        self._fill_peek()

    def _fill_peek(self):
        if self._done:
            return
        with self._leg("etl_consumer_wait", self._m_wait):
            v = self._queue.get()
        self._m_depth.set(self._queue.qsize(), pipeline=self.name)
        if v is self._SENTINEL:
            # exhausted; an error is held until the already-prefetched batch
            # is delivered, then surfaced exactly once (has_next or
            # reset/close, whichever the consumer reaches first)
            self._done = True
            self._peek = None
            self._pending_error = self._error
        else:
            self._peek = v

    def _claim_error(self):
        """The not-yet-raised producer error, claimed exactly once."""
        if self._error_raised:
            return None
        err = self._pending_error if self._pending_error is not None \
            else self._error
        if err is not None:
            self._error_raised = True
            self._pending_error = None
        return err

    # ---- DataSetIterator contract ------------------------------------------
    def next(self):
        v = self._peek
        self._consumed = True
        self._fill_peek()
        return v

    def has_next(self):
        if self._done:
            err = self._claim_error()
            if err is not None:
                raise err
        return not self._done

    def batch(self):
        return self.underlying.batch()

    def _join_worker(self, what):
        if self._thread is not None and self._thread.is_alive():
            self._stop.set()
            # the worker may legitimately block inside a large device_put;
            # interrupting mid-transfer would race the shared iterator
            self._thread.join(timeout=60)
            if self._thread.is_alive():
                raise RuntimeError(
                    f"DevicePrefetcher worker did not stop within 60s; "
                    f"cannot safely {what}")

    def close(self):
        """Stop the worker; surface a swallowed producer error exactly once."""
        self._join_worker("close")
        self._done = True
        self._peek = None
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        err = self._claim_error()
        if err is not None:
            raise err

    def reset(self):
        if not self._consumed and not self._done:
            return                  # fresh iterator: keep the prefetched data
        self._join_worker("reset")
        err = self._claim_error()
        self.underlying.reset()
        self._start()
        if err is not None:
            raise err
