"""ETL subsystem tests: schema/transform serialization, streaming
normalizers, the parallel pipeline executor (ordering, backpressure, error
propagation, telemetry), sharded device prefetch, and the end-to-end
CSV -> TransformProcess -> DataNormalizer -> ParallelPipelineExecutor ->
DevicePrefetcher -> network.fit acceptance path.

Mirrors the coverage the reference stack gets from the external DataVec
library's transform tests (org.datavec.api.transform.*) plus nd4j's
NormalizerStandardize/MinMaxScaler tests — here with the TPU-specific
additions: vectorized batch execution, mesh-sharded placement, and the
consumer wait-time histogram (deterministic via util.time_source
.ManualClock).
"""
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator.base import ListDataSetIterator
from deeplearning4j_tpu.datasets.records.reader import (CollectionRecordReader,
                                                        RecordReader)
from deeplearning4j_tpu.etl import (ColumnType, DataNormalizer,
                                    DevicePrefetcher, NormalizerMinMaxScaler,
                                    NormalizerStandardize,
                                    ParallelPipelineExecutor, Schema,
                                    TransformProcess)
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
from deeplearning4j_tpu.util.model_serializer import ModelSerializer
from deeplearning4j_tpu.util.time_source import (ManualClock,
                                                 TimeSourceProvider)


@pytest.fixture
def manual_clock():
    clock = ManualClock(start_s=1000.0)
    TimeSourceProvider.set_instance(clock)
    try:
        yield clock
    finally:
        TimeSourceProvider.reset()


def _demo_schema():
    return (Schema.builder().add_numeric("a", "b")
            .add_categorical("color", ["red", "green", "blue"])
            .add_integer("label").build())


# ------------------------------------------------------------------- schema

def test_schema_builder_and_json_round_trip():
    s = _demo_schema()
    assert s.names() == ["a", "b", "color", "label"]
    assert s.column("color").kind == ColumnType.CATEGORICAL
    assert s.column("color").categories == ["red", "green", "blue"]
    assert s.index_of("label") == 3
    s2 = Schema.from_json(s.to_json())
    assert s2 == s

    with pytest.raises(ValueError):
        Schema.builder().add_numeric("x", "x").build()   # duplicate names


def test_schema_batch_round_trip():
    s = _demo_schema()
    recs = [[1.0, 2.0, "red", 0], [3.0, 4.0, "blue", 2]]
    batch = s.to_batch(recs)
    assert batch["a"].dtype == np.float64
    assert batch["label"].dtype == np.int64
    assert list(batch["color"]) == ["red", "blue"]
    assert s.to_records(batch) == recs


# ---------------------------------------------------------------- transform

def test_transform_ops_chain():
    tp = (TransformProcess.builder(_demo_schema())
          .categorical_to_one_hot("color")
          .derived_column("ab", "mul", ["a", "b"])
          .min_max_normalize("a", 0.0, 10.0)
          .rename_column("b", "bee")
          .remove_columns("label")
          .build())
    assert tp.final_schema().names() == [
        "a", "bee", "color[red]", "color[green]", "color[blue]", "ab"]
    out = tp.execute([[5.0, 3.0, "green", 1]])
    np.testing.assert_allclose(out[0], [0.5, 3.0, 0.0, 1.0, 0.0, 15.0])


def test_transform_filter_and_categorical_to_integer():
    tp = (TransformProcess.builder(_demo_schema())
          .filter_rows("a", "lt", 0.0)          # REMOVE rows where a < 0
          .categorical_to_integer("color")
          .standardize("b", mean=2.0, std=2.0)
          .build())
    out = tp.execute([[1.0, 4.0, "blue", 0],
                      [-1.0, 0.0, "red", 1],    # filtered out
                      [2.0, 0.0, "red", 2]])
    assert len(out) == 2
    np.testing.assert_allclose(out[0], [1.0, 1.0, 2, 0])
    np.testing.assert_allclose(out[1], [2.0, -1.0, 0, 2])
    assert tp.final_schema().column("color").kind == ColumnType.INTEGER


def test_transform_json_round_trip_and_equality():
    tp = (TransformProcess.builder(_demo_schema())
          .categorical_to_one_hot("color")
          .filter_rows("a", "ge", 100.0)
          .derived_column("lg", "log", ["b"])
          .standardize("a", 1.0, 2.0)
          .sequence_window(4, 2)
          .build())
    tp2 = TransformProcess.from_json(tp.to_json())
    assert tp2 == tp
    assert tp2.final_schema() == tp.final_schema()
    recs = [[float(i), float(i + 1), "red", 0] for i in range(8)]
    b1 = tp.execute_batch(tp.initial_schema.to_batch(recs))
    b2 = tp2.execute_batch(tp2.initial_schema.to_batch(recs))
    for k in b1:
        np.testing.assert_allclose(b1[k].astype(float),
                                   b2[k].astype(float))


def test_transform_validates_eagerly():
    with pytest.raises(KeyError):
        TransformProcess.builder(_demo_schema()) \
            .standardize("missing", 0, 1).build()
    with pytest.raises(ValueError):
        # sequence_window over a still-categorical column
        TransformProcess.builder(_demo_schema()).sequence_window(2).build()


def test_sequence_window_assembles_time_major():
    schema = Schema.builder().add_numeric("x", "y").build()
    tp = (TransformProcess.builder(schema)
          .sequence_window(3, 1).build())
    reader = CollectionRecordReader(
        [[float(i), float(10 * i)] for i in range(6)])
    ex = ParallelPipelineExecutor(reader, tp, batch_size=6, workers=1,
                                  registry=MetricsRegistry())
    ds = ex.next()
    assert ds.features.shape == (4, 3, 2)     # [windows, time, features]
    np.testing.assert_allclose(ds.features[1, :, 0], [1, 2, 3])
    np.testing.assert_allclose(ds.features[1, :, 1], [10, 20, 30])
    ex.close()


# --------------------------------------------------------------- normalizer

def test_standardize_streaming_matches_whole_data():
    rng = np.random.default_rng(0)
    data = rng.normal(3.0, 2.0, size=(257, 5)).astype(np.float32)
    it = ListDataSetIterator(DataSet(data, data).batch_by(16))  # ragged tail
    nz = NormalizerStandardize().fit(it)
    np.testing.assert_allclose(nz.mean, data.mean(axis=0), rtol=1e-5)
    np.testing.assert_allclose(nz.std, data.std(axis=0, ddof=1), rtol=1e-4)
    out = nz.transform(DataSet(data, data))
    assert abs(float(out.features.mean())) < 1e-5
    back = nz.revert(out)
    np.testing.assert_allclose(back.features, data, atol=1e-4)
    # labels untouched unless fit_labels
    np.testing.assert_allclose(out.labels, data)


def test_min_max_scaler_and_fit_labels():
    x = np.array([[0.0, 10.0], [5.0, 20.0], [10.0, 40.0]], np.float32)
    y = np.array([[1.0], [2.0], [3.0]], np.float32)
    nz = NormalizerMinMaxScaler(fit_labels=True).fit(DataSet(x, y))
    out = nz.transform(DataSet(x, y))
    np.testing.assert_allclose(out.features,
                               [[0, 0], [0.5, 1 / 3], [1, 1]], atol=1e-6)
    np.testing.assert_allclose(out.labels, [[0], [0.5], [1]], atol=1e-6)
    np.testing.assert_allclose(nz.revert_labels(out.labels), y, atol=1e-6)
    rt = DataNormalizer.from_json(nz.to_json())
    np.testing.assert_allclose(rt.transform(DataSet(x, y)).features,
                               out.features, atol=1e-6)


def test_normalizer_rides_in_model_zip(tmp_path):
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    DenseLayer, OutputLayer,
                                    MultiLayerNetwork, Sgd)
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=4, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="MCXENT"))
            .input_type(InputType.feed_forward(3)).build())
    net = MultiLayerNetwork(conf).init()
    data = np.random.default_rng(1).normal(5, 3, (32, 3)).astype(np.float32)
    nz = NormalizerStandardize().fit(DataSet(data, data))
    p = str(tmp_path / "model.zip")
    ModelSerializer.write_model(net, p, normalizer=nz)
    rt = ModelSerializer.restore_normalizer(p)
    assert isinstance(rt, NormalizerStandardize)
    np.testing.assert_allclose(rt.mean, nz.mean, rtol=1e-6)
    # a zip without one: None
    p2 = str(tmp_path / "bare.zip")
    ModelSerializer.write_model(net, p2)
    assert ModelSerializer.restore_normalizer(p2) is None
    # add_normalizer retrofits an existing zip
    ModelSerializer.add_normalizer(p2, nz)
    assert ModelSerializer.restore_normalizer(p2) is not None
    assert ModelSerializer.read_format(p2)["model_class"] \
        == "MultiLayerNetwork"


# ----------------------------------------------------------------- pipeline

def _simple_records(n, width=3):
    return [[float(i)] * width for i in range(n)]


def test_pipeline_ordered_matches_sequential():
    recs = _simple_records(40)
    ex = ParallelPipelineExecutor(CollectionRecordReader(recs),
                                  batch_size=8, workers=4, ordered=True,
                                  registry=MetricsRegistry())
    batches = list(ex)
    assert len(batches) == 5
    flat = np.concatenate([b.features for b in batches])
    np.testing.assert_allclose(flat, np.asarray(recs, np.float32))
    # reset replays identically
    ex.reset()
    flat2 = np.concatenate([b.features for b in ex])
    np.testing.assert_allclose(flat2, flat)
    ex.close()


def test_pipeline_unordered_vs_ordered_delivery():
    """Chunk 0's worker blocks until chunk 1 has been PROCESSED: unordered
    delivery hands the consumer chunk 1 first, ordered delivery still waits
    for chunk 0."""
    def make(ordered):
        gate = threading.Event()

        def assemble(records):
            tag = records[0][0]
            if tag == 0.0:
                assert gate.wait(20), "chunk 1 never processed"
            else:
                gate.set()
            arr = np.full((len(records), 2), tag, np.float32)
            return DataSet(arr, arr)
        reader = CollectionRecordReader([[0.0], [0.0], [1.0], [1.0]])
        return ParallelPipelineExecutor(reader, batch_size=2, workers=2,
                                        ordered=ordered, assemble=assemble,
                                        registry=MetricsRegistry())

    ex = make(ordered=False)
    first = ex.next().features[0, 0]
    assert first == 1.0                       # fast chunk overtakes
    assert ex.next().features[0, 0] == 0.0
    ex.close()

    ex = make(ordered=True)
    assert ex.next().features[0, 0] == 0.0    # source order preserved
    assert ex.next().features[0, 0] == 1.0
    ex.close()


def test_pipeline_filtered_out_chunk_is_skipped():
    schema = Schema.builder().add_numeric("x").build()
    tp = (TransformProcess.builder(schema)
          .filter_rows("x", "lt", 2.0).build())     # removes records 0, 1
    ex = ParallelPipelineExecutor(CollectionRecordReader(_simple_records(6, 1)),
                                  tp, batch_size=2, workers=2,
                                  registry=MetricsRegistry())
    batches = list(ex)
    flat = sorted(float(v) for b in batches for v in b.features.ravel())
    assert flat == [2.0, 3.0, 4.0, 5.0]       # chunk 0 fully filtered away
    ex.close()


class _BoomReader(RecordReader):
    """Fails at record `boom` on the first pass only."""

    def __init__(self, n, boom, exc=None):
        self.n, self.boom = n, boom
        self.exc = exc or RuntimeError("reader exploded")
        self._i = 0
        self._armed = True

    def has_next(self):
        return self._i < self.n

    def next_record(self):
        if self._armed and self._i == self.boom:
            raise self.exc
        self._i += 1
        return [float(self._i)]

    def reset(self):
        self._i = 0
        self._armed = False


def test_pipeline_reader_error_reaches_consumer_exactly_once():
    ex = ParallelPipelineExecutor(_BoomReader(20, boom=10), batch_size=2,
                                  workers=2, registry=MetricsRegistry())
    with pytest.raises(RuntimeError, match="reader exploded"):
        list(ex)
    assert not ex.has_next()                  # no double raise
    ex.close()                                # no double raise here either


def test_pipeline_runtimeerror_from_reader_is_not_swallowed():
    """RuntimeError is also what a closed MagicQueue raises internally; a
    reader's own RuntimeError must still reach the consumer."""
    ex = ParallelPipelineExecutor(
        _BoomReader(20, boom=4, exc=RuntimeError("custom runtime issue")),
        batch_size=2, workers=1, registry=MetricsRegistry())
    with pytest.raises(RuntimeError, match="custom runtime issue"):
        list(ex)
    ex.close()


def test_pipeline_worker_error_surfaces_on_close_when_consumer_stopped():
    """A transform failure after the consumer stops pulling must not be
    swallowed: close() re-raises it (exactly once)."""
    def assemble(records):
        if records[0][0] >= 4.0:
            raise ValueError("transform exploded")
        arr = np.asarray(records, np.float32)
        return DataSet(arr, arr)

    ex = ParallelPipelineExecutor(CollectionRecordReader(_simple_records(8, 1)),
                                  batch_size=2, workers=1, assemble=assemble,
                                  ordered=True, registry=MetricsRegistry())
    assert ex.next().num_examples() == 2      # consume one batch, then stop
    deadline = time.monotonic() + 20
    while not ex._out.has_error() and time.monotonic() < deadline:
        time.sleep(0.01)
    with pytest.raises(ValueError, match="transform exploded"):
        ex.close()
    ex.close()                                # second close: clean no-op


def test_pipeline_close_mid_stream_then_reset():
    """Deterministic close(): stops with batches still queued, joins all
    threads; reset() afterwards restarts a full clean pass."""
    ex = ParallelPipelineExecutor(CollectionRecordReader(_simple_records(64)),
                                  batch_size=4, workers=3, queue_capacity=2,
                                  registry=MetricsRegistry())
    assert ex.next() is not None
    ex.close()
    assert all(not t.is_alive() for t in ex._threads)
    assert not ex.has_next()
    ex.reset()
    assert sum(1 for _ in ex) == 16
    ex.close()


def test_pipeline_inline_mode_and_telemetry_counters():
    reg = MetricsRegistry()
    ex = ParallelPipelineExecutor(CollectionRecordReader(_simple_records(20)),
                                  batch_size=5, workers=0, name="inline",
                                  registry=reg)
    assert sum(1 for _ in ex) == 4
    assert reg.counter("etl_batches_total").get(pipeline="inline") == 4
    assert reg.counter("etl_records_total").get(pipeline="inline") == 20
    assert reg.histogram("etl_consumer_wait_ms").count(pipeline="inline") > 0


class _SlowClockReader(RecordReader):
    """Reader whose per-record cost exists only on the ManualClock: each
    record advances the fake clock by `cost_s` — the deterministic stand-in
    for a slow decode/augment stage."""

    def __init__(self, n, clock, cost_s, width=3):
        self.n, self.clock, self.cost_s, self.width = n, clock, cost_s, width
        self._i = 0

    def has_next(self):
        return self._i < self.n

    def next_record(self):
        self.clock.advance(self.cost_s)
        self._i += 1
        return [float(self._i)] * self.width

    def reset(self):
        self._i = 0


def test_consumer_wait_histogram_shrinks_with_prefetch(manual_clock):
    """The acceptance metric for the whole subsystem: with the pipeline
    prefetching (workers > 0, buffered), the consumer's recorded wait is ~0;
    with everything inline (workers=0), the consumer waits for the full
    read cost of every batch. Deterministic via ManualClock — the only
    clock advances are the slow reader's."""
    n_batches, batch, cost_s = 4, 8, 0.005
    reg = MetricsRegistry()

    # ---- prefetch OFF: inline stages run inside next() -------------------
    ex = ParallelPipelineExecutor(
        _SlowClockReader(n_batches * batch, manual_clock, cost_s),
        batch_size=batch, workers=0, name="off", registry=reg)
    assert sum(1 for _ in ex) == n_batches
    off = reg.histogram("etl_consumer_wait_ms")
    off_sum = off.sum(pipeline="off")
    assert off_sum >= n_batches * batch * cost_s * 1000.0 * 0.99

    # ---- prefetch ON: buffer everything, then consume --------------------
    ex = ParallelPipelineExecutor(
        _SlowClockReader(n_batches * batch, manual_clock, cost_s),
        batch_size=batch, workers=2, queue_capacity=n_batches + 1,
        name="on", registry=reg)
    deadline = time.monotonic() + 20
    while ex._out.depth() < n_batches and time.monotonic() < deadline:
        time.sleep(0.01)                     # real time; fake clock frozen
    assert sum(1 for _ in ex) == n_batches
    on_sum = reg.histogram("etl_consumer_wait_ms").sum(pipeline="on")
    assert on_sum < off_sum * 0.01, \
        f"prefetch-on wait {on_sum}ms not << prefetch-off wait {off_sum}ms"
    ex.close()


# ----------------------------------------------------------- device prefetch

def test_device_prefetcher_batches_are_resident():
    import jax
    data = DataSet(np.ones((16, 4), np.float32), np.ones((16, 2), np.float32))
    pf = DevicePrefetcher(ListDataSetIterator(data.batch_by(4)), queue_size=2,
                          registry=MetricsRegistry())
    seen = list(pf)
    assert len(seen) == 4
    for ds in seen:
        assert isinstance(ds.features, jax.Array)
        assert ds.features.devices() == {jax.devices()[0]}
    pf.close()


def test_device_prefetcher_legs_are_phases_labeled_by_pipeline():
    """Every leg of a batch goes through Tracer.phase into its own
    histogram under pipeline=<name>, once: the worker's etl_h2d and
    etl_producer_blocked per batch (etl_device_transform only with a
    transform), the consumer's wait per pull — not also as a bare
    observation, which a bare read would now count twice."""
    reg = MetricsRegistry()
    x = np.ones((4, 3), np.float32)
    pf = DevicePrefetcher(ListDataSetIterator([DataSet(x, x)] * 3),
                          registry=reg, name="legs")
    assert sum(1 for _ in pf) == 3
    pf.close()
    for name, n in (("etl_h2d_ms", 3), ("etl_producer_blocked_ms", 3),
                    ("etl_device_transform_ms", 0),
                    ("etl_consumer_wait_ms", 4)):   # 3 batches + the end
        h = reg.histogram(name)
        assert h.count(pipeline="legs") == n == h.count(), name
        assert [ls for ls, _ in h.series()] == [{"pipeline": "legs"}] * (n > 0)


def test_device_prefetcher_phases_on_the_profilers_clock(tmp_path):
    """Under a profiler session the worker's transfer and the consumer's
    wait are events of the profiler's own trace, on two host lines (two
    threads): dl4j:etl_h2d / dl4j:etl_producer_blocked where the worker
    ran them, dl4j:etl_consumer_wait where next() was called."""
    import glob
    import jax
    from jax.profiler import ProfileData
    x = np.ones((8, 4), np.float32)

    class Slow(ListDataSetIterator):
        def next(self):
            time.sleep(0.01)
            return super().next()

    jax.profiler.start_trace(str(tmp_path))
    try:
        pf = DevicePrefetcher(Slow([DataSet(x, x)] * 4),
                              registry=MetricsRegistry(), name="prof")
        assert sum(1 for _ in pf) == 4
        pf.close()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):      # a line a thread
                for e in line.events:
                    if e.name.startswith("dl4j:etl_"):
                        lines.setdefault(e.name, set()).add(i)
    assert set(lines) == {"dl4j:etl_h2d", "dl4j:etl_producer_blocked",
                          "dl4j:etl_consumer_wait"}
    assert lines["dl4j:etl_h2d"] == lines["dl4j:etl_producer_blocked"]
    assert not lines["dl4j:etl_h2d"] & lines["dl4j:etl_consumer_wait"]


def test_device_prefetcher_sharded_placement():
    """Acceptance: sharded prefetch places each batch shard on its mesh
    device — asserted via .devices() / committed placement."""
    import jax
    from deeplearning4j_tpu.parallel.sharding import (DATA_AXIS,
                                                      batch_sharding,
                                                      make_mesh)
    mesh = make_mesh()
    n_dev = mesh.shape[DATA_AXIS]
    assert n_dev == 8                       # conftest virtual mesh
    data = DataSet(np.random.default_rng(0).normal(size=(32, 4))
                   .astype(np.float32),
                   np.ones((32, 2), np.float32))
    pf = DevicePrefetcher(ListDataSetIterator(data.batch_by(16)),
                          queue_size=3, mesh=mesh,
                          registry=MetricsRegistry())
    for ds in pf:
        for arr in (ds.features, ds.labels):
            assert set(arr.devices()) == set(mesh.devices.ravel())
            assert arr.sharding == batch_sharding(mesh, arr.ndim)
            assert arr.committed
            # each device holds exactly its 1/n_dev slice of the batch
            for shard in arr.addressable_shards:
                assert shard.data.shape[0] == arr.shape[0] // n_dev
    pf.close()


def test_device_prefetcher_non_divisible_batch_falls_back_unsharded():
    from deeplearning4j_tpu.parallel.sharding import make_mesh
    data = DataSet(np.ones((10, 4), np.float32), np.ones((10, 2), np.float32))
    pf = DevicePrefetcher(ListDataSetIterator([data.slice(0, 10)]),
                          mesh=make_mesh(), registry=MetricsRegistry())
    ds = pf.next()
    assert len(ds.features.devices()) == 1   # unsharded put; trainer pads
    pf.close()


def test_device_prefetcher_error_on_close_exactly_once():
    class Boom(ListDataSetIterator):
        def next(self):
            if self._i == 1:
                raise RuntimeError("producer died")
            return super().next()

    data = DataSet(np.ones((12, 3), np.float32))
    pf = DevicePrefetcher(Boom(data.batch_by(4)), queue_size=4,
                          registry=MetricsRegistry())
    pf.next()                                # consumer pulls once, then stops
    deadline = time.monotonic() + 20
    while pf._error is None and time.monotonic() < deadline:
        time.sleep(0.01)
    with pytest.raises(RuntimeError, match="producer died"):
        pf.close()
    pf.close()                               # second close: clean


def test_device_prefetcher_error_once_behind_a_chunked_batch():
    """A chunked batch is handed over in its chunks, the worker having
    fenced their transfers and nothing else; the producer's error behind
    it still reaches the consumer exactly once, after the staged batch."""
    class Boom(ListDataSetIterator):
        def next(self):
            if self._i == 1:
                raise RuntimeError("producer died")
            return super().next()

    x = np.arange(3 * 32 * 8192, dtype=np.float32).reshape(96, 8192)
    pf = DevicePrefetcher(Boom(DataSet(x, x[:, :2]).batch_by(32)),
                          queue_size=2, registry=MetricsRegistry(),
                          transfer_streams=4)     # 1 MiB a batch: chunked
    assert pf.has_next()
    np.testing.assert_array_equal(np.asarray(pf.next().features), x[:32])
    with pytest.raises(RuntimeError, match="producer died"):
        pf.has_next()
    assert not pf.has_next()                 # raised once, then just ended
    pf.close()                               # and not again on close


def test_fit_prefetch_knob():
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    DenseLayer, OutputLayer,
                                    MultiLayerNetwork, Adam)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(axis=1) > 0).astype(int)]
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="MCXENT"))
            .input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    it = ListDataSetIterator(DataSet(x, y).batch_by(32))
    net.fit(it, epochs=10, prefetch=2)
    assert net.evaluate(it).accuracy() > 0.9


# ------------------------------------------------------------- end to end

def test_smoke_etl_tool():
    """CSV -> TransformProcess -> normalizer -> parallel pipeline -> device
    prefetch -> network.fit, with zero steady-state recompiles (fast
    variant of tools/smoke_etl.py, mirroring smoke_serving/smoke_telemetry
    wiring)."""
    import tools.smoke_etl as smoke
    out = smoke.run(n_rows=256, workers=2, epochs=6)
    assert out["accuracy"] > 0.9
    assert out["steady_state_recompiles"] == 0
    assert out["etl_batches_total"] > 0


def test_derived_column_binary_without_scalar_fails_at_build():
    """Regression: a binary derive fn with one column and no scalar must be
    rejected at build time, not explode in a worker thread at batch N."""
    schema = Schema.builder().add_numeric("x").build()
    with pytest.raises(ValueError, match="scalar"):
        TransformProcess.builder(schema) \
            .derived_column("x2", "mul", ["x"]).build()
    # unary fns and column+scalar forms stay valid
    TransformProcess.builder(schema).derived_column("lx", "log", ["x"]).build()
    TransformProcess.builder(schema) \
        .derived_column("x2", "mul", ["x"], scalar=2.0).build()


def test_pipeline_label_config_validated_at_build():
    """Regression: label routing without a TransformProcess used to be
    silently ignored (model trains on wrong data); one_hot_labels without a
    label column used to IndexError in a worker at batch time."""
    reader = CollectionRecordReader(_simple_records(4))
    with pytest.raises(ValueError, match="TransformProcess"):
        ParallelPipelineExecutor(reader, label_columns=["label"],
                                 registry=MetricsRegistry())
    with pytest.raises(ValueError, match="label_columns"):
        schema = Schema.builder().add_numeric("a", "b", "c").build()
        tp = TransformProcess.builder(schema).build()
        ParallelPipelineExecutor(reader, tp, one_hot_labels=3,
                                 registry=MetricsRegistry())
