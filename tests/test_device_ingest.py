"""Device-side ingest tests (etl.device_transform + its wiring).

The load-bearing guarantee is PARITY: for every TransformProcess column op
and both normalizer kinds, the narrow path (host prefix -> packed narrow
wire batch -> jnp device_apply) must match the wide host NumPy path to
float32 tolerance on the same records — otherwise train/serve skew creeps
in between the two representations. On top of that: the DevicePrefetcher
ingest modes (transfer_dtype narrowing, device_transform, multi-stream
chunked puts, sharded placement, h2d byte accounting + ingest span), the
fused `network.set_ingest` train path (identical params to training on the
wide path; zero steady-state recompiles), the pipeline's device_ingest
mode, the serving registry's lowered per-version normalizer, and the
donation regression (scanned multistep paths must not warn "Some donated
buffers were not usable" — tools/smoke_ingest.py asserts the same on the
image-shaped paths).
"""
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator.base import ListDataSetIterator
from deeplearning4j_tpu.etl import (DeviceIngest, DevicePrefetcher,
                                    NormalizerMinMaxScaler,
                                    NormalizerStandardize,
                                    ParallelPipelineExecutor, Schema,
                                    TransformProcess, lower_normalizer)
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry


def _schema():
    return (Schema.builder().add_numeric("a", "b")
            .add_categorical("color", ["red", "green", "blue"])
            .add_integer("label").build())


def _records(n=48, seed=0):
    rng = np.random.default_rng(seed)
    return [[float(rng.uniform(0, 10)), float(rng.normal()),
             ["red", "green", "blue"][int(c)], int(c)]
            for c in rng.integers(0, 3, n)]


def _assert_parity(tp, records=None, label_columns=("label",),
                   one_hot_labels=3, normalizer=None, **kw):
    """device_apply(prepare_host(records)) == host_reference(records)."""
    ing = DeviceIngest(tp, normalizer=normalizer,
                       label_columns=list(label_columns or []),
                       one_hot_labels=one_hot_labels, **kw)
    records = records if records is not None else _records()
    narrow = ing.prepare_host(records)
    ref = ing.host_reference(records)
    dev_x = np.asarray(ing.jit_apply_features(jnp.asarray(narrow.features)))
    np.testing.assert_allclose(dev_x, ref.features, rtol=1e-5, atol=1e-5)
    if label_columns:
        dev_y = np.asarray(ing.jit_apply_labels(jnp.asarray(narrow.labels)))
        np.testing.assert_allclose(dev_y, ref.labels, rtol=1e-5, atol=1e-5)
    return ing, narrow, ref


# -------------------------------------------------------------- op parity

def test_parity_categorical_to_one_hot():
    tp = (TransformProcess.builder(_schema())
          .categorical_to_one_hot("color").build())
    ing, narrow, _ = _assert_parity(tp)
    assert not ing._host_ops            # fully device-lowered
    # the one-hot expansion happens ON DEVICE: the wire carries one narrow
    # column per categorical, not |vocab| float32 columns
    assert narrow.features.shape[-1] == 3


def test_parity_categorical_to_integer():
    tp = (TransformProcess.builder(_schema())
          .categorical_to_integer("color").build())
    _assert_parity(tp)


def test_parity_min_max_normalize():
    tp = (TransformProcess.builder(_schema())
          .categorical_to_one_hot("color")
          .min_max_normalize("a", 0.0, 10.0, lo=-1.0, hi=1.0).build())
    _assert_parity(tp)


def test_parity_standardize():
    tp = (TransformProcess.builder(_schema())
          .categorical_to_one_hot("color")
          .standardize("b", mean=0.3, std=1.7).build())
    _assert_parity(tp)


def test_parity_filter_rows_runs_in_host_prefix():
    tp = (TransformProcess.builder(_schema())
          .filter_rows("a", "gt", 6.0)
          .categorical_to_one_hot("color").build())
    ing, narrow, ref = _assert_parity(tp)
    # data-dependent row drop cannot trace: it must sit in the host prefix
    assert [type(o).__name__ for o in ing._host_ops] == ["FilterRows"]
    assert narrow.features.shape[0] == ref.features.shape[0] < 48


def test_parity_remove_and_rename_columns():
    tp = (TransformProcess.builder(_schema())
          .categorical_to_one_hot("color")
          .remove_columns("b")
          .rename_column("a", "alpha").build())
    _assert_parity(tp)


@pytest.mark.parametrize("fn,cols,scalar", [
    ("mul", ["a", "b"], None), ("add", ["a", "b"], None),
    ("sub", ["a", "b"], None), ("div", ["a"], 3.0),
    ("log", ["a"], None), ("abs", ["b"], None)])
def test_parity_derived_column(fn, cols, scalar):
    tp = (TransformProcess.builder(_schema())
          .categorical_to_one_hot("color")
          .derived_column("d", fn, cols, scalar=scalar).build())
    # log needs strictly positive input: records draw a from U(0, 10)
    _assert_parity(tp)


def test_parity_sequence_window():
    schema = Schema.builder().add_numeric("x", "y").build()
    tp = (TransformProcess.builder(schema)
          .sequence_window(size=4, stride=2).build())
    rng = np.random.default_rng(1)
    recs = [[float(a), float(b)] for a, b in rng.normal(size=(20, 2))]
    _assert_parity(tp, records=recs, label_columns=(), one_hot_labels=None)


def test_parity_full_chain_with_normalizer_kinds():
    tp = (TransformProcess.builder(_schema())
          .filter_rows("b", "lt", -2.5)
          .categorical_to_one_hot("color")
          .derived_column("ab", "mul", ["a", "b"])
          .min_max_normalize("a", 0.0, 10.0)
          .standardize("b", 0.0, 1.0)
          .rename_column("ab", "prod").build())
    for nz in (NormalizerStandardize(), NormalizerMinMaxScaler(lo=-1, hi=1)):
        probe = DeviceIngest(tp, label_columns=["label"], one_hot_labels=3)
        nz.fit(probe.host_reference(_records(seed=7)))
        _assert_parity(tp, normalizer=nz)


def test_parity_fit_labels_normalizer_with_label_columns():
    """fit_labels=True + float label columns: the LABEL stats must ride
    into apply_labels — the host path normalizes regression targets, so
    skipping them on device would be silent train skew."""
    tp = (TransformProcess.builder(_schema())
          .categorical_to_one_hot("color").build())
    nz = NormalizerStandardize(fit_labels=True)
    probe = DeviceIngest(tp, label_columns=["label"])
    nz.fit(probe.host_reference(_records(seed=5)))
    ing, narrow, ref = _assert_parity(tp, normalizer=nz,
                                      label_columns=("label",),
                                      one_hot_labels=None)
    # and the labels really were normalized (device output != raw wire)
    assert not np.allclose(np.asarray(narrow.labels, np.float32), ref.labels)


def test_parity_mirrored_labels_with_normalizer():
    """No label columns: labels mirror features. Host transform() leaves
    mirrored labels un-normalized unless fit_labels — the device path must
    not leak FEATURE stats into them, and must apply LABEL stats iff
    fit_labels."""
    schema = (Schema.builder().add_numeric("a", "b")
              .add_categorical("color", ["red", "green", "blue"]).build())
    tp = (TransformProcess.builder(schema)
          .categorical_to_one_hot("color").build())
    recs = [r[:3] for r in _records(seed=13)]
    for fit_labels in (False, True):
        nz = NormalizerStandardize(fit_labels=fit_labels)
        nz.fit(DeviceIngest(tp).host_reference(recs))
        ing = DeviceIngest(tp, normalizer=nz)
        narrow = ing.prepare_host(recs)
        ref = ing.host_reference(recs)
        dev_y = np.asarray(ing.jit_apply_labels(jnp.asarray(narrow.labels)))
        np.testing.assert_allclose(dev_y, ref.labels, rtol=1e-5, atol=1e-5,
                                   err_msg=f"fit_labels={fit_labels}")


# ------------------------------------------------------- normalizer lowering

@pytest.mark.parametrize("make", [
    lambda: NormalizerStandardize(fit_labels=True),
    lambda: NormalizerMinMaxScaler(lo=-2.0, hi=2.0, fit_labels=True)])
def test_lower_normalizer_apply_and_revert_round_trip(make):
    rng = np.random.default_rng(3)
    nz = make().fit(DataSet(rng.normal(2.0, 3.0, (64, 5)).astype(np.float32),
                            rng.normal(-1.0, 0.5, (64, 2)).astype(np.float32)))
    apply, revert = lower_normalizer(nz)
    x = rng.normal(2.0, 3.0, (16, 5)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(apply(jnp.asarray(x))),
                               nz.transform_features(x), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(revert(apply(jnp.asarray(x)))), x,
                               rtol=1e-3, atol=1e-3)
    lapply, lrevert = lower_normalizer(nz, labels=True)
    y = rng.normal(size=(16, 2)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(lrevert(jnp.asarray(y))),
                               nz.revert_labels(y), rtol=1e-5, atol=1e-5)
    assert np.asarray(lapply(jnp.asarray(y))).shape == y.shape


def test_lower_normalizer_requires_fitted_stats():
    with pytest.raises(RuntimeError):
        lower_normalizer(NormalizerStandardize())


# --------------------------------------------------------------- prefetcher

def test_prefetcher_transfer_dtype_narrows_wire_bytes():
    reg = MetricsRegistry()
    n, d = 8, 6
    x = np.linspace(0, 255, n * d).reshape(n, d).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[np.arange(n) % 2]
    pf = DevicePrefetcher(ListDataSetIterator([DataSet(x, y)]),
                          registry=reg, transfer_dtype=np.uint8,
                          name="narrow")
    ds = next(iter(pf))
    pf.close()
    assert str(ds.features.dtype) == "uint8"
    # the counter records what CROSSED the link: uint8 features + f32 labels
    assert reg.counter("etl_h2d_bytes_total").get() == n * d + y.nbytes


def test_prefetcher_device_transform_and_ingest_span():
    from deeplearning4j_tpu.telemetry.trace import Tracer
    reg = MetricsRegistry()
    tracer = Tracer(max_spans=64)
    x = np.arange(24, dtype=np.uint8).reshape(4, 6)
    ing = DeviceIngest(normalizer=None)     # identity feature path
    import jax
    scale = jax.jit(lambda a: a.astype(jnp.float32) / 255.0)
    pf = DevicePrefetcher(ListDataSetIterator([DataSet(x, x)]),
                          registry=reg, device_transform=scale,
                          tracer=tracer, name="dt")
    ds = next(iter(pf))
    pf.close()
    np.testing.assert_allclose(np.asarray(ds.features),
                               x.astype(np.float32) / 255.0)
    spans = [s for s in tracer.finished_spans() if s.name == "ingest"]
    assert spans and {"transfer_ms", "transform_ms", "bytes"} <= \
        set(spans[0].attributes)
    assert ing.apply_labels is not None     # touched: identity ingest builds


def test_prefetcher_multi_stream_chunked_put_matches():
    n, d = 64, 512             # > 1 MiB of float32 so chunking engages
    x = np.random.default_rng(0).normal(size=(n, d * 9)).astype(np.float32)
    y = np.ones((n, 2), np.float32)
    pf = DevicePrefetcher(ListDataSetIterator([DataSet(x, y)]),
                          registry=MetricsRegistry(), transfer_streams=4)
    ds = next(iter(pf))
    pf.close()
    np.testing.assert_array_equal(np.asarray(ds.features), x)


@pytest.mark.parametrize("kind", ["dataset", "multidataset"])
def test_prefetcher_fences_the_chunk_puts_not_their_join(kind, monkeypatch):
    """With transfer_streams > 1 the worker's `etl_h2d` leg waits for the
    chunks' transfers and for nothing else: joining them takes a device
    program (it would wait its turn behind a running train step), so the
    worker runs none and hands the chunks over as a RowChunks. What is
    delivered still equals the host array bit for bit."""
    import jax
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    from deeplearning4j_tpu.etl.prefetch import RowChunks
    n, d = 64, 512 * 9         # > 1 MiB of float32 so chunking engages
    x = np.random.default_rng(1).normal(size=(n, d)).astype(np.float32)
    y = np.ones((n, 2), np.float32)
    item = DataSet(x, y) if kind == "dataset" else MultiDataSet([x, x[::-1]],
                                                                [y])
    fenced = []
    real = jax.block_until_ready

    def spy(tree):
        fenced.extend(leaf.shape for leaf in jax.tree_util.tree_leaves(tree))
        return real(tree)
    monkeypatch.setattr(jax, "block_until_ready", spy)
    pf = DevicePrefetcher(ListDataSetIterator([item]),
                          registry=MetricsRegistry(), transfer_streams=4)
    ds = pf.next()
    pf.close()
    monkeypatch.undo()
    arrays = 1 if kind == "dataset" else 2
    assert fenced == [(n // 4, d)] * (4 * arrays)   # never the joined (n, d)
    if kind == "dataset":
        assert isinstance(ds.features, RowChunks) and ds.num_examples() == n
        assert [p.shape for p in ds.features.parts] == [(n // 4, d)] * 4
        np.testing.assert_array_equal(np.asarray(ds.features), x)
        np.testing.assert_array_equal(np.asarray(jnp.asarray(ds.features)), x)
    else:
        np.testing.assert_array_equal(np.asarray(ds.features[0]), x)
        np.testing.assert_array_equal(np.asarray(ds.features[1]), x[::-1])
        np.testing.assert_array_equal(np.asarray(ds.labels[0]), y)


@pytest.mark.parametrize("kind", ["multilayer", "graph"])
def test_chunked_batches_serve_every_consumer(kind):
    """A RowChunks stands in for the array wherever a prefetched batch
    goes: output, score, evaluate, fit_batch and both fit loops take it."""
    from deeplearning4j_tpu import (Adam, ComputationGraph, DenseLayer,
                                    InputType, NeuralNetConfiguration,
                                    OutputLayer)
    from deeplearning4j_tpu.etl.prefetch import RowChunks
    n, d = 64, 4096                          # 1 MiB a batch: chunking engages
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3 * n, d)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 3 * n)]
    if kind == "graph":
        net = ComputationGraph(
            NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
            .graph_builder().add_inputs("in")
            .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="MCXENT"), "d")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(d)).build()).init()
    else:
        net = _tabular_net(d)
    pf = DevicePrefetcher(ListDataSetIterator(DataSet(x, y).batch_by(n)),
                          registry=MetricsRegistry(), transfer_streams=4)
    ds = pf.next()
    assert isinstance(ds.features, RowChunks)
    np.testing.assert_allclose(np.asarray(net.output(ds.features)),
                               np.asarray(net.output(x[:n])), rtol=1e-6)
    assert np.isfinite(net.score(ds))
    net.fit_batch(ds)
    pf.reset()
    assert 0.0 <= net.evaluate(pf).accuracy() <= 1.0
    pf.reset()
    net.fit(pf)                              # per batch
    pf.reset()
    net.fit(pf, steps_per_execution=3)       # one scanned execution
    pf.close()
    assert net.iteration_count == 7 and net.last_scores.shape == (3,)


def test_prefetcher_sharded_mode_applies_transform_under_sharding():
    import jax
    from deeplearning4j_tpu.parallel.sharding import make_mesh
    mesh = make_mesh(n_data=1, devices=jax.devices()[:1])
    scale = jax.jit(lambda a: a.astype(jnp.float32) * 2.0)
    x = np.arange(12, dtype=np.uint8).reshape(4, 3)
    pf = DevicePrefetcher(ListDataSetIterator([DataSet(x, x)]), mesh=mesh,
                          registry=MetricsRegistry(), device_transform=scale)
    ds = next(iter(pf))
    pf.close()
    np.testing.assert_allclose(np.asarray(ds.features),
                               x.astype(np.float32) * 2.0)


# ------------------------------------------------------------- fused fit

def _tabular_net(n_features, seed=0):
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    DenseLayer, OutputLayer,
                                    MultiLayerNetwork, Adam)
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="MCXENT"))
            .input_type(InputType.feed_forward(n_features)).build())
    return MultiLayerNetwork(conf).init()


def test_set_ingest_trains_identically_to_host_path():
    """The whole point: raw narrow batches + fused device ingest produce
    the SAME parameters as preprocessed float batches — through fit_batch
    AND the scanned multistep executable."""
    tp = (TransformProcess.builder(_schema())
          .categorical_to_one_hot("color")
          .min_max_normalize("a", 0.0, 10.0).build())
    ing = DeviceIngest(tp, label_columns=["label"], one_hot_labels=3)
    recs = _records(192, seed=5)
    narrow = [ing.prepare_host(recs[i * 32:(i + 1) * 32]) for i in range(6)]
    wide = [ing.host_reference(recs[i * 32:(i + 1) * 32]) for i in range(6)]
    n_feat = wide[0].features.shape[-1]

    dev = _tabular_net(n_feat).set_ingest(ing)
    dev.fit(ListDataSetIterator(narrow), epochs=2, steps_per_execution=3)
    host = _tabular_net(n_feat)
    host.fit(ListDataSetIterator(wide), epochs=2, steps_per_execution=3)
    for layer in dev.params:
        for k in dev.params[layer]:
            np.testing.assert_allclose(
                np.asarray(dev.params[layer][k]),
                np.asarray(host.params[layer][k]), rtol=2e-4, atol=2e-4)


def test_graph_multi_output_ingest_trains_identically():
    """ComputationGraph.set_ingest with TWO output heads: labels[0] goes
    through apply_labels, and labels[1:] must still land on the param dtype
    (the non-ingest _prep_batch cast) — so both paths train identically."""
    import jax
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    DenseLayer, OutputLayer,
                                    ComputationGraph, MultiDataSet, Adam)

    def conf():
        return (NeuralNetConfiguration.builder().seed(42).updater(Adam(1e-2))
                .graph_builder()
                .add_inputs("in")
                .add_layer("dense", DenseLayer(n_out=16, activation="relu"),
                           "in")
                .add_layer("cls", OutputLayer(n_out=3, activation="softmax",
                                              loss="MCXENT"), "dense")
                .add_layer("reg", OutputLayer(n_out=2, activation="identity",
                                              loss="MSE"), "dense")
                .set_outputs("cls", "reg")
                .set_input_types(InputType.feed_forward(4))
                .build())

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    ids = rng.integers(0, 3, 64).astype(np.int32)
    y_cls = np.eye(3, dtype=np.float32)[ids]
    y_reg = rng.normal(size=(64, 2)).astype(np.float64)  # exercises the cast

    g_ref = ComputationGraph(conf()).init()
    seed_params = jax.tree_util.tree_map(lambda a: np.array(a), g_ref.params)
    g_ing = ComputationGraph(conf()).init(
        params=jax.tree_util.tree_map(lambda a: np.array(a), seed_params))
    g_ref.fit([MultiDataSet([x], [y_cls, y_reg])], epochs=3)
    g_ing.set_ingest(DeviceIngest(one_hot_labels=3))
    g_ing.fit([MultiDataSet([x], [ids, y_reg])], epochs=3)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref.params),
                    jax.tree_util.tree_leaves(g_ing.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_set_ingest_zero_steady_state_recompiles():
    tp = (TransformProcess.builder(_schema())
          .categorical_to_one_hot("color").build())
    ing = DeviceIngest(tp, label_columns=["label"], one_hot_labels=3)
    recs = _records(96, seed=9)
    narrow = [ing.prepare_host(recs[i * 32:(i + 1) * 32]) for i in range(3)]
    net = _tabular_net(narrow[0].features.shape[-1] + 2).set_ingest(ing)
    from deeplearning4j_tpu.telemetry.registry import get_registry
    compiles = get_registry().counter("jit_compiles_total")
    net.fit(ListDataSetIterator(narrow), epochs=1)
    before = compiles.get()
    net.fit(ListDataSetIterator(narrow), epochs=3)
    assert compiles.get() == before, "steady-state recompile with ingest"


def test_pipeline_device_ingest_mode_emits_narrow_and_exposes_ingest():
    from deeplearning4j_tpu.datasets.records.reader import (
        CollectionRecordReader)
    tp = (TransformProcess.builder(_schema())
          .categorical_to_one_hot("color").build())
    recs = _records(64, seed=11)
    nz = NormalizerStandardize()
    probe = DeviceIngest(tp, label_columns=["label"], one_hot_labels=3)
    nz.fit(probe.host_reference(recs))
    pipe = ParallelPipelineExecutor(
        CollectionRecordReader(recs), tp, batch_size=16, workers=2,
        normalizer=nz, label_columns=["label"], one_hot_labels=3,
        device_ingest=True, name="ingest_pipe", registry=MetricsRegistry())
    batches = list(pipe)
    pipe.close()
    assert len(batches) == 4
    # narrow on the wire: float32 packed features, uint8 class ids — and the
    # normalizer was NOT applied on host (it is fused into ingest instead)
    assert batches[0].features.shape == (16, 3)
    assert str(batches[0].labels.dtype) == "uint8"
    dev = np.asarray(pipe.ingest.jit_apply_features(
        jnp.asarray(batches[0].features)))
    ref = pipe.ingest.host_reference(recs[:16])
    np.testing.assert_allclose(dev, ref.features, rtol=1e-5, atol=1e-5)


def test_pipeline_device_ingest_rejects_bad_configs():
    tp = (TransformProcess.builder(_schema())
          .categorical_to_one_hot("color").build())
    from deeplearning4j_tpu.datasets.records.reader import (
        CollectionRecordReader)
    with pytest.raises(ValueError):
        ParallelPipelineExecutor(CollectionRecordReader([]), None,
                                 device_ingest=True,
                                 registry=MetricsRegistry())
    with pytest.raises(ValueError):
        ParallelPipelineExecutor(CollectionRecordReader([]), tp,
                                 device_ingest=True,
                                 assemble=lambda r: None,
                                 registry=MetricsRegistry())


# ---------------------------------------------------------------- serving

def test_serving_version_lowers_normalizer_to_device():
    from deeplearning4j_tpu.serving.registry import ModelVersion
    rng = np.random.default_rng(2)
    nz = NormalizerStandardize().fit(
        DataSet(rng.normal(3.0, 2.0, (128, 4)).astype(np.float32), None))
    mv = ModelVersion("v1", model=object(), transform=nz)
    x = rng.normal(3.0, 2.0, (8, 4)).astype(np.float32)
    out = mv.transform_features_device(x)
    assert mv._device_transform is not False    # actually lowered
    np.testing.assert_allclose(np.asarray(out), nz.transform_features(x),
                               rtol=1e-5, atol=1e-5)
    assert str(np.asarray(out).dtype) == "float32"
    # non-lowerable transform falls back to the host path
    mv2 = ModelVersion("v2", model=object(), transform=lambda a: a * 2)
    np.testing.assert_allclose(mv2.transform_features_device(x), x * 2)


# ------------------------------------------------------------------ smoke

def test_smoke_ingest_tool():
    """uint8 CSV + image batches -> device transform -> fit: zero
    steady-state recompiles, no donation warnings, narrow bytes on the wire
    (tools/smoke_ingest.py at its own defaults: at 256 rows x 5 epochs —
    40 steps — the image net separates two of the three classes, 0.695,
    through the device ingest and through host-normalized floats alike,
    so that was the step count and not the ingest)."""
    import tools.smoke_ingest as smoke
    out = smoke.run()
    assert out["tabular_accuracy"] > 0.9 and out["image_accuracy"] > 0.9
    assert out["tabular_recompiles"] == 0 and out["image_recompiles"] == 0
    assert out["donation_warnings"] == 0
    assert out["etl_h2d_bytes_total"] > 0


# -------------------------------------------------------------- donation

def test_scanned_paths_donate_cleanly():
    """The warning 'Some donated buffers were not usable:
    float32[64,256] x4' from the scanned TBPTT executable must stay gone:
    the final carries are now scan outputs, so the donated carry buffers
    alias them."""
    from deeplearning4j_tpu.zoo.models import char_rnn_lstm
    net = char_rnn_lstm(vocab_size=12, hidden=16, layers=2, tbptt=5)
    net.init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 12, size=(4, 11))
    x = np.eye(12, dtype=np.float32)[ids[:, :-1]]
    y = np.eye(12, dtype=np.float32)[ids[:, 1:]]
    ds = DataSet(jnp.asarray(x), jnp.asarray(y))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        plan = net.prepare_steps([ds] * 3)
        assert plan is not None and plan[0] == "tbptt"
        net.fit_prepared(plan)
        net2 = _tabular_net(4)
        flat = DataSet(np.random.default_rng(1).normal(size=(8, 4))
                       .astype(np.float32),
                       np.eye(3, dtype=np.float32)[np.arange(8) % 3])
        net2.fit(ListDataSetIterator([flat] * 4), steps_per_execution=2)
    donation = [w for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert donation == [], [str(w.message) for w in donation]
