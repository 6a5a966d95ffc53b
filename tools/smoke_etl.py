"""ETL smoke test: drive the whole input pipeline end to end on synthetic
data —

  CSV on disk -> CSVRecordReader -> TransformProcess (one-hot + derived +
  normalize ops, JSON round-tripped first to prove serialization) ->
  NormalizerStandardize (fitted streaming) -> ParallelPipelineExecutor
  (N workers, ordered) -> DevicePrefetcher (double-buffered device_put) ->
  network.fit

and assert (a) the model actually learns the synthetic rule, (b) steady
state trains with ZERO recompiles after the first epoch (jit_compiles_total
stable), and (c) the telemetry layer saw the pipeline (etl_batches_total,
etl_consumer_wait_ms populated).

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_etl.py [-n 512] [-w 4] [-e 8]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def make_csv(path, n_rows, seed=0):
    """Synthetic classification CSV: 3 numeric cols + a categorical col +
    integer class label derived from the numerics (learnable rule)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    cats = ["low", "mid", "high"]
    with open(path, "w") as f:
        for _ in range(n_rows):
            cls = int(rng.integers(0, 3))
            feats = rng.normal(loc=2.0 * cls, scale=0.6, size=3)
            cat = cats[cls]
            f.write(",".join([f"{v:.5f}" for v in feats])
                    + f",{cat},{cls}\n")
    return cats


def run(n_rows=512, workers=4, epochs=8, batch_size=32, seed=0):
    import numpy as np
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    DenseLayer, OutputLayer,
                                    MultiLayerNetwork, Adam)
    from deeplearning4j_tpu.datasets.records import CSVRecordReader
    from deeplearning4j_tpu.etl import (Schema, TransformProcess,
                                        NormalizerStandardize,
                                        ParallelPipelineExecutor,
                                        DevicePrefetcher)
    from deeplearning4j_tpu.telemetry import get_registry

    with tempfile.TemporaryDirectory() as tmp:
        csv_path = os.path.join(tmp, "train.csv")
        cats = make_csv(csv_path, n_rows, seed=seed)

        schema = (Schema.builder().add_numeric("f0", "f1", "f2")
                  .add_categorical("level", cats)
                  .add_integer("label").build())
        tp = (TransformProcess.builder(schema)
              .categorical_to_one_hot("level")
              .derived_column("f01", "mul", ["f0", "f1"])
              .build())
        # serialization proof: the executed process IS the round-tripped one
        tp = TransformProcess.from_json(tp.to_json())
        n_features = tp.final_schema().num_columns() - 1   # minus label

        reader = CSVRecordReader().initialize(csv_path)

        def pipeline(normalizer=None):
            reader.reset()
            return ParallelPipelineExecutor(
                reader, tp, batch_size=batch_size, workers=workers,
                ordered=True, label_columns=["label"], one_hot_labels=3,
                normalizer=normalizer, name="smoke_etl")

        normalizer = NormalizerStandardize().fit(pipeline())

        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Adam(1e-2)).list()
                .layer(DenseLayer(n_out=16, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="MCXENT"))
                .input_type(InputType.feed_forward(n_features)).build())
        net = MultiLayerNetwork(conf).init()

        reg = get_registry()
        compiles = reg.counter("jit_compiles_total")
        ex = pipeline(normalizer)
        pf = DevicePrefetcher(ex, queue_size=2)
        net.fit(pf, epochs=1)                  # epoch 1 pays the compile
        steady_before = compiles.get()
        net.fit(pf, epochs=epochs - 1)
        recompiles = compiles.get() - steady_before
        assert recompiles == 0, \
            f"{recompiles} steady-state recompiles (shapes not stable)"
        pf.close()

        eval_it = pipeline(normalizer)
        acc = net.evaluate(eval_it).accuracy()
        eval_it.close()
        assert acc > 0.9, f"accuracy {acc} too low"

        snap = reg.snapshot()
        batches = reg.counter("etl_batches_total").get()
        assert batches > 0, "etl_batches_total never incremented"
        wait = reg.histogram("etl_consumer_wait_ms")
        assert wait.count(pipeline="smoke_etl") > 0, \
            "consumer wait histogram empty"
        return {"accuracy": round(float(acc), 4),
                "etl_batches_total": batches,
                "etl_records_total": reg.counter("etl_records_total").get(),
                "steady_state_recompiles": recompiles,
                "jit_compiles_total": compiles.get(),
                "consumer_wait_p50_ms": wait.percentile(
                    0.5, pipeline="smoke_etl"),
                "metrics_keys": sorted(k for k in snap if "etl" in k)}


def main(argv=None):
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--n-rows", type=int, default=512)
    ap.add_argument("-w", "--workers", type=int, default=4)
    ap.add_argument("-e", "--epochs", type=int, default=8)
    args = ap.parse_args(argv)
    out = run(n_rows=args.n_rows, workers=args.workers, epochs=args.epochs)
    print("etl smoke OK:", json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
