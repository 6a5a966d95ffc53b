"""Bytes-diet smoke test: the two quantization levers end to end —

  train a small classifier with 8-BIT OPTIMIZER MOMENTS riding inside the
  ZeRO flatten-pad layout (ShardedTrainer(shard_update=True,
  moment_dtype="q8") at 4 shards) -> checkpoint (ModelSerializer zip:
  canonical per-param f32 updater state, topology- AND precision-
  independent) -> restore at a DIFFERENT shard count (2) with the q8 codec
  re-applied, train on, re-checkpoint -> deploy that zip to a ServingServer
  with `quantize="int8"` (per-channel weight quantization, parity-gated,
  dequant fused into the warmed executables) -> /predict.

Asserts (a) the q8-moment model actually learns (accuracy gate) and its
per-device moment bytes sit >= 3.5x under f32 at the same shard count,
(b) the restore-at-2-shards run continues from the checkpointed momentum
(finite, still learning), (c) the int8 deploy passes the accuracy-parity
gate and /predict answers match the f32 model within it, (d) steady-state
serving pays ZERO recompiles after the deploy warm-up (compiles_total flat
across repeated /predict waves AND the output executable's XLA cache stays
at one entry), and (e) NO XLA donation warning fires anywhere in the run.

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_quant.py [-e 30]
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


def _data(n=256, nin=32, nout=4, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, nin)).astype(np.float32)
    w = rng.normal(size=(nin, nout))
    y = np.argmax(X @ w, axis=1)
    return X, np.eye(nout, dtype=np.float32)[y], y


def _net(nin=32, nout=4, seed=3):
    from deeplearning4j_tpu import (Adam, DenseLayer, InputType,
                                    MultiLayerNetwork,
                                    NeuralNetConfiguration, OutputLayer)
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(5e-3))
            .list()
            # hidden 512: weight leaves big enough that the q8 codes'
            # block*n_shards pad granule is noise (production-like ratio)
            .layer(DenseLayer(n_out=512, activation="relu"))
            .layer(OutputLayer(n_out=nout, activation="softmax",
                               loss="MCXENT"))
            .input_type(InputType.feed_forward(nin)).build())
    return MultiLayerNetwork(conf).init()


def run(steps=30):
    import numpy as np
    import jax
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.parallel.sharding import ShardedTrainer, make_mesh
    from deeplearning4j_tpu.parallel.zero import moment_bytes
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.util.http import post_json
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    X, Y, y_cls = _data()
    ds = DataSet(X, Y)
    out = {}
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        # ---- train with 8-bit moments at 4 shards --------------------------
        net = _net()
        tr4 = ShardedTrainer(net, mesh=make_mesh(n_data=4,
                                                 devices=jax.devices()[:4]),
                             shard_update=True, moment_dtype="q8")
        for _ in range(steps):
            tr4.fit_batch(ds)
        # moment bytes vs an f32-moment twin at the SAME shard count
        ref = _net()
        ShardedTrainer(ref, mesh=make_mesh(n_data=4,
                                           devices=jax.devices()[:4]),
                       shard_update=True)
        reduction = moment_bytes(ref.opt_state) / moment_bytes(net.opt_state)
        assert reduction >= 3.5, f"moment reduction {reduction:.2f}x < 3.5x"
        out["moment_bytes_reduction_x"] = round(float(reduction), 2)

        with tempfile.TemporaryDirectory() as tmp:
            # ---- checkpoint -> restore at a DIFFERENT shard count ----------
            ModelSerializer.write_model(net, os.path.join(tmp, "v1.zip"))
            restored = ModelSerializer.restore(os.path.join(tmp, "v1.zip"))
            tr2 = ShardedTrainer(restored,
                                 mesh=make_mesh(n_data=2,
                                                devices=jax.devices()[:2]),
                                 shard_update=True, moment_dtype="q8")
            for _ in range(steps // 3):
                tr2.fit_batch(ds)
            acc = float(np.mean(np.argmax(
                np.asarray(restored.output(X)), 1) == y_cls))
            assert acc > 0.9, f"q8-moment accuracy {acc} too low"
            out["q8_train_accuracy"] = round(acc, 4)
            f32_pred = np.asarray(restored.output(X[:32]))
            ModelSerializer.write_model(restored,
                                        os.path.join(tmp, "v2.zip"))

            # ---- deploy the zip int8-quantized, serve, count compiles ------
            srv = ServingServer(scan_dir=tmp, alert_interval_s=0).start()
            try:
                r = post_json(srv.url + "/deploy",
                              {"version": "v2", "quantize": "int8",
                               "parity_inputs": X[:32].tolist()})
                assert r["quantized"] == "int8" and r["parity"]["gated"]
                out["parity"] = r["parity"]
                p1 = post_json(srv.url + "/predict",
                               {"data": X[:32].tolist()})
                assert p1["version"] == "v2"
                rel = float(np.max(np.abs(np.asarray(p1["prediction"])
                                          - f32_pred))
                            / np.max(np.abs(f32_pred)))
                assert rel < 0.1, f"/predict vs f32 delta {rel} beyond gate"
                out["predict_rel_delta"] = round(rel, 5)
                # steady state: more waves of the same shape, compiles flat
                compiles = srv.metrics.registry.counter("compiles_total")
                jits = srv.metrics.registry.counter("jit_compiles_total")
                before = (compiles.get(), jits.get())
                for _ in range(3):
                    post_json(srv.url + "/predict", {"data": X[:32].tolist()})
                recompiles = (compiles.get() - before[0]) \
                    + (jits.get() - before[1])
                assert recompiles == 0, \
                    f"{recompiles} steady-state recompiles on the int8 path"
                out["steady_state_recompiles"] = int(recompiles)
                mv = srv.registry.get("v2")
                key = ("output", False, False)
                cache = mv.model._jit_cache[key]._cache_size()
                assert cache == 1, f"output executable cache grew to {cache}"
            finally:
                srv.stop()
    donation = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert donation == [], f"XLA donation warnings: {donation}"
    out["donation_warnings"] = 0
    return out


def main(argv=None):
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-e", "--steps", type=int, default=30)
    args = ap.parse_args(argv)
    out = run(steps=args.steps)
    print("quant smoke OK:", json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
