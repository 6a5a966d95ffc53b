"""Pipeline parallelism end to end: 1F1B training, gather, checkpoint.

Trains a deep MLP split into 4 pipeline stages (each stage's parameters on
its own device, microbatches streamed through the interleaved
one-forward-one-backward schedule as compiled per-stage XLA executables),
then gathers the model onto one device for inference and writes/restores a
sharded checkpoint. Needs 4 devices: 8 virtual ones on the CPU, or the chips
of a multi-chip TPU host.

Run: JAX_PLATFORMS=cpu python examples/06_pipeline_parallelism.py
"""
import os
import sys

# the demo needs SEVERAL devices. The platform comes from the environment,
# as in the other examples: on the CPU (JAX_PLATFORMS=cpu) this flag gives
# it 8 virtual devices; on a multi-chip TPU host the flag does nothing and
# the stages land on the chips
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax
import numpy as np

from deeplearning4j_tpu import (DataSet, DenseLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer, Sgd)
from deeplearning4j_tpu.nn.conf.layers import BatchNormalization
from deeplearning4j_tpu.parallel.pipeline import PipelineTrainer
from deeplearning4j_tpu.util.sharded_checkpoint import (restore_sharded,
                                                        save_sharded)


def main():
    b = NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.05)).list()
    for _ in range(6):
        b = b.layer(DenseLayer(n_out=128, activation="relu"))
        b = b.layer(BatchNormalization())
    conf = (b.layer(OutputLayer(n_out=5, activation="softmax", loss="MCXENT"))
            .input_type(InputType.feed_forward(32))
            .build())
    net = MultiLayerNetwork(conf).init()

    n_stages = min(4, len(jax.devices()))
    pt = PipelineTrainer(net, n_stages=n_stages, n_microbatches=8,
                        devices=jax.devices()[:n_stages])
    rng = np.random.default_rng(0)
    X = rng.normal(size=(256, 32)).astype(np.float32)
    w = rng.normal(size=(32, 5))
    Y = np.eye(5, dtype=np.float32)[np.argmax(X @ w, axis=1)]

    print(f"training over {n_stages} pipeline stages x 8 microbatches "
          f"(BatchNorm stats update per microbatch)")
    for step in range(30):
        score = pt.fit_batch(DataSet(X, Y))
        if step % 10 == 0:
            print(f"  step {step}: loss {score:.4f}")

    pt.gather()          # re-colocate for inference/serialization
    preds = np.asarray(net.output(X))
    acc = (preds.argmax(1) == Y.argmax(1)).mean()
    print(f"post-gather inference accuracy on train set: {acc:.2f}")

    ckpt = "/tmp/pipeline_example_ckpt"
    save_sharded(net, ckpt)
    net2 = restore_sharded(ckpt)     # shardings re-derived from the meta
    assert np.allclose(np.asarray(net2.output(X)), preds, atol=1e-6)
    print("checkpoint round-trip: restored model predicts identically")


if __name__ == "__main__":
    main()
