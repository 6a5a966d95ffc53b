"""Data + tensor parallel training over a device mesh.

Run on one host: python examples/02_sharded_training.py
(uses all visible devices; to simulate a mesh on CPU:
 XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu ...)

Multi-host: call parallel.multihost.initialize(coordinator, N, i) in every
process first; everything below is unchanged (SPMD).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np
import jax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu import (NeuralNetConfiguration, InputType, DenseLayer,
                                OutputLayer, MultiLayerNetwork, DataSet, Adam)
from deeplearning4j_tpu.parallel.sharding import (make_mesh, ShardedTrainer,
                                                  ShardingRules)

n = len(jax.devices())
# model axis only when the device count splits evenly; otherwise pure DP
mesh = make_mesh(n_model=2 if n % 2 == 0 and n >= 2 else 1)

conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3)).list()
        .layer(DenseLayer(n_out=512, activation="relu"))
        .layer(DenseLayer(n_out=512, activation="relu"))
        .layer(OutputLayer(n_out=10, activation="softmax", loss="MCXENT"))
        .set_input_type(InputType.feed_forward(784))
        .build())
net = MultiLayerNetwork(conf).init()

rules = ShardingRules()                       # tensor parallelism on layer 0
rules.add(r"^0/W$", P(None, "model"))
rules.add(r"^0/b$", P("model"))
trainer = ShardedTrainer(net, mesh=mesh, rules=rules)

rng = np.random.default_rng(0)
X = rng.random((512, 784)).astype(np.float32)
Y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 512)]
for step in range(20):
    trainer.fit_batch(DataSet(X, Y))
print("final score:", net.score_value)
