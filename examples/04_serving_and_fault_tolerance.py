"""Online inference serving + preemption-safe training.

Run: python examples/04_serving_and_fault_tolerance.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import json
import urllib.request

import numpy as np

from deeplearning4j_tpu import (NeuralNetConfiguration, InputType, DenseLayer,
                                OutputLayer, MultiLayerNetwork, DataSet,
                                ListDataSetIterator, Sgd)
from deeplearning4j_tpu.streaming import InferenceServer
from deeplearning4j_tpu.train import CheckpointConfig, FaultTolerantTrainer


def factory():
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="MCXENT"))
            .set_input_type(InputType.feed_forward(8))
            .build())
    return MultiLayerNetwork(conf)


rng = np.random.default_rng(0)
X = rng.random((256, 8)).astype(np.float32)
Y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 256)]

# checkpoint every 5 iterations; rerunning this script RESUMES automatically
trainer = FaultTolerantTrainer(factory, CheckpointConfig("/tmp/ft_demo",
                                                         frequency=5))
print("resumed from checkpoint:" if trainer.resumed else "fresh run:",
      trainer.state)
trainer.fit(ListDataSetIterator(DataSet(X, Y), batch_size=32), epochs=3)

# serve the trained model over HTTP
server = InferenceServer(trainer.model, port=0).start()
req = urllib.request.Request(server.url + "/predict",
                             data=json.dumps({"data": X[:2].tolist()}).encode())
with urllib.request.urlopen(req, timeout=30) as r:
    print("served prediction:", json.loads(r.read())["prediction"][0])
server.stop()
