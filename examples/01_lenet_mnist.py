"""LeNet on MNIST — the minimum end-to-end slice (BASELINE config #1).

Run: python examples/01_lenet_mnist.py
(MNIST falls back to a deterministic synthetic digit set when the real
download is unavailable; place the IDX files under ~/.deeplearning4j_tpu/mnist to
use real data.)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

from deeplearning4j_tpu import ModelSerializer, ScoreIterationListener
from deeplearning4j_tpu.datasets.fetchers.mnist import MnistDataSetIterator
from deeplearning4j_tpu.zoo.models import lenet_mnist

net = lenet_mnist()
net.init()
net.set_listeners(ScoreIterationListener(10))
train = MnistDataSetIterator(64, train=True, num_examples=1024)
test = MnistDataSetIterator(64, train=False, num_examples=256)

net.fit(train, epochs=5)
e = net.evaluate(test, top_n=3)
print(e.stats())
print("top-3 accuracy:", round(e.top_n_accuracy(), 4))

ModelSerializer.write_model(net, "/tmp/lenet.zip")
print("saved to /tmp/lenet.zip; restore with ModelSerializer.restore(path)")
