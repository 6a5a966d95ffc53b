"""Pretrained zoo weights + streaming over the TCP broker.

Loads the committed pretrained LeNet (real handwritten digits), decodes
predictions to label names, then serves it as a streaming route: producers
publish image batches to a broker topic over TCP, the route runs the jitted
forward, and consumers poll predictions off another topic — the reduced
Kafka-serve-route shape of the reference's dl4j-streaming.

Run: python examples/05_pretrained_and_broker.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np

from deeplearning4j_tpu.datasets.fetchers.mnist import MnistDataSetIterator
from deeplearning4j_tpu.streaming import (BrokerClient, BrokerSink,
                                          BrokerSource, MessageBroker,
                                          NDArrayMessage, ServeRoute)
from deeplearning4j_tpu.zoo import load_pretrained


def main():
    # 1) pretrained weights -> ready-for-inference model + label table
    net, labels = load_pretrained("lenet_mnist_real")
    ds = MnistDataSetIterator(batch_size=8, train=False, shuffle=False).next()
    top = labels.decode_predictions(net.output(ds.features), top=1)
    truth = np.argmax(np.asarray(ds.labels), axis=1)
    print("pretrained top-1 vs truth:")
    for (label_prob,), t in zip(top, truth):
        print(f"  predicted {label_prob[0]!r} ({label_prob[1]:.2f})"
              f"  truth 'digit {t}'")

    # 2) the same model behind a broker-backed serve route
    broker = MessageBroker(port=0).start()
    route = ServeRoute(
        net,
        BrokerSource(BrokerClient(port=broker.port), "images"),
        BrokerSink(BrokerClient(port=broker.port), "predictions"))
    route.start()
    producer = BrokerClient(port=broker.port)
    consumer = BrokerClient(port=broker.port)
    feats = np.asarray(ds.features)
    for i in range(4):
        producer.publish("images",
                         NDArrayMessage(feats[i:i + 1], {"i": i}).to_dict())
    got = 0
    deadline = time.time() + 60
    while got < 4 and time.time() < deadline:
        d = consumer.poll("predictions", timeout=1)
        if d is None:
            continue
        m = NDArrayMessage.from_json(d)
        name, p = labels.decode_predictions(m.array, top=1)[0][0]
        print(f"  broker record {m.meta['i']}: {name!r} ({p:.2f})")
        got += 1
    route.stop()
    broker.stop()
    assert got == 4
    print("done: 4 predictions served over TCP")


if __name__ == "__main__":
    main()
