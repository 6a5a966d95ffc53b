"""Train the decoder-only transformer LM (new model family) with bf16 mixed
precision and the Pallas flash-attention kernel.

Run: python examples/03_transformer_lm.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.zoo.models import transformer_lm

VOCAB, SEQ = 64, 128
net = transformer_lm(vocab_size=VOCAB, d_model=128, n_layers=2, n_heads=2,
                     use_pallas=True, compute_dtype="bfloat16")
net.init()

rng = np.random.default_rng(0)
starts = rng.integers(0, VOCAB, size=(32, 1))
ids = (starts + np.arange(SEQ + 1)) % VOCAB     # learnable: next = cur + 1
x = np.eye(VOCAB, dtype=np.float32)[ids[:, :-1]]
y = np.eye(VOCAB, dtype=np.float32)[ids[:, 1:]]

for step in range(10):
    net.fit_batch(DataSet(x, y))
    if step % 5 == 0:
        print(f"step {step}: loss {net.score_value:.4f}")

# the hot-path way: K steps per compiled executable — one host dispatch per
# K optimizer steps (lax.scan with donated carry, nn/multistep.py); per-step
# scores stay available on device as net.last_scores
from deeplearning4j_tpu.datasets.iterator.base import ListDataSetIterator
net.fit(ListDataSetIterator([DataSet(x, y)] * 20), steps_per_execution=10)
print("scanned scores tail:",
      [round(float(s), 4) for s in np.asarray(net.last_scores)[-3:]])
print("final loss:", round(net.score_value, 4))
