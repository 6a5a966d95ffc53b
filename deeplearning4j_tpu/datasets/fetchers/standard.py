"""Standard dataset fetchers/iterators beyond MNIST.

Reference: deeplearning4j-core datasets/iterator/impl/{IrisDataSetIterator,
CifarDataSetIterator, LFWDataSetIterator, CurvesDataSetIterator}.java and
datasets/fetchers/{IrisDataFetcher, CifarDataFetcher, LFWDataFetcher}.java.

Zero-egress environment: like the MNIST fetcher, each iterator looks for a
local copy first (env var pointing at the standard binary layout) and falls
back to a deterministic, clearly-synthetic surrogate with the same shapes and
class-conditional structure so models can actually learn in tests/benchmarks.
"""
from __future__ import annotations

import os

import numpy as np

from ..dataset import DataSet
from ..iterator.base import DataSetIterator


class _ArrayIterator(DataSetIterator):
    """Batch iterator over in-memory arrays."""

    def __init__(self, x, y, batch_size):
        self._x, self._y = x, y
        self.batch = int(batch_size)
        self._i = 0

    def reset(self):
        self._i = 0
        return self

    def has_next(self):
        return self._i < len(self._x)

    def next(self, num=None):
        n = num or self.batch
        s = self._i
        self._i += n
        return DataSet(self._x[s:s + n], self._y[s:s + n])

    def total_examples(self):
        return len(self._x)

    def input_columns(self):
        return int(np.prod(self._x.shape[1:]))

    def total_outcomes(self):
        return self._y.shape[-1]

    def __iter__(self):
        while self.has_next():
            yield self.next()


def _synthetic_gaussian_classes(n, dims, n_classes, seed, spread=2.0):
    """Deterministic class-conditional Gaussian clusters."""
    rng = np.random.default_rng(seed)
    means = rng.normal(scale=spread, size=(n_classes,) + (dims if isinstance(dims, tuple) else (dims,)))
    ys = np.tile(np.arange(n_classes), n // n_classes + 1)[:n]
    x = means[ys] + rng.normal(scale=1.0, size=(n,) + means.shape[1:])
    y = np.eye(n_classes, dtype=np.float32)[ys]
    order = rng.permutation(n)
    return x[order].astype(np.float32), y[order]


class IrisDataSetIterator(_ArrayIterator):
    """(reference: datasets/iterator/impl/IrisDataSetIterator.java; fetcher
    datasets/fetchers/IrisDataFetcher.java — 150 x 4 features, 3 classes).
    Loads a local `iris.data` CSV (IRIS_PATH env) or synthesizes 3-cluster
    data with the same shape."""

    N, DIMS, CLASSES = 150, 4, 3

    def __init__(self, batch_size=150, num_examples=150):
        path = os.environ.get("IRIS_PATH")
        if path and os.path.exists(path):
            rows = []
            names = {}
            with open(path) as fh:
                for line in fh:
                    parts = line.strip().split(",")
                    if len(parts) != 5:
                        continue
                    lbl = names.setdefault(parts[4], len(names))
                    rows.append([float(v) for v in parts[:4]] + [lbl])
            arr = np.array(rows, np.float32)
            x = arr[:, :4]
            y = np.eye(self.CLASSES, dtype=np.float32)[arr[:, 4].astype(int)]
        else:
            x, y = _synthetic_gaussian_classes(self.N, self.DIMS, self.CLASSES,
                                               seed=4242)
        super().__init__(x[:num_examples], y[:num_examples], batch_size)


def _find_cifar_dir():
    """First directory holding CIFAR-format binary batches: CIFAR_DIR wins
    (a full real CIFAR-10 download drops in unchanged), then local caches,
    then the committed real-photo fixture tests/fixtures/cifar_real (960
    train / 240 test genuine 32x32 photograph crops in the CIFAR binary
    record layout — real pixels, NOT the CIFAR-10 classes; provenance in
    tools/make_cifar_fixture.py)."""
    candidates = [
        os.environ.get("CIFAR_DIR"),
        os.path.expanduser("~/.deeplearning4j_tpu/cifar"),
        "/root/data/cifar",
        os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                     os.pardir, "tests", "fixtures", "cifar_real"),
    ]
    def has(d, base):
        return any(os.path.exists(os.path.join(d, base + sfx))
                   for sfx in ("", ".gz"))

    for d in candidates:
        if not d or not os.path.isdir(d):
            continue
        # require BOTH splits: a partial copy that satisfied only the train
        # side would silently pair real train data with the synthetic test
        # fallback — and publish a bogus accuracy
        if has(d, "data_batch_1.bin") and has(d, "test_batch.bin"):
            return d
        import warnings
        warnings.warn(f"CIFAR dir {d} is missing a split "
                      "(need data_batch_1.bin and test_batch.bin, raw or "
                      ".gz); skipping it", stacklevel=2)
    return None


def _read_cifar_records(path):
    """label/RGB-plane records (CifarDataSetIterator.java's layout), raw or
    gzipped. Returns (images NHWC uint8, labels uint8)."""
    import gzip
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rb") as f:
        raw = np.frombuffer(f.read(), np.uint8)
    recs = raw.reshape(-1, 3073)
    return recs[:, 1:].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1), recs[:, 0]


def load_cifar(train=True, num_examples=None):
    """(images [n,32,32,3] float32 in [0,1], labels [n] int64, class_names
    list | None). Falls back to deterministic synthetic data (clearly not
    real photos) when no local copy or fixture exists."""
    d = _find_cifar_dir()
    if d is not None:
        files = [f"data_batch_{i}.bin" for i in range(1, 6)] if train \
            else ["test_batch.bin"]
        xs, ys = [], []
        for f in files:
            for suffix in ("", ".gz"):
                p = os.path.join(d, f + suffix)
                if os.path.exists(p):
                    x, y = _read_cifar_records(p)
                    xs.append(x)
                    ys.append(y)
                    break
        if xs:
            names = None
            meta = os.path.join(d, "batches.meta.txt")
            if os.path.exists(meta):
                with open(meta) as f:
                    names = [l.strip() for l in f if l.strip()]
            x = (np.concatenate(xs) / 255.0).astype(np.float32)
            y = np.concatenate(ys).astype(np.int64)
            if num_examples is not None:
                x, y = x[:num_examples], y[:num_examples]
            return x, y, names
    n = num_examples or 1000
    rng = np.random.default_rng(777 if train else 778)
    ys_i = np.tile(np.arange(10), n // 10 + 1)[:n]
    base = rng.normal(size=(10, 32, 32, 3))
    x = base[ys_i] * 0.4 + rng.normal(scale=0.3, size=(n, 32, 32, 3))
    x = ((x - x.min()) / (x.max() - x.min())).astype(np.float32)
    return x, ys_i.astype(np.int64), None


def real32_gate_accuracy(epochs=10, seed=3):
    """The real-photo 32x32 accuracy gate of tests/test_real_cifar.py: small
    convnet (zoo.cifar_convnet) + horizontal-flip augmentation on the
    committed cifar_real fixture, evaluated on the spatially-split held-out
    crops. Returns accuracy, or None when only synthetic data is found."""
    from ..dataset import DataSet
    from ..iterator.base import ListDataSetIterator
    from ...zoo.models import cifar_convnet

    if _find_cifar_dir() is None:
        return None  # synthetic fallback engaged; accuracy would be bogus
    x, y, _ = load_cifar(train=True)
    xa = np.concatenate([x, x[:, :, ::-1]])      # horizontal flips
    ya = np.concatenate([y, y])
    order = np.random.default_rng(seed).permutation(len(xa))
    xa = xa[order]
    yh = np.eye(10, dtype=np.float32)[ya[order]]
    sets = [DataSet(xa[i:i + 64], yh[i:i + 64])
            for i in range(0, len(xa), 64)]
    net = cifar_convnet()
    net.init()
    net.fit(ListDataSetIterator(sets), epochs=epochs)
    xt, yt, _ = load_cifar(train=False)
    pred = np.argmax(np.asarray(net.output(xt)), axis=1)
    return float((pred == yt).mean())


class CifarDataSetIterator(_ArrayIterator):
    """(reference: datasets/iterator/impl/CifarDataSetIterator.java — 32x32x3,
    10 classes). Reads CIFAR-10 binary batches (label byte + 3072 RGB plane
    bytes per record) from CIFAR_DIR / local caches / the committed
    real-photo fixture, else synthesizes class-conditional images. Labels
    one-hot to 10 columns regardless of how many classes the data uses, so
    model shapes match real CIFAR-10. `labels` carries class names when the
    source ships a batches.meta.txt."""

    H = W = 32
    C = 3
    CLASSES = 10

    def __init__(self, batch_size=32, num_examples=None, train=True,
                 shuffle=False, seed=123):
        x, ys, self.labels = load_cifar(train, num_examples)
        if shuffle:
            idx = np.random.default_rng(seed).permutation(len(x))
            x, ys = x[idx], ys[idx]
        y = np.eye(self.CLASSES, dtype=np.float32)[ys]
        super().__init__(x, y, batch_size)


class LFWDataSetIterator(_ArrayIterator):
    """(reference: datasets/iterator/impl/LFWDataSetIterator.java — labelled
    faces; default 250x250x3 scaled down). Synthetic fallback with
    `num_labels` identities at image_size."""

    def __init__(self, batch_size=16, num_examples=64, image_size=(64, 64),
                 num_labels=8):
        h, w = image_size
        rng = np.random.default_rng(999)
        ys_i = np.tile(np.arange(num_labels),
                       num_examples // num_labels + 1)[:num_examples]
        base = rng.normal(size=(num_labels, h, w, 3))
        x = base[ys_i] * 0.5 + rng.normal(scale=0.25,
                                          size=(num_examples, h, w, 3))
        x = ((x - x.min()) / (x.max() - x.min())).astype(np.float32)
        y = np.eye(num_labels, dtype=np.float32)[ys_i]
        super().__init__(x, y, batch_size)


class CurvesDataSetIterator(_ArrayIterator):
    """(reference: datasets/iterator/impl/CurvesDataSetIterator.java — the
    'curves' autoencoder benchmark: 28x28 synthetic curve images). Generated
    deterministic sine-curve raster images; labels == features (autoencoder
    regime, like the reference's unsupervised use)."""

    def __init__(self, batch_size=32, num_examples=256, size=28):
        rng = np.random.default_rng(1234)
        xs = np.zeros((num_examples, size * size), np.float32)
        t = np.linspace(0, 1, size)
        for i in range(num_examples):
            amp = rng.uniform(0.2, 0.45)
            freq = rng.uniform(0.5, 3.0)
            phase = rng.uniform(0, 2 * np.pi)
            curve = 0.5 + amp * np.sin(2 * np.pi * freq * t + phase)
            img = np.zeros((size, size), np.float32)
            rows = np.clip((curve * size).astype(int), 0, size - 1)
            img[rows, np.arange(size)] = 1.0
            xs[i] = img.ravel()
        super().__init__(xs, xs.copy(), batch_size)
