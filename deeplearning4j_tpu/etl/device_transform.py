"""Device-side ingest: lower a fitted TransformProcess + DataNormalizer into
the jitted step, so the host ships narrow bytes and XLA does the widening.

Why this module exists: a float32 image batch is four times the bytes of
its uint8 source, and every one of them crosses the host link and is cast
by a host thread the train loop may end up waiting for (the benchmark's
`input_wait_ms_per_step`). The TPU-paper idiom (PAPERS.md: the Julia-to-TPU
compiler moving whole programs into XLA, the cross-replica-sharding paper
moving the update path) is to move work INTO the compiled program: transfer
raw uint8/int records, and let cast/normalize/one-hot be the first fused ops
of the step. The column ops in `etl.transform` are already vectorized NumPy
— this module re-expresses them in `jnp` (near-verbatim) as a traceable
`device_apply`, so one executable covers ingest + forward + backward +
update, with zero steady-state recompiles.

Three cooperating pieces:

- `lower_normalizer(nz)` — a fitted `DataNormalizer`'s affine stats as
  traceable `apply(x)` / `revert(y)` closures (serving reuses this so
  `/predict` preprocessing also runs on-device).
- op lowerers — one jnp re-expression per TransformProcess op class
  (`FilterRows` is the exception: data-dependent output shape cannot trace).
- `DeviceIngest` — the composite: splits an op chain into the minimal host
  prefix (non-lowerable ops + categorical string->code encoding) and the
  maximal device suffix, packs the host-side columns into ONE narrow array
  for the wire, and exposes `apply_features` / `apply_labels` for fusion
  into a network's train step (`network.set_ingest`), a `DevicePrefetcher`
  (`device_transform=`), or a standalone jit.

Parity contract (tested per-op in tests/test_device_ingest.py): for any
records batch, `device_apply(prepare_host(records))` matches the host NumPy
path (`host_reference`) to float32 tolerance — train/serve skew cannot creep
in between the wide and narrow paths.
"""
from __future__ import annotations

import numpy as np

from ..datasets.dataset import DataSet
from .normalizer import DataNormalizer
from .schema import ColumnType
from .transform import (CategoricalToInteger, CategoricalToOneHot,
                        DerivedColumn, MinMaxNormalize, RemoveColumns,
                        RenameColumn, SequenceWindow, Standardize,
                        TransformProcess)


# ---------------------------------------------------------------------------
# normalizer lowering
# ---------------------------------------------------------------------------

def lower_normalizer(normalizer: DataNormalizer, labels=False):
    """(apply, revert) traceable closures over a FITTED normalizer's stats.

    Both are the exact jnp transliteration of the host formulas
    (`(x - sub) / div * scale + add` and its inverse), closing over float32
    constants, so host/device outputs agree to float32 rounding. Safe to
    call inside jit (no host syncs) or to wrap in `jax.jit` standalone.
    """
    import jax.numpy as jnp

    sub, div, scale, add = (jnp.asarray(v, jnp.float32)
                            for v in normalizer.device_stats(labels=labels))

    def apply(x):
        return (x.astype(jnp.float32) - sub) / div * scale + add

    def revert(y):
        return (y.astype(jnp.float32) - add) / scale * div + sub

    return apply, revert


# ---------------------------------------------------------------------------
# per-op lowerers: op -> traceable fn({name: jnp array}) -> {name: jnp array}
#
# Each mirrors the NumPy `apply` of its TransformOp, with two deliberate
# differences: math runs in float32 (not float64 — parity is to f32
# tolerance), and the fns tolerate absent keys (label columns ship in a
# separate narrow array and never enter the device feature dict).
# ---------------------------------------------------------------------------


def _lower_categorical_to_integer(op, schema):
    import jax.numpy as jnp

    def fn(cols):
        out = dict(cols)
        if op.column in out:        # host already encoded strings -> codes
            out[op.column] = out[op.column].astype(jnp.int32)
        return out
    return fn


def _lower_categorical_to_one_hot(op, schema):
    import jax
    import jax.numpy as jnp
    cats = schema.column(op.column).categories
    names = [f"{op.column}[{c}]" for c in cats]

    def fn(cols):
        out = {}
        for c in schema.columns:
            if c.name == op.column:
                if op.column not in cols:
                    continue
                eye = jax.nn.one_hot(cols[op.column].astype(jnp.int32),
                                     len(cats), dtype=jnp.float32)
                for k, n in enumerate(names):
                    out[n] = eye[..., k]
            elif c.name in cols:
                out[c.name] = cols[c.name]
        return out
    return fn


def _lower_min_max(op, schema):
    import jax.numpy as jnp
    span = (op.max - op.min) or 1.0

    def fn(cols):
        out = dict(cols)
        if op.column in out:
            x = out[op.column].astype(jnp.float32)
            out[op.column] = ((x - op.min) / span * (op.hi - op.lo) + op.lo)
        return out
    return fn


def _lower_standardize(op, schema):
    import jax.numpy as jnp
    std = op.std or 1.0

    def fn(cols):
        out = dict(cols)
        if op.column in out:
            out[op.column] = (out[op.column].astype(jnp.float32)
                              - op.mean) / std
        return out
    return fn


def _lower_remove_columns(op, schema):
    def fn(cols):
        return {k: v for k, v in cols.items() if k not in op.columns}
    return fn


def _lower_rename_column(op, schema):
    def fn(cols):
        return {(op.new if k == op.old else k): v for k, v in cols.items()}
    return fn


def _lower_derived_column(op, schema):
    import jax.numpy as jnp
    der = {"add": lambda a, b: a + b, "sub": lambda a, b: a - b,
           "mul": lambda a, b: a * b, "div": lambda a, b: a / b,
           "log": lambda a, _: jnp.log(a), "abs": lambda a, _: jnp.abs(a)}

    def fn(cols):
        out = dict(cols)
        a = cols[op.columns[0]].astype(jnp.float32)
        if op.fn in ("log", "abs"):
            out[op.name] = der[op.fn](a, None)
        elif len(op.columns) >= 2:
            acc = a
            for c in op.columns[1:]:
                acc = der[op.fn](acc, cols[c].astype(jnp.float32))
            out[op.name] = acc
        else:
            out[op.name] = der[op.fn](a, jnp.float32(op.scalar))
        return out
    return fn


def _lower_sequence_window(op, schema):
    import jax.numpy as jnp

    def fn(cols):
        out = {}
        for k, v in cols.items():
            n = v.shape[0]          # static under jit: windows trace fixed
            if n >= op.size:
                starts = range(0, n - op.size + 1, op.stride)
                out[k] = jnp.stack([v[s:s + op.size] for s in starts])
            else:
                out[k] = jnp.zeros((0, op.size) + v.shape[1:], v.dtype)
        return out
    return fn


_LOWERERS = {
    CategoricalToInteger: _lower_categorical_to_integer,
    CategoricalToOneHot: _lower_categorical_to_one_hot,
    MinMaxNormalize: _lower_min_max,
    Standardize: _lower_standardize,
    RemoveColumns: _lower_remove_columns,
    RenameColumn: _lower_rename_column,
    DerivedColumn: _lower_derived_column,
    SequenceWindow: _lower_sequence_window,
}
# FilterRows is intentionally absent: its output row count depends on the
# data, which XLA's static shapes cannot express — it always runs in the
# host prefix (where dropping rows is a cheap boolean index).


def _op_touches(op, columns):
    """Does `op` read or write any of `columns`? Used to keep label columns
    out of the device suffix (labels ship as their own narrow array)."""
    cols = set(columns)
    if isinstance(op, SequenceWindow):
        return True                 # windows every column, labels included
    for attr in ("column", "old", "new", "name"):
        if getattr(op, attr, None) in cols:
            return True
    if cols & set(getattr(op, "columns", ()) or ()):
        return True
    return False


# ---------------------------------------------------------------------------
# the composite
# ---------------------------------------------------------------------------

class DeviceIngest:
    """Compile an ETL column chain into (host prefix, narrow wire, device
    suffix).

    Host side: `prepare_host(records)` runs only the non-lowerable prefix
    ops, encodes categorical strings to integer codes, and packs the
    surviving feature columns into ONE narrow array (`wire_dtype`), labels
    into another — the bytes that actually cross the host link.

    Device side: `apply_features(x)` / `apply_labels(y)` are traceable jnp
    functions doing decode/cast/one-hot/normalize; fuse them into a train
    step with `network.set_ingest(ingest)` (ONE executable, zero
    steady-state recompiles) or run them standalone via `jit_apply_features`
    (what `DevicePrefetcher(device_transform=...)` consumes).

    Without a `transform` this is the image idiom: uint8 pixels on the wire,
    the lowered normalizer (or the model's own scaler preprocessor) widening
    on-chip. `one_hot_labels=N` ships integer class ids and expands them on
    device — the label matrix never crosses the link.
    """

    def __init__(self, transform: TransformProcess | None = None,
                 normalizer: DataNormalizer | None = None,
                 label_columns=None, one_hot_labels=None, feature_dtype=None):
        self.transform = transform
        self.normalizer = normalizer
        self.label_columns = list(label_columns or [])
        self.one_hot_labels = int(one_hot_labels) if one_hot_labels else None
        if self.one_hot_labels and len(self.label_columns) > 1:
            raise ValueError("one_hot_labels needs exactly one label column")
        self._wire_override = feature_dtype
        self._norm_apply = self._norm_apply_labels = None
        if normalizer is not None:
            self._norm_apply, _ = lower_normalizer(normalizer)
            if normalizer.fit_labels:
                # host transform() normalizes labels iff fit_labels, with
                # the labels=True stats — mirror that exactly on device
                self._norm_apply_labels, _ = lower_normalizer(normalizer,
                                                              labels=True)
        self._jit_features = None
        self._jit_labels = None
        self._compile_split()

    # ---- chain split -------------------------------------------------------
    def _compile_split(self):
        tp = self.transform
        if tp is None:
            self._host_ops, self._device_ops = [], []
            self._mid_schema = None
            self._feature_names = self._final_feature_names = None
            self.wire_dtype = None
            return
        ops = tp.ops
        split = len(ops)
        for i in reversed(range(len(ops))):
            if type(ops[i]) not in _LOWERERS:
                break
            if self.label_columns and _op_touches(ops[i], self.label_columns):
                break
            split = i
        self._split = split
        self._host_ops = ops[:split]
        self._device_ops = ops[split:]
        self._mid_schema = tp.schema_at(split)
        mid_names = self._mid_schema.names()
        missing = [c for c in self.label_columns if c not in mid_names]
        if missing:
            raise ValueError(
                f"label columns {missing} not present at the device-ingest "
                f"split (schema: {mid_names}); create them before any "
                f"device-lowerable op")
        self._feature_names = [n for n in mid_names
                               if n not in self.label_columns]
        final = tp.final_schema().names()
        self._final_feature_names = [n for n in final
                                     if n not in self.label_columns]
        # lowered device chain, one fn per suffix op, schemas pre-resolved
        self._lowered = [
            _LOWERERS[type(op)](op, tp.schema_at(split + i))
            for i, op in enumerate(self._device_ops)]
        self.wire_dtype = self._pick_wire_dtype()

    def _pick_wire_dtype(self):
        if self.transform is None:
            return None
        if self._wire_override is not None:
            return np.dtype(self._wire_override)
        kinds, vocab_max = set(), 0
        for n in self._feature_names:
            c = self._mid_schema.column(n)
            kinds.add(c.kind)
            if c.kind == ColumnType.CATEGORICAL:
                vocab_max = max(vocab_max, len(c.categories))
        if ColumnType.NUMERIC in kinds or ColumnType.STRING in kinds:
            return np.dtype(np.float32)     # half the float64 batch bytes
        if ColumnType.INTEGER in kinds:
            return np.dtype(np.int32)
        return np.dtype(np.uint8 if vocab_max <= 256 else np.int32)

    # ---- host side ---------------------------------------------------------
    def prepare_host(self, records) -> DataSet:
        """records -> narrow DataSet: host prefix ops + categorical encoding
        + packing, NO float widening (that is the device's job)."""
        if self.transform is None:
            raise ValueError("prepare_host needs a TransformProcess; for "
                             "array sources build narrow DataSets directly")
        batch = self.transform.initial_schema.to_batch(records)
        return self.prepare_host_batch(batch)

    def prepare_host_batch(self, batch) -> DataSet:
        """Vectorized entry point: a column batch from `Schema.to_batch`."""
        for i, op in enumerate(self._host_ops):
            batch = op.apply(batch, self.transform.schema_at(i))
        cols = {n: self._encode(n, batch[n]) for n in self._mid_schema.names()}
        x = np.stack([np.asarray(cols[n], self.wire_dtype)
                      for n in self._feature_names], axis=-1)
        y = self._pack_labels(cols)
        return DataSet(x, y)

    def _encode(self, name, values):
        col = self._mid_schema.column(name)
        if col.kind != ColumnType.CATEGORICAL:
            return values
        lut = {c: i for i, c in enumerate(col.categories)}
        return np.asarray([lut[v] for v in values], np.int32)

    def _pack_labels(self, cols):
        if not self.label_columns:
            return None                     # DataSet mirrors features
        if self.one_hot_labels:
            ids = np.asarray(cols[self.label_columns[0]])
            return ids.astype(np.uint8 if self.one_hot_labels <= 256
                              else np.int32)
        return np.stack([np.asarray(cols[n], np.float32)
                         for n in self.label_columns], axis=-1)

    def host_reference(self, records) -> DataSet:
        """The WIDE host path (full NumPy chain + host normalizer) — the
        parity oracle `device_apply` is tested against, and exactly what
        `ParallelPipelineExecutor` produces without device ingest."""
        tp = self.transform
        cols = tp.execute_batch(tp.initial_schema.to_batch(records))
        feats = np.stack([np.asarray(cols[n], np.float32)
                          for n in self._final_feature_names], axis=-1)
        if self.one_hot_labels:
            idx = np.asarray(cols[self.label_columns[0]], np.int64)
            labels = np.eye(self.one_hot_labels, dtype=np.float32)[idx]
        elif self.label_columns:
            labels = np.stack([np.asarray(cols[n], np.float32)
                               for n in self.label_columns], axis=-1)
        else:
            labels = feats
        ds = DataSet(feats, labels)
        if self.normalizer is not None:
            ds = self.normalizer.transform(ds)
        return ds

    # ---- device side (traceable) -------------------------------------------
    def _apply_chain(self, x):
        """Unpack the narrow wire batch, run the lowered op suffix, stack in
        final-schema order — the transform chain WITHOUT the normalizer."""
        import jax.numpy as jnp
        if self.transform is None:
            return x
        cols = {n: x[..., i]
                for i, n in enumerate(self._feature_names)}
        for fn in self._lowered:
            cols = fn(cols)
        return jnp.stack([cols[n].astype(jnp.float32)
                          for n in self._final_feature_names], axis=-1)

    def apply_features(self, x):
        """Narrow wire batch -> float32 feature batch, entirely in jnp:
        unpack columns, run the lowered op suffix, stack in final-schema
        order, apply the lowered normalizer. Traceable — fusing it into a
        jitted train step adds ZERO host round-trips."""
        x = self._apply_chain(x)
        if self._norm_apply is not None:
            x = self._norm_apply(x)
        return x

    def apply_labels(self, y):
        """Narrow label batch -> what the loss consumes (one-hot expansion
        happens here, on device — the label matrix never crosses the wire).
        Mirrors the host path: labels see the transform chain (when they
        mirror features) and the normalizer's LABEL stats iff fit_labels —
        never the feature stats."""
        import jax
        import jax.numpy as jnp
        if self.one_hot_labels:
            if y.ndim > 1 and y.shape[-1] == 1:
                y = y[..., 0]
            y = jax.nn.one_hot(y.astype(jnp.int32), self.one_hot_labels,
                               dtype=jnp.float32)
        elif not self.label_columns:
            y = self._apply_chain(y)        # mirrored features-as-labels
        if self._norm_apply_labels is not None:
            y = self._norm_apply_labels(y)
        return y

    # ---- standalone jits (DevicePrefetcher / serving use) ------------------
    @property
    def jit_apply_features(self):
        if self._jit_features is None:
            import jax
            self._jit_features = jax.jit(self.apply_features)
        return self._jit_features

    @property
    def jit_apply_labels(self):
        if self._jit_labels is None:
            import jax
            self._jit_labels = jax.jit(self.apply_labels)
        return self._jit_labels

    # ---- accounting --------------------------------------------------------
    def bytes_per_row(self):
        """Wire bytes per record (features + labels): what a batch of
        this ingest costs the host link."""
        if self.transform is None:
            return None
        n = len(self._feature_names) * self.wire_dtype.itemsize
        if self.one_hot_labels:
            n += 1 if self.one_hot_labels <= 256 else 4
        elif self.label_columns:
            n += 4 * len(self.label_columns)
        return n

    def __repr__(self):
        host = [type(o).__name__ for o in self._host_ops] \
            if self.transform else []
        dev = [type(o).__name__ for o in self._device_ops] \
            if self.transform else []
        return (f"DeviceIngest(host={host}, device={dev}, "
                f"wire_dtype={self.wire_dtype}, "
                f"normalizer={type(self.normalizer).__name__ if self.normalizer else None})")
