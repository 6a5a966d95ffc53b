"""Central metrics registry: thread-safe counters, gauges, and bounded
histograms with exact-bucket percentiles.

One registry replaces the three ad-hoc metric stores the stack grew
(`serving/metrics.py` private counters+reservoir, `ui/stats.py` listener
state, `optimize/listeners` throughput fields): producers get-or-create
named instruments here, and every consumer (JSON snapshot, Prometheus text
exposition, the ui/storage router flush) reads the same state.

Instruments support labels Prometheus-style: `c.inc(2, bucket="8")` keeps
one value per label-set inside the instrument. Histograms keep, per
label-set, the fixed-bucket cumulative counts (for Prometheus `_bucket`
series) plus a bounded most-recent-sample reservoir for exact percentiles —
the reservoir is COPIED under the lock and sorted outside it, so a
percentile read never stalls the recording hot path (the old
ServingMetrics.snapshot sorted 4096 samples while holding the lock).
"""
from __future__ import annotations

import threading

from .trace import current_span
from ..util.time_source import now_s


def _labelkey(labels):
    return tuple(sorted(labels.items()))


def _quantile(sorted_vals, q):
    """Exact quantile over an already-sorted list, or None when empty (the
    one implementation behind percentile/percentiles)."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              int(round(float(q) * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


class _Instrument:
    kind = "untyped"

    def __init__(self, name, help=""):
        self.name = str(name)
        self.help = str(help)
        self._lock = threading.Lock()

    def series(self):
        """[(labels_dict, value)] for exposition."""
        raise NotImplementedError


class Counter(_Instrument):
    """Monotonically increasing. `add`/`get` mirror util.concurrency
    .AtomicCounter so existing callers swap in without code changes."""

    kind = "counter"

    def __init__(self, name, help=""):
        super().__init__(name, help)
        self._values = {}

    def inc(self, n=1, **labels):
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        key = _labelkey(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0) + n
            return self._values[key]

    add = inc                       # AtomicCounter-compatible spelling

    def get(self, **labels):
        """Value for one label-set, or the sum over all when unlabeled."""
        with self._lock:
            if labels:
                return self._values.get(_labelkey(labels), 0)
            return sum(self._values.values()) if self._values else 0

    @property
    def value(self):
        return self.get()

    def series(self):
        with self._lock:
            return [(dict(k), v) for k, v in sorted(self._values.items())]


class Gauge(_Instrument):
    """Point-in-time value; either set explicitly or computed by a callback
    at collection time (queue depth, device memory)."""

    kind = "gauge"

    def __init__(self, name, help="", fn=None):
        super().__init__(name, help)
        self._values = {}
        self._fn = fn
        self.fn_label = "name"      # label key for dict-returning callbacks

    def set(self, value, **labels):
        with self._lock:
            self._values[_labelkey(labels)] = float(value)

    def inc(self, n=1, **labels):
        key = _labelkey(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + n

    def dec(self, n=1, **labels):
        self.inc(-n, **labels)

    def set_function(self, fn):
        self._fn = fn

    def get(self, **labels):
        if self._fn is not None:
            try:
                return self._fn()
            except Exception as e:
                self._log_callback_error(e)
                return None
        with self._lock:
            return self._values.get(_labelkey(labels))

    def _log_callback_error(self, exc):
        # prefer the owning registry's logger (a ServingServer wires its own
        # StructuredLogger there, so the error shows on THAT server's /logs);
        # lazy import: logging builds its counter on this module's registry
        try:
            logger = getattr(getattr(self, "_owner", None), "logger", None)
            if logger is None:
                from .logging import get_logger
                logger = get_logger()
            logger.warning("gauge_callback_error", metric=self.name,
                           error=f"{type(exc).__name__}: {exc}")
        except Exception:
            pass                       # logging must never break a scrape

    def series(self):
        if self._fn is not None:
            try:
                v = self._fn()
            except Exception as e:     # a dead callback must not kill scrape
                self._log_callback_error(e)
                return []
            if v is None:
                return []
            if isinstance(v, dict):    # callback may return {label: value}
                return [({self.fn_label: str(k)}, float(x)) for k, x in
                        sorted(v.items())]
            return [({}, float(v))]
        with self._lock:
            return [(dict(k), v) for k, v in sorted(self._values.items())]


DEFAULT_LATENCY_BUCKETS_MS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                              500.0, 1000.0, 2500.0, 5000.0, 10000.0)


class _HistState:
    __slots__ = ("count", "sum", "max", "bucket_counts", "reservoir", "_cap",
                 "exemplars", "_ex_cap")

    def __init__(self, n_buckets, reservoir_cap, exemplar_cap):
        self.count = 0
        self.sum = 0.0
        self.max = None                        # over the series' whole life
        self.bucket_counts = [0] * n_buckets   # non-cumulative, per bound
        self.reservoir = []                    # most-recent cap samples
        self._cap = reservoir_cap
        # bounded latest-wins (value, trace_id) exemplars: the join key from
        # a metric anomaly back to its /trace spans and /logs records
        self.exemplars = []
        self._ex_cap = exemplar_cap

    def observe(self, v, bounds, trace_id=None):
        self.count += 1
        self.sum += v
        if self.max is None or v > self.max:
            self.max = v
        for i, b in enumerate(bounds):
            if v <= b:
                self.bucket_counts[i] += 1
                break
        self.reservoir.append(v)
        if len(self.reservoir) > self._cap:
            del self.reservoir[:len(self.reservoir) - self._cap]
        if trace_id is not None and self._ex_cap > 0:
            self.exemplars.append({"value": v, "trace_id": trace_id,
                                   "time": now_s()})
            if len(self.exemplars) > self._ex_cap:
                del self.exemplars[:len(self.exemplars) - self._ex_cap]


class Histogram(_Instrument):
    """Fixed-bound buckets (+inf implicit) plus a bounded most-recent
    reservoir for exact percentiles over recent traffic."""

    kind = "histogram"
    RESERVOIR = 4096
    EXEMPLARS = 10      # per label-set: bounded, latest-wins

    def __init__(self, name, help="", buckets=DEFAULT_LATENCY_BUCKETS_MS,
                 reservoir=RESERVOIR, exemplars=EXEMPLARS):
        super().__init__(name, help)
        self.bounds = tuple(sorted(float(b) for b in buckets))
        self.reservoir_cap = int(reservoir)
        self.exemplar_cap = int(exemplars)
        self._states = {}

    def _state(self, labels):
        key = _labelkey(labels)
        st = self._states.get(key)
        if st is None:
            st = self._states[key] = _HistState(len(self.bounds) + 1,
                                                self.reservoir_cap,
                                                self.exemplar_cap)
        return st

    def observe(self, value, trace_id=None, **labels):
        """Record one observation. `trace_id` (or, by default, the calling
        thread's current span) becomes a bounded OpenMetrics exemplar —
        the pointer from "p99 spiked" to the exact trace that spiked it."""
        v = float(value)
        if trace_id is None:
            span = current_span()
            if span is not None:
                trace_id = span.trace_id
        with self._lock:
            st = self._state(labels)
            bounded = self.bounds + (float("inf"),)
            st.observe(v, bounded, trace_id=trace_id)

    def exemplars(self, **labels):
        """Recorded exemplars, oldest first: one label-set's when labels are
        given, else the union across every label-set (the alert-rule read)."""
        with self._lock:
            out = [dict(e) for st in self._series(labels)
                   for e in st.exemplars]
        out.sort(key=lambda e: e["time"])
        return out

    def _series(self, labels):
        """The states a read covers, under the lock: one label-set's when
        labels are given, else every label-set's — a bare read is the total
        over the series, as `Counter.get()` is (a reader that names no
        labels must not miss `pipeline=<name>` observations)."""
        if labels:
            st = self._states.get(_labelkey(labels))
            return [st] if st else []
        return list(self._states.values())

    def count(self, **labels):
        with self._lock:
            return sum(st.count for st in self._series(labels))

    def sum(self, **labels):
        with self._lock:
            return sum((st.sum for st in self._series(labels)), 0.0)

    def max(self, **labels):
        """Largest observation of the series' whole life (the reservoir only
        holds the recent ones), or None before the first; without labels,
        over every label-set."""
        with self._lock:
            return max((st.max for st in self._series(labels)
                        if st.max is not None), default=None)

    def _reservoir_copy(self, labels):
        with self._lock:
            return [v for st in self._series(labels) for v in st.reservoir]

    def percentile(self, q, **labels):
        """Exact percentile over the recent reservoir (sorted OUTSIDE the
        lock), or None when empty; without labels, over the union of every
        label-set's reservoir."""
        vals = self._reservoir_copy(labels)
        vals.sort()
        return _quantile(vals, q)

    def percentiles(self, qs=(0.50, 0.95, 0.99), **labels):
        """One reservoir copy + one sort for several quantiles; returns
        {"count", "p50", ..., "max"} (the old ServingMetrics latency shape),
        all over the recent reservoir; the life's maximum is `max()`."""
        vals = self._reservoir_copy(labels)
        vals.sort()
        out = {"count": len(vals)}
        for q in qs:
            out[f"p{int(round(q * 100))}"] = _quantile(vals, q)
        out["max"] = vals[-1] if vals else None
        return out

    def series(self):
        """[(labels, {"count", "sum", "max", "buckets": [(le,
        cumulative)...], "exemplars": [...]})]."""
        with self._lock:
            out = []
            for key, st in sorted(self._states.items()):
                cum, buckets = 0, []
                bounded = self.bounds + (float("inf"),)
                for b, c in zip(bounded, st.bucket_counts):
                    cum += c
                    buckets.append((b, cum))
                out.append((dict(key), {"count": st.count, "sum": st.sum,
                                        "max": st.max, "buckets": buckets,
                                        "exemplars": [dict(e) for e in
                                                      st.exemplars]}))
            return out


class MetricsRegistry:
    """Get-or-create named instruments; collect them all for exposition.
    `logger` (optional, a StructuredLogger) receives instrument-level
    problems like raising gauge callbacks — a server wires its own logger
    here so those records show on that server's /logs."""

    def __init__(self, logger=None):
        self._metrics = {}
        self._lock = threading.Lock()
        self.logger = logger

    def _get_or_create(self, cls, name, help, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help=help, **kw)
                m._owner = self
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {m.kind}")
            return m

    def counter(self, name, help="") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name, help="", fn=None) -> Gauge:
        g = self._get_or_create(Gauge, name, help)
        if fn is not None:
            g.set_function(fn)
        return g

    def histogram(self, name, help="",
                  buckets=DEFAULT_LATENCY_BUCKETS_MS) -> Histogram:
        return self._get_or_create(Histogram, name, help, buckets=buckets)

    def get(self, name):
        with self._lock:
            return self._metrics.get(name)

    def unregister(self, name):
        with self._lock:
            self._metrics.pop(name, None)

    def collect(self):
        with self._lock:
            return [self._metrics[k] for k in sorted(self._metrics)]

    # ---- consumers ---------------------------------------------------------
    def snapshot(self):
        """JSON-friendly dump of every instrument (counters/gauges by
        label-set; histograms as count/sum/percentiles)."""
        out = {"time": now_s()}
        for m in self.collect():
            if m.kind == "histogram":
                d = m.percentiles()
                d["sum"] = m.sum()
                ex = m.exemplars()
                if ex:
                    d["exemplars"] = ex
                out[m.name] = d
            else:
                series = m.series()
                if len(series) == 1 and not series[0][0]:
                    out[m.name] = series[0][1]
                else:
                    out[m.name] = {
                        ",".join(f"{k}={v}" for k, v in sorted(ls.items()))
                        or "": v for ls, v in series}
        return out

    def to_prometheus(self):
        from .prometheus import render
        return render(self)


_default_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """Process-default registry (training listeners, streaming, the UI
    server's /metrics endpoint)."""
    return _default_registry
