"""XLA cost accounting: compile/recompile tracking and device-memory gauges.

Following the Julia-to-TPU paper's central observation (PAPERS.md), compile
time is THE dominant hidden cost of an XLA-backed serving/training stack: a
shape the jit cache has not seen stalls the request that triggers it for
orders of magnitude longer than a steady-state dispatch. This module gives
that cost first-class metrics:

- `CompileTracker` wraps the jit-cache path the micro-batcher already
  tracks (its `observed` (signature, bucket) set): the first dispatch of a
  new bucket is the compile, and its wall time is attributed to
  `compile_ms_total` with a per-bucket labeled `compiles_total`.
- `timed_first_call` wraps a freshly-jitted callable so its first invocation
  (which triggers XLA compilation) is timed and counted in the process
  registry — the training-side (`network._jit_cache`) analog.
- `register_device_memory_gauges` installs callback gauges that read
  `jax.local_devices()[i].memory_stats()` at scrape time (periodic by virtue
  of the scraper's cadence; zero cost between scrapes).
"""
from __future__ import annotations

from .registry import get_registry
from ..util.stack_room import call_with_stack_room
from ..util.time_source import monotonic_s


class CompileTracker:
    """Counts XLA (re)compiles and accumulates compile wall-time into a
    MetricsRegistry. One instance per serving stack, sharing the stack's
    registry so `/metrics` exposes `compiles_total` next to request counts."""

    def __init__(self, registry=None, prefix=""):
        self.registry = registry if registry is not None else get_registry()
        p = prefix
        self.compiles = self.registry.counter(
            p + "compiles_total",
            "XLA executable compiles, labeled by padded batch bucket")
        self.compile_ms = self.registry.counter(
            p + "compile_ms_total",
            "Wall milliseconds spent in XLA compiles (first-dispatch proxy)")
        self.compiles.inc(0)
        self.compile_ms.inc(0)

    def record(self, ms, bucket=None, **labels):
        """Record one compile of `ms` wall-milliseconds. The measured first
        dispatch includes one steady-state execution — an upper bound, same
        proxy the Julia-TPU paper reports as compile+first-run."""
        if bucket is not None:
            labels["bucket"] = str(bucket)
        self.compiles.inc(1, **labels)
        self.compile_ms.inc(ms)

    def total(self):
        return self.compiles.get()

    def total_ms(self):
        return self.compile_ms.get()

    def by_bucket(self):
        return {ls.get("bucket", ""): v for ls, v in self.compiles.series()
                if ls}


def record_jit_compile(label, ms, registry=None):
    """Count one training-side jit-cache compile in the (default) registry."""
    reg = registry if registry is not None else get_registry()
    reg.counter("jit_compiles_total",
                "jit-cache misses (new executables), labeled by fn"
                ).inc(1, fn=str(label))
    reg.counter("jit_compile_ms_total",
                "Wall ms spent compiling jit-cache entries "
                "(first-call proxy)").inc(ms)


class _TimedFirstCall:
    """Callable proxy timing only the FIRST invocation (where XLA actually
    compiles). Attribute access (e.g. jax's `_cache_size`) passes through to
    the wrapped jitted callable."""

    __slots__ = ("__wrapped__", "_label", "_registry", "_first")

    def __init__(self, fn, label, registry):
        self.__wrapped__ = fn
        self._label = label
        self._registry = registry
        self._first = True

    def __call__(self, *args, **kwargs):
        if self._first:
            self._first = False
            # Abstract-arg snapshot BEFORE the call: donated buffers are
            # invalidated by it, and cost capture re-lowers from shapes only.
            from .cost import abstractify, get_cost_registry
            cost = get_cost_registry()
            if cost is not None:
                abs_args = abstractify(args)
                abs_kwargs = abstractify(kwargs)
            t0 = monotonic_s()
            # tracing and lowering make millions of Python calls: with room
            # on the data stack their cost does not depend on how deep the
            # caller happens to be (util/stack_room.py)
            out = call_with_stack_room(self.__wrapped__, *args, **kwargs)
            record_jit_compile(self._label, (monotonic_s() - t0) * 1000.0,
                               registry=self._registry)
            if cost is not None:
                cost.capture(self._label, self.__wrapped__,
                             abs_args, abs_kwargs)
            return out
        return self.__wrapped__(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.__wrapped__, name)


def timed_first_call(fn, label, registry=None):
    """Wrap a jitted callable so its FIRST call (where XLA actually
    compiles) is timed and counted via `record_jit_compile`. Later calls
    pay one boolean check. Only the first shape's compile is attributed;
    per-shape recompiles inside jax's own cache stay invisible here (the
    serving path counts those per-bucket via CompileTracker instead)."""
    return _TimedFirstCall(fn, label, registry)


def register_device_memory_gauges(registry=None):
    """Install `device_memory_bytes_in_use` / `..._peak` callback gauges
    reading jax device memory stats at scrape time. Safe everywhere: on
    backends without memory_stats (CPU) the callbacks return {} and the
    gauges render no samples."""
    reg = registry if registry is not None else get_registry()

    def _read(key):
        def fn():
            try:
                import jax
                out = {}
                for d in jax.local_devices():
                    ms = d.memory_stats()
                    if ms and key in ms:
                        out[f"{d.platform}:{d.id}"] = float(ms[key])
                return out
            except Exception:
                return {}
        return fn

    g1 = reg.gauge("device_memory_bytes_in_use",
                   "Per-device bytes currently allocated (jax memory_stats)",
                   fn=_read("bytes_in_use"))
    g2 = reg.gauge("device_memory_peak_bytes",
                   "Per-device peak bytes allocated (jax memory_stats)",
                   fn=_read("peak_bytes_in_use"))
    g1.fn_label = g2.fn_label = "device"
    return g1, g2
