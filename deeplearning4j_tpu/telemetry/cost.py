"""Live cost attribution: per-executable FLOPs / HBM bytes / roofline plane.

XLA can say what an executable COSTS (`Compiled.cost_analysis()` → flops +
bytes accessed, `memory_analysis()` → temp/argument/output buffer bytes).
This module asks it for every executable of the live system (serving batcher
buckets, decode step/prefill/verify, mesh dispatch, training jit caches), so
a running deployment can say which of them is eating the bandwidth, in bytes
per sample per deploy, and raise an alarm when that regresses:

- `compiled_costs(compiled)` / `classify(...)` — ONE implementation of the
  cost-dict extraction and the roofline arithmetic (two legs, one binding
  rule: hbm leg vs the configured nominal bandwidth, matmul leg vs the
  configured MXU ceiling).
- `ExecutableCostRegistry` — hooks every compile site the stack already
  funnels through `CompileTracker`/`timed_first_call`. At compile time it
  re-lowers the jitted callable from `ShapeDtypeStruct` abstractions of the
  real arguments (captured BEFORE the donating first call invalidates them;
  AOT lowering does not touch jax's dispatch cache, so the zero-recompile
  invariants hold) and records flops, bytes accessed, and buffer sizes,
  normalized per-sample/per-token, classified into `roofline_binding` /
  `roofline_util` gauges on the stack's MetricsRegistry.
- A cheap sampled per-dispatch wall-time histogram (`dispatch_ms`, every Nth
  dispatch, one lock + int increment off the sampled path) makes
  achieved-vs-roofline live: `roofline_util` is re-estimated from each
  sampled dispatch.
- A "bytes regression at deploy time" plane: when a deploy/hot-swap
  re-captures an executable family at a new version, the registry sets
  `deploy_hbm_bytes_per_sample_ratio{family}` (and an unlabeled max) to
  new/old bytes-per-sample — the gauge a default AlertEngine rule watches so
  a quantized→f32 fallback trips an alarm instead of silently doubling HBM
  traffic.
- `install_donation_watch()` — donation failures observable at runtime: a
  chained `warnings.showwarning` hook counts XLA "donated buffers were not
  usable" warnings into `donation_warnings_total{site}` with a
  trace-correlated structured log record, instead of scraping stderr.
- `capture_trace(steps)` — the bounded on-demand capture behind
  `GET /profile/trace?steps=N`: flips the in-process Tracer on, waits (hard
  iteration bound, never a jax.profiler session) for N fresh spans, restores
  the tracer's prior state, and returns a Chrome-trace dict of just the
  captured window.
"""
from __future__ import annotations

import sys
import threading
import time
import warnings as _pywarnings

from .registry import get_registry
from .trace import get_tracer
from ..util.time_source import monotonic_s

# Published peaks of one chip, keyed by `jax.devices()[0].device_kind` — the
# one table the live plane reads. A registry may be given other ceilings
# (`ExecutableCostRegistry(matmul_tflops_ceiling=, hbm_gbps_ceiling=)`);
# cost_analysis byte counts are an upper bound, so an HBM leg is one too.
# A device that is not listed has no peaks: its rows carry no
# roofline legs, binding or util (None) — never another chip's numbers.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,         # bf16 dense, FLOP/s
        "hbm_bps": 819e9,        # bytes/s
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip"},
}


def device_peaks(device_kind=None):
    """The `DEVICE_PEAKS` row of `device_kind` (default: the first device
    JAX reports), or None for a device the table does not list."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    return DEVICE_PEAKS.get(device_kind)


_COST_KEYS = (("flops", "flops"), ("bytes accessed", "hbm_bytes"))
_MEM_KEYS = (("temp_size_in_bytes", "temp_bytes"),
             ("argument_size_in_bytes", "argument_bytes"),
             ("output_size_in_bytes", "output_bytes"),
             ("generated_code_size_in_bytes", "code_bytes"))


def abstractify(tree):
    """Map a pytree of concrete arrays to `jax.ShapeDtypeStruct` leaves so an
    executable can be re-lowered WITHOUT live buffers — donated arguments are
    invalidated by the first real call, so capture this before it."""
    import jax

    def leaf(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        return a

    return jax.tree_util.tree_map(leaf, tree)


def compiled_costs(compiled):
    """Normalize `Compiled.cost_analysis()` + `memory_analysis()` into one
    flat dict: {flops, hbm_bytes, temp_bytes, argument_bytes, output_bytes,
    code_bytes}. Missing keys and backends that report nothing degrade to
    0.0, never raise."""
    out = {name: 0.0 for _, name in _COST_KEYS}
    out.update({name: 0.0 for _, name in _MEM_KEYS})
    try:
        ca = compiled.cost_analysis()
        for key, name in _COST_KEYS:
            v = ca.get(key)
            if v is not None:
                out[name] = float(v)
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for attr, name in _MEM_KEYS:
            v = getattr(ma, attr, None)
            if v is not None:
                out[name] = float(v)
    except Exception:
        pass
    return out


def _pallas_kernel_count(compiled):
    """Pallas (Mosaic) custom calls in a compiled program's text: 0 on the
    CPU, where interpret mode runs the kernel body as plain XLA ops; None
    for a stand-in that has no text to read."""
    try:
        return compiled.as_text().count("tpu_custom_call")
    except Exception:
        return None


def classify(flops, hbm_bytes, tflops_ceiling=None, hbm_bps_ceiling=None,
             measured_ms=None):
    """The roofline arithmetic of every row of the cost plane:
    compute leg = flops / matmul ceiling, HBM leg = bytes / bandwidth
    ceiling; binding is whichever leg is longer; util (when a measured wall
    time is supplied) is the longer leg over the measured time — util ≈ 1.0
    means the executable already runs as fast as its binding wall allows.
    Ceilings are FLOP/s and bytes/s; without both (a device `DEVICE_PEAKS`
    does not list) every field is None."""
    if not tflops_ceiling or not hbm_bps_ceiling:
        return {"roofline_compute_ms": None, "roofline_hbm_ms": None,
                "roofline_binding": None, "roofline_util": None}
    t_mm_ms = float(flops) / float(tflops_ceiling) * 1e3
    t_bw_ms = float(hbm_bytes) / float(hbm_bps_ceiling) * 1e3
    out = {"roofline_compute_ms": t_mm_ms,
           "roofline_hbm_ms": t_bw_ms,
           "roofline_binding": "hbm" if t_bw_ms > t_mm_ms else "matmul"}
    if measured_ms and measured_ms > 0:
        out["roofline_util"] = max(t_mm_ms, t_bw_ms) / float(measured_ms)
    else:
        out["roofline_util"] = None
    return out


class ExecutableCostRegistry:
    """Per-executable cost table + live roofline gauges for one stack.

    One instance per serving/training stack (CompileTracker-style), sharing
    the stack's MetricsRegistry. Call sites:

    - `capture(label, fn, args, ...)` at each first-call/compile seam, with
      the ABSTRACT argument snapshot (see `abstractify`); the jitted fn is
      re-lowered AOT (dispatch cache untouched) and its XLA-reported costs
      recorded.
    - `record_dispatch(label, ms)` on EVERY dispatch: pays one lock + int
      increment; every `sample_every`th dispatch lands in the `dispatch_ms`
      histogram and refreshes that executable's `roofline_util` gauge.
    """

    def __init__(self, registry=None, matmul_tflops_ceiling=None,
                 hbm_gbps_ceiling=None, sample_every=16):
        self.registry = registry if registry is not None else get_registry()
        # Ceilings arrive in the units people quote (TFLOP/s, GB/s) and are
        # held in base units (FLOP/s, bytes/s); left
        # out, they are this device's published peaks, or None when
        # DEVICE_PEAKS does not list it.
        peaks = {} if matmul_tflops_ceiling and hbm_gbps_ceiling \
            else device_peaks() or {}
        self.tf_ceiling = (float(matmul_tflops_ceiling) * 1e12
                           if matmul_tflops_ceiling else peaks.get("flops"))
        self.bw_ceiling = (float(hbm_gbps_ceiling) * 1e9
                           if hbm_gbps_ceiling else peaks.get("hbm_bps"))
        self.sample_every = max(1, int(sample_every))
        self._lock = threading.Lock()
        self._records = {}            # label -> row dict
        self._dispatch_n = {}         # label -> total dispatch count
        self._ratio = {}              # (family, label) -> last deploy ratio
        r = self.registry
        self.captures = r.counter(
            "cost_captures_total",
            "Executable cost captures (XLA cost_analysis at compile time)")
        self.capture_errors = r.counter(
            "cost_capture_errors_total",
            "Executable cost captures that failed (backend reported nothing)")
        self.captures.inc(0)
        self.capture_errors.inc(0)
        self.flops_gauge = r.gauge(
            "executable_flops_per_sample",
            "XLA-reported FLOPs per sample/token, labeled by executable")
        self.bytes_gauge = r.gauge(
            "executable_hbm_bytes_per_sample",
            "XLA-reported HBM bytes accessed per sample/token, "
            "labeled by executable")
        self.binding_gauge = r.gauge(
            "roofline_binding",
            "Roofline binding per executable: 1 = hbm-bound, 0 = matmul-bound "
            "(no series for a device without published peaks)")
        self.util_gauge = r.gauge(
            "roofline_util",
            "Live roofline utilization estimate per executable "
            "(binding leg / sampled dispatch wall time)")
        self.dispatch_hist = r.histogram(
            "dispatch_ms",
            "Sampled per-dispatch wall milliseconds, labeled by executable")
        self.ratio_gauge = r.gauge(
            "deploy_hbm_bytes_per_sample_ratio",
            "hbm_bytes_per_sample of the newest captured version over the "
            "previous version, per executable family (unlabeled = worst); "
            ">1.2 means a deploy regressed the byte diet")
        self.ratio_gauge.set(1.0)

    # ---- capture ----------------------------------------------------------
    def capture(self, label, fn, args=(), kwargs=None, family=None,
                samples=1, version=None):
        """Lower `fn` (a jitted callable, possibly timed_first_call-wrapped)
        for the given ABSTRACT args and record its XLA costs under `label`.
        `samples` is the batch/token count one execution serves (the padded
        bucket, decode slots, verify window...) — the per-sample normalizer.
        Never raises: capture is observability, not control flow."""
        try:
            # Unwrap timed_first_call-style wrappers, but stop at the first
            # object that can lower: jax.jit functions set __wrapped__ to the
            # RAW python function, so unwrapping past them loses .lower.
            target = fn
            while not hasattr(target, "lower"):
                inner = getattr(target, "__wrapped__", None)
                if inner is None:
                    break
                target = inner
            # This is a SHADOW compile for accounting only: abstract args
            # carry no sharding/placement, so XLA may re-emit warnings
            # (donation-unusable on sharded caches) that the real compile
            # did not — silence them here so the diagnostic lower never
            # pollutes donation watches or test warning nets.
            t0 = monotonic_s()
            with _pywarnings.catch_warnings():
                _pywarnings.simplefilter("ignore")
                comp = target.lower(*args, **(kwargs or {})).compile()
            capture_ms = (monotonic_s() - t0) * 1e3
        except Exception as e:
            # counted AND said: a seam that cannot lower on this device must
            # not look fine because only a counter moved
            self.capture_errors.inc(1, executable=str(label))
            from .logging import get_logger
            (getattr(self.registry, "logger", None) or get_logger()).warning(
                "cost_capture_failed", executable=str(label),
                error=f"{type(e).__name__}: {e}")
            return None
        return self.capture_compiled(label, comp, family=family,
                                     samples=samples, version=version,
                                     capture_ms=capture_ms)

    def capture_compiled(self, label, compiled, family=None, samples=1,
                         version=None, capture_ms=None):
        """Record costs for an already-compiled executable (what `capture`
        ends in, and where a caller that compiled ahead of time comes in).
        Returns the stored row. `capture_ms` is what the shadow
        lower + compile cost — with the persistent compilation cache on
        (util/compile_cache.py) a re-trace and a cache read, not a second
        compile. `pallas_kernels` counts the Pallas custom calls in the
        compiled program: 0 under `use_pallas=True` means every attention
        call gave way to a pure-JAX path (see `pallas_fallback_total`)."""
        label = str(label)
        family = str(family) if family else label.split(":", 1)[0]
        samples = max(1, int(samples))
        costs = compiled_costs(compiled)
        cls = classify(costs["flops"], costs["hbm_bytes"],
                       self.tf_ceiling, self.bw_ceiling)
        row = dict(costs)
        row.update(executable=label, family=family, samples=samples,
                   version=None if version is None else str(version),
                   flops_per_sample=costs["flops"] / samples,
                   hbm_bytes_per_sample=costs["hbm_bytes"] / samples,
                   roofline_compute_ms=cls["roofline_compute_ms"],
                   roofline_hbm_ms=cls["roofline_hbm_ms"],
                   roofline_binding=cls["roofline_binding"],
                   roofline_util=None, dispatch_ms_p50=None, dispatches=0,
                   capture_ms=capture_ms,
                   pallas_kernels=_pallas_kernel_count(compiled))
        with self._lock:
            prev = self._records.get(label)
            self._records[label] = row
            row["dispatches"] = self._dispatch_n.get(label, 0)
            self._update_deploy_ratio_locked(family, label, row, prev)
        self.captures.inc(1, executable=label, family=family)
        self.flops_gauge.set(row["flops_per_sample"], executable=label)
        self.bytes_gauge.set(row["hbm_bytes_per_sample"], executable=label)
        if row["roofline_binding"] is not None:
            self.binding_gauge.set(
                1.0 if row["roofline_binding"] == "hbm" else 0.0,
                executable=label)
        return row

    def _update_deploy_ratio_locked(self, family, label, row, prev):
        """A re-capture of a known label at a DIFFERENT version is a
        deploy/hot-swap: record new/old bytes-per-sample for the label, and
        publish per-family (max over its labels' latest transitions) plus an
        unlabeled worst-family series — `Gauge.get()` with no labels reads
        only the unlabeled series, and that is what the default alert rule
        watches."""
        if (prev is None or prev.get("version") == row.get("version")
                or not prev.get("hbm_bytes_per_sample")):
            return
        self._ratio[(family, label)] = (row["hbm_bytes_per_sample"]
                                        / prev["hbm_bytes_per_sample"])
        fams = {}
        for (fam, _), r in self._ratio.items():
            fams[fam] = max(fams.get(fam, 0.0), r)
        for fam, r in fams.items():
            self.ratio_gauge.set(r, family=fam)
        self.ratio_gauge.set(max(fams.values()))

    # ---- dispatch sampling ------------------------------------------------
    def dispatch_due(self, label):
        """Count one dispatch of `label`; True when THIS dispatch should be
        timed (every `sample_every`th, starting with the first). Call sites
        whose wall time is not already measured (decode's async step) use
        this to pay the device sync only on sampled dispatches."""
        with self._lock:
            n = self._dispatch_n.get(label, 0) + 1
            self._dispatch_n[label] = n
            row = self._records.get(label)
            if row is not None:
                row["dispatches"] = n
        return n % self.sample_every == 1 or self.sample_every == 1

    def observe_dispatch(self, label, ms):
        """Record one SAMPLED dispatch wall time: lands in the dispatch_ms
        histogram and refreshes the label's live roofline_util estimate
        (binding leg over measured time)."""
        label = str(label)
        self.dispatch_hist.observe(float(ms), executable=label)
        with self._lock:
            row = self._records.get(label)
        if row is not None and ms and ms > 0:
            row["dispatch_ms_p50"] = self.dispatch_hist.percentile(
                0.50, executable=label)
            if row["roofline_binding"] is not None:
                util = max(row["roofline_compute_ms"],
                           row["roofline_hbm_ms"]) / float(ms)
                row["roofline_util"] = util
                self.util_gauge.set(util, executable=label)

    def record_dispatch(self, label, ms):
        """Called on EVERY dispatch where the wall time is already measured
        (the batcher times each dispatch anyway); off the sampled path it
        costs one lock acquire and an int increment."""
        label = str(label)
        if self.dispatch_due(label):
            self.observe_dispatch(label, ms)

    def dispatches(self, label):
        with self._lock:
            return self._dispatch_n.get(str(label), 0)

    # ---- reading ----------------------------------------------------------
    def get(self, label):
        with self._lock:
            row = self._records.get(str(label))
            return dict(row) if row else None

    def labels(self):
        with self._lock:
            return sorted(self._records)

    def table(self, sort="hbm_bytes_per_sample", family=None):
        """Sortable per-executable rows (the `/profile/cost` payload).
        Unknown sort keys fall back to bytes-per-sample — a scrape never
        500s over a typo'd query param on the UI side."""
        with self._lock:
            rows = [dict(r) for r in self._records.values()
                    if family is None or r["family"] == family]
        keyed = sort if rows and sort in rows[0] else "hbm_bytes_per_sample"
        rows.sort(key=lambda r: ((r.get(keyed) is not None, r.get(keyed))
                                 if not isinstance(r.get(keyed), str)
                                 else (True, r.get(keyed))), reverse=True)
        return rows

    def to_dict(self, sort="hbm_bytes_per_sample", family=None):
        return {"ceilings": {
                    "matmul_tflops_ceiling":
                        self.tf_ceiling and self.tf_ceiling / 1e12,
                    "hbm_gbps_ceiling":
                        self.bw_ceiling and self.bw_ceiling / 1e9},
                "sample_every": self.sample_every,
                "executables": self.table(sort=sort, family=family)}


# ---- process-default registry ----------------------------------------------
# None until a stack opts in (smoke tools, ServingServer): the
# training jit-cache seam (`timed_first_call`) consults this and pays a
# single None-check per first call when nobody is attributing costs, so unit
# tests that merely train never pay the AOT re-lower.

_default_cost = None
_default_cost_lock = threading.Lock()


def get_cost_registry():
    return _default_cost


def set_cost_registry(reg):
    global _default_cost
    with _default_cost_lock:
        _default_cost = reg
    return reg


# ---- donation watch ---------------------------------------------------------

DONATION_MARKER = "donated buffers were not usable"

_donation_lock = threading.Lock()
_donation_subscribers = []       # (counter, logger) pairs
_donation_installed = False


def _donation_site():
    """First stack frame outside jax/warnings machinery — the code that
    triggered the donating compile, which is the label that makes the
    counter actionable (`mlir.py` would not be)."""
    f = sys._getframe(2)
    while f is not None:
        fn = f.f_code.co_filename.replace("\\", "/")
        if ("/jax/" not in fn and "/warnings" not in fn
                and not fn.endswith("telemetry/cost.py")):
            parts = fn.rsplit("/", 2)
            return "/".join(parts[-2:]) + f":{f.f_lineno}"
        f = f.f_back
    return "unknown"


def _on_donation_warning(message):
    site = _donation_site()
    with _donation_lock:
        subs = list(_donation_subscribers)
    for counter, logger in subs:
        try:
            counter.inc(1, site=site)
            if logger is not None:
                logger.warning("xla_donation_unusable", site=site,
                               detail=str(message))
        except Exception:   # graftlint: disable=GL005 this IS the error
            pass            # reporter; a raise here would mask the warning


def install_donation_watch(registry=None, logger=None):
    """Make XLA donation failures a live metric instead of stderr noise:
    chain-wrap `warnings.showwarning` so every "donated buffers were not
    usable" warning increments `donation_warnings_total{site}` and emits a
    trace-correlated structured log record. The previous showwarning still
    runs (stderr visibility is kept). Returns an uninstall callable removing
    THIS subscriber (the chain itself stays; it is a no-op with no
    subscribers). Note: `warnings.catch_warnings` blocks that swap
    showwarning (a test's recording net) bypass the chain while active."""
    global _donation_installed
    reg = registry if registry is not None else get_registry()
    counter = reg.counter(
        "donation_warnings_total",
        "XLA donated-buffer-unusable warnings at runtime, labeled by the "
        "triggering call site")
    counter.inc(0)
    sub = (counter, logger)
    with _donation_lock:
        _donation_subscribers.append(sub)
        # (Re-)install whenever the current showwarning is not ours: test
        # harnesses (pytest's warning plugin) and catch_warnings blocks swap
        # showwarning wholesale, silently dropping an earlier chain. Checking
        # the marker instead of a one-shot flag re-chains on top of whatever
        # handler is live now.
        if not hasattr(_pywarnings.showwarning, "_donation_prev"):
            _donation_installed = True
            # Donation warnings repeat per compile; without an "always"
            # filter the warnings registry dedupes after the first and the
            # counter undercounts every later regression.
            _pywarnings.filterwarnings(
                "always", message=".*" + DONATION_MARKER + ".*")
            prev = _pywarnings.showwarning

            def showwarning(message, category, filename, lineno,
                            file=None, line=None):
                if DONATION_MARKER in str(message):
                    _on_donation_warning(message)
                return prev(message, category, filename, lineno,
                            file=file, line=line)

            showwarning._donation_prev = prev
            _pywarnings.showwarning = showwarning

    def uninstall():
        with _donation_lock:
            if sub in _donation_subscribers:
                _donation_subscribers.remove(sub)

    return uninstall


# ---- bounded trace capture --------------------------------------------------

MAX_TRACE_STEPS = 2048


def capture_trace(steps, tracer=None, timeout_s=2.0, poll_s=0.01):
    """Bounded on-demand span capture (the `/profile/trace?steps=N` body):
    enable the in-process Tracer (never a `jax.profiler` session), wait for
    `steps` NEW spans with a hard iteration bound, restore the tracer's
    previous enabled state, and return a Chrome-trace dict of the captured
    window (falling back to the newest ring-buffer spans if traffic is
    idle). Raises ValueError for a non-positive or oversized `steps` — the
    HTTP layer maps that to 400."""
    steps = int(steps)
    if steps <= 0 or steps > MAX_TRACE_STEPS:
        raise ValueError(f"steps must be in [1, {MAX_TRACE_STEPS}]")
    tr = tracer if tracer is not None else get_tracer()
    was_enabled = tr.enabled
    tr.enabled = True
    try:
        have = len(tr.finished_spans())
        # Hard bound: ceil(timeout/poll) real-sleep polls, independent of any
        # ManualClock (which freezes monotonic_s, not time.sleep) — the
        # capture ALWAYS stops.
        for _ in range(max(1, int(float(timeout_s) / max(poll_s, 1e-3)))):
            if len(tr.finished_spans()) - have >= steps:
                break
            time.sleep(poll_s)
    finally:
        tr.enabled = was_enabled
    spans = tr.finished_spans()
    window = spans[have:] if len(spans) > have else spans
    window = window[-steps:]
    keep = {s.span_id for s in window}
    chrome = tr.to_chrome_trace()
    events = [e for e in chrome["traceEvents"]
              if e.get("args", {}).get("span_id") in keep]
    chrome["traceEvents"] = events
    chrome["otherData"]["captured_spans"] = len(window)
    chrome["otherData"]["requested_steps"] = steps
    return chrome
