"""What the harness reads while a window runs: the program's counters before
and after, a profiler trace of a slice of the window, and polled gauges."""
from __future__ import annotations

import json
import math
import shutil
import threading
import time

from . import trace_reduce


def note(**fields):
    """An earlier line of stdout worth keeping (never the last)."""
    print(json.dumps(fields, default=str), flush=True)


def nearest_rank(values, q):
    """The q-quantile of `values` by nearest rank."""
    vals = sorted(values)
    return vals[min(len(vals), max(1, math.ceil(q * len(vals) - 1e-9))) - 1]


def memory_peak_bytes(cost_rows=()):
    """Peak bytes on the fullest chip, as JAX reports them: the larger of the
    allocator's `peak_bytes_in_use` and, over the programs the window ran,
    arguments + temporaries of `memory_analysis()` (the program's cost plane
    holds them per compile seam). On this runtime the allocator's counter
    leaves out a program's temporaries (1.0 GB read beside 8.8 GB planned for
    the ResNet-50 step), so alone it is no peak."""
    import jax
    stats = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.local_devices())
    planned = max(((r.get("argument_bytes") or 0) + (r.get("temp_bytes") or 0)
                   for r in cost_rows), default=0)
    note(memory={"peak_bytes_in_use": int(stats),
                 "largest_program_args_plus_temps": int(planned)})
    return int(max(stats, planned))


def snapshot(registry):
    """{instrument name: value} for counters and gauges, {count, sum, p50}
    for histograms (their unlabeled series), of a telemetry registry. A
    counter's labeled series are there too, as `name{key="value",...}` with
    the keys in order (`decode_steps_ahead_total{ahead="1"}`); its bare name
    is their sum."""
    out = {}
    for m in registry.collect():
        try:
            if m.kind == "histogram":
                out[m.name] = {"count": m.count(), "sum": m.sum(),
                               "p50": m.percentile(0.5)}
            else:
                out[m.name] = m.get()
            if m.kind == "counter":
                for labels, value in m.series():
                    if labels:
                        out[m.name + "{" + ",".join(
                            f'{k}="{labels[k]}"' for k in sorted(labels))
                            + "}"] = value
        except Exception:            # a gauge whose callback cannot answer
            continue
    return out


class TraceSlice:
    """Profiles what runs inside its `with` block, marked `bench:window`;
    without `enabled` it does nothing. reduce() returns
    trace_reduce.reduce's dict, or None when nothing was profiled."""

    def __init__(self, log_dir, enabled):
        self.log_dir, self.enabled = log_dir, enabled
        self._mark = None
        self._done = False

    def __enter__(self):
        if self.enabled:
            import jax
            shutil.rmtree(self.log_dir, ignore_errors=True)
            # the harness's own marks and the device, nothing else of the
            # host. Keep slices short: the profiler holds about a quarter of
            # a GB of host memory per thousand device operations, and a
            # slice of ten training executions outgrew the host's 40 GiB
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._mark = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self._mark.__enter__()
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax
            self._mark.__exit__(*exc)
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            note(stop_trace_s=time.perf_counter() - t0)
            self._done = True

    def reduce(self):
        if not self._done:
            return None
        t0 = time.perf_counter()
        loaded = trace_reduce.load_xplane(self.log_dir)
        shutil.rmtree(self.log_dir, ignore_errors=True)
        reduced = trace_reduce.reduce(loaded)
        kept = {"device": 0, "host": 0}
        for plane in loaded["planes"]:
            side = "device" if trace_reduce.DEVICE_PLANE.match(
                plane["name"]) else "host"
            kept[side] += sum(len(l["events"]) for l in plane["lines"])
        note(trace_load_and_reduce_s=time.perf_counter() - t0,
             trace_events_kept=kept)
        return reduced


class GaugePoll:
    """Samples named gauges of a registry every `period` seconds."""

    def __init__(self, registry, names, period=0.02):
        self.registry, self.names, self.period = registry, names, period
        self.samples = {n: [] for n in names}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()
        return self

    def _run(self):
        gauges = {n: self.registry.get(n) for n in self.names}
        while not self._stop.wait(self.period):
            for n, g in gauges.items():
                if g is not None:
                    self.samples[n].append(float(g.get()))

    def stop(self):
        self._stop.set()
        self._thread.join()
        return self.samples
