"""From a profiler trace to device busy/idle time, the programs and the top
device operations, and the longest idle gaps.

Two stages, so the second can be checked on a small recorded trace
(tests/recorded_trace.json) without a chip:

  load_xplane(dir)  the newest `.xplane.pb` under a `jax.profiler` log
                    directory -> {"planes": [{"name", "lines": [{"name",
                    "events": [[name, start_ns, duration_ns], ...]}]}]}
  reduce(trace)     that dict -> busy_s, window_s, devices, programs,
                    program_busy, program_p50_s, device_ops, kernels,
                    kernels_cover, idle_gaps.

Device planes are those named `/device:TPU:<n>`. The device counts as busy
while a program runs on it: the union of the plane's `XLA Modules` events.
`XLA Ops` ranks operations by time; `Async XLA Ops` (copies in flight) does
not count as busy.

What a slice of the training loop shows is the loop under the profiler, not
the loop: while the profiler is on, the input path delivers a batch every
0.5 s (five times slower than untraced), so the device waits between the
5-step programs and the slice reads 76-83 % idle at an MFU that alone needs
more. The device time of each program is what it is untraced (501.9 ms
against 500.3 ms), so the readers take device time per program from here and
how often the programs run from the window (layer_metrics/
device_idle_pct.train.py). The `Steps` line is no witness of busy time: its
events run from one program's end to the next one's, back to back by
construction. The window here runs from the first program event to the last:
a program in flight at either end of the trace may leave no whole event.

The harness marks its own calls with
`jax.profiler.TraceAnnotation("bench:<what>")` and the program its timed
phases with `dl4j:<phase>` (telemetry/trace.py `Tracer.phase`); both land on
host-thread lines, on the profiler's own clock, and they are all of the host
that is kept (host memory is what limits a slice). The window is the
`bench:window` span when there is one, cut to the extent of the program
events. An idle gap is named after the shortest span of either family that
covers its middle, without the prefix (`decode_step_sync`, `fit_dispatch`,
`generate_request`), or `unattributed`.

`programs` counts the program events that lie whole in the window (name,
events, their seconds); `program_busy` is every program's time clipped to the
window (it sums to `busy_s` where programs do not overlap); `program_p50_s`
the median duration of a program's whole events.

`device_ops` ranks ten operations by call site (`fusion.12`), clipped to the
window. `kernels` is the whole ranking: rows [program, name, calls, seconds],
where an operation belongs to the program whose `XLA Modules` event contains
it and is counted only inside program events that lie whole in the window
(so seconds / events is seconds a step, unclipped); seconds are SELF time,
the operation's own less those of the operations its interval contains on
the same line (a `while`, `conditional` or `call` is what it spends outside
its body, and its body is counted once); and call sites are merged by name
with the instance suffix dropped (`flash_decode.41` -> `flash_decode`).
`kernels_cover` says how well that adds up: per program [name, the events
counted, the union of its operations' intervals inside them, the sum of
their self times], in seconds.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
MARK = "bench:"
PHASE = "dl4j:"                 # telemetry/trace.py PROFILER_PREFIX
HOST_KEPT = (MARK, PHASE)
WINDOW = MARK + "window"
INSTANCE = re.compile(r"\.\d+$")


def load_xplane(log_dir):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_KEPT):
                    continue        # of the host, only marks and phases
                events.append([ev.name, float(ev.start_ns),
                               float(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(text):
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`: the trace names a
    device operation by its whole HLO line."""
    return text.split(" = ", 1)[0].lstrip("%")[:120]


def _merge(intervals):
    """Union of [start, end) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def program_name(text):
    """`jit_multi_step(11314223348443836704)` -> `jit_multi_step`."""
    return text.split("(", 1)[0][:120]


def kernel_name(text):
    """`%flash_decode.41 = ...` -> `flash_decode`: the call sites of one
    kernel or one kind of HLO instruction under one name."""
    return INSTANCE.sub("", op_name(text))


def span_name(text):
    for prefix in HOST_KEPT:
        if text.startswith(prefix):
            return text[len(prefix):]
    return text


def _self_times(ops):
    """[(name, self_ns)] of one line's operations [(name, start, duration)]:
    each one's duration less what the operations nested directly inside its
    interval take. Events of one line nest or follow each other; one that
    straddles its predecessor's end is counted from that end on."""
    out, stack = [], []         # stack: [end, index into out]
    for n, s, d in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        e = s + d
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            e = min(e, stack[-1][0])
            out[stack[-1][1]][1] -= e - s
        out.append([n, e - s])
        stack.append([e, len(out) - 1])
    return out


def _kernels(programs, ops, lo, hi, by_kernel, cover):
    """Adds one device's operations, by the whole program event that holds
    each, to `by_kernel` {(program, kernel): [calls, self_ns]} and `cover`
    {program: [events, union_ns, self_ns]}."""
    whole = sorted((s, s + d, program_name(n)) for n, s, d in programs
                   if s >= lo and s + d <= hi)
    ops = sorted(ops, key=lambda ev: ev[1])
    starts = [ev[1] for ev in ops]
    for ps, pe, prog in whole:
        inside = ops[bisect.bisect_left(starts, ps):
                     bisect.bisect_left(starts, pe)]
        row = cover.setdefault(prog, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += sum(e - s for s, e in _merge(
            _clip([(s, s + d) for _, s, d in inside], ps, pe)))
        for n, t in _self_times(inside):
            k = by_kernel.setdefault((prog, kernel_name(n)), [0, 0.0])
            k[0] += 1
            k[1] += t
            row[2] += t


def reduce(trace, top=10):
    planes, marks = [], []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            of = lambda name: [ev for l in plane["lines"]
                               if l["name"] == name for ev in l["events"]]
            planes.append((of(PROGRAMS_LINE), of(OPS_LINE)))
        else:
            marks += [ev for l in plane["lines"] for ev in l["events"]
                      if ev[0].startswith(HOST_KEPT)]
    planes = [p for p in planes if p[0]]
    if not planes:
        return None
    windows = [(s, s + d) for n, s, d in marks if n == WINDOW]
    lo = min(w[0] for w in windows) if windows else -float("inf")
    hi = max(w[1] for w in windows) if windows else float("inf")
    for programs, _ in planes:
        lo = max(lo, min(s for _, s, _ in programs))
        hi = min(hi, max(s + d for _, s, d in programs))
    if not hi > lo:
        return None
    busy, by_program, by_op, gaps = [], {}, {}, []
    clipped, durations, by_kernel, cover = {}, {}, {}, {}
    for programs, ops in planes:
        merged = _merge(_clip([(s, s + d) for _, s, d in programs], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for n, s, d in programs:
            n = program_name(n)
            if min(s + d, hi) > max(s, lo):
                clipped[n] = clipped.get(n, 0.0) + min(s + d, hi) - max(s, lo)
            if s >= lo and s + d <= hi:
                row = by_program.setdefault(n, [0, 0.0])
                row[0] += 1
                row[1] += d
                durations.setdefault(n, []).append(d)
        _kernels(programs, ops, lo, hi, by_kernel, cover)
        for n, s, d in ops:
            if s + d > lo and s < hi:
                n = op_name(n)
                by_op[n] = by_op.get(n, 0.0) + (min(s + d, hi) - max(s, lo))
    n_dev = len(planes)
    spans = [(n, s, s + d) for n, s, d in marks if n != WINDOW]

    def owner(s, e):
        mid = (s + e) / 2
        covering = [(e2 - s2, n) for n, s2, e2 in spans if s2 <= mid < e2]
        return span_name(min(covering)[1]) if covering else "unattributed"

    gap_by_owner = {}
    for s, e in gaps:
        o = owner(s, e)
        gap_by_owner[o] = gap_by_owner.get(o, 0.0) + (e - s)
    rank = lambda d, top=top: [[k, v / 1e9 / n_dev] for k, v in
                               sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    order = {p: i for i, (p, _) in enumerate(
        sorted(cover.items(), key=lambda kv: -kv[1][2]))}
    return {"busy_s": sum(busy) / n_dev / 1e9,
            "window_s": (hi - lo) / 1e9,
            "devices": n_dev,
            "programs": [[k, c / n_dev, t / 1e9 / n_dev] for k, (c, t) in
                         sorted(by_program.items(),
                                key=lambda kv: -kv[1][1])[:top]],
            "program_busy": rank(clipped, None),
            "program_p50_s": {k: statistics.median(v) / 1e9
                              for k, v in durations.items()},
            "device_ops": rank(by_op),
            "kernels": [[p, k, c / n_dev, t / 1e9 / n_dev]
                        for (p, k), (c, t) in sorted(
                            by_kernel.items(),
                            key=lambda kv: (order[kv[0][0]], -kv[1][1]))],
            "kernels_cover": [[p, c / n_dev, u / 1e9 / n_dev, t / 1e9 / n_dev]
                              for p, (c, u, t) in sorted(
                                  cover.items(), key=lambda kv: order[kv[0]])],
            "idle_gaps": rank(gap_by_owner)}


def top_kernels(reduced, top=10):
    """[[name, seconds]] of the `top` merged names with most self time in the
    program with most device time: the result's `breakdown.device_ops`."""
    rows = reduced.get("kernels") or []
    return [[k, t] for p, k, _, t in rows if p == rows[0][0]][:top]
