"""AlertEngine: declarative rules over the MetricsRegistry with a
pending -> firing -> resolved lifecycle.

This is the piece that closes observe -> detect -> react: PR 2's registry
records p99 latency, error counters, shed counts, and ETL starvation, but
nothing watched them. An `AlertRule` declares a condition over registry
instruments; the engine evaluates all rules on an interval (or on demand —
every timestamp comes from util/time_source, so ManualClock tests drive the
whole lifecycle with zero wall-clock sleeps) and pushes each firing/resolved
transition to sinks exactly once.

Rule kinds (all JSON-round-trippable via to_dict/from_dict):

- `threshold` — instantaneous value vs a bound: a gauge or counter's value,
  or a histogram percentile (`metric="latency_ms", percentile=0.99`).
- `ratio` — windowed counter-delta ratio, e.g. errors_total/requests_total
  over the last `window_s`. The denominator may be a list of counters
  (summed), so a true shed ratio is `shed/(requests+shed)`.
- `burn_rate` — multiwindow-style SLO burn: the ratio's windowed error rate
  divided by the SLO's error budget (`1 - slo`); `threshold` is the burn
  factor (14.4 ~ "exhausting a 30-day budget in 2 days").

Lifecycle per rule: inactive -> (condition true) pending -> (held for
`for_duration_s`) firing -> (condition false) resolved -> inactive.
Pending that recovers before `for_duration_s` never notifies — that is the
flap damping. Counter history for windowed rules is sampled at evaluation
time, so the engine needs no hooks inside the instruments.
"""
from __future__ import annotations

import threading

from ..util.time_source import monotonic_s, now_s

INACTIVE, PENDING, FIRING = "inactive", "pending", "firing"
_OPS = {
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
}


def _as_names(spec):
    """Metric spec -> tuple of names (a str or a list of summed counters)."""
    if spec is None:
        return ()
    if isinstance(spec, str):
        return (spec,)
    return tuple(str(s) for s in spec)


class AlertRule:
    """One declarative condition + its lifecycle state."""

    KINDS = ("threshold", "ratio", "burn_rate")

    def __init__(self, name, kind="threshold", *, metric=None, percentile=None,
                 labels=None, op=">", threshold=None, numerator=None,
                 denominator=None, window_s=60.0, slo=None,
                 for_duration_s=0.0, severity="warning", description=""):
        self.name = str(name)
        self.kind = str(kind)
        if self.kind not in self.KINDS:
            raise ValueError(f"unknown rule kind {kind!r}")
        if op not in _OPS:
            raise ValueError(f"unknown op {op!r}")
        if threshold is None:
            raise ValueError(f"rule {name!r} needs a threshold")
        if self.kind == "threshold" and not metric:
            raise ValueError(f"threshold rule {name!r} needs `metric`")
        if self.kind in ("ratio", "burn_rate") and \
                (not numerator or not denominator):
            raise ValueError(
                f"{self.kind} rule {name!r} needs numerator+denominator")
        if self.kind == "burn_rate":
            if slo is None or not (0.0 < float(slo) < 1.0):
                raise ValueError(
                    f"burn_rate rule {name!r} needs 0 < slo < 1")
            self.slo = float(slo)
        else:
            self.slo = None
        self.metric = metric
        self.percentile = None if percentile is None else float(percentile)
        self.labels = dict(labels or {})
        self.op = op
        self.threshold = float(threshold)
        self.numerator = _as_names(numerator)
        self.denominator = _as_names(denominator)
        self.window_s = float(window_s)
        self.for_duration_s = float(for_duration_s)
        self.severity = str(severity)
        self.description = str(description)
        # lifecycle state (engine-managed)
        self.state = INACTIVE
        self.pending_since = None      # monotonic_s of condition onset
        self.firing_since = None       # wall now_s when it fired
        self.last_value = None
        self.transitions = 0           # firing/resolved notifications sent

    # ---- declarative round-trip -------------------------------------------
    def to_dict(self):
        d = {"name": self.name, "kind": self.kind, "op": self.op,
             "threshold": self.threshold, "severity": self.severity,
             "for_duration_s": self.for_duration_s,
             "description": self.description}
        if self.labels:
            # labels scope ANY kind: a threshold on one label-set, or a
            # ratio/burn_rate over one cohort's counters (the canary case)
            d["labels"] = dict(self.labels)
        if self.kind == "threshold":
            d["metric"] = self.metric
            if self.percentile is not None:
                d["percentile"] = self.percentile
        else:
            d["numerator"] = list(self.numerator)
            d["denominator"] = list(self.denominator)
            d["window_s"] = self.window_s
            if self.slo is not None:
                d["slo"] = self.slo
        return d

    @classmethod
    def from_dict(cls, d):
        d = dict(d)
        return cls(d.pop("name"), d.pop("kind", "threshold"), **d)

    def status(self):
        """JSON state row for GET /alerts."""
        return {**self.to_dict(), "state": self.state,
                "value": self.last_value, "firing_since": self.firing_since,
                "transitions": self.transitions}


def _instrument_value(registry, name, percentile=None, labels=None):
    """Instantaneous value of one instrument, or None when absent/empty."""
    m = registry.get(name)
    if m is None:
        return None
    labels = labels or {}
    if m.kind == "histogram":
        # no labels named: the read covers every label-set, so a rule like
        # etl_consumer_starvation sees pipeline=<name> observations too
        return m.percentile(0.99 if percentile is None else percentile,
                            **labels)
    v = m.get(**labels)
    if isinstance(v, dict):            # fn-gauge returning {label: value}
        return None
    return v


class AlertEngine:
    """Evaluates rules against one MetricsRegistry; notifies sinks on
    firing/resolved transitions; optionally runs on a background interval."""

    def __init__(self, registry=None, rules=None, sinks=None, interval_s=5.0,
                 logger=None):
        if registry is None:
            from .registry import get_registry
            registry = get_registry()
        self.registry = registry
        self.rules = []
        self.sinks = list(sinks or [])
        self.interval_s = float(interval_s)
        self.logger = logger
        self._history = {}             # counter name -> [(mono_t, value)]
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = None
        for r in (rules or []):
            self.add_rule(r)

    # ---- configuration -----------------------------------------------------
    def add_rule(self, rule):
        if isinstance(rule, dict):
            rule = AlertRule.from_dict(rule)
        with self._lock:
            old = [r for r in self.rules if r.name == rule.name]
            self.rules = [r for r in self.rules if r.name != rule.name]
            self.rules.append(rule)
        self._resolve_displaced(old)
        return rule

    def remove_rule(self, name):
        with self._lock:
            old = [r for r in self.rules if r.name == name]
            self.rules = [r for r in self.rules if r.name != name]
        self._resolve_displaced(old)

    def drop_history(self, names, labels=None):
        """Forget the windowed samples for `names` under the given label
        scope. Counter history outlives rules (so a re-added long-lived rule
        keeps its window), which means a windowed rule re-added over a
        REUSED label-set — back-to-back canary cohorts — would otherwise see
        the previous occupant's deltas in its window and could fire on
        traffic the new deploy never served."""
        lk = tuple(sorted((labels or {}).items()))
        with self._lock:
            for name in names:
                self._history.pop((name, lk), None)

    def _resolve_displaced(self, old_rules):
        """A FIRING rule that is replaced/removed must still resolve: its
        receiver (pager, Alertmanager) has an open incident keyed on the
        firing event and would otherwise never see it close."""
        for r in old_rules:
            if r.state == FIRING:
                self._notify(self._event(r, "resolved", r.last_value))
                r.state = INACTIVE

    def add_sink(self, sink):
        self.sinks.append(sink)
        return sink

    # ---- evaluation --------------------------------------------------------
    def _sample_counters(self, now):
        """Record current totals for every windowed rule's counters (per the
        rule's label scope — a labeled rule windows one label-set's series,
        an unlabeled one the summed total) and prune history past the
        largest window."""
        with self._lock:
            rules = list(self.rules)
        keys, max_window = set(), 0.0
        for r in rules:
            if r.kind in ("ratio", "burn_rate"):
                lk = tuple(sorted(r.labels.items()))
                keys.update((n, lk) for n in r.numerator)
                keys.update((n, lk) for n in r.denominator)
                max_window = max(max_window, r.window_s)
        for name, lk in keys:
            v = _instrument_value(self.registry, name, labels=dict(lk))
            hist = self._history.setdefault((name, lk), [])
            hist.append((now, 0.0 if v is None else float(v)))
            # keep one sample at-or-before the window edge as the baseline
            cut = now - max_window
            while len(hist) >= 2 and hist[1][0] <= cut:
                hist.pop(0)

    def _window_delta(self, names, window_s, now, labels=None):
        """Sum of counter increases over the last `window_s` (baseline = the
        newest sample at-or-before the window edge, else the oldest known —
        so a counter that was already nonzero at engine start never reads as
        a burst)."""
        lk = tuple(sorted((labels or {}).items()))
        total = 0.0
        for name in names:
            hist = self._history.get((name, lk))
            if not hist:
                return None
            base = hist[0][1]
            for t, v in hist:
                if t <= now - window_s:
                    base = v
                else:
                    break
            total += hist[-1][1] - base
        return total

    def _condition(self, rule, now):
        """(condition_bool, observed_value) — condition is False on no-data."""
        if rule.kind == "threshold":
            v = _instrument_value(self.registry, rule.metric,
                                  percentile=rule.percentile,
                                  labels=rule.labels)
            if v is None:
                return False, None
            return _OPS[rule.op](float(v), rule.threshold), float(v)
        dn = self._window_delta(rule.numerator, rule.window_s, now,
                                labels=rule.labels)
        dd = self._window_delta(rule.denominator, rule.window_s, now,
                                labels=rule.labels)
        if dn is None or dd is None or dd <= 0:
            return False, None
        v = dn / dd
        if rule.kind == "burn_rate":
            v = v / (1.0 - rule.slo)   # error rate over the error budget
        return _OPS[rule.op](v, rule.threshold), v

    def evaluate(self):
        """One evaluation pass over every rule; returns the transition
        events emitted (each already delivered to every sink exactly once)."""
        now = monotonic_s()
        self._sample_counters(now)
        with self._lock:
            rules = list(self.rules)
        events = []
        for rule in rules:
            cond, value = self._condition(rule, now)
            rule.last_value = value
            if cond:
                if rule.state == INACTIVE:
                    rule.state = PENDING
                    rule.pending_since = now
                if rule.state == PENDING and \
                        now - rule.pending_since >= rule.for_duration_s:
                    rule.state = FIRING
                    rule.firing_since = now_s()
                    events.append(self._event(rule, FIRING, value))
            else:
                if rule.state == FIRING:
                    events.append(self._event(rule, "resolved", value))
                rule.state = INACTIVE
                rule.pending_since = None
                rule.firing_since = None
        for ev in events:
            self._notify(ev)
        return events

    def _event(self, rule, transition, value):
        rule.transitions += 1
        ev = {"type": "alert", "rule": rule.name, "state": transition,
              "severity": rule.severity, "value": value,
              "threshold": rule.threshold, "kind": rule.kind,
              "description": rule.description, "time": now_s()}
        if transition == FIRING and rule.kind == "threshold" and rule.metric:
            # a histogram-backed alert carries its freshest exemplars: the
            # receiver pivots alert -> exemplar trace_id -> /trace + /logs
            # without scraping anything else
            m = self.registry.get(rule.metric)
            if m is not None and getattr(m, "kind", None) == "histogram":
                ex = m.exemplars(**rule.labels)
                if ex:
                    ev["exemplars"] = ex[-3:]
        return ev

    def _notify(self, event):
        if self.logger is not None:
            level = "error" if event["state"] == FIRING else "info"
            self.logger.log(level, f"alert_{event['state']}",
                            rule=event["rule"], value=event["value"],
                            severity=event["severity"])
        for sink in self.sinks:
            try:
                sink(event)
            except Exception:
                if self.logger is not None:
                    self.logger.warning("alert_sink_error",
                                        sink=type(sink).__name__,
                                        rule=event["rule"])

    # ---- reading -----------------------------------------------------------
    def state(self):
        """GET /alerts payload: every rule's full status, firing first."""
        with self._lock:
            rules = list(self.rules)
        order = {FIRING: 0, PENDING: 1, INACTIVE: 2}
        rows = sorted((r.status() for r in rules),
                      key=lambda s: (order[s["state"]], s["name"]))
        return {"time": now_s(),
                "firing": sum(1 for s in rows if s["state"] == FIRING),
                "rules": rows}

    # ---- background loop ---------------------------------------------------
    def start(self):
        """Evaluate every `interval_s` (real time) on a daemon thread; tests
        that want determinism call evaluate() themselves instead."""
        if self.interval_s <= 0 or \
                (self._thread is not None and self._thread.is_alive()):
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="alert-engine")
        self._thread.start()
        return self

    def _run(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.evaluate()
            except Exception:
                if self.logger is not None:
                    self.logger.error("alert_engine_error")

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(5.0)
            self._thread = None


# ---- sinks ------------------------------------------------------------------

class LogAlertSink:
    """Route alert events into a StructuredLogger (they then show at /logs
    and in every attached log sink)."""

    def __init__(self, logger):
        self.logger = logger

    def __call__(self, event):
        level = "error" if event["state"] == FIRING else "info"
        self.logger.log(level, "alert", **event)


class WebhookAlertSink:
    """POST each transition event as JSON to a webhook URL (PagerDuty /
    Alertmanager-receiver shape: one POST per firing and per resolve)."""

    def __init__(self, url, timeout=5.0):
        self.url = str(url)
        self.timeout = float(timeout)
        self.delivered = 0

    def __call__(self, event):
        from ..util.http import post_json
        post_json(self.url, event, timeout=self.timeout)
        self.delivered += 1


class RouterAlertSink:
    """Append alert events to a ui/storage StatsStorageRouter as
    `type: "telemetry"` reports (excluded from training charts, durable in
    the File/Sqlite tiers like any other report)."""

    def __init__(self, router, session_id="alerts"):
        self.router = router
        self.session_id = str(session_id)

    def __call__(self, event):
        self.router.put_update({"type": "telemetry",
                                "session_id": self.session_id,
                                "time": event["time"], "alert": event})


# ---- stock rule sets --------------------------------------------------------

def default_serving_rules(max_p99_ms=1000.0, error_ratio=0.05,
                          shed_ratio=0.10, window_s=60.0,
                          for_duration_s=15.0, bytes_ratio=1.2):
    """The SLO set a ServingServer watches out of the box: dispatch error
    ratio, p99 latency, true shed ratio (shed/(requests+shed)), and the
    deploy-time bytes regression (a hot-swap that inflates an executable
    family's hbm_bytes_per_sample >20% vs the previous version — the alarm
    a quantized->f32 fallback trips; see telemetry/cost.py)."""
    return [
        AlertRule("serving_error_ratio", "ratio",
                  numerator="errors_total", denominator="requests_total",
                  threshold=error_ratio, window_s=window_s,
                  for_duration_s=for_duration_s, severity="page",
                  description="model dispatch errors per answered request"),
        AlertRule("serving_p99_latency_ms", "threshold",
                  metric="latency_ms", percentile=0.99,
                  threshold=max_p99_ms, for_duration_s=for_duration_s,
                  severity="page",
                  description="p99 request latency over the SLO bound"),
        AlertRule("serving_shed_ratio", "ratio",
                  numerator="shed_total",
                  denominator=["requests_total", "shed_total"],
                  threshold=shed_ratio, window_s=window_s,
                  for_duration_s=for_duration_s, severity="warning",
                  description="admission load-shedding (429) fraction"),
        AlertRule("deploy_bytes_regression", "threshold",
                  metric="deploy_hbm_bytes_per_sample_ratio",
                  threshold=bytes_ratio, op=">", for_duration_s=0.0,
                  severity="page",
                  description="a deploy/hot-swap raised an executable "
                              "family's HBM bytes per sample vs the "
                              "previous version (quantization fallback?)"),
    ]


def default_training_rules(max_consumer_wait_ms=250.0):
    """Watchdog set for a training process: NaN/divergence events from
    TrainingHealthListener and ETL consumer starvation."""
    return [
        AlertRule("training_nan", "threshold",
                  metric="training_nan_total", threshold=0, op=">",
                  severity="page",
                  description="non-finite loss or gradients observed"),
        AlertRule("training_divergence", "threshold",
                  metric="training_divergence_total", threshold=0, op=">",
                  severity="page",
                  description="loss diverged from its rolling best"),
        AlertRule("etl_consumer_starvation", "threshold",
                  metric="etl_consumer_wait_ms", percentile=0.5,
                  threshold=max_consumer_wait_ms, severity="warning",
                  description="device waiting on the host input pipeline"),
    ]
