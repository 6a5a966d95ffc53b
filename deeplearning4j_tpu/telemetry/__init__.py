"""Unified telemetry subsystem (SURVEY.md §5: the reference stack has *no
tracer* — this is the observability layer the north-star production system
runs on).

Four cooperating parts, one import surface:

- `trace` — structured tracing: `Tracer` producing nested `Span`s with
  ids/attributes, a thread-local current-span context propagated through the
  serving hot path (/predict: admission -> micro-batch coalesce -> registry
  dispatch -> model step; /generate: generate -> decode_queue_wait,
  decode_prefill, generate_front per request, one decode_wave span per
  scheduler pass) and training (per batch: epoch -> iteration -> jit step;
  fit(steps_per_execution=K): one fit_execution span per execution, from
  the first pull of its group to its listeners; the input path: one ingest
  span per prefetched batch), exportable as Chrome-trace/Perfetto JSON.
  `Tracer.phase` is the one call site for a timed phase of those loops and
  of the prefetcher's legs (etl_h2d, etl_consumer_wait, ...): a
  `jax.profiler` annotation named "dl4j:<phase>" (so a profiler session
  shows host phases beside the device's lines, on one clock, on the thread
  that ran them), a `<phase>_ms` histogram in the registry (the series
  `labels=` names), and the ring span.
- `registry` — central `MetricsRegistry`: thread-safe counters, gauges, and
  bounded histograms with exact-bucket percentiles; ServingMetrics, the
  training listeners, and streaming all register here instead of keeping
  private state. A counter or histogram read without labels is the total
  over its label-sets.
- `prometheus` — text exposition (`/metrics?format=prometheus` on the
  ServingServer and the UI server).
- `xla` — compile/recompile cost accounting (`compiles_total`,
  `compile_ms_total`, per-bucket compile counts) and device-memory gauges,
  per the compile-vs-run accounting of the Julia-to-TPU paper (PAPERS.md).

`TelemetryListener` flushes the registry into the existing ui/storage
router tier so the UI can tail live metrics like training stats.

The health & alerting tier sits on top and closes observe -> detect ->
react:

- `logging` — structured JSON log records with automatic trace/span-id
  correlation, a bounded ring buffer (`GET /logs`), pluggable sinks, and
  `log_events_total{level}`.
- `health` — `HealthMonitor` aggregating per-component probes (batcher,
  registry, admission queue, ETL pipelines, trainer) into a deep `/healthz`
  that answers 503 when any component is unhealthy.
- `alerts` — `AlertEngine` evaluating declarative threshold / ratio /
  SLO-burn-rate rules over the registry on a ManualClock-testable interval,
  with a pending -> firing -> resolved lifecycle and log/webhook/router
  sinks (`GET /alerts`); `optimize.listeners.TrainingHealthListener` is the
  training watchdog feeding it (NaN loss/gradients, divergence, step-time
  regression) and the checkpoint-and-halt trigger for FaultTolerantTrainer.

The fleet tier makes every signal above cross-process:

- `propagation` — W3C `traceparent` inject/extract (`SpanContext`): the
  util/http clients inject the active span's context, server handlers and
  broker messages extract it, so one request is ONE trace across hosts;
  span/trace ids are collision-free random hex (kernel CSPRNG).
- `fleet` — `FleetCollector`/`FleetServer`: poll N peer base-URLs and
  aggregate `GET /fleet/{metrics,healthz,alerts,trace}` (per-`instance`
  labels + merged totals, worst-status health with down-peers-as-degraded,
  one Chrome-trace `pid` lane per host).
- Histograms carry bounded `(value, trace_id)` exemplars, rendered as
  OpenMetrics exemplars in the Prometheus exposition and attached to firing
  alert events — the alert → trace → logs pivot.

The ETL subsystem (deeplearning4j_tpu/etl) instruments through this layer
too: per-stage spans (etl_read/etl_transform), `etl_batches_total` /
`etl_records_total`, the `etl_queue_depth` gauge, and the
`etl_consumer_wait_ms` histogram — the device-starvation signal (prefetch
working = consumer wait ~0).
"""
from .alerts import (AlertEngine, AlertRule, LogAlertSink, RouterAlertSink,
                     WebhookAlertSink, default_serving_rules,
                     default_training_rules)
from .cost import (ExecutableCostRegistry, abstractify, capture_trace,
                   classify, compiled_costs, get_cost_registry,
                   install_donation_watch, set_cost_registry)
from .fleet import FleetCollector, FleetServer
from .health import (DEGRADED, HEALTHY, UNHEALTHY, HealthMonitor,
                     get_monitor, set_monitor)
from .listener import TelemetryListener, TelemetryReport
from .logging import (FileJsonSink, LogBuffer, StderrJsonSink,
                      StructuredLogger, get_logger, set_logger)
from .prometheus import CONTENT_TYPE as PROMETHEUS_CONTENT_TYPE
from .prometheus import render as render_prometheus
from .propagation import (SpanContext, extract, extract_message,
                          format_traceparent, inject, inject_message,
                          parse_traceparent)
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       get_registry)
from .trace import (NOOP_SPAN, Phase, Span, Tracer, current_span,
                    enable_tracing,
                    get_tracer, new_span_id, new_trace_id, set_tracer)
from .xla import (CompileTracker, record_jit_compile,
                  register_device_memory_gauges, timed_first_call)

__all__ = ["AlertEngine", "AlertRule", "LogAlertSink", "RouterAlertSink",
           "WebhookAlertSink", "default_serving_rules",
           "default_training_rules",
           "FleetCollector", "FleetServer",
           "DEGRADED", "HEALTHY", "UNHEALTHY", "HealthMonitor",
           "get_monitor", "set_monitor",
           "FileJsonSink", "LogBuffer", "StderrJsonSink", "StructuredLogger",
           "get_logger", "set_logger",
           "TelemetryListener", "TelemetryReport",
           "PROMETHEUS_CONTENT_TYPE", "render_prometheus",
           "SpanContext", "extract", "extract_message", "format_traceparent",
           "inject", "inject_message", "parse_traceparent",
           "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "get_registry",
           "NOOP_SPAN", "Phase", "Span", "Tracer", "current_span", "enable_tracing",
           "get_tracer", "new_span_id", "new_trace_id", "set_tracer",
           "CompileTracker", "record_jit_compile",
           "register_device_memory_gauges", "timed_first_call",
           "ExecutableCostRegistry", "abstractify", "capture_trace",
           "classify", "compiled_costs", "get_cost_registry",
           "install_donation_watch", "set_cost_registry"]
