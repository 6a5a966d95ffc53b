"""ServingServer: production HTTP front-end over the micro-batcher,
registry, and admission queue.

Endpoints (all JSON unless noted, shared stdlib plumbing from util/http.py):
  POST /predict   {"data": nested list, "timeout_ms"?: N} or serde envelope
                  -> {"prediction", "shape", "version"}
                  429 + Retry-After when shed, 504 when the deadline expires
  POST /generate  {"prompt": [ids], "max_new_tokens"?, "timeout_ms"?,
                  "stop"?} -> {"tokens", "n_prompt", "version", "ttft_ms",
                  "finish_reason"} — KV-cache continuous-batching decode
                  (decode/; requires decode=True); same 429/504/503 contract
  GET  /models    -> {"models": [per-version info], "active": version}
  POST /deploy    {"version": v, "path"?: zip} -> load (if path) + warm-up +
                  atomic hot-swap; old version serves during warm-up
  POST /rollback  -> redeploy the previously active version
  GET  /metrics   -> latency p50/p95/p99, queue depth, batch-size histogram,
                  shed/expired counts, compile accounting; JSON by default
                  (back-compat), Prometheus text exposition with
                  ?format=prometheus; also routed to the ui/stats storage
                  router when one is configured
  GET  /trace     -> Chrome-trace/Perfetto JSON of recent spans (each
                  /predict produces a predict -> admission/batch -> dispatch
                  span tree)
  GET  /healthz   -> deep health: {"status", "health", "components": {name:
                  {"status", detail...}}, "served", "queue_depth",
                  "active_version"}; HTTP 503 when any component probe
                  (admission queue, batcher thread, model registry, plus
                  anything registered on server.health) reports unhealthy
  GET  /alerts    -> AlertEngine state: every rule with its
                  pending/firing/resolved lifecycle position and last value
  GET  /logs      -> bounded ring of structured log records
                  (?level=error&n=100&trace_id=N), trace/span-correlated
"""
from __future__ import annotations

import json
import threading
from concurrent.futures import Future, TimeoutError as FuturesTimeoutError
from urllib.parse import parse_qs, urlparse

import numpy as np

from .admission import (AdmissionQueue, DeadlineExceeded, RejectedError,
                        Request, safe_set_exception, safe_set_result)
from .batcher import DynamicBatcher
from .metrics import ServingMetrics
from .registry import ModelRegistry, NoModelDeployed
from ..telemetry.alerts import (AlertEngine, RouterAlertSink,
                                WebhookAlertSink, default_serving_rules)
from ..telemetry.cost import (ExecutableCostRegistry, capture_trace,
                              install_donation_watch)
from ..telemetry.health import HealthMonitor
from ..telemetry.logging import StructuredLogger
from ..telemetry.prometheus import CONTENT_TYPE as PROMETHEUS_CONTENT_TYPE
from ..telemetry.propagation import server_span
from ..telemetry.trace import Tracer
from ..telemetry.xla import CompileTracker, register_device_memory_gauges
from ..util.http import BackgroundHttpServer, QuietHandler
from ..util.time_source import monotonic_s


class ServingServer(BackgroundHttpServer):
    def __init__(self, model=None, *, registry=None, version="v1",
                 host="127.0.0.1", port=0, max_batch_size=32,
                 max_latency_ms=5.0, queue_capacity=256,
                 default_timeout_ms=None, stats_router=None,
                 session_id="serving", router_interval_s=10.0,
                 transform=None, tracer=None, scan_dir=None,
                 alert_rules=None, alert_sinks=None, alert_webhook=None,
                 alert_interval_s=5.0, log_sinks=None,
                 seq_len_bucketing=True, decode=False, decode_slots=4,
                 decode_max_len=128, decode_queue_capacity=64,
                 decode_max_new_tokens=32, decode_paged=False,
                 decode_block_size=16, decode_pool_blocks=None,
                 quant_gate=None, mesh=None):
        # scan_dir: persistent registry directory — every ModelSerializer zip
        # in it is loaded at startup and POST /deploy accepts any model name
        # from it (see ModelRegistry.scan / deploy-by-name)
        super().__init__(host=host, port=port)
        self.tracer = tracer if tracer is not None else Tracer(enabled=True)
        # mesh-sharded serving (serving/mesh.py): every registered version is
        # wrapped by the context's MeshDispatcher through the registry
        # adapter, so the batcher's coalesced batch splits over the mesh data
        # axis and TP-ruled weights span chips — this whole server stays ONE
        # fleet replica (one ReplicaHandle, one breaker, one health probe)
        self.mesh = None
        if mesh is not None and mesh is not False:
            from .mesh import MeshContext
            self.mesh = MeshContext(mesh, tracer=self.tracer)
        adapter = self.mesh.wrap if self.mesh is not None else None
        self.registry = registry or ModelRegistry(scan_dir=scan_dir,
                                                  adapter=adapter)
        if adapter is not None and registry is not None:
            self.registry.set_adapter(adapter)
        if model is not None:
            self.registry.register(version, model)
            self.registry.deploy(version)
        self.metrics = ServingMetrics(session_id=session_id)
        # telemetry: per-server tracer (bounded buffer, exported at /trace),
        # XLA compile accounting + device-memory gauges in the same registry
        # the /metrics exposition renders
        self.compile_tracker = CompileTracker(self.metrics.registry)
        # live cost attribution (telemetry/cost.py): per-executable XLA
        # flops/bytes captured at every compile seam, sampled dispatch_ms,
        # the /profile/cost table, and the deploy bytes-regression gauge
        self.cost = ExecutableCostRegistry(self.metrics.registry)
        if self.mesh is not None:
            self.mesh.cost_registry = self.cost
        register_device_memory_gauges(self.metrics.registry)
        self.metrics.registry.gauge(
            "queue_depth", "Requests admitted and not yet dispatched",
            fn=lambda: float(self.queue.depth()))
        self.queue = AdmissionQueue(capacity=queue_capacity,
                                    metrics=self.metrics)
        self.batcher = DynamicBatcher(self.registry, self.queue, self.metrics,
                                      max_batch_size=max_batch_size,
                                      max_latency_ms=max_latency_ms,
                                      tracer=self.tracer,
                                      compile_tracker=self.compile_tracker,
                                      cost_registry=self.cost)
        self.default_timeout_ms = default_timeout_ms
        # accuracy-parity thresholds for quantize="int8" deploys (None ->
        # nn.quant.QuantGate defaults)
        self.quant_gate = quant_gate
        self.stats_router = stats_router
        self.router_interval_s = float(router_interval_s)
        self._last_router_flush = None     # None: never flushed
        self._router_flush_lock = threading.Lock()
        self._final_flush_done = False
        self.transform = transform
        # health & alerting tier: structured logs (GET /logs), deep health
        # probes (GET /healthz -> 503 when any component is unhealthy), and
        # rule-driven alerts over this server's registry (GET /alerts)
        self.logger = StructuredLogger(name=f"serving.{session_id}",
                                       registry=self.metrics.registry,
                                       sinks=log_sinks)
        # instrument-level problems (raising gauge callbacks) log HERE, so
        # they show on this server's /logs, not a process-global buffer
        self.metrics.registry.logger = self.logger
        # XLA donation failures become donation_warnings_total{site} + a
        # trace-correlated log record instead of unscraped stderr
        self._donation_unwatch = install_donation_watch(self.metrics.registry,
                                                        self.logger)
        self.health = HealthMonitor(logger=self.logger)
        self.health.register("admission", self._probe_admission)
        self.health.register("batcher", self._probe_batcher)
        self.health.register("registry", self._probe_registry)
        if self.mesh is not None:
            # the whole mesh group reports through THIS server's single
            # health probe — the fleet ejects/serves it all-or-none
            self.health.register("mesh", self._probe_mesh)
            self.metrics.registry.gauge(
                "mesh_dispatch_chips",
                "Chips answering one mesh-sharded dispatch",
                fn=lambda: float(self.mesh.chips))
            self.metrics.registry.gauge(
                "mesh_dispatches_total", "Mesh-routed batch dispatches",
                fn=lambda: float(self.mesh.dispatches))
        rules = default_serving_rules() if alert_rules is None \
            else list(alert_rules)
        sinks = list(alert_sinks or [])
        if alert_webhook is not None:
            sinks.append(WebhookAlertSink(alert_webhook))
        if stats_router is not None:
            sinks.append(RouterAlertSink(stats_router,
                                         session_id=f"{session_id}-alerts"))
        self.alerts = AlertEngine(registry=self.metrics.registry,
                                  rules=rules, sinks=sinks,
                                  interval_s=alert_interval_s,
                                  logger=self.logger)
        # padded+masked sequence-length buckets for 3-D (sequence) requests:
        # requires the deployed models' output() to take a mask (every nn
        # network type does); turn off for exotic duck-typed models
        self.seq_len_bucketing = bool(seq_len_bucketing)
        # autoregressive decode plane: POST /generate through a
        # DecodeScheduler (KV-cache continuous batching; decode/)
        self.decode = None
        if decode:
            from ..decode.scheduler import DecodeScheduler
            self.decode = DecodeScheduler(
                self.registry, self.metrics.registry,
                slots=decode_slots, max_len=decode_max_len,
                queue_capacity=decode_queue_capacity,
                default_max_new_tokens=decode_max_new_tokens,
                tracer=self.tracer, compile_tracker=self.compile_tracker,
                logger=self.logger, paged=decode_paged,
                block_size=decode_block_size,
                pool_blocks=decode_pool_blocks,
                cost_registry=self.cost)
            self.health.register("decode", self.decode.probe)
            self._m_generate_front = self.metrics.registry.histogram(
                "generate_front_ms", "The /generate handler's own time per "
                "request: its wall less the wait on the scheduler, ms")

    # ---- health probes -----------------------------------------------------
    def _probe_admission(self):
        depth, cap = self.queue.depth(), self.queue.capacity
        if self.queue.closed:
            return "unhealthy", {"reason": "draining", "depth": depth}
        if depth >= 0.8 * cap:
            return "degraded", {"reason": "near capacity", "depth": depth,
                                "capacity": cap}
        return "healthy", {"depth": depth, "capacity": cap}

    def _probe_batcher(self):
        t = self.batcher._thread
        if t is None:
            return "degraded", {"reason": "not started"}
        if not t.is_alive():
            return "unhealthy", {"reason": "batcher thread dead"}
        return "healthy", {}

    def _probe_mesh(self):
        import jax
        d = self.mesh.describe()
        if self.mesh.chips > len(jax.devices()):
            return "unhealthy", {**d, "reason": "mesh larger than the "
                                               "visible device set"}
        return "healthy", d

    def _probe_registry(self):
        versions = self.registry.versions()
        if self.registry.active_version is None:
            return "unhealthy", {"reason": "no model deployed",
                                 "registered": len(versions)}
        detail = {"active": self.registry.active_version,
                  "registered": len(versions)}
        if self.registry.scan_errors:
            # a zip the startup scan could not load was previously recorded
            # but invisible to the health plane (and so to the fleet view):
            # surface it as degraded — the server serves, the debt shows
            return "degraded", {**detail, "reason": "registry scan errors",
                                "scan_errors": dict(self.registry.scan_errors)}
        return "healthy", detail

    # ---- programmatic API --------------------------------------------------
    def submit(self, x, timeout_ms=None):
        """Admit one request; returns its Future (shed raises RejectedError)."""
        x = np.asarray(x)
        if self.transform is not None:  # applied exactly once, pre-lift
            x = np.asarray(self.transform(x))
        return self._submit_transformed(x, timeout_ms)

    def _submit_transformed(self, x, timeout_ms):
        if x.ndim == 1:
            # legacy clients may send a single example as a flat vector; it
            # must not be treated as N one-feature rows (padded/chunked along
            # the feature axis). Lift to a 1-row batch, squeeze on the way out.
            inner = self._submit_transformed(x[None], timeout_ms)
            outer = self._map_future(
                inner,
                lambda res: {"prediction": res["prediction"][0],
                             "version": res["version"]})
            outer.inner = inner      # lets _abandon cascade to the real work
            return outer
        timeout_ms = timeout_ms if timeout_ms is not None \
            else self.default_timeout_ms
        deadline = None if timeout_ms is None \
            else monotonic_s() + float(timeout_ms) / 1000.0
        if x.shape[0] > self.batcher.max_batch_size:
            # split server-side instead of dispatching an oversized bucket:
            # arbitrary row counts would mint unbounded executables past the
            # log2(max_batch_size)+1 bound and pollute the warm-up set, but
            # legacy clients may legitimately send any batch size
            return self._submit_chunked(x, deadline)
        req = Request(x, deadline=deadline,
                      seq_bucket=self.seq_len_bucketing)
        self.queue.offer(req)
        return req.future

    def _abandon(self, fut):
        """Best-effort cancellation of a submitted request whose caller has
        given up: cancel the future, follow a 1-D lift's `inner` handle, and
        withdraw any still-queued chunks of an oversized request."""
        while fut is not None:
            fut.cancel()
            for sib in self.queue.withdraw(getattr(fut, "chunks", [])):
                sib.fail(FuturesTimeoutError("abandoned by handler"))
            fut = getattr(fut, "inner", None)

    @staticmethod
    def _map_future(inner, fn):
        """Future returning fn(inner.result()); errors pass through."""
        agg = Future()

        def on_done(f):
            try:
                res = fn(f.result())
            except BaseException as e:     # incl. CancelledError
                safe_set_exception(agg, e)
                return
            safe_set_result(agg, res)

        inner.add_done_callback(on_done)
        return agg

    def _submit_chunked(self, x, deadline):
        """Enqueue an oversized request as max_batch_size-row chunks and
        return one future that concatenates the parts in order."""
        step = self.batcher.max_batch_size
        reqs = [Request(x[i:i + step], deadline=deadline,
                        count_as_request=(i == 0),
                        seq_bucket=self.seq_len_bucketing)
                for i in range(0, x.shape[0], step)]
        agg = Future()
        remaining = [len(reqs)]
        lock = threading.Lock()

        def on_done(f):
            # The success-path concatenate below runs on the batcher thread
            # (last chunk's complete()) — a bounded single-copy stall, small
            # next to a dispatch. The failure path (which can run under the
            # admission lock via expiry) does no concatenation.
            # Future.exception() raises on a cancelled future, and
            # CancelledError is a BaseException — handle both explicitly
            exc = (RuntimeError("chunk cancelled") if f.cancelled()
                   else f.exception())
            if exc is not None:
                # fail fast: pull still-queued siblings back so they don't
                # burn dispatches whose aggregate the caller won't see
                for sib in self.queue.withdraw(
                        [r for r in reqs if not r.future.done()]):
                    sib.fail(exc)
            with lock:
                remaining[0] -= 1
                if remaining[0]:
                    return
            try:
                parts = [r.future.result() for r in reqs]
                # chunks dispatch as separate batches, so a hot-swap can
                # land between them; report honestly instead of claiming
                # the first chunk's version for all rows
                versions = sorted({p["version"] for p in parts})
                res = {"prediction": np.concatenate(
                           [p["prediction"] for p in parts], axis=0),
                       "version": (versions[0] if len(versions) == 1
                                   else versions)}
            except BaseException as e:     # incl. CancelledError
                safe_set_exception(agg, e)
                return
            safe_set_result(agg, res)

        for r in reqs:
            r.future.add_done_callback(on_done)
        self.queue.offer_all(reqs)  # all chunks admitted, or one clean shed
        agg.chunks = reqs           # lets an abandoning caller withdraw them
        return agg

    def predict(self, x, timeout_ms=None, wait_s=60.0):
        """Blocking convenience: submit + wait; returns the result dict with
        the prediction array and serving version. `wait_s` is per chunk (an
        oversized request dispatches sequentially, like the HTTP path's
        scaled wait); a timeout abandons the queued work before re-raising."""
        return self._await_scaled(self.submit(x, timeout_ms=timeout_ms),
                                  wait_s)

    def _await_scaled(self, fut, per_chunk_wait_s):
        """Wait scaled by the (post-transform) chunk count — an oversized
        request dispatches sequentially, so a flat wait would spuriously
        abandon progressing work; a real timeout abandons it properly."""
        n_chunks = len(getattr(fut, "chunks", ())) or 1
        try:
            return fut.result(timeout=per_chunk_wait_s * n_chunks)
        except FuturesTimeoutError:
            self._abandon(fut)
            raise

    def deploy(self, version, path=None, quantize=None, parity_inputs=None):
        """Load (optional) + warm-up + atomic swap; returns prior version.
        If this call registered the version from `path` and the deploy then
        fails (e.g. warm-up error), the registration is rolled back so the
        identical request can simply be retried.

        quantize="int8" serves the version with per-channel int8 weights
        (nn/quant.py) behind an accuracy-parity gate: parity rows come from
        the request (`parity_inputs`), else are synthesized from the
        model's configured input shape; a gate breach fails the deploy with
        the f32 weights restored and the old version still serving."""
        loaded = path is not None
        if loaded:
            self.registry.load(version, path)
        try:
            pin = None
            if quantize:
                pin = self._parity_inputs(version, parity_inputs)
            return self.registry.deploy(version, warmup=self._warmup,
                                        quantize=quantize,
                                        parity_inputs=pin,
                                        gate=self.quant_gate)
        except Exception:
            if loaded:
                self.registry.unregister(version)
            raise

    def _parity_inputs(self, version, explicit):
        """Parity rows for a quantized deploy: the request's own rows when
        given, else a deterministic synthetic batch shaped from the model's
        configured input type (nn.quant.synthetic_parity_inputs)."""
        if explicit is not None:
            return np.asarray(explicit, np.float32)
        from ..nn.quant import synthetic_parity_inputs
        try:
            mv = self.registry.get(version)
        except KeyError:
            # deploy-by-name: the zip is in scan_dir but not registered yet
            # (registry.deploy would load it AFTER this); resolve it now so
            # a quantized by-name deploy works like a plain one
            spath = self.registry._scan_path(str(version))
            if spath is None:
                raise
            try:
                self.registry.load(version, spath)
            except ValueError:
                pass            # a concurrent scan registered it: fine
            mv = self.registry.get(version)
        x = synthetic_parity_inputs(mv.model)
        if x is None:
            raise ValueError(
                "quantized deploy needs parity_inputs: the model conf "
                "carries no input shape to synthesize them from")
        return x

    def _version_of(self, model):
        """Registry version owning `model` (identity match — the registry
        hands warmup the exact adapted model object), or None for a model
        outside the registry."""
        for info in self.registry.versions():
            try:
                if self.registry.get(info["version"]).model is model:
                    return info["version"]
            except KeyError:
                pass
        return None

    def _warmup(self, model):
        """Deploy-time warm-up: batcher buckets AND (when the decode plane
        is on and the model streams) the decode executables, so neither
        /predict nor /generate ever hits a cold hot-swapped version. The
        warmed buckets re-capture their costs under the incoming version —
        the deploy-time bytes-regression check happens HERE."""
        self.batcher.warmup(model, version=self._version_of(model))
        if self.decode is not None:
            from ..decode.engine import DecodeUnsupported
            try:
                self.decode.warmup(model)
            except DecodeUnsupported:
                pass    # non-streaming model: /predict-only deploy is fine

    def rollback(self):
        return self.registry.rollback(warmup=self._warmup)

    # ---- lifecycle ---------------------------------------------------------
    def start(self):
        if self._httpd is not None:
            return self            # already running: idempotent
        # opt-in runtime lock monitoring ($GRAFT_LOCK_SANITIZER=1): a no-op
        # (no patching, zero per-acquire overhead) unless the env var is
        # set; state is served live at GET /debug/locks either way
        from ..util.concurrency import lock_sanitizer
        lock_sanitizer.install_from_env()
        if self.queue.closed:
            # stop()/start() cycle: a closed queue sheds everything forever
            # and its batcher thread has exited — rebuild both for resume,
            # carrying the observed buckets so deploy warm-up still covers
            # pre-restart traffic shapes
            self.queue = AdmissionQueue(capacity=self.queue.capacity,
                                        metrics=self.metrics)
            observed = set(self.batcher.observed)
            self.batcher = DynamicBatcher(
                self.registry, self.queue, self.metrics,
                max_batch_size=self.batcher.max_batch_size,
                max_latency_ms=self.batcher.max_latency_ms,
                tracer=self.tracer,
                compile_tracker=self.compile_tracker,
                cost_registry=self.cost)
            self.batcher.observed = observed
            self._final_flush_done = False
        self.batcher.start()
        self.alerts.start()
        if self.decode is not None:
            self.decode.start()
        server = self

        class Handler(QuietHandler):
            def _traced(self, fn):
                """Serve inside a server span with the caller's remote
                parent when a W3C traceparent header arrived (util.http
                clients inject it), so client and server spans share ONE
                trace id."""
                with server_span(server.tracer, self.headers,
                                 "http " + self.path.partition("?")[0]):
                    return fn()

            def do_GET(self):
                self._traced(self._do_get)

            def do_POST(self):
                self._traced(self._do_post)

            def _do_get(self):
                u = urlparse(self.path)
                query = {k: v[0] for k, v in parse_qs(u.query).items()}
                # default=str: probe detail and log fields are free-form
                # (numpy scalars, exceptions) — stringify, never 500
                if u.path == "/healthz":
                    report = server._healthz()
                    self.send_json(
                        503 if report["health"] == "unhealthy" else 200,
                        report, default=str)
                elif u.path == "/alerts":
                    self.send_json(200, server.alerts.state(), default=str)
                elif u.path == "/logs":
                    try:
                        payload = server.logger.buffer.to_dict(
                            level=query.get("level"),
                            n=int(query.get("n", 256)),
                            trace_id=query.get("trace_id"))
                    except ValueError as e:   # ?n=all / ?trace_id=abc -> 400
                        self.send_json(400, {"error": f"bad query: {e}"})
                        return
                    self.send_json(200, payload, default=str)
                elif u.path == "/models":
                    self.send_json(200, {
                        "models": server.registry.versions(),
                        "active": server.registry.active_version})
                elif u.path == "/metrics":
                    if query.get("format") == "prometheus":
                        self.send_text(200, server.metrics.to_prometheus(),
                                       content_type=PROMETHEUS_CONTENT_TYPE)
                    else:              # JSON stays the default (back-compat)
                        self.send_json(200, server._metrics_snapshot())
                elif u.path == "/trace":
                    self.send_json(200, server.tracer.to_chrome_trace())
                elif u.path == "/profile/cost":
                    self.send_json(200, server.cost.to_dict(
                        sort=query.get("sort", "hbm_bytes_per_sample"),
                        family=query.get("family")), default=str)
                elif u.path == "/debug/locks":
                    # live lock-sanitizer state (installed flag, held-lock
                    # sets, acquisition-order edges, violations); harmless
                    # {"installed": false, ...} when the sanitizer is off
                    from ..util.concurrency import lock_sanitizer
                    self.send_json(200, lock_sanitizer.table(), default=str)
                elif u.path == "/profile/trace":
                    # bounded on-demand capture: ?steps=N spans (hard
                    # iteration cap inside capture_trace — always stops,
                    # never a leaked jax.profiler session)
                    try:
                        steps = int(query.get("steps", ""))
                        timeout_s = min(float(query.get("timeout_s", 2.0)),
                                        10.0)
                        payload = capture_trace(steps, tracer=server.tracer,
                                                timeout_s=timeout_s)
                    except (TypeError, ValueError) as e:
                        self.send_json(400, {"error": f"bad query: {e}"})
                        return
                    self.send_json(200, payload)
                else:
                    self.send_json(404, {"error": "not found"})

            def _do_post(self):
                try:
                    if self.path == "/predict":
                        server._handle_predict(self)
                    elif self.path == "/generate":
                        server._handle_generate(self)
                    elif self.path == "/deploy":
                        d = json.loads(self.body() or b"{}")
                        prev = server.deploy(
                            d["version"], path=d.get("path"),
                            quantize=d.get("quantize"),
                            parity_inputs=d.get("parity_inputs"))
                        info = {"active": server.registry.active_version,
                                "previous": prev}
                        if d.get("quantize"):
                            mv = server.registry.get(d["version"])
                            info["quantized"] = mv.quantized
                            info["parity"] = mv.parity
                        self.send_json(200, info)
                    elif self.path == "/rollback":
                        active = server.rollback()
                        self.send_json(200, {"active": active})
                    else:
                        self.send_json(404, {"error": "not found"})
                except RejectedError as e:
                    self.send_json(429, {"error": str(e)},
                                   headers={"Retry-After": e.retry_after_s})
                except Exception as e:
                    self.send_json(400,
                                   {"error": f"{type(e).__name__}: {e}"})

        return self.start_with(Handler)

    def stop(self, drain=True, timeout=30.0):
        """Graceful drain: stop admitting (new requests shed with 429),
        serve everything already queued, then stop the HTTP server."""
        self._donation_unwatch()    # idempotent: removes THIS subscriber
        self.alerts.stop()
        if self.decode is not None:
            self.decode.stop(drain=drain, timeout=timeout)
        self.queue.close()
        if not drain:
            self.queue.flush_expired_or_fail()
        self.batcher.join(timeout)
        if self.batcher._thread is None:
            # batcher never ran: nothing will drain the queue — fail what
            # was admitted instead of leaving callers blocked on futures
            self.queue.flush_expired_or_fail()
        if self.stats_router is not None and not self._final_flush_done:
            # idempotent: double-stop (finally + atexit) must not append
            # duplicate trailing reports to a durable storage tier — and a
            # failing/closed router must not abort the shutdown itself
            self._final_flush_done = True
            try:
                self.metrics.flush_to_router(self.stats_router,
                                             snapshot=self._snapshot())
            except Exception:
                pass
        super().stop()

    # ---- handlers ----------------------------------------------------------
    def _parse_body(self, body):
        d = json.loads(body)
        if "dtype" in d and "shape" in d:     # serde envelope (streaming.serde)
            from ..streaming.serde import deserialize_array
            return deserialize_array(d), d
        return np.asarray(d["data"], dtype=np.float32), d

    def _handle_predict(self, handler):
        x, d = self._parse_body(handler.body())
        timeout_ms = d.get("timeout_ms", self.default_timeout_ms)
        # root span for the request: submit() runs inside it, so the Request
        # captures it as trace context and the batcher thread parents its
        # admission/batch/dispatch spans under this tree
        with self.tracer.span(
                "predict",
                rows=int(x.shape[0]) if x.ndim > 1 else 1) as root:
            fut = self.submit(x, timeout_ms=timeout_ms)
            # wait at least the request's own deadline plus dispatch slack —
            # a client asking for timeout_ms > 60s must not be cut off at 60s
            per_chunk_wait_s = 60.0 if timeout_ms is None \
                else float(timeout_ms) / 1000.0 + 60.0
            try:
                res = self._await_scaled(fut, per_chunk_wait_s)
            except DeadlineExceeded as e:
                root.set_attribute("status", 504)
                handler.send_json(504, {"error": str(e)})
                return
            except FuturesTimeoutError:
                # server-side stall (work already abandoned by
                # _await_scaled), not a client error: report 503 so load
                # balancers and retry policies treat it as such
                root.set_attribute("status", 503)
                handler.send_json(503, {"error": "serving timed out"})
                return
            except NoModelDeployed as e:
                # deploy gap is a server condition too, not the client's fault
                root.set_attribute("status", 503)
                handler.send_json(503, {"error": str(e)})
                return
            root.set_attribute("status", 200)
            root.set_attribute("version", res["version"])
            # one structured record per answered request, inside the span:
            # /logs?trace_id=<id> joins an exemplar/trace straight to it
            self.logger.debug("predict_ok", rows=root.attributes.get("rows"),
                              version=res["version"])
        out = res["prediction"]
        handler.send_json(200, {"prediction": out.tolist(),
                                "shape": list(out.shape),
                                "version": res["version"]})

    def _handle_generate(self, handler):
        """POST /generate {"prompt": [token ids], "max_new_tokens"?: N,
        "timeout_ms"?: T, "stop"?: id, "temperature"?: T, "top_k"?: K,
        "top_p"?: P, "seed"?: S} -> {"tokens", "n_prompt", "version",
        "ttft_ms", "queue_wait_ms", "server_ms", "finish_reason"}. Sampling
        params become array operands of the shared decode step
        (decode/sampling.py) — any mix per request, zero recompiles;
        omitting them decodes greedily. 404 when the decode plane is off,
        429 when shed, 504 when the deadline passed before the first token,
        503 with no model. A deadline hit MID-generation answers 200 with
        the partial tokens and finish_reason="deadline" (the per-token
        budget semantics).

        Times in the answer, all on the server's clock: `queue_wait_ms`
        (enqueue -> a decode slot), `ttft_ms` (enqueue -> first token),
        `server_ms` (this handler's wall up to serialising the body), so
        client latency - server_ms is the wire's and the client's. The
        handler's own time — its wall less the wait on the scheduler's
        future — is the `generate_front` phase (`generate_front_ms`)."""
        if self.decode is None:
            handler.send_json(
                404, {"error": "decode plane disabled; start the server "
                               "with decode=True"})
            return
        with self.tracer.span("generate") as root, \
                self.tracer.phase("generate_front",
                                  histogram=self._m_generate_front,
                                  parent=root) as front:
            status, body = self._generate(handler.body(), root, front)
            root.set_attribute("status", status)
            if status == 200:
                body["server_ms"] = \
                    (monotonic_s() - front.start_mono) * 1000.0
            handler.send_json(status, body)

    def _generate(self, raw, root, front):
        """(status, body) of one /generate request; a shed request raises
        RejectedError through to the handler's 429."""
        d = json.loads(raw or b"{}")
        prompt = d.get("prompt")
        if not isinstance(prompt, list) or not prompt:
            return 400, {"error": "prompt must be a non-empty list of "
                                  "token ids"}
        root.set_attribute("n_prompt", len(prompt))
        from ..decode.sampling import SamplerConfig
        try:
            sampler = SamplerConfig.from_request(d)
        except (TypeError, ValueError) as e:
            return 400, {"error": f"bad sampling params: {e}"}
        timeout_ms = d.get("timeout_ms", self.default_timeout_ms)
        try:
            fut = self.decode.submit(
                prompt, max_new_tokens=d.get("max_new_tokens"),
                timeout_ms=timeout_ms, stop_id=d.get("stop"),
                sampler=sampler)
            wait_s = 120.0 if timeout_ms is None \
                else float(timeout_ms) / 1000.0 + 120.0
            try:
                with front.paused():
                    res = fut.result(timeout=wait_s)
            except FuturesTimeoutError:
                # withdraw/clamp the request: an abandoned generation
                # must not keep burning a decode slot (mirror of the
                # /predict path's _abandon)
                self.decode.abandon(fut)
                raise
        except DeadlineExceeded as e:
            return 504, {"error": str(e)}
        except FuturesTimeoutError:
            return 503, {"error": "decode timed out"}
        except NoModelDeployed as e:
            return 503, {"error": str(e)}
        except ValueError as e:      # unservable request shape
            return 400, {"error": str(e)}
        root.set_attribute("version", res["version"])
        root.set_attribute("n_tokens", len(res["tokens"]))
        self.logger.debug("generate_ok", n_prompt=len(prompt),
                          n_tokens=len(res["tokens"]),
                          finish_reason=res["finish_reason"],
                          version=res["version"])
        return 200, res

    def _healthz(self):
        """Deep health: aggregate of every registered component probe plus
        the legacy summary fields. `status` stays "ok" when everything is
        healthy (back-compat with clients asserting the old constant);
        `health` always carries the raw healthy/degraded/unhealthy word.
        The HTTP layer answers 503 only when some component is unhealthy."""
        h = self.health.check()
        report = {
            "status": "ok" if h["status"] == "healthy" else h["status"],
            "health": h["status"],
            "components": h["components"],
            "served": self.metrics.rows.get(),
            "requests": self.metrics.requests.get(),
            "queue_depth": self.queue.depth(),
            "active_version": self.registry.active_version}
        if self.mesh is not None:
            # surfaced so the fleet planes can display chip counts while
            # still counting this whole group as ONE replica
            report["mesh_chips"] = self.mesh.chips
        return report

    def _snapshot(self):
        snap = self.metrics.snapshot(
            queue_depth=self.queue.depth(),
            version_rows={v["version"]: v["serve_count"]
                          for v in self.registry.versions()})
        if self.decode is not None:
            snap["decode"] = self.decode.snapshot()
        if self.mesh is not None:
            # the JSON exposition is curated: mirror the mesh gauges here so
            # scrapers that never speak Prometheus still see the chip count
            snap["mesh_dispatch_chips"] = self.mesh.chips
            snap["mesh_dispatches_total"] = self.mesh.dispatches
        return snap

    def _metrics_snapshot(self):
        snap = self._snapshot()
        # rate-limit the routed copy: a 1 Hz monitoring scraper must not
        # append one report per GET to a durable storage tier; the
        # check-and-set is locked so concurrent scrapes flush once
        if self.stats_router is not None:
            with self._router_flush_lock:
                now = monotonic_s()
                due = (self._last_router_flush is None
                       or now - self._last_router_flush
                       >= self.router_interval_s)
                if due:
                    self._last_router_flush = now
            if due:
                try:
                    self.metrics.flush_to_router(self.stats_router,
                                                 snapshot=snap)
                except Exception:
                    pass    # a broken router must not fail the scrape itself
        return snap
