"""Alert-gated canary deploys: the observe -> detect -> react loop closing
on *deployments*, not just incidents (ROADMAP item 1's canary half).

`POST /deploy {"version": v, "canary": 0.1}` on a FleetFrontend deploys the
incoming version on ONE replica (the canary cohort) and routes that traffic
fraction there; everything else keeps serving the stable version. The
controller then gates the outcome on the PR-4 AlertEngine, with SLO rules
scoped to the canary cohort's labels (`frontend_errors_total{cohort=
"canary"} / frontend_attempts_total{cohort="canary"}`):

- the error-ratio rule (and, when an `slo` is configured, a burn-rate rule)
  FIRING auto-rolls the canary back — the replica redeploys its previous
  version, the cohort dissolves, and the fleet never saw the bad version at
  full fraction. Because the frontend fails a bad canary attempt over to a
  stable replica, clients see 200s throughout.
- a `canary_promote_ready` threshold rule fires once the canary has baked
  `bake_s` seconds, served at least `min_requests` attempts, and no breach
  rule is pending/firing — the controller then promotes: the version
  deploys to every stable replica and the cohort dissolves.

Both transitions ride the standard alert lifecycle (visible in `/alerts`,
notified to sinks exactly once, resolved on rule removal), emit structured
log events with trace correlation, count into
`canary_promotions_total`/`canary_rollbacks_total`, and fan out as
registry-change events over the broker. Every timestamp reads the injected
clock, so the whole lifecycle tests under ManualClock with zero sleeps.
"""
from __future__ import annotations

import threading

from ..telemetry.alerts import AlertRule, INACTIVE
from ..util.time_source import monotonic_s, now_s

IDLE, OBSERVING = "idle", "observing"
#: transient states reserving the controller while its blocking HTTP runs
#: OUTSIDE the lock (a wedged replica must never stall /healthz or /alerts,
#: which read status() under the same lock)
DEPLOYING, PROMOTING, ROLLING_BACK = "deploying", "promoting", "rolling_back"
PROMOTED, ROLLED_BACK = "promoted", "rolled_back"

_BREACH_RULES = ("canary_error_ratio", "canary_burn_rate")
_PROMOTE_RULE = "canary_promote_ready"


class CanaryController:
    """One canary at a time per frontend; see module docstring. Constructed
    by FleetFrontend (`canary_opts={...}` passes through here)."""

    def __init__(self, frontend, bake_s=300.0, min_requests=20,
                 error_ratio=0.05, window_s=60.0, for_duration_s=0.0,
                 slo=None, burn_threshold=14.4, history_cap=64):
        self.frontend = frontend
        self.bake_s = float(bake_s)
        self.min_requests = int(min_requests)
        self.error_ratio = float(error_ratio)
        self.window_s = float(window_s)
        self.for_duration_s = float(for_duration_s)
        self.slo = None if slo is None else float(slo)
        self.burn_threshold = float(burn_threshold)
        self.history_cap = int(history_cap)
        self.state = IDLE
        self.version = None
        self.fraction = 0.0
        self.replica_name = None
        self.path = None
        self._started_mono = None
        self._attempts_at_start = 0.0
        self._lock = threading.Lock()
        self.history = []
        reg = frontend.registry
        self.m_promotions = reg.counter(
            "canary_promotions_total", "Canaries promoted to the fleet")
        self.m_rollbacks = reg.counter(
            "canary_rollbacks_total", "Canaries auto/manually rolled back")
        self.m_promotions.inc(0)
        self.m_rollbacks.inc(0)
        reg.gauge(_PROMOTE_RULE,
                  "1 when the canary has baked healthy and may promote",
                  fn=self._promote_ready)
        frontend.alerts.add_sink(self._on_alert)

    # ---- rule set ----------------------------------------------------------
    def _rules(self):
        labels = {"cohort": "canary"}
        rules = [AlertRule(
            "canary_error_ratio", "ratio",
            numerator="frontend_errors_total",
            denominator="frontend_attempts_total", labels=labels,
            threshold=self.error_ratio, window_s=self.window_s,
            for_duration_s=self.for_duration_s, severity="page",
            description="canary cohort error ratio over the rollback bound")]
        if self.slo is not None:
            rules.append(AlertRule(
                "canary_burn_rate", "burn_rate",
                numerator="frontend_errors_total",
                denominator="frontend_attempts_total", labels=labels,
                slo=self.slo, threshold=self.burn_threshold,
                window_s=self.window_s,
                for_duration_s=self.for_duration_s, severity="page",
                description="canary cohort burning the SLO error budget"))
        rules.append(AlertRule(
            _PROMOTE_RULE, "threshold", metric=_PROMOTE_RULE,
            op=">=", threshold=1.0, severity="info",
            description="canary baked healthy; auto-promote"))
        return rules

    def _promote_ready(self):
        """Gauge callback: 1.0 when promotable, 0.0 while baking, None when
        idle (no-data keeps the rule inactive between canaries). Runs on the
        metrics-scrape thread, so the rollout state written by start() is
        snapshotted under the lock (GL018), and the metric/alert reads stay
        outside it."""
        with self._lock:
            state = self.state
            started_mono = self._started_mono
            attempts_at_start = self._attempts_at_start
        if state != OBSERVING:
            return None
        if monotonic_s() - started_mono < self.bake_s:
            return 0.0
        served = self.frontend.m_attempts.get(cohort="canary") \
            - attempts_at_start
        if served < self.min_requests:
            return 0.0
        for rule in self.frontend.alerts.rules:
            if rule.name in _BREACH_RULES and rule.state != INACTIVE:
                return 0.0
        return 1.0

    # ---- lifecycle ---------------------------------------------------------
    def start(self, version, fraction, path=None, replica=None):
        """Deploy `version` on the canary replica (default: the LAST replica
        in the pool) and start routing `fraction` of /predict traffic there.
        Returns the status dict; raises while another canary is active. The
        deploy POST runs OUTSIDE the lock (DEPLOYING reserves the
        controller), so a slow replica never stalls status() readers."""
        if not 0.0 < float(fraction) <= 1.0:
            raise ValueError("canary fraction must be in (0, 1]")
        with self._lock:
            if self.state != IDLE:
                raise RuntimeError(
                    f"canary {self.version!r} already {self.state}")
            if len(self.frontend.replicas) < 2:
                raise RuntimeError("canary needs >= 2 replicas (one canary "
                                   "+ a stable cohort to fail over to)")
            stuck = [r.name for r in self.frontend.replicas
                     if r.cohort != "stable"]
            if stuck:
                raise RuntimeError(
                    f"replica(s) {stuck} still hold an undeployed canary "
                    "version (a previous rollback could not land); run a "
                    "fleet-wide /deploy to re-admit them first")
            target = self.frontend._replica(replica) if replica is not None \
                else self.frontend.replicas[-1]
            self.state = DEPLOYING
        body = {"version": version}
        if path is not None:
            body["path"] = path
        try:
            from ..util.http import post_json
            post_json(target.url + "/deploy", body, timeout=60.0)
        except Exception:
            with self._lock:
                self.state = IDLE
            raise
        with self._lock:
            target.cohort = "canary"
            self.state = OBSERVING
            self.version = str(version)
            self.fraction = float(fraction)
            self.replica_name = target.name
            self.path = path
            self._started_mono = monotonic_s()
            self._attempts_at_start = \
                self.frontend.m_attempts.get(cohort="canary")
        for rule in self._rules():
            if rule.kind in ("ratio", "burn_rate"):
                # the cohort label-set is reused by every canary: this
                # deploy's window must not inherit the previous one's errors
                self.frontend.alerts.drop_history(
                    rule.numerator + rule.denominator, labels=rule.labels)
            self.frontend.alerts.add_rule(rule)
        self.frontend.logger.info("canary_start", version=self.version,
                                  fraction=self.fraction,
                                  replica=self.replica_name)
        self.frontend.publish_registry_event(
            {"kind": "canary_start", "version": self.version,
             "replica": self.replica_name, "fraction": self.fraction})
        return self.status()

    def _on_alert(self, event):
        """AlertEngine sink: the gate. Exactly-once transition events drive
        the react step — no polling loop of our own. The state read takes
        the lock (alert-engine thread vs start()); rollback() re-acquires
        it itself, so the reaction runs outside the critical section."""
        with self._lock:
            observing = self.state == OBSERVING
        if not observing or event.get("state") != "firing":
            return
        rule = event.get("rule")
        if rule in _BREACH_RULES:
            self.rollback(reason=rule, value=event.get("value"))
        elif rule == _PROMOTE_RULE:
            self.promote()

    def promote(self):
        """Deploy the canary version fleet-wide and dissolve the cohort.
        The broadcast runs OUTSIDE the lock (PROMOTING reserves the
        controller against a concurrent rollback)."""
        with self._lock:
            observing = self.state == OBSERVING
            if observing:
                self.state = PROMOTING
                version, path = self.version, self.path
                stable = [r for r in self.frontend.replicas
                          if r.name != self.replica_name]
        if not observing:
            # status() takes the lock itself — calling it from inside the
            # critical section self-deadlocks (graftlint GL020)
            return self.status()
        body = {"version": version}
        if path is not None:
            body["path"] = path
        results = self.frontend.broadcast("/deploy", body, replicas=stable)
        self._finish(PROMOTED, {"results": results})
        self.m_promotions.inc(1)
        self.frontend.logger.info("canary_promoted", version=version)
        self.frontend.publish_registry_event(
            {"kind": "deploy", "version": version,
             **({"path": path} if path is not None else {})})
        return self.status()

    def rollback(self, reason="manual", value=None):
        """Redeploy the canary replica's previous version and dissolve the
        cohort; the stable fleet never changed. The rollback POST runs
        OUTSIDE the lock (ROLLING_BACK reserves the controller) and is
        retried; if it STILL fails (replica unreachable right when its bad
        version must come off), the replica is NOT returned to the stable
        cohort — with the controller idle its cohort gets zero primary
        traffic (failover target only), instead of silently serving the
        bad version at full weight. A later fleet-wide /deploy re-admits
        it; until then start() refuses a new canary over the wreckage."""
        with self._lock:
            observing = self.state == OBSERVING
            if observing:
                self.state = ROLLING_BACK
                version, replica = self.version, self.replica_name
                target = self.frontend._replica(replica)
        if not observing:
            # as in promote(): status() re-acquires self._lock (GL020)
            return self.status()
        from ..resilience.policy import RetryPolicy, advance_aware_sleep
        from ..util.http import post_json
        try:
            result = RetryPolicy(max_attempts=3, base_s=0.2, cap_s=1.0,
                                 sleep=advance_aware_sleep).call(
                post_json, target.url + "/rollback", {}, timeout=60.0)
            undeployed = True
        except Exception as e:
            result = {"error": f"{type(e).__name__}: {e}"}
            undeployed = False
        self._finish(ROLLED_BACK, {"reason": reason, "value": value,
                                   "result": result,
                                   "undeployed": undeployed},
                     stuck_replica=None if undeployed else replica)
        self.m_rollbacks.inc(1)
        if undeployed:
            self.frontend.logger.error("canary_rolled_back", version=version,
                                       replica=replica, reason=reason,
                                       value=value)
        else:
            self.frontend.logger.error("canary_rollback_failed",
                                       version=version, replica=replica,
                                       reason=reason, value=value,
                                       error=result["error"])
        self.frontend.publish_registry_event(
            {"kind": "canary_rollback", "version": version,
             "replica": replica, "reason": reason,
             "undeployed": undeployed})
        return self.status()

    def _finish(self, outcome, detail, stuck_replica=None):
        """Dissolve the cohort and record the transition (`stuck_replica`
        stays in the canary cohort: its rollback never landed, so it must
        not rejoin the stable rotation with the bad version live). The
        rules are removed AFTER the lock releases: removal resolves any
        FIRING rule through the engine's displaced-rule path (so pagers see
        the incident close), and that notifies sinks — which may themselves
        read status() and must not deadlock on this lock."""
        with self._lock:
            for r in self.frontend.replicas:
                if r.name != stuck_replica:
                    r.cohort = "stable"
            entry = {"outcome": outcome, "version": self.version,
                     "replica": self.replica_name, "fraction": self.fraction,
                     "time": now_s(), **detail}
            self.history.append(entry)
            if len(self.history) > self.history_cap:
                del self.history[:len(self.history) - self.history_cap]
            self.state = IDLE
            self.version = None
            self.fraction = 0.0
            self.replica_name = None
            self.path = None
            self._started_mono = None
        for name in _BREACH_RULES + (_PROMOTE_RULE,):
            self.frontend.alerts.remove_rule(name)

    # ---- reading -----------------------------------------------------------
    def status(self):
        with self._lock:
            out = {"state": self.state, "version": self.version,
                   "fraction": self.fraction,
                   "replica": self.replica_name,
                   "promotions": self.m_promotions.get(),
                   "rollbacks": self.m_rollbacks.get(),
                   "history": [dict(h) for h in self.history[-8:]]}
            if self.state == OBSERVING:
                out["observing_s"] = monotonic_s() - self._started_mono
                out["bake_s"] = self.bake_s
            return out
