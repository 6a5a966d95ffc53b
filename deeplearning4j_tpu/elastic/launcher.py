"""ReplicaLauncher SPI: the one place serving replicas are spawned.

The AutoscaleController decides *when* to scale; a launcher owns *how* a
replica starts, warms, drains, and dies — and it owns the max-count guard
(graftlint GL012 `unbounded-spawn`: spawn sites outside a launcher must be
bounded). Two implementations:

- `InProcessLauncher` — replicas are ServingServer instances on threads in
  this process, sharing a `scan_dir` of model zips. The deterministic
  choice for tests and the ManualClock autoscale smoke, and the way to run
  replicas ON AN ACCELERATOR: a chip belongs to one process at a time, and
  the process that has touched JAX holds it, so replicas that share a chip
  (or a host's chips) are threads of that one process. Every replica gets
  JAX's default device — on a four-chip host all of them sit on the first
  chip; one replica per device is the router cell's work (ROADMAP R5).
- `SubprocessLauncher` — each replica is a real OS process (its own Python,
  its own XLA client), for process-grade isolation. The child runs on
  whatever platform ITS environment gives it; the launcher refuses to start
  a child that needs an accelerator this process already holds.

Warm-up contract: a launcher replays the newest registry deploy event
through the `RegistrySubscriber` path (`subscriber.apply`, the same code
broker-fanned events run) *synchronously inside launch()*, so a replica
joins the pool already serving the fleet's active version — and, when a
broker client factory is given, attaches a live subscriber on the
replica's own topic (`<topic>.<name>`; broker topics are competing-
consumer queues, so per-replica topics keep every replica seeing every
event) for subsequent deploys. `fan_deploy(event)` publishes to every
replica topic and records the event as the newest for future launches.
"""
from __future__ import annotations

import threading

from ..serving.frontend import RegistrySubscriber


class ReplicaLauncher:
    """SPI. Implementations must bound their replica count (`max_replicas`)
    — the GL012 invariant lives here, not at call sites."""

    def launch(self, name) -> str:
        """Start replica `name`, warm it, and return its base URL."""
        raise NotImplementedError

    def drain(self, name):
        """Gracefully stop `name`: finish queued work, then shut down."""
        raise NotImplementedError

    def terminate(self, name):
        """Hard-kill `name` (preemption cleanup); idempotent."""
        raise NotImplementedError

    def alive(self, name) -> bool:
        raise NotImplementedError

    def names(self):
        """Names of replicas this launcher has running."""
        raise NotImplementedError


class InProcessLauncher(ReplicaLauncher):
    """Threaded ServingServer replicas sharing one scan_dir; see module
    docstring. `server_opts` pass through to every ServingServer —
    including `mesh` (serving/mesh.py), so a launcher configured with
    `server_opts={"mesh": {...}}` spawns MESH-GROUP replicas: each launch
    is one server spanning N chips that registers in the fleet as ONE
    ReplicaHandle. `broker_factory` (zero-arg -> streaming.BrokerClient)
    enables the live per-replica deploy subscription."""

    def __init__(self, scan_dir=None, server_opts=None, max_replicas=8,
                 broker_factory=None, topic="registry_events",
                 deploy_event=None):
        self.scan_dir = scan_dir
        self.server_opts = dict(server_opts or {})
        self.max_replicas = int(max_replicas)
        self.broker_factory = broker_factory
        self.topic = str(topic)
        self.last_deploy_event = deploy_event
        self.fan_errors = []    # bounded; a failed fan is debt, not silence
        self._lock = threading.Lock()
        self._replicas = {}     # guarded by: self._lock — name -> record

    def _record_fan_error(self, name, exc):
        if len(self.fan_errors) < 100:
            self.fan_errors.append(
                {"replica": name, "error": f"{type(exc).__name__}: {exc}"})

    def fan_deploy(self, event):
        """Record `event` as the newest deploy and fan it to every live
        replica's broker topic (each replica's subscriber applies it). The
        newest event is what the next launch() replays for warm-up."""
        self.last_deploy_event = dict(event)
        with self._lock:
            records = list(self._replicas.items())
        fanned = 0
        for name, rec in records:
            sub = rec.get("subscriber")
            if sub is not None and sub.client is not None:
                try:
                    sub.client.publish(f"{self.topic}.{name}", dict(event))
                    fanned += 1
                except Exception as e:
                    # replayed at the replica's next launch; recorded as debt
                    self._record_fan_error(name, e)
        return fanned

    def launch(self, name):
        from ..serving.server import ServingServer
        name = str(name)
        with self._lock:
            if name in self._replicas:
                raise ValueError(f"replica {name!r} already running")
            # THE max-count guard: an autoscaler bug (or a flapping signal)
            # must hit this wall, not fork servers until the host dies
            if len(self._replicas) >= self.max_replicas:
                raise RuntimeError(
                    f"launcher at max_replicas={self.max_replicas}")
            self._replicas[name] = {}   # reserve the slot under the lock
        rec = {}                        # filled as pieces come up, so a
        try:                            # failure closes what DID start
            rec["server"] = ServingServer(scan_dir=self.scan_dir,
                                          **self.server_opts).start()
            if self.broker_factory is not None:
                rec["subscriber"] = RegistrySubscriber(
                    rec["server"], self.broker_factory(),
                    topic=f"{self.topic}.{name}").start()
            else:
                rec["subscriber"] = RegistrySubscriber(rec["server"],
                                                       client=None)
            if self.last_deploy_event is not None:
                # warm BEFORE the replica is handed to the pool: the same
                # RegistrySubscriber.apply the broker loop uses, run
                # synchronously, so /predict never reaches a cold replica
                rec["subscriber"].apply(dict(self.last_deploy_event))
        except Exception:
            with self._lock:
                self._replicas.pop(name, None)
            self._close(rec, drain=False)
            raise
        with self._lock:
            if name not in self._replicas:
                # terminated/closed mid-launch (chaos kill racing the
                # controller): honoring the kill means NOT resurrecting —
                # tear down what started instead of re-inserting it
                raced = True
            else:
                raced = False
                self._replicas[name] = rec
        if raced:
            self._close(rec, drain=False)
            raise RuntimeError(f"replica {name!r} terminated during launch")
        return rec["server"].url

    def _pop(self, name):
        with self._lock:
            return self._replicas.pop(str(name), None)

    @staticmethod
    def _close(rec, drain=True):
        sub = rec.get("subscriber")
        if sub is not None:
            try:
                sub.close(timeout=2.0)
            except Exception:
                pass
        server = rec.get("server")
        if server is not None:
            server.stop(drain=drain)

    def drain(self, name):
        rec = self._pop(name)
        if rec:
            self._close(rec, drain=True)

    def terminate(self, name):
        rec = self._pop(name)
        if rec:
            self._close(rec, drain=False)

    def kill(self, name):
        """Chaos entry point: preempt the replica like the platform would —
        hard stop, no drain, no pool bookkeeping beyond forgetting it."""
        self.terminate(name)

    def alive(self, name):
        with self._lock:
            return str(name) in self._replicas

    def names(self):
        with self._lock:
            return sorted(self._replicas)

    def server(self, name):
        """The live ServingServer behind `name` (tests/smoke)."""
        with self._lock:
            rec = self._replicas.get(str(name))
        return None if rec is None else rec.get("server")

    def close(self):
        with self._lock:
            records, self._replicas = dict(self._replicas), {}
        for rec in records.values():
            self._close(rec, drain=False)


_SUBPROCESS_SCRIPT = r"""
import sys, json
from deeplearning4j_tpu.serving.server import ServingServer
opts = json.loads(sys.argv[1])
server = ServingServer(**opts).start()
print("PORT=%d" % server.port, flush=True)
import threading
threading.Event().wait()        # serve until killed
"""


class SubprocessLauncher(ReplicaLauncher):
    """One OS process per replica (process-grade isolation for smoke runs):
    spawns `python -c <bootstrap>` that starts a ServingServer over the
    shared scan_dir and prints its port. Warm-up deploys go over HTTP
    (POST /deploy) since the subscriber lives in the child. Bounded by
    `max_replicas` like every launcher.

    One process for each chip: the child takes its platform from its
    environment — this process's, with `env` laid over it (a value of None
    removes a variable) — and nothing in code pins it. A child that is not
    pinned to the CPU (`JAX_PLATFORMS=cpu`) needs an accelerator of its own;
    if this process runs JAX on one, the child would fail or hang at
    start-up, so `launch` raises instead. On an accelerator use
    `InProcessLauncher`, or launch from a process that runs JAX on the CPU
    and hand the children an `env` without the pin.

    Mesh groups: `server_opts["mesh"]` is normalized to its JSON dict form
    so it survives the argv hand-off; to smoke-test a CPU mesh give the
    children XLA_FLAGS=--xla_force_host_platform_device_count=N."""

    def __init__(self, scan_dir, server_opts=None, max_replicas=4,
                 deploy_event=None, start_timeout_s=60.0, env=None):
        self.scan_dir = str(scan_dir)
        self.env = dict(env or {})
        self.server_opts = dict(server_opts or {})
        mesh = self.server_opts.get("mesh")
        if mesh is not None and hasattr(mesh, "to_dict"):
            self.server_opts["mesh"] = mesh.to_dict()
        self.max_replicas = int(max_replicas)
        self.last_deploy_event = deploy_event
        self.start_timeout_s = float(start_timeout_s)
        self.fan_errors = []    # bounded; a failed fan is debt, not silence
        self._lock = threading.Lock()
        self._replicas = {}     # guarded by: self._lock — name -> record

    _record_fan_error = InProcessLauncher._record_fan_error

    def fan_deploy(self, event):
        from ..util.http import post_json
        self.last_deploy_event = dict(event)
        with self._lock:
            records = list(self._replicas.items())
        fanned = 0
        for name, rec in records:
            try:
                post_json(rec["url"] + "/deploy",
                          {"version": event["version"],
                           **({"path": event["path"]} if "path" in event
                              else {})}, timeout=60.0)
                fanned += 1
            except Exception as e:
                self._record_fan_error(name, e)
        return fanned

    def child_env(self):
        """The environment a replica process starts with."""
        import os
        env = dict(os.environ)
        for key, value in self.env.items():
            if value is None:
                env.pop(key, None)
            else:
                env[key] = str(value)
        return env

    @staticmethod
    def check_chip_is_free(env):
        """Raise unless a child started with `env` can get the platform it
        will ask for: either it is pinned to the CPU, or this process does
        not run JAX on an accelerator (asking initialises this process's
        backend, which a process that serves models does anyway)."""
        if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
            return
        import jax
        backend = jax.default_backend()
        if backend != "cpu":
            raise RuntimeError(
                f"SubprocessLauncher: this process runs JAX on {backend!r} "
                "and holds that accelerator; a replica process that is not "
                "pinned to the CPU needs one of its own and would fail or "
                "hang at start-up. Use InProcessLauncher (replicas as "
                "threads of the process that holds the chip), or launch "
                "from a process that runs JAX on the CPU, or pin the "
                "replicas with env={'JAX_PLATFORMS': 'cpu'}.")

    def launch(self, name):
        import json as _json
        import subprocess
        import sys
        from ..util.http import post_json
        name = str(name)
        env = self.child_env()
        self.check_chip_is_free(env)
        with self._lock:
            if name in self._replicas:
                raise ValueError(f"replica {name!r} already running")
            if len(self._replicas) >= self.max_replicas:
                raise RuntimeError(
                    f"launcher at max_replicas={self.max_replicas}")
            self._replicas[name] = {}
        proc = None                     # killed on ANY failure below: a
        try:                            # half-launched child must not
            opts = {"scan_dir": self.scan_dir, **self.server_opts}   # orphan
            proc = subprocess.Popen(
                [sys.executable, "-c", _SUBPROCESS_SCRIPT,
                 _json.dumps(opts)],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=env)
            line = self._read_port_line(proc, self.start_timeout_s)
            if not line.startswith("PORT="):
                raise RuntimeError(f"replica {name} failed to start: "
                                   f"{line!r}")
            url = f"http://127.0.0.1:{int(line.split('=', 1)[1])}"
            if self.last_deploy_event is not None:
                ev = self.last_deploy_event
                post_json(url + "/deploy",
                          {"version": ev["version"],
                           **({"path": ev["path"]} if "path" in ev
                              else {})}, timeout=self.start_timeout_s)
        except Exception:
            with self._lock:
                self._replicas.pop(name, None)
            if proc is not None:
                proc.kill()
            raise
        with self._lock:
            if name not in self._replicas:   # terminated mid-launch
                raced = True
            else:
                raced = False
                self._replicas[name] = {"proc": proc, "url": url}
        if raced:
            proc.kill()
            raise RuntimeError(f"replica {name!r} terminated during launch")
        return url

    @staticmethod
    def _read_port_line(proc, timeout_s):
        """First stdout line, bounded by `timeout_s`: a child that hangs
        before printing PORT= (wedged import, stuck bind) must fail the
        launch, not block the controller forever. Reader-thread based
        (portable; select on a pipe is POSIX-only)."""
        out = {}

        def read():
            out["line"] = (proc.stdout.readline() or "").strip()
        t = threading.Thread(target=read, daemon=True, name="port-reader")
        t.start()
        t.join(timeout_s)
        if "line" not in out:
            proc.kill()
            raise RuntimeError(
                f"replica did not report a port within {timeout_s}s")
        return out["line"]

    def _pop_kill(self, name):
        with self._lock:
            rec = self._replicas.pop(str(name), None)
        if rec and rec.get("proc") is not None:
            rec["proc"].kill()
            rec["proc"].wait(timeout=10)
        return rec

    def drain(self, name):
        # no in-process handle to drain through: terminate is the best a
        # process boundary offers (the child's queue dies with it)
        self._pop_kill(name)

    def terminate(self, name):
        self._pop_kill(name)

    kill = terminate

    def alive(self, name):
        with self._lock:
            rec = self._replicas.get(str(name))
        return rec is not None and rec["proc"].poll() is None

    def names(self):
        with self._lock:
            return sorted(self._replicas)

    def close(self):
        for name in self.names():
            self._pop_kill(name)
