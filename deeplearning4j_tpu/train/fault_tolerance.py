"""Checkpoint-restart fault tolerance with durable, verified checkpoints.

Design (TPU-native, no reference counterpart — SURVEY.md §5 gap):
- durable checkpoints: every file fsync'd and the directory published via
  `util.fs.atomic_publish_dir` (fsync before AND after the `os.replace`),
  so a preemption or power loss mid-write never corrupts — or half-
  publishes — the latest checkpoint;
- verified format: each checkpoint dir carries a `MANIFEST.json` written
  LAST (per-file sha256 + byte sizes, step, wall time, topology). A
  checkpoint without a valid manifest is by definition incomplete. The
  digests are computed from the in-memory bytes the writer intended, so
  restore-time verification catches torn writes and bit rot that write-time
  read-back (served from the page cache) never could;
- fallback restore: `_try_restore` walks `ckpt-*` newest -> oldest,
  verifies manifests, QUARANTINES failures under `corrupt-<name>`
  (mirroring the `halt-*` forensics idiom — kept, never auto-restored),
  and resumes from the first checkpoint that verifies AND loads. Fallbacks
  surface as `ckpt_restore_fallbacks_total` / `ckpt_verify_failures_total`
  and as a degraded health-probe detail until the next good publish;
- async writes: `checkpoint()` snapshots params/opt-state/rng to host in
  ONE blocking device-get, then serializes+verifies+publishes on a
  background writer thread — at most one write in flight (the next
  checkpoint joins), writer errors re-raised exactly once at the next
  `checkpoint()`/fit-end (the ETL error-propagation idiom), except
  ENOSPC/EDQUOT (disk full is retryable capacity debt: counted, logged,
  degraded-probe-visible, and training keeps running — the previously
  published checkpoint stays intact). `ckpt_blocking_ms` vs `ckpt_write_ms`
  histograms make the async win measurable;
- training state beyond weights: epoch, batch index within the epoch, total
  iteration count, and the model's PRNG key all persist, so the resumed loss
  curve continues where the dead process stopped (mid-epoch included);
- the model file is the standard ModelSerializer zip (configuration.json +
  coefficients + updater state — util/model_serializer.py), so any checkpoint
  doubles as a normal saved model;
- `FaultTolerantTrainer.fit` skips already-consumed batches when resuming
  mid-epoch by fast-forwarding the iterator.

Chaos: `resilience.chaos.FaultPlan` disk rules (`torn_write` / `bitflip` /
`enospc` / `slow_disk`) inject through the `util.fs` write seam the async
writer uses; `tools/ckpt_doctor.py` is the operator CLI over the same
verify/quarantine primitives.

Reference analogs for the retry/resume idea: Spark task retry (RDD lineage),
MnistFetcher.java:103-107 download retry.
"""
from __future__ import annotations

import errno
import io
import json
import os
import shutil
import threading

import numpy as np

from ..telemetry.registry import get_registry
from ..telemetry.trace import get_tracer
from ..util import fs
from ..util.model_serializer import ModelSerializer
from ..util.time_source import monotonic_s, now_s


class CheckpointConfig:
    def __init__(self, directory, frequency=50, keep_last=2, format="zip",
                 keep_every=None, async_write=True):
        """format: "zip" (ModelSerializer contract, host-gathered) or
        "sharded" (orbax tensor store — mesh-sharded params checkpoint
        without host gathering, util/sharded_checkpoint.py).

        `keep_every=K`: checkpoints whose iteration is a multiple of K are
        ANCHORS — never garbage-collected, however far outside the
        `keep_last` window they fall (the long-run forensics ladder).

        `async_write`: serialize+verify+publish on the background writer
        thread (the training thread pays only the host snapshot). Forced
        off for the sharded format — orbax streams device shards itself,
        and host-gathering them first would defeat that format's point."""
        assert format in ("zip", "sharded")
        self.directory = str(directory)
        self.frequency = int(frequency)
        self.keep_last = int(keep_last)
        self.format = format
        self.keep_every = None if keep_every is None else int(keep_every)
        self.async_write = bool(async_write) and format == "zip"


def _is_disk_full(exc) -> bool:
    """ENOSPC/EDQUOT: capacity debt, retryable at the next interval — the
    one writer-error class that must not kill a training run."""
    return isinstance(exc, OSError) and \
        exc.errno in (errno.ENOSPC, getattr(errno, "EDQUOT", errno.ENOSPC))


class _ModelSnapshot:
    """Host-side copy of the serializable network state, detached from the
    live model so the background writer never races training (or reads a
    donated buffer). `model_class` stands in for the isinstance checks
    ModelSerializer.write_model would do on the live network; `_zero` is
    None because the updater state was already converted to its canonical
    layout during the blocking snapshot."""

    def __init__(self, conf, model_class, params, states, opt_state):
        self.conf = conf
        self.model_class = model_class
        self.params = params
        self.states = states
        self.opt_state = opt_state
        self._zero = None


class _CheckpointWriter:
    """At most one checkpoint write in flight. The trainer thread is the
    only caller: it `join()`s the in-flight write, then `claim_error()`s —
    the parked exception surfaces exactly ONCE (the ETL error-propagation
    idiom) — before submitting the next job."""

    def __init__(self):
        self._thread = None
        self._error = None

    def submit(self, job):
        if self._thread is not None:
            raise RuntimeError("join() the in-flight checkpoint write first")

        def run():
            try:
                job()
            except BaseException as e:   # parked; claimed on the next join
                self._error = e

        t = threading.Thread(target=run, name="ckpt-writer", daemon=True)
        self._thread = t
        t.start()

    def join(self):
        t = self._thread
        if t is not None:
            t.join()
            self._thread = None

    def claim_error(self):
        err, self._error = self._error, None
        return err


class FaultTolerantTrainer:
    """Drives `model.fit`-style training with periodic durable checkpoints
    and preemption resume.

    Usage:
        trainer = FaultTolerantTrainer(model_factory, CheckpointConfig(dir))
        trainer.fit(iterator, epochs=N)   # auto-resumes if checkpoints exist
    `model_factory()` builds the (un-initialized) model when no checkpoint
    exists; on resume the model is restored from the newest checkpoint that
    VERIFIES (manifest hashes) — corrupt newer ones are quarantined under
    `corrupt-<name>` and the restore falls back down the chain.
    """

    STATE_FILE = "train_state.json"
    MODEL_FILE = "model.zip"
    SHARDED_DIR = "model_sharded"

    def __init__(self, model_or_factory, checkpoint: CheckpointConfig,
                 health=None, monitor=None):
        """`health`: a TrainingHealthListener (optimize.listeners) — the
        trainer attaches it to the model and, when a fatal condition trips
        (NaN loss/gradients, divergence), writes one final QUARANTINED
        checkpoint (`halt-<iter>`, kept for forensics but never auto-
        restored — its params are the corrupted/diverged state) and raises
        TrainingHalted instead of burning accelerator hours on a dead run.
        Restarting resumes from the newest periodic `ckpt-*` checkpoint,
        which predates the blow-up.

        `monitor`: the telemetry.health.HealthMonitor this trainer's
        liveness probe registers into (default: the process monitor, the
        one UIServer /healthz — and so /fleet/healthz — aggregates). The
        probe carries iteration/heartbeat state and is re-registered on the
        restore path too, so a RESUMED run is immediately visible to the
        fleet plane instead of silently losing its membership entry; pass
        monitor=False to opt out entirely. A restore that fell back past a
        corrupt checkpoint — or a swallowed disk-full write failure —
        reports DEGRADED with the debt in the detail until the next
        verified publish clears it."""
        self.ckpt = checkpoint
        os.makedirs(self.ckpt.directory, exist_ok=True)
        self._factory = (model_or_factory if callable(model_or_factory)
                         else (lambda: model_or_factory))
        self.model = None
        self.health = health
        if monitor is None:
            from ..telemetry.health import get_monitor
            monitor = get_monitor()
        self.monitor = monitor or None     # False -> None (no probe)
        self.health_key = None
        self._last_beat = None
        self._writer = _CheckpointWriter()
        self._ckpt_debt = None    # restore-fallback / write-failure detail
        self._last_good = None    # newest checkpoint name known verified
        self.state = {"epoch": 0, "batch": 0, "iteration": 0, "rng": None}
        self._restored = self._try_restore()
        self._register_probe()

    def _net(self):
        """The serializable network under self.model. A trainer wrapper
        (ShardedTrainer — incl. ZeRO mode — exposes the wrapped network as
        `.model` and drives it via fit_batch) checkpoints its INNER network;
        a bare network is itself. Wrapper checkpoints therefore stay plain
        ModelSerializer zips / orbax stores, loadable anywhere."""
        m = self.model
        inner = getattr(m, "model", None)
        if inner is not None and hasattr(inner, "conf") \
                and callable(getattr(m, "fit_batch", None)):
            return inner
        return m

    # ------------------------------------------------------------ checkpoint
    def _ckpt_dirs(self):
        out = []
        for name in os.listdir(self.ckpt.directory):
            if name.startswith("ckpt-") and os.path.isfile(
                    os.path.join(self.ckpt.directory, name, self.STATE_FILE)):
                out.append(name)
        return sorted(out, key=lambda n: int(n.split("-")[1]))

    def _gc_orphans(self):
        for name in os.listdir(self.ckpt.directory):
            if name.startswith("tmp-"):
                shutil.rmtree(os.path.join(self.ckpt.directory, name),
                              ignore_errors=True)

    def checkpoint(self, prefix="ckpt"):
        """Write a durable checkpoint of model + training state. The
        blocking cost to the training thread (one device-get snapshot on
        the async path; the whole serialize+fsync+publish otherwise) is
        `ckpt_blocking_ms`; the writer's cost is `ckpt_write_ms`, both
        under a `checkpoint` span next to the iteration timings.

        Joins any in-flight write first (at most one in flight) and
        surfaces a previous writer error exactly once — disk-full errors
        are absorbed as checkpoint debt (counter + degraded probe) so the
        run keeps training and retries at the next interval.

        `prefix` other than "ckpt" (the watchdog's "halt") is invisible to
        _try_restore/_gc: quarantined, kept, never auto-resumed."""
        t0 = monotonic_s()   # before the join: a checkpoint interval shorter
        #                      than the write time stalls the training thread
        #                      HERE, and the histogram must see that stall
        self._writer.join()
        self._surface_writer_error()
        it = self.state["iteration"]
        final = os.path.join(self.ckpt.directory, f"{prefix}-{it:09d}")
        if os.path.isdir(final):
            return final  # this iteration is already durably checkpointed
        with get_tracer().span("checkpoint", iteration=it,
                               mode=("async" if self.ckpt.async_write
                                     else "sync")):
            if self.ckpt.format == "sharded":
                job = self._sharded_job(final, it)
            else:
                job = self._snapshot_zip_job(final, it)
            if self.ckpt.async_write:
                self._writer.submit(job)
            else:
                try:
                    job()
                except BaseException as e:
                    if not self._absorb_write_error(e):
                        raise
        get_registry().histogram(
            "ckpt_blocking_ms",
            "Wall ms the training thread spends inside checkpoint()"
        ).observe((monotonic_s() - t0) * 1000.0)
        return final

    def drain_checkpoints(self, raise_errors=True):
        """Join the in-flight background write (if any) and surface its
        error exactly once. fit() calls this at fit-end; drivers shutting a
        run down (or a preemption handler with grace seconds) call it so
        the last submitted checkpoint is durably on disk before exit.

        `raise_errors=False` still COUNTS and logs a parked writer error
        (the absorb path) — it only suppresses the raise, for callers about
        to propagate a more important exception."""
        self._writer.join()
        if raise_errors:
            self._surface_writer_error()
        else:
            err = self._writer.claim_error()
            if err is not None:
                self._absorb_write_error(err)

    def _surface_writer_error(self):
        err = self._writer.claim_error()
        if err is None:
            return
        if not self._absorb_write_error(err):
            raise err

    def _absorb_write_error(self, err):
        """Count+log a checkpoint write failure; True when it is absorbable
        (disk full -> checkpoint debt, training continues), False when the
        caller must re-raise."""
        from ..telemetry.logging import get_logger
        disk_full = _is_disk_full(err)
        reason = "enospc" if disk_full else type(err).__name__
        get_registry().counter(
            "ckpt_write_failures_total",
            "Checkpoint writes that failed before publish").inc(
                1, reason=reason)
        log = get_logger()
        (log.warning if disk_full else log.error)(
            "checkpoint_write_failed", reason=reason,
            error=f"{type(err).__name__}: {err}",
            iteration=self.state["iteration"])
        if disk_full:
            self._ckpt_debt = {"write_failed": reason,
                               "iteration": self.state["iteration"]}
            return True
        return False

    # -- write jobs (run on the writer thread on the async path) -------------
    def _snapshot_zip_job(self, final, it):
        """BLOCKING phase: capture training state + ONE jax.device_get of
        params/opt-state/rng to host numpy (canonical ZeRO layout first, so
        the zip stays topology-independent). Returns the closure that
        serializes, writes through the util.fs seam, manifests, verifies,
        and durably publishes — safe to run concurrently with training.
        The zip is host-gathered, so in a multi-process job process 0
        alone writes and publishes (non-zero processes would race the
        shared tmp dir and the os.replace)."""
        import jax
        if jax.process_index() != 0:
            return lambda: None
        net = self._net()
        st = dict(self.state)
        # wrapper-ness persists so a restore only pays a factory build
        # (and adopt) when the checkpointed run actually used one; plain
        # networks restore without ever constructing a throwaway model
        st["wrapper"] = self.model is not self._net()
        opt_state = net.opt_state
        zero = getattr(net, "_zero", None)
        if zero is not None and opt_state is not None:
            opt_state = zero.to_canonical(opt_state, net.params)
        snap = jax.device_get({"params": net.params, "states": net.states,
                               "opt_state": opt_state,
                               "rng": getattr(net, "_rng", None)})
        st["rng"] = (None if snap["rng"] is None
                     else np.asarray(snap["rng"]).tolist())
        proxy = _ModelSnapshot(conf=net.conf, model_class=type(net).__name__,
                               params=snap["params"], states=snap["states"],
                               opt_state=snap["opt_state"])

        def job():
            t0 = monotonic_s()
            with get_tracer().span("ckpt_write", iteration=it):
                tmp = os.path.join(self.ckpt.directory, f"tmp-{it:09d}")
                os.makedirs(tmp, exist_ok=True)
                try:
                    buf = io.BytesIO()
                    ModelSerializer.write_model(proxy, buf)
                    files = {}
                    for name, data in ((self.MODEL_FILE, buf.getvalue()),
                                       (self.STATE_FILE,
                                        json.dumps(st).encode())):
                        fs.write_bytes(os.path.join(tmp, name), data)
                        files[name] = (fs.sha256_bytes(data), len(data))
                    self._manifest_and_publish(tmp, final, it, files=files,
                                               format="zip")
                except BaseException:
                    shutil.rmtree(tmp, ignore_errors=True)
                    raise
            self._published(final, t0)

        return job

    def _sharded_job(self, final, it):
        """Sharded (orbax) writes stay on the calling thread: orbax streams
        each process's device shards itself, which is the format's whole
        point — a host-gathered snapshot would defeat it. Manifest digests
        come from read-back (orbax owns the files), which still catches
        later bit rot at restore time."""
        # deterministic tmp name so multi-process jobs agree on the orbax
        # write path; process 0 alone publishes/GCs below
        def job():
            import jax
            t0 = monotonic_s()
            tmp = os.path.join(self.ckpt.directory, f"tmp-{it:09d}")
            os.makedirs(tmp, exist_ok=True)
            try:
                from ..util.sharded_checkpoint import save_sharded
                net = self._net()
                save_sharded(net, os.path.join(tmp, self.SHARDED_DIR))
                if jax.process_index() != 0:
                    return  # process 0 publishes the checkpoint dir
                st = dict(self.state)
                st["wrapper"] = self.model is not self._net()
                rng = getattr(net, "_rng", None)
                st["rng"] = None if rng is None else np.asarray(rng).tolist()
                fs.write_bytes(os.path.join(tmp, self.STATE_FILE),
                               json.dumps(st).encode())
                self._manifest_and_publish(tmp, final, it, files=None,
                                           format="sharded")
            except BaseException:
                shutil.rmtree(tmp, ignore_errors=True)
                raise
            self._published(final, t0)

        return job

    def _manifest_and_publish(self, tmp, final, it, files, format):
        """Manifest LAST, verify completeness, durable publish. The verify
        step re-reads the manifest from disk and checks every listed file
        EXISTS — what a crash-free writer can honestly check. Sizes and
        hashes are deliberately NOT re-checked here: a write-time read-back
        (or stat) is served from the page cache, which reports the bytes
        the writer just handed the kernel — the bytes a power loss will
        never persist. Torn writes and bit rot are real only on the
        platters, so content verification belongs to the restore path
        (and to tools/ckpt_doctor.py), where it can actually see them."""
        import jax
        fs.write_manifest(
            tmp, files=files, step=it, wall_time_s=now_s(), format=format,
            topology={"process_index": jax.process_index(),
                      "process_count": jax.process_count(),
                      "device_count": jax.device_count()})
        doc = fs.read_manifest(tmp)
        missing = [rel for rel in sorted(doc.get("files", {}))
                   if not os.path.isfile(os.path.join(tmp, rel))]
        if missing:
            raise IOError(f"checkpoint incomplete before publish: "
                          f"missing {missing}")
        fs.atomic_publish_dir(tmp, final)

    def _published(self, final, t0):
        get_registry().histogram(
            "ckpt_write_ms",
            "Wall ms serializing+publishing one checkpoint (writer side)"
        ).observe((monotonic_s() - t0) * 1000.0)
        name = os.path.basename(final)
        if name.startswith("ckpt-"):
            self._last_good = name
            self._ckpt_debt = None     # a fresh verified publish clears debt
        self._gc()

    def _gc(self):
        dirs = self._ckpt_dirs()
        # keep_last=0 retains everything (slicing parity with the original
        # dirs[:-0] -> delete-nothing semantics)
        keep = set(dirs[-self.ckpt.keep_last:] if self.ckpt.keep_last
                   else dirs)
        # the last checkpoint KNOWN to verify survives any retention window:
        # if everything newer turns out corrupt, it is the restore of record
        if self._last_good is not None:
            keep.add(self._last_good)
        K = self.ckpt.keep_every
        if K:
            keep.update(n for n in dirs if int(n.split("-")[1]) % K == 0)
        for name in dirs:
            if name not in keep:
                shutil.rmtree(os.path.join(self.ckpt.directory, name),
                              ignore_errors=True)
        # orphaned tmp-* dirs are half-written checkpoints from a process
        # that was preempted mid-write; this (single-writer) driver owns the
        # directory, so any tmp-* present outside checkpoint() is garbage
        self._gc_orphans()

    # ------------------------------------------------------------ restore
    def _try_restore(self):
        """Walk `ckpt-*` newest -> oldest: verify the manifest (hashes
        included), then load; any failure quarantines the dir under
        `corrupt-<name>` and falls back to the next. Restoring anything
        but the newest counts a fallback and leaves the probe degraded
        until the next good publish."""
        self._gc_orphans()
        dirs = self._ckpt_dirs()
        newest = dirs[-1] if dirs else None
        for fell_back, name in enumerate(reversed(dirs)):
            path = os.path.join(self.ckpt.directory, name)
            ok, errors = fs.verify_manifest(path)
            if not ok:
                self._quarantine(name, errors)
                continue
            try:
                self._restore_from(path)
            except Exception as e:
                self._quarantine(name, [f"restore raised "
                                        f"{type(e).__name__}: {e}"])
                continue
            self._last_good = name
            if fell_back:
                get_registry().counter(
                    "ckpt_restore_fallbacks_total",
                    "Restores that fell back past corrupt checkpoints"
                ).inc(1)
                self._ckpt_debt = {"restore_fallback": True,
                                   "quarantined": fell_back,
                                   "newest_was": newest, "restored": name}
                from ..telemetry.logging import get_logger
                get_logger().warning(
                    "checkpoint_restore_fell_back", restored=name,
                    newest_was=newest, quarantined=fell_back)
            return True
        self.model = self._factory()
        if getattr(self._net(), "params", None) is None:
            self._net().init()
        return False

    def _restore_from(self, latest):
        """Load one verified checkpoint dir; only commits to self.state /
        self.model when the whole load succeeded, so a fallback after a
        partial failure never leaks half-restored state."""
        sharded_dir = os.path.join(latest, self.SHARDED_DIR)
        with open(os.path.join(latest, self.STATE_FILE)) as f:
            state = json.load(f)
        if os.path.isdir(sharded_dir):
            from ..util.sharded_checkpoint import restore_sharded
            restored = restore_sharded(sharded_dir)
        else:
            restored = ModelSerializer.restore(
                os.path.join(latest, self.MODEL_FILE))
        model = restored
        if state.get("wrapper"):
            # the checkpointed run drove a trainer wrapper (ShardedTrainer):
            # rebuild it via the factory — its mesh/ZeRO config reflects
            # THIS process's topology — and adopt the restored network state
            # (canonical updater state re-shards for the current replica
            # count). Plain-network checkpoints never pay this factory build.
            candidate = self._factory()
            if getattr(candidate, "model", None) is not None \
                    and callable(getattr(candidate, "adopt", None)):
                candidate.adopt(restored)
                model = candidate
        self.state = state
        self.model = model
        net = self._net()
        rng = self.state.get("rng")
        if rng is not None:
            import jax.numpy as jnp
            net._rng = jnp.asarray(np.asarray(rng, dtype=np.uint32))
        net.iteration_count = self.state["iteration"]
        net.epoch_count = self.state["epoch"]

    def _quarantine(self, name, errors):
        """Move a failed checkpoint aside as `corrupt-<name>` — invisible to
        _ckpt_dirs/_gc (same forensics idiom as `halt-*`), recoverable by an
        operator via tools/ckpt_doctor.py."""
        dst = fs.quarantine_dir(self.ckpt.directory, name)
        get_registry().counter(
            "ckpt_verify_failures_total",
            "Checkpoints that failed manifest verification or load").inc(1)
        from ..telemetry.logging import get_logger
        get_logger().error("checkpoint_quarantined", checkpoint=name,
                           quarantined_as=dst, errors=list(errors)[:4])

    @property
    def resumed(self):
        return self._restored

    # ------------------------------------------------------------ liveness
    def _register_probe(self):
        """(Re-)register the trainer's health probe + heartbeat state. Runs
        at construction — AFTER _try_restore, so the restore path (which
        rebuilds self.model via adopt and previously surfaced nowhere)
        re-registers too and a resumed run shows up on /healthz //fleet
        immediately, at its restored iteration. A restore primes the
        heartbeat so the probe reports a live (not never-beaten) trainer."""
        if self.monitor is None:
            return
        if self._restored:
            self._touch_beat()
        if self.health_key is not None:
            self.monitor.unregister(self.health_key)
        self.health_key = self.monitor.register_unique("trainer", self._probe)
        return self.health_key

    def unregister_probe(self):
        """Withdraw the liveness probe (a driver shutting the run down)."""
        if self.monitor is not None and self.health_key is not None:
            self.monitor.unregister(self.health_key)
            self.health_key = None

    def _touch_beat(self):
        self._last_beat = monotonic_s()

    def _probe_detail(self):
        """Extra probe fields; subclasses (ElasticTrainer) extend."""
        return {}

    def _probe(self):
        halted = self.health is not None and \
            getattr(self.health, "should_halt", False)
        # one read: the writer thread clears the debt on a good publish,
        # and the probe runs on the health monitor's thread
        debt = self._ckpt_debt
        status = "unhealthy" if halted else \
            ("degraded" if debt else "healthy")
        beat_age = None if self._last_beat is None \
            else monotonic_s() - self._last_beat
        detail = {"iteration": self.state["iteration"],
                  "epoch": self.state["epoch"],
                  "resumed": self._restored,
                  "last_step_age_s": beat_age,
                  **self._probe_detail()}
        if debt:
            detail["checkpoint_debt"] = dict(debt)
        if halted:
            detail["reason"] = getattr(self.health, "trip_reason", "halted")
        return status, detail

    # ------------------------------------------------------------ training
    def _before_batch(self):
        """Hook run between batches (before each fit_batch). The elastic
        policy (elastic.ElasticTrainer) overrides this with its membership
        poll/re-shard; the base trainer does nothing — keeping ONE fit
        loop so resume/checkpoint/halt fixes apply to every policy."""

    def fit(self, iterator, epochs=1):
        """Train with checkpoints every `frequency` iterations; on resume,
        fast-forwards past the batches the dead process already consumed.
        With a health listener attached, a fatal watchdog condition
        checkpoints once more and raises TrainingHalted. Returns only
        after the final checkpoint is durably published (drains the
        background writer, surfacing its errors per the idiom above)."""
        from ..datasets.iterator.base import as_iterator
        it = as_iterator(iterator)
        listeners = getattr(self._net(), "listeners", None)
        if self.health is not None and listeners is not None \
                and self.health not in listeners:
            listeners.append(self.health)
        freq = self.ckpt.frequency
        start_epoch = self.state["epoch"]
        for epoch in range(start_epoch, epochs):
            it.reset()
            skip = self.state["batch"] if epoch == self.state["epoch"] else 0
            b = 0
            for ds in it:
                if b < skip:
                    b += 1
                    continue
                self._before_batch()
                self.model.fit_batch(ds)
                self._touch_beat()
                b += 1
                self.state.update(epoch=epoch, batch=b,
                                  iteration=self.state["iteration"] + 1)
                self._halt_if_unhealthy()
                if freq and self.state["iteration"] % freq == 0:
                    self.checkpoint()
            self.state.update(epoch=epoch + 1, batch=0)
        self.checkpoint()
        self.drain_checkpoints()
        return self.model

    def _halt_if_unhealthy(self):
        if self.health is None or not self.health.should_halt:
            return
        from ..optimize.listeners.health import TrainingHalted
        # the fatal update is already applied to the params, so this state
        # is forensics, not a resume point: quarantine it under halt-* and
        # leave the ckpt-* chain ending at the last pre-blow-up checkpoint.
        # Drain without raising: TrainingHalted is the primary signal, and a
        # failed halt-write is already counted/logged by the absorb path.
        path = self.checkpoint(prefix="halt")
        self.drain_checkpoints(raise_errors=False)
        raise TrainingHalted(self.health.trip_reason,
                             self.state["iteration"], checkpoint_path=path)
