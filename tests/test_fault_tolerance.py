"""Preemption / checkpoint-restart tests (SURVEY.md §5 must-add: TPUs are
preemptible; the driver must survive a killed process and continue the loss
curve from the last checkpoint, mid-epoch included)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from deeplearning4j_tpu import (NeuralNetConfiguration, InputType, DenseLayer,
                                OutputLayer, MultiLayerNetwork, DataSet,
                                ListDataSetIterator, Sgd)
from deeplearning4j_tpu.train import CheckpointConfig, FaultTolerantTrainer


def _factory(seed=11):
    def make():
        conf = (NeuralNetConfiguration.builder()
                .seed(seed).updater(Sgd(0.1)).list()
                .layer(DenseLayer(n_out=16, activation="tanh"))
                .layer(OutputLayer(n_out=3, activation="softmax", loss="MCXENT"))
                .input_type(InputType.feed_forward(8))
                .build())
        return MultiLayerNetwork(conf)
    return make


def _data(n=80, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    w = rng.normal(size=(8, 3))
    Y = np.eye(3, dtype=np.float32)[np.argmax(X @ w, axis=1)]
    return X, Y


def test_checkpoint_resume_in_process(tmp_path):
    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)  # 10 batches/epoch

    # uninterrupted reference run
    ref = FaultTolerantTrainer(_factory(), CheckpointConfig(tmp_path / "ref",
                                                            frequency=0))
    ref.fit(it, epochs=2)

    # interrupted run: train only epoch 1 (10 iters) with freq 7 -> last
    # checkpoint at iteration 7; then build a NEW trainer from the same dir
    # (as a restarted process would) and finish
    ck = CheckpointConfig(tmp_path / "ckpt", frequency=7)
    t1 = FaultTolerantTrainer(_factory(), ck)
    assert not t1.resumed
    t1.fit(it, epochs=1)  # checkpoints at 7, 10(final)

    t2 = FaultTolerantTrainer(_factory(), ck)
    assert t2.resumed
    assert t2.state["iteration"] == 10 and t2.state["epoch"] == 1
    t2.fit(it, epochs=2)
    np.testing.assert_allclose(ref.model.get_flat_params(),
                               t2.model.get_flat_params(), rtol=1e-6, atol=1e-7)


_KILLED_SCRIPT = textwrap.dedent("""
    import os, sys
    import numpy as np
    sys.path.insert(0, {repo!r})
    sys.path.insert(0, {testdir!r})
    import jax
    # numerics must match the pytest parent (conftest.py): CPU + x64 enabled,
    # else replayed steps drift by ~1e-4 and the bitwise comparison fails
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    from test_fault_tolerance import _factory, _data
    from deeplearning4j_tpu import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.train import CheckpointConfig, FaultTolerantTrainer

    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    trainer = FaultTolerantTrainer(_factory(), CheckpointConfig({ckdir!r},
                                                                frequency=5))

    class Killer:
        def iteration_done(self, model, iteration):
            if trainer.state["iteration"] >= 12:
                # the async writer may still be publishing ckpt-10: join it
                # (the preemption-grace flush a real SIGTERM handler does)
                # so the newest surviving checkpoint is deterministically 10
                trainer.drain_checkpoints(raise_errors=False)
                os._exit(17)   # hard preemption: no cleanup, no atexit
        def on_epoch_start(self, model):
            pass
        def on_epoch_end(self, model):
            pass
        def record_batch_size(self, b):
            pass

    trainer.model.set_listeners(Killer())
    trainer.fit(it, epochs=2)
    os._exit(0)  # unreachable if the kill fired
""")


def test_preemption_kill_and_resume_matches_uninterrupted(tmp_path):
    """Kill the training process mid-epoch (SIGKILL-style os._exit), resume in
    a fresh trainer, and require the final params to MATCH an uninterrupted
    run bit-for-bit in replayed batch order (checkpointed rng + iterator
    position make the resume deterministic)."""
    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    ref = FaultTolerantTrainer(_factory(), CheckpointConfig(tmp_path / "ref",
                                                            frequency=0))
    ref.fit(it, epochs=2)

    ckdir = str(tmp_path / "ckpt")
    script = _KILLED_SCRIPT.format(repo=os.getcwd(),
                                   testdir=os.path.dirname(__file__),
                                   ckdir=ckdir)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, timeout=300)
    assert proc.returncode == 17, proc.stderr.decode()[-2000:]

    t = FaultTolerantTrainer(_factory(), CheckpointConfig(ckdir, frequency=5))
    assert t.resumed
    # the process died at iteration 12; the newest surviving checkpoint is 10
    assert t.state["iteration"] == 10
    t.fit(it, epochs=2)
    np.testing.assert_allclose(ref.model.get_flat_params(),
                               t.model.get_flat_params(), rtol=1e-6, atol=1e-7)


def test_checkpoint_gc_keeps_last(tmp_path):
    X, Y = _data(n=40)
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)  # 5 batches/epoch
    ck = CheckpointConfig(tmp_path / "ck", frequency=2, keep_last=2)
    t = FaultTolerantTrainer(_factory(), ck)
    t.fit(it, epochs=2)  # iters 1..10, ckpts at 2,4,6,8,10 + final
    names = sorted(os.listdir(ck.directory))
    assert len([n for n in names if n.startswith("ckpt-")]) <= 2


def test_checkpoint_resume_sharded_format(tmp_path):
    """FaultTolerantTrainer with the orbax sharded tensor-store format
    (CheckpointConfig(format='sharded')) resumes identically to zip."""
    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    ck = CheckpointConfig(tmp_path / "sc", frequency=7, format="sharded")
    t1 = FaultTolerantTrainer(_factory(), ck)
    t1.fit(it, epochs=1)
    t2 = FaultTolerantTrainer(_factory(), ck)
    assert t2.resumed and t2.state["iteration"] == 10
    np.testing.assert_allclose(t1.model.get_flat_params(),
                               t2.model.get_flat_params(), rtol=0, atol=0)
    t2.fit(it, epochs=2)

    ref = FaultTolerantTrainer(_factory(), CheckpointConfig(tmp_path / "rf",
                                                            frequency=0))
    ref.fit(it, epochs=2)
    np.testing.assert_allclose(ref.model.get_flat_params(),
                               t2.model.get_flat_params(), rtol=1e-6, atol=1e-7)


def test_trainer_health_probe_survives_restore(tmp_path):
    """Elastic-fleet satellite regression: the trainer registers a liveness
    probe into the health monitor, and the RESTORE path re-registers it
    with primed heartbeat state — a resumed run is immediately visible on
    /healthz (and so /fleet/healthz), at its restored iteration, instead
    of silently losing its membership entry."""
    from deeplearning4j_tpu.telemetry.health import HealthMonitor

    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    ck = CheckpointConfig(tmp_path / "ck", frequency=7)

    m1 = HealthMonitor()
    t1 = FaultTolerantTrainer(_factory(), ck, monitor=m1)
    assert t1.health_key in m1.components()
    comp = m1.check()["components"][t1.health_key]
    assert comp["status"] == "healthy" and comp["iteration"] == 0
    assert comp["resumed"] is False and comp["last_step_age_s"] is None
    t1.fit(it, epochs=1)
    comp = m1.check()["components"][t1.health_key]
    assert comp["iteration"] == 10 and comp["last_step_age_s"] is not None

    # a restarted process: fresh monitor, fresh trainer, same directory —
    # the probe must be re-registered and report the restored state as a
    # LIVE (heartbeat-primed) member
    m2 = HealthMonitor()
    t2 = FaultTolerantTrainer(_factory(), ck, monitor=m2)
    assert t2.resumed
    comp = m2.check()["components"][t2.health_key]
    assert comp["status"] == "healthy"
    assert comp["iteration"] == 10 and comp["resumed"] is True
    assert comp["last_step_age_s"] is not None

    # probe withdrawal for drivers that shut the run down
    t2.unregister_probe()
    assert t2.health_key is None and m2.components() == []
    # monitor=False opts out entirely
    t3 = FaultTolerantTrainer(_factory(), ck, monitor=False)
    assert t3.monitor is None and t3.health_key is None


def test_async_and_sync_checkpoints_bit_identical(tmp_path):
    """The async snapshot-then-write path must serialize EXACTLY what the
    synchronous path does: same training run, async_write on vs off, the
    model zip and training state BYTE-identical on disk (write_model emits
    deterministic zip entries — fixed DOS timestamps — precisely so this
    holds), manifests recording identical digests."""
    X, Y = _data()
    dirs = {}
    for mode, async_write in (("async", True), ("sync", False)):
        it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
        ck = CheckpointConfig(tmp_path / mode, frequency=7,
                              async_write=async_write)
        assert ck.async_write is async_write
        t = FaultTolerantTrainer(_factory(), ck)
        t.fit(it, epochs=1)
        dirs[mode] = ck.directory
    a = os.path.join(dirs["async"], "ckpt-000000010")
    s = os.path.join(dirs["sync"], "ckpt-000000010")
    for name in ("model.zip", FaultTolerantTrainer.STATE_FILE):
        with open(os.path.join(a, name), "rb") as f1, \
                open(os.path.join(s, name), "rb") as f2:
            assert f1.read() == f2.read(), name
    from deeplearning4j_tpu.util import fs
    ma, ms = fs.read_manifest(a), fs.read_manifest(s)
    assert ma["files"] == ms["files"]
    assert ma["step"] == ms["step"] == 10


def test_keep_every_anchor_checkpoints_survive_gc(tmp_path):
    """CheckpointConfig(keep_every=K): iteration-multiple-of-K checkpoints
    are anchors — kept outside the keep_last window."""
    X, Y = _data()                                   # 10 batches/epoch
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    ck = CheckpointConfig(tmp_path / "ck", frequency=2, keep_last=1,
                          keep_every=4)
    t = FaultTolerantTrainer(_factory(), ck)
    t.fit(it, epochs=1)  # ckpts at 2,4,6,8,10; anchors 4,8; last 10
    names = sorted(n for n in os.listdir(ck.directory)
                   if n.startswith("ckpt-"))
    assert names == ["ckpt-000000004", "ckpt-000000008", "ckpt-000000010"]
    for n in names:
        from deeplearning4j_tpu.util import fs
        ok, errors = fs.verify_manifest(os.path.join(ck.directory, n))
        assert ok, (n, errors)


def test_gc_never_deletes_last_verified_good(tmp_path):
    """Even when the last verified-good checkpoint falls outside keep_last,
    _gc retains it — if everything newer later turns out corrupt, it is
    the restore of record."""
    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    import shutil

    ck = CheckpointConfig(tmp_path / "ck", frequency=5, keep_last=1)
    t = FaultTolerantTrainer(_factory(), ck)
    t.fit(it, epochs=1)                       # keep_last=1 -> only ckpt-10
    assert [n for n in sorted(os.listdir(ck.directory))
            if n.startswith("ckpt-")] == ["ckpt-000000010"]
    # fabricate newer checkpoints (the restore-fallback window: newer dirs
    # exist on disk but the VERIFIED one is older), then GC with window 1
    for it_n in (20, 25):
        shutil.copytree(os.path.join(ck.directory, "ckpt-000000010"),
                        os.path.join(ck.directory, f"ckpt-{it_n:09d}"))
    t._last_good = "ckpt-000000010"
    t._gc()
    names = sorted(n for n in os.listdir(ck.directory)
                   if n.startswith("ckpt-"))
    assert names == ["ckpt-000000010", "ckpt-000000025"]


def test_restore_falls_back_past_manually_corrupted_chain(tmp_path):
    """Both newest checkpoints corrupted on disk (no chaos plan — raw byte
    damage): restore quarantines BOTH, restores the third-newest, and the
    fallback counter/probe reflect it."""
    from deeplearning4j_tpu.telemetry.health import HealthMonitor
    from deeplearning4j_tpu.telemetry.registry import get_registry

    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    ck = CheckpointConfig(tmp_path / "ck", frequency=3, keep_last=4)
    t1 = FaultTolerantTrainer(_factory(), ck)
    t1.fit(it, epochs=1)                           # ckpts 3, 6, 9, 10
    for n in ("ckpt-000000009", "ckpt-000000010"):
        p = os.path.join(ck.directory, n, "model.zip")
        with open(p, "r+b") as f:
            f.seek(100)
            b = f.read(1)
            f.seek(100)
            f.write(bytes([b[0] ^ 0xFF]))
    v0 = get_registry().counter("ckpt_verify_failures_total").get()
    mon = HealthMonitor()
    t2 = FaultTolerantTrainer(_factory(), ck, monitor=mon)
    assert t2.resumed and t2.state["iteration"] == 6
    assert get_registry().counter("ckpt_verify_failures_total").get() \
        == v0 + 2
    quarantined = sorted(n for n in os.listdir(ck.directory)
                         if n.startswith("corrupt-"))
    assert quarantined == ["corrupt-ckpt-000000009",
                           "corrupt-ckpt-000000010"]
    comp = mon.check()["components"][t2.health_key]
    assert comp["status"] == "degraded"
    assert comp["checkpoint_debt"]["quarantined"] == 2
    t2.unregister_probe()


def test_legacy_checkpoint_without_manifest_is_quarantined(tmp_path):
    """A checkpoint with no MANIFEST.json is by definition incomplete:
    quarantined on restore, with the fresh-model path taken when nothing
    verifies — and ckpt_doctor's `manifest` command can re-bless it."""
    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    ck = CheckpointConfig(tmp_path / "ck", frequency=0)
    t1 = FaultTolerantTrainer(_factory(), ck)
    t1.fit(it, epochs=1)                           # final ckpt-10 only
    man = os.path.join(ck.directory, "ckpt-000000010", "MANIFEST.json")
    os.unlink(man)
    t2 = FaultTolerantTrainer(_factory(), ck)
    assert not t2.resumed and t2.state["iteration"] == 0
    corrupt = [n for n in os.listdir(ck.directory)
               if n.startswith("corrupt-")]
    assert corrupt == ["corrupt-ckpt-000000010"]
    # operator re-blesses the quarantined dir and moves it back
    from tools import ckpt_doctor
    src = os.path.join(ck.directory, corrupt[0])
    assert ckpt_doctor.cmd_manifest(src) == 0
    os.rename(src, os.path.join(ck.directory, "ckpt-000000010"))
    t3 = FaultTolerantTrainer(_factory(), ck)
    assert t3.resumed and t3.state["iteration"] == 10


def test_manifest_shape_and_doctor_cli(tmp_path, capsys):
    """MANIFEST.json carries per-file sha256+bytes, step, wall time,
    topology, format; ckpt_doctor verify/list/quarantine drive the same
    primitives from the CLI."""
    from deeplearning4j_tpu.util import fs
    from tools import ckpt_doctor

    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    ck = CheckpointConfig(tmp_path / "ck", frequency=7)
    FaultTolerantTrainer(_factory(), ck).fit(it, epochs=1)
    man = fs.read_manifest(os.path.join(ck.directory, "ckpt-000000010"))
    assert man["step"] == 10 and man["format"] == "zip"
    assert man["version"] == 1 and man["wall_time_s"] > 0
    assert set(man["files"]) == {"model.zip", "train_state.json"}
    for entry in man["files"].values():
        assert len(entry["sha256"]) == 64 and entry["bytes"] > 0
    assert man["topology"]["process_count"] >= 1
    assert man["topology"]["device_count"] >= 1

    assert ckpt_doctor.main(["verify", ck.directory]) == 0
    assert ckpt_doctor.main(["list", ck.directory]) == 0
    # flip one byte -> verify fails with a sha256 error, exit 1
    p = os.path.join(ck.directory, "ckpt-000000010", "model.zip")
    with open(p, "r+b") as f:
        f.seek(50)
        b = f.read(1)
        f.seek(50)
        f.write(bytes([b[0] ^ 0x01]))
    assert ckpt_doctor.main(["verify", ck.directory]) == 1
    out = capsys.readouterr().out
    assert "sha256 mismatch" in out
    assert ckpt_doctor.main(
        ["quarantine", ck.directory, "ckpt-000000010"]) == 0
    assert os.path.isdir(
        os.path.join(ck.directory, "corrupt-ckpt-000000010"))
    assert ckpt_doctor.main(["verify", ck.directory]) == 0  # 12 remains ok


def test_smoke_ckpt_tool(tmp_path):
    """The full durable-checkpoint arc (tools/smoke_ckpt.py): train with
    async checkpoints under a seeded disk-fault plan (slow_disk advancing a
    ManualClock — zero real sleeps), torn_write AND bitflip on the newest
    checkpoint each followed by restore-with-fallback + final-param parity
    vs an uninterrupted run, and an ENOSPC mid-checkpoint that leaves
    training running with the prior published checkpoint intact."""
    import tools.smoke_ckpt as smoke
    out = smoke.run(str(tmp_path))
    assert out["tear_parity"] and out["flip_parity"]
    assert out["tear_fallbacks"] == 1 and out["flip_fallbacks"] == 1
    assert out["tear_verify_failures"] == 1
    assert out["flip_verify_failures"] == 1
    assert out["enospc_write_failures"] == 1
    assert out["enospc_survivors"] == ["ckpt-000000005", "ckpt-000000012"]
    assert out["ckpt_write_ms_count"] > 0
    assert out["tear_clock_advance_s"] >= 0.15  # simulated, not slept


def test_trainer_probe_visible_through_fleet_healthz(tmp_path):
    """The probe lands on the PROCESS monitor by default, which UIServer
    /healthz aggregates and FleetCollector scrapes — a training run shows
    up on /fleet/healthz with its iteration/heartbeat detail.

    The process monitor keeps every probe any earlier test of this worker
    left registered (a tripped trainer, a pipeline with a dead worker), and
    the host's status is the worst of them — so which files shared the
    worker decided this test. It gets a process monitor of its own."""
    from deeplearning4j_tpu.telemetry.fleet import FleetServer
    from deeplearning4j_tpu.telemetry.health import (HealthMonitor,
                                                     get_monitor, set_monitor)
    from deeplearning4j_tpu.ui.server import UIServer
    from deeplearning4j_tpu.util.http import get_json

    X, Y = _data(n=40)
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    shared = get_monitor()
    set_monitor(HealthMonitor())
    trainer = FaultTolerantTrainer(_factory(),
                                   CheckpointConfig(tmp_path / "ck",
                                                    frequency=0))
    try:
        trainer.fit(it, epochs=1)
        ui = UIServer(port=0).start()
        fleet = FleetServer([ui.url], names=["trainer-host"],
                            interval_s=0.0).start()
        try:
            report = get_json(fleet.url + "/fleet/healthz", timeout=30)
            host = report["components"]["trainer-host"]
            assert host["status"] == "healthy"
            comps = host["components"]
            assert trainer.health_key in comps
            assert comps[trainer.health_key]["iteration"] == 5
        finally:
            fleet.stop()
            ui.stop()
    finally:
        trainer.unregister_probe()
        set_monitor(shared)
