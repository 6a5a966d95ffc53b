"""Elastic fleet subsystem (deeplearning4j_tpu/elastic/): preemption-
tolerant training, the serving autoscaler, the ReplicaLauncher SPI, and
the open-loop load generator.

The acceptance scenarios from the elastic ISSUE run LIVE here:
- a chaos FaultPlan kills a training replica mid-run; the run re-shards
  ZeRO state to the survivors and finishes with final-param parity vs an
  uninterrupted run (zero checkpoint-and-halt restarts);
- the ManualClock autoscale smoke (tools/smoke_elastic.py): ramp ->
  scale 1->3 -> preemption -> zero client 5xx -> drain back to 1, every
  transition visible on /fleet/* and the trace-correlated logs.
"""
import json
import tempfile

import numpy as np
import pytest
import jax

from deeplearning4j_tpu import (NeuralNetConfiguration, InputType, DenseLayer,
                                OutputLayer, MultiLayerNetwork, DataSet,
                                ListDataSetIterator, Sgd)
from deeplearning4j_tpu.elastic import (AutoscaleController, AutoscalePolicy,
                                        ElasticTrainer, InProcessLauncher,
                                        MembershipView)
from deeplearning4j_tpu.parallel.sharding import ShardedTrainer, make_mesh
from deeplearning4j_tpu.resilience import FaultPlan, FaultRule
from deeplearning4j_tpu.telemetry.health import HealthMonitor
from deeplearning4j_tpu.train import CheckpointConfig
from deeplearning4j_tpu.util.time_source import (ManualClock,
                                                 TimeSourceProvider)


@pytest.fixture
def clock():
    c = ManualClock(start_s=1000.0)
    TimeSourceProvider.set_instance(c)
    yield c
    TimeSourceProvider.reset()


def _factory(seed=11):
    def make():
        conf = (NeuralNetConfiguration.builder()
                .seed(seed).updater(Sgd(0.1)).list()
                .layer(DenseLayer(n_out=16, activation="tanh"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="MCXENT"))
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


# ---------------------------------------------------------------- membership

def test_membership_heartbeat_ttl_and_kill_revive(clock):
    view = MembershipView(["w0", "w1", "w2"], ttl_s=10.0)
    assert view.alive() == ["w0", "w1", "w2"]
    v0 = view.version
    # silence past the ttl = dead, no explicit signal needed
    clock.advance(5.0)
    view.heartbeat("w0")
    view.heartbeat("w1")
    clock.advance(6.0)
    assert view.alive() == ["w0", "w1"]
    # explicit preemption beats a fresh heartbeat
    assert view.kill("w1") is True
    assert view.kill("w1") is False          # idempotent
    view.heartbeat("w1")                     # straggler beat is ignored
    assert view.alive() == ["w0"]
    assert view.version > v0
    # revive brings it back with a fresh beat
    assert view.revive("w1") is True
    assert view.revive("w1") is False        # already alive + fresh
    assert view.alive() == ["w0", "w1"]
    st = view.status()
    assert st["members"]["w2"]["alive"] is False
    assert st["members"]["w1"]["killed"] is False
    with pytest.raises(KeyError):
        view.revive("nope")


def test_preempt_rule_round_trip_and_poll(clock):
    plan = FaultPlan([FaultRule("preempt", target="w2", at_step=5,
                                cooldown_s=30.0, name="p")])
    # JSON round-trip preserves the preempt fields
    plan = FaultPlan.from_json(json.loads(json.dumps(plan.to_json())))
    [d] = plan.to_json()
    assert d == {"kind": "preempt", "name": "p", "target": "w2",
                 "at_step": 5, "cooldown_s": 30.0}
    assert plan.poll_preemptions(4) == []
    [kill] = plan.poll_preemptions(5)
    assert kill == {"action": "kill", "target": "w2", "rule": "p",
                    "step": 5}
    assert plan.poll_preemptions(6) == []    # fires exactly once
    clock.advance(29.0)
    assert plan.poll_preemptions(7) == []    # cooldown not elapsed
    clock.advance(1.0)
    [rev] = plan.poll_preemptions(8)
    assert rev["action"] == "revive" and rev["target"] == "w2"
    assert plan.poll_preemptions(9) == []    # revive fires exactly once
    assert plan.injected() == {"p": 1}
    # preempt rules never touch the HTTP interceptor
    assert plan.intercept("POST", "http://x/predict", 1.0) is None


def test_preempt_rule_validation():
    with pytest.raises(ValueError):
        FaultRule("preempt", name="no-target", at_step=3)
    with pytest.raises(ValueError):
        FaultRule("preempt", target="w0", name="no-step")


# ---------------------------------------------------------- elastic training

def test_chaos_preemption_reshards_and_matches_uninterrupted(tmp_path):
    """THE acceptance scenario: a FaultPlan preempt rule kills replica w3
    at step 10 of a 4-replica ZeRO run; training re-shards to the three
    survivors in-process and finishes with final params matching an
    uninterrupted 4-replica run (f32 tolerance) — momentum intact, zero
    checkpoint-and-halt restarts."""
    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)

    ref_net = _factory()()
    ref = ShardedTrainer(ref_net,
                         mesh=make_mesh(n_data=4, devices=jax.devices()[:4]),
                         shard_update=True)
    ref.fit(it, epochs=2)

    plan = FaultPlan([FaultRule("preempt", target="w3", at_step=10,
                                name="kill-w3")])
    monitor = HealthMonitor()
    trainer = ElasticTrainer(_factory(), CheckpointConfig(tmp_path / "ck",
                                                          frequency=0),
                             devices=jax.devices()[:4], plan=plan,
                             monitor=monitor)
    assert not trainer.resumed
    trainer.fit(it, epochs=2)

    assert trainer.reshards == 1
    assert trainer._alive == ["w0", "w1", "w2"]
    assert plan.injected() == {"kill-w3": 1}
    assert [e["action"] for e in trainer.preemption_events] == ["kill"]
    np.testing.assert_allclose(ref_net.get_flat_params(),
                               trainer._net().get_flat_params(),
                               rtol=1e-5, atol=1e-6)
    # zero restarts: nothing ever restored, nothing quarantined
    import os
    assert not trainer.resumed
    assert not any(n.startswith("halt-")
                   for n in os.listdir(tmp_path / "ck"))
    # the run is visible to the health/fleet plane, with elastic detail
    report = monitor.check()
    comp = report["components"][trainer.health_key]
    assert comp["status"] == "healthy"
    assert comp["iteration"] == 20 and comp["replicas"] == 3
    assert comp["membership"]["members"]["w3"]["killed"] is True


def test_elastic_regain_reshards_up_and_training_continues(tmp_path):
    """Replica loss then regain: kill + revive via the membership view
    across epochs — the trainer re-shards down then back up and keeps
    training (momentum carried through both hops)."""
    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    trainer = ElasticTrainer(_factory(), CheckpointConfig(tmp_path / "ck",
                                                          frequency=0),
                             devices=jax.devices()[:4],
                             monitor=HealthMonitor())
    trainer.membership.kill("w2")
    trainer.fit(it, epochs=1)
    assert trainer.reshards == 1 and len(trainer._alive) == 3
    trainer.membership.revive("w2")
    trainer.fit(it, epochs=2)
    assert trainer.reshards == 2 and len(trainer._alive) == 4
    assert trainer.state["iteration"] == 20
    assert np.isfinite(trainer._net().score_value)


def test_elastic_below_min_replicas_checkpoints_and_raises(tmp_path):
    from deeplearning4j_tpu.elastic import ElasticImpossible
    X, Y = _data(n=40)
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    plan = FaultPlan([
        FaultRule("preempt", target="w0", at_step=2, name="k0"),
        FaultRule("preempt", target="w1", at_step=2, name="k1")])
    trainer = ElasticTrainer(_factory(), CheckpointConfig(tmp_path / "ck",
                                                          frequency=0),
                             devices=jax.devices()[:2], plan=plan,
                             min_replicas=2, monitor=HealthMonitor())
    with pytest.raises(ElasticImpossible):
        trainer.fit(it, epochs=1)
    # the final checkpoint landed before the raise: a fresh trainer resumes
    t2 = ElasticTrainer(_factory(), CheckpointConfig(tmp_path / "ck",
                                                     frequency=0),
                        devices=jax.devices()[:2],
                        monitor=HealthMonitor())
    assert t2.resumed and t2.state["iteration"] == 2


def test_elastic_checkpoint_resume_at_new_topology(tmp_path):
    """An ElasticTrainer checkpoint restores into a trainer built for a
    DIFFERENT replica count (the canonical-state re-shard on adopt)."""
    X, Y = _data()
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    ck = CheckpointConfig(tmp_path / "ck", frequency=7)
    t1 = ElasticTrainer(_factory(), ck, devices=jax.devices()[:4],
                        monitor=HealthMonitor())
    t1.fit(it, epochs=1)
    t2 = ElasticTrainer(_factory(), ck, devices=jax.devices()[:2],
                        monitor=HealthMonitor())
    assert t2.resumed and t2.state["iteration"] == 10
    t2.fit(it, epochs=2)
    ref = ElasticTrainer(_factory(), CheckpointConfig(tmp_path / "ref",
                                                      frequency=0),
                         devices=jax.devices()[:4],
                         monitor=HealthMonitor())
    ref.fit(it, epochs=2)
    np.testing.assert_allclose(ref._net().get_flat_params(),
                               t2._net().get_flat_params(),
                               rtol=1e-5, atol=1e-6)


def test_elastic_external_view_ttl_staleness_reshards(tmp_path):
    """Regression (review finding): with an EXTERNAL membership view —
    somebody else beats — a member going silent past the ttl must re-shard
    even though staleness bumps no version counter. The poll diffs the
    alive set itself."""
    from deeplearning4j_tpu.util.time_source import monotonic_s
    X, Y = _data(n=40)
    it = ListDataSetIterator(DataSet(X, Y), batch_size=8)
    view = MembershipView(["w0", "w1", "w2", "w3"], ttl_s=3600.0)
    trainer = ElasticTrainer(_factory(), CheckpointConfig(tmp_path / "ck",
                                                          frequency=0),
                             devices=jax.devices()[:4], membership=view,
                             monitor=HealthMonitor())

    beats = {"skip": set()}
    orig_before = trainer.poll_membership

    def beat_then_poll():
        # the "external system": beats every member except the silenced
        # ones; nothing ever calls kill(), so version never changes
        for n in view.members():
            if n not in beats["skip"]:
                view.heartbeat(n)
        if trainer.state["iteration"] == 2:
            beats["skip"].add("w3")
            view._beats["w3"] = monotonic_s() - 7200.0   # long silent
        return orig_before()
    trainer._before_batch = beat_then_poll

    trainer.fit(it, epochs=1)
    assert trainer.reshards == 1
    assert trainer._alive == ["w0", "w1", "w2"]


# ------------------------------------------------------------- policy JSON

def test_autoscale_policy_round_trip_and_validation():
    p = AutoscalePolicy(min_replicas=1, max_replicas=4, step=2,
                        cooldown_s=30.0, for_duration_s=5.0, window_s=60.0,
                        scale_up={"queue_depth": 16, "shed_ratio": 0.1},
                        scale_down={"queue_depth": 1})
    q = AutoscalePolicy.from_dict(json.loads(json.dumps(p.to_dict())))
    assert q.to_dict() == p.to_dict()
    up, down = q.rules()
    assert {r.name for r in up} == {"autoscale_up_queue_depth",
                                    "autoscale_up_shed_ratio"}
    assert [r.name for r in down] == ["autoscale_down_queue_depth"]
    assert all(r.for_duration_s == 5.0 for r in up + down)
    with pytest.raises(ValueError):
        AutoscalePolicy(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        AutoscalePolicy(scale_up={"bogus_signal": 1})


# ------------------------------------------------- launcher + frontend pool

def _write_zip(path, seed=0, nin=6):
    from tools.smoke_telemetry import _tiny_net
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer
    ModelSerializer.write_model(_tiny_net(nin=nin, seed=seed), str(path))


def test_inprocess_launcher_warm_launch_and_max_guard(tmp_path):
    from deeplearning4j_tpu.util.http import get_json, post_json
    _write_zip(tmp_path / "v1.zip")
    launcher = InProcessLauncher(
        scan_dir=str(tmp_path), max_replicas=2,
        server_opts=dict(alert_interval_s=0),
        deploy_event={"kind": "deploy", "version": "v1"})
    try:
        url = launcher.launch("r0")
        # came up WARM: the deploy event replayed through the
        # RegistrySubscriber path before launch() returned
        models = get_json(url + "/models", timeout=30)
        assert models["active"] == "v1"
        res = post_json(url + "/predict", {"data": [[0.1] * 6]}, timeout=30)
        assert res["version"] == "v1"
        launcher.launch("r1")
        assert launcher.names() == ["r0", "r1"]
        # THE bound: a third spawn hits the max_replicas wall
        with pytest.raises(RuntimeError):
            launcher.launch("r2")
        with pytest.raises(ValueError):
            launcher.launch("r0")            # duplicate name
        launcher.drain("r1")
        assert launcher.names() == ["r0"] and not launcher.alive("r1")
    finally:
        launcher.close()


def test_launcher_broker_fan_deploy_reaches_every_replica(tmp_path):
    """Deploy fan-out over the broker RegistrySubscriber path: a fan_deploy
    publishes to each replica's own topic (competing-consumer queues need
    per-replica topics) and every replica applies it."""
    from deeplearning4j_tpu.streaming.broker import BrokerClient, MessageBroker
    _write_zip(tmp_path / "v1.zip", seed=0)
    _write_zip(tmp_path / "v2.zip", seed=1)
    broker = MessageBroker(port=0).start()
    launcher = InProcessLauncher(
        scan_dir=str(tmp_path), max_replicas=3,
        server_opts=dict(alert_interval_s=0),
        broker_factory=lambda: BrokerClient(port=broker.port, retries=3),
        deploy_event={"kind": "deploy", "version": "v1"})
    try:
        launcher.launch("a")
        launcher.launch("b")
        assert launcher.fan_deploy({"kind": "deploy", "version": "v2"}) == 2
        deadline = 50
        import time
        for _ in range(deadline):
            active = {n: launcher.server(n).registry.active_version
                      for n in ("a", "b")}
            if set(active.values()) == {"v2"}:
                break
            time.sleep(0.1)
        assert set(active.values()) == {"v2"}, active
        assert launcher.fan_errors == []
        # the NEXT launch warms straight to the newest event
        url = launcher.launch("c")
        assert launcher.server("c").registry.active_version == "v2"
        assert url
    finally:
        launcher.close()
        broker.stop()


def test_frontend_add_remove_replica_routes_and_probes(tmp_path):
    from deeplearning4j_tpu.serving import FleetFrontend, ServingServer
    from deeplearning4j_tpu.util.http import post_json
    from tools.smoke_telemetry import _tiny_net
    s1 = ServingServer(_tiny_net(), version="v1", alert_interval_s=0).start()
    s2 = ServingServer(_tiny_net(), version="v1", alert_interval_s=0).start()
    fe = FleetFrontend([s1.url], names=["a"], health_interval_s=1e9,
                       alert_interval_s=0).start()
    try:
        body = {"data": [[0.1] * 6]}
        assert post_json(fe.url + "/predict", body, timeout=30)["replica"] \
            == "a"
        fe.add_replica(s2.url, name="b")
        assert "replica:b" in fe.health.components()
        seen = {post_json(fe.url + "/predict", body, timeout=30)["replica"]
                for _ in range(6)}
        assert seen == {"a", "b"}
        with pytest.raises(ValueError):
            fe.add_replica(s2.url, name="b")
        fe.remove_replica("b")
        assert "replica:b" not in fe.health.components()
        seen = {post_json(fe.url + "/predict", body, timeout=30)["replica"]
                for _ in range(4)}
        assert seen == {"a"}
        with pytest.raises(ValueError):
            fe.remove_replica("a")           # never empty the pool
        with pytest.raises(KeyError):
            fe.remove_replica("ghost")
    finally:
        fe.stop()
        s1.stop()
        s2.stop()


def test_frontend_forwards_pool_wide_shed_as_429():
    """Admission backpressure stays 429 at the frontend (not a dressed-up
    502): with every replica shedding, the client sees the real status."""
    import urllib.error
    from deeplearning4j_tpu.serving import FleetFrontend, ServingServer
    from deeplearning4j_tpu.util.http import post_json
    from tools.smoke_telemetry import _tiny_net
    server = ServingServer(_tiny_net(), version="v1",
                           alert_interval_s=0).start()
    fe = FleetFrontend([server.url], health_interval_s=1e9,
                       alert_interval_s=0).start()
    try:
        # the replica sheds every /predict (admission 429), stays healthy
        plan = FaultPlan([FaultRule("error", match=server.url + "/predict",
                                    status=429, name="shed")])
        with plan:
            with pytest.raises(urllib.error.HTTPError) as ei:
                post_json(fe.url + "/predict", {"data": [[0.1] * 6]},
                          timeout=30)
        assert ei.value.code == 429
        body = json.loads(ei.value.read() or b"{}")
        assert body.get("attempts", 1) >= 1
    finally:
        fe.stop()
        server.stop()


# -------------------------------------------------------------- autoscaler

def test_autoscaler_scale_up_down_on_injected_signals(tmp_path, clock):
    """Clock-driven controller arc without load: the queue-depth gauge is
    fed by a stub replica /metrics, so the AlertEngine lifecycle (pending
    -> firing with for_duration damping), cooldown gating, and the
    launcher round-trip are all assertable deterministically."""
    from deeplearning4j_tpu.serving import FleetFrontend
    _write_zip(tmp_path / "v1.zip")
    launcher = InProcessLauncher(
        scan_dir=str(tmp_path), max_replicas=3,
        server_opts=dict(alert_interval_s=0),
        deploy_event={"kind": "deploy", "version": "v1"})
    fe = None
    try:
        url0 = launcher.launch("r0")
        fe = FleetFrontend([url0], names=["r0"], health_interval_s=1e9,
                           alert_interval_s=0).start()
        policy = AutoscalePolicy(
            min_replicas=1, max_replicas=3, step=1, cooldown_s=10.0,
            for_duration_s=0.0, window_s=60.0,
            scale_up={"queue_depth": 4.0},
            scale_down={"queue_depth": 0.5})
        events = []
        ctl = AutoscaleController(fe, launcher, policy,
                                  sinks=[events.append], interval_s=0)
        # stub the collected queue depth: the decision plumbing under test
        # is gauge -> rule -> action, not the scrape
        depth = {"v": 0.0}
        orig = ctl.collect_signals

        def collect():
            out = orig()
            ctl._g_queue.set(depth["v"])
            out["queue_depth"] = depth["v"]
            return out
        ctl.collect_signals = collect

        assert ctl.evaluate()["action"] is None
        depth["v"] = 9.0
        r = ctl.evaluate()
        assert r["action"] == "scale_up"
        assert len(fe.replicas) == 2
        # cooldown gates the next hop until the clock passes it
        assert ctl.evaluate()["action"] is None
        clock.advance(11.0)
        assert ctl.evaluate()["action"] == "scale_up"
        assert len(fe.replicas) == 3
        clock.advance(11.0)
        assert ctl.evaluate()["action"] is None   # at max_replicas
        # load drops -> drain one per cooldown window, down to min
        depth["v"] = 0.0
        clock.advance(11.0)
        assert ctl.evaluate()["action"] == "scale_down"
        assert len(fe.replicas) == 2
        clock.advance(11.0)
        assert ctl.evaluate()["action"] == "scale_down"
        assert [r.name for r in fe.replicas] == ["r0"]
        clock.advance(11.0)
        assert ctl.evaluate()["action"] is None   # at min_replicas
        kinds = [e["action"] for e in events]
        assert kinds == ["scale_up", "scale_up", "scale_down", "scale_down"]
        assert ctl.status()["transitions"][-1]["action"] == "scale_down"
    finally:
        if fe is not None:
            fe.stop()
        launcher.close()


def test_autoscaler_heals_sole_dead_replica(tmp_path, clock):
    """A preempted ONLY replica is still healable: the controller spawns
    the replacement before removing the corpse (the pool may never go
    empty), and traffic recovers."""
    from deeplearning4j_tpu.serving import FleetFrontend
    from deeplearning4j_tpu.util.http import post_json
    _write_zip(tmp_path / "v1.zip")
    launcher = InProcessLauncher(
        scan_dir=str(tmp_path), max_replicas=2,
        server_opts=dict(alert_interval_s=0),
        deploy_event={"kind": "deploy", "version": "v1"})
    fe = None
    try:
        url0 = launcher.launch("r0")
        fe = FleetFrontend([url0], names=["r0"], health_interval_s=1e9,
                           alert_interval_s=0).start()
        policy = AutoscalePolicy(min_replicas=1, max_replicas=2,
                                 cooldown_s=0.0, down_grace_s=0.0)
        ctl = AutoscaleController(fe, launcher, policy, interval_s=0)
        ctl.evaluate()
        launcher.kill("r0")                  # the whole pool dies
        r = ctl.evaluate()
        assert r["action"] == "replace_dead"
        [handle] = fe.replicas
        assert handle.name != "r0"
        res = post_json(fe.url + "/predict", {"data": [[0.1] * 6]},
                        timeout=30)
        assert res["version"] == "v1" and res["replica"] == handle.name
    finally:
        if fe is not None:
            fe.stop()
        launcher.close()


# ---------------------------------------------------------------- loadgen

def test_loadgen_open_loop_report():
    from tools.loadgen import predict_body, run_loadgen
    from deeplearning4j_tpu.serving import ServingServer
    from tools.smoke_telemetry import _tiny_net
    server = ServingServer(_tiny_net(), version="v1",
                           alert_interval_s=0).start()
    try:
        rep = run_loadgen(server.url, predict_body(nin=6), rate=150.0,
                          duration_s=0.5, seed=7, max_inflight=64)
        assert rep["arrivals"] > 30
        # every arrival is accounted for: completed with some status, or
        # dropped at the in-flight cap and COUNTED (open-loop honesty)
        assert rep["ok"] + rep["shed"] + rep["errors_5xx"] \
            + rep["transport_errors"] + rep["other_4xx"] \
            + rep["dropped_inflight"] == rep["arrivals"]
        assert rep["ok"] > 0 and rep["errors_5xx"] == 0
        assert rep["p99_ms"] >= rep["p50_ms"] > 0.0
        assert rep["offered_rate"] == 150.0 and rep["achieved_rate"] > 0
        # the arrival schedule is the seeded Poisson process: same seed,
        # same offered schedule (open loop = deterministic arrivals)
        import random
        r1 = random.Random(7)
        first_gap = r1.expovariate(150.0)
        assert 0 < first_gap < 1.0
    finally:
        server.stop()


# ------------------------------------------------------------------- smoke

def test_smoke_elastic_tool(tmp_path):
    """The full ManualClock autoscale arc (tools/smoke_elastic.py): ramp ->
    1->2->3 -> preempt -> failover (zero client 5xx) -> reap -> drain back
    to 1, transitions on /fleet/* and trace-correlated logs."""
    import tools.smoke_elastic as smoke
    out = smoke.run(scan_dir=str(tmp_path))
    assert out["client_5xx"] == 0
    assert out["pool_sizes"][0] == 1 and max(out["pool_sizes"]) == 3
    assert out["pool_sizes"][-1] == 1
    assert out["scale_ups"] == ["scale_up", "scale_up"]
    assert out["reap_action"] == "replace_dead"
    assert out["ramp_shed"] > 0 and out["failover_ok"] > 0
    assert out["fleet_sees_autoscale"] and out["scale_logs_traced"]
    assert out["preemptions"] == {"preempt-as1": 1}


def test_subprocess_launcher_one_process_for_each_chip(tmp_path,
                                                        monkeypatch):
    """The child takes its platform from its environment (nothing in code
    pins it), and a child that needs an accelerator this process holds is
    refused with a clear error instead of failing or hanging at start-up."""
    import jax
    from deeplearning4j_tpu.elastic import SubprocessLauncher
    from deeplearning4j_tpu.elastic import launcher as launcher_mod
    assert "jax_platforms" not in launcher_mod._SUBPROCESS_SCRIPT
    assert "JAX_PLATFORMS" not in launcher_mod._SUBPROCESS_SCRIPT
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("DROPPED", "1")
    lau = SubprocessLauncher(str(tmp_path), env={"DROPPED": None, "K": 3})
    env = lau.child_env()
    assert env["JAX_PLATFORMS"] == "cpu" and env["K"] == "3"
    assert "DROPPED" not in env
    # this process on the CPU holds no chip: any child may start
    SubprocessLauncher.check_chip_is_free({})
    # this process on an accelerator: only CPU-pinned children may
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    SubprocessLauncher.check_chip_is_free({"JAX_PLATFORMS": "cpu"})
    unpinned = SubprocessLauncher(str(tmp_path),
                                  env={"JAX_PLATFORMS": None})
    with pytest.raises(RuntimeError, match="InProcessLauncher"):
        unpinned.launch("p0")
    assert not unpinned.alive("p0") and unpinned.names() == []


@pytest.mark.slow
def test_subprocess_launcher_real_process_replica(tmp_path):
    """SubprocessLauncher: one OS process per replica — launch, warm
    deploy over HTTP, serve, terminate. Slow (a full Python+jax boot per
    replica); the in-process launcher covers the fast path in tier-1."""
    from deeplearning4j_tpu.elastic import SubprocessLauncher
    from deeplearning4j_tpu.util.http import get_json, post_json
    _write_zip(tmp_path / "v1.zip")
    launcher = SubprocessLauncher(
        str(tmp_path), max_replicas=1,
        server_opts=dict(alert_interval_s=0),
        deploy_event={"kind": "deploy", "version": "v1"})
    try:
        url = launcher.launch("p0")
        assert launcher.alive("p0")
        assert get_json(url + "/models", timeout=30)["active"] == "v1"
        res = post_json(url + "/predict", {"data": [[0.1] * 6]}, timeout=60)
        assert res["version"] == "v1"
        with pytest.raises(RuntimeError):
            launcher.launch("p1")            # max_replicas wall
    finally:
        launcher.close()
    assert not launcher.alive("p0")
