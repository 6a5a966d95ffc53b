"""Mesh-sharded serving (serving/mesh.py): one dispatch, all chips.

Pins the tentpole's contracts on the 8-virtual-device CPU mesh:

- /predict and /generate under mesh dispatch are bit-comparable (f32
  tolerance; token-exact for greedy decode) to single-chip serving, for
  MultiLayerNetwork AND ComputationGraph — including int8-quantized
  weights placed under tensor-parallel sharding;
- a model whose global footprint exceeds a per-chip budget demonstrably
  serves once TP-sharded (the OOM proxy: per-chip bytes < budget < total
  bytes — real OOM is not reproducible on a shared-host CPU mesh);
- zero steady-state recompiles: compile counters and XLA executable cache
  sizes stay flat across repeated mesh waves (GL011's invariant survives
  the sharded cache + out_shardings pinning);
- the fleet plane counts GROUPS: a mesh replica is ONE ReplicaHandle (one
  breaker, one cohort member), the never-empty guard and autoscaler
  min/max/step math count handles, and chips surface as display/capacity
  gauges only;
- per-shard accounting: DecodeEngine.cache_bytes(per_shard=True) and the
  scheduler's decode_cache_mb gauge report what ONE chip holds.
"""
import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.decode.engine import DecodeEngine
from deeplearning4j_tpu.parallel.sharding import (
    MODEL_AXIS, ShardingRules, even_sharding, make_mesh,
    match_partition_rules, spec_shards)
from deeplearning4j_tpu.serving.mesh import (MeshContext, MeshDispatcher,
                                             MeshServingConfig)
from deeplearning4j_tpu.zoo.models import char_rnn_lstm, transformer_lm

V = 24


def _mln(seed=0, nin=6, nout=3):
    from tools.smoke_telemetry import _tiny_net
    return _tiny_net(nin=nin, nout=nout, seed=seed)


def _graph_lm(seed=7, heads=2):
    return transformer_lm(vocab_size=V, d_model=32, n_layers=2,
                          n_heads=heads, seed=seed).init()


def _rnn(seed=3):
    return char_rnn_lstm(vocab_size=V, hidden=16, layers=1,
                         seed=seed).init()


def _onehot_batch(rng, rows, L):
    return np.eye(V, dtype=np.float32)[rng.integers(0, V, (rows, L))]


# ------------------------------------------------------------ config/rules

def test_mesh_config_from_spec_forms():
    assert MeshServingConfig.from_spec(None) is None
    c = MeshServingConfig.from_spec(True)
    assert c.n_data is None and c.n_model == 1 and c.rules is None
    c = MeshServingConfig.from_spec(2)
    assert c.n_model == 2 and c.resolve_rules().rules  # tensor_parallel
    c = MeshServingConfig.from_spec({"n_data": 2, "n_model": 4,
                                     "rules": "tensor_parallel"})
    assert (c.n_data, c.n_model) == (2, 4)
    assert c.to_dict() == {"n_data": 2, "n_model": 4,
                           "rules": "tensor_parallel"}
    with pytest.raises(TypeError):
        MeshServingConfig.from_spec(3.5)
    with pytest.raises(ValueError):
        MeshServingConfig(rules="bogus").resolve_rules()


def test_match_partition_rules_specs_and_even_fallback():
    m = _mln()
    specs = match_partition_rules(ShardingRules.tensor_parallel_dense(),
                                  m.params)
    flat = {"/".join(str(p) for p in path): s for path, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    w = [s for k, s in flat.items() if k.endswith("['W']")]
    b = [s for k, s in flat.items() if k.endswith("['b']")]
    assert w and all(s == P(None, MODEL_AXIS) for s in w)
    assert b and all(s == P(MODEL_AXIS) for s in b)
    # even_sharding degrades a non-divisible partitioned dim to replicated
    mesh = make_mesh(n_data=2, n_model=4)
    ok = even_sharding(mesh, P(None, MODEL_AXIS), (3, 8))
    assert ok.spec == P(None, MODEL_AXIS)
    odd = even_sharding(mesh, P(None, MODEL_AXIS), (3, 7))
    assert odd.spec == P()
    assert spec_shards(mesh, ok.spec) == 4
    assert spec_shards(mesh, odd.spec) == 1


# ---------------------------------------------------------- /predict parity

@pytest.mark.parametrize("spec", [
    {"n_data": 8, "n_model": 1, "rules": None},
    {"n_data": 4, "n_model": 2, "rules": "tensor_parallel"},
], ids=["data_parallel", "tensor_parallel"])
def test_mesh_predict_parity_multilayernetwork(spec):
    m = _mln(seed=11)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 6)).astype(np.float32)   # 5 rows: forces padding
    want = np.asarray(m.output(x))
    w = MeshContext(spec).wrap(m)
    assert isinstance(w, MeshDispatcher)
    got = np.asarray(w.output(x))
    assert got.shape == want.shape                   # pad rows sliced off
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert w.mesh_context.dispatches == 1
    # idempotent wrap: the registry adapter may see a wrapped model again
    assert MeshContext(spec).wrap(w) is w


def test_mesh_predict_parity_computation_graph():
    g = _graph_lm(seed=12)
    rng = np.random.default_rng(1)
    x = _onehot_batch(rng, 3, 5)
    want = np.asarray(g.output(x))
    ctx = MeshContext({"n_data": 4, "n_model": 2, "rules": "tensor_parallel"})
    got = np.asarray(ctx.wrap(g).output(x))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # TP actually placed: some weight leaf spans the model axis
    specs = {str(l.sharding.spec) for l in
             jax.tree_util.tree_leaves(g.params) if hasattr(l, "sharding")}
    assert any(MODEL_AXIS in s for s in specs), specs


def test_mesh_int8_weights_parity_under_tp():
    """int8 serving weights compose with TP placement: the placed leaves
    ARE the codes (same W shapes), parity holds through the wrapper, and
    a dequantize re-places cleanly (identity-based re-placement)."""
    ref = _mln(seed=21)
    ref.quantize_weights("int8")
    x = np.random.default_rng(2).normal(size=(4, 6)).astype(np.float32)
    want = np.asarray(ref.output(x))

    m = _mln(seed=21)
    ctx = MeshContext({"n_data": 4, "n_model": 2, "rules": "tensor_parallel"})
    w = ctx.wrap(m)
    w.output(x)                       # place the f32 weights first
    w.quantize_weights("int8")        # delegates; swaps the params object
    got = np.asarray(w.output(x))     # must re-place the NEW (code) leaves
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    codes = [l for l in jax.tree_util.tree_leaves(m.params)
             if l.dtype == np.int8]
    assert codes, "int8 codes not placed in the params tree"
    assert any(MODEL_AXIS in str(l.sharding.spec) for l in codes)
    per, total = w.param_shard_bytes()
    assert per < total                # the diet composes with TP capacity
    w.dequantize_weights()
    np.testing.assert_allclose(np.asarray(w.output(x)),
                               np.asarray(_mln(seed=21).output(x)),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------- /generate parity

@pytest.mark.parametrize("make,label", [(_graph_lm, "graph_lm"),
                                        (_rnn, "mln_rnn")])
def test_mesh_generate_parity_and_sharded_cache(make, label):
    prompt = [3, 1, 4, 9, 2]

    def greedy(eng, n=6):
        cache = eng.init_cache()
        cache, nid, _ = eng.prefill(cache, 0, np.asarray(prompt, np.int32))
        out = [int(np.asarray(nid))]
        ids = np.zeros((eng.slots,), np.int32)
        for _ in range(n):
            ids[0] = out[-1]
            cache, nxt, _ = eng.step(cache, ids)
            out.append(int(np.asarray(nxt)[0]))
        return out

    want = greedy(DecodeEngine(make(), slots=2, max_len=32))
    ctx = MeshContext({"n_data": 4, "n_model": 2, "rules": "tensor_parallel"})
    eng = DecodeEngine(ctx.wrap(make()), slots=2, max_len=32)
    assert eng.mesh is ctx
    got = greedy(eng)
    assert got == want, label
    # the cache is genuinely partitioned -> per-shard bytes < global bytes
    per, total = eng.cache_bytes(per_shard=True), eng.cache_bytes()
    assert per < total, label
    # zero steady state: one executable per label even under shardings
    assert all(v == 1 for v in eng.executable_counts().values())


def test_decode_scheduler_cache_gauge_reports_per_shard_mb():
    from deeplearning4j_tpu.decode.scheduler import DecodeScheduler
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

    ctx = MeshContext({"n_data": 4, "n_model": 2, "rules": "tensor_parallel"})
    reg = ModelRegistry(adapter=ctx.wrap)
    reg.register("v1", _graph_lm(seed=5))
    reg.deploy("v1")
    sched = DecodeScheduler(reg, MetricsRegistry(), slots=2, max_len=32)
    sched.start()
    try:
        sched.generate([1, 2, 3], max_new_tokens=2)
        eng = sched._engine
        want_mb = eng.cache_bytes(per_shard=True) / 1e6
        assert sched.cache_mb() == pytest.approx(want_mb)
        assert sched.cache_mb() < eng.cache_bytes() / 1e6
        assert sched.snapshot()["cache_mb"] == pytest.approx(want_mb)
        g = sched.metrics_registry.get("decode_cache_mb")
        assert g is not None and g.get() == pytest.approx(want_mb)
    finally:
        sched.stop()


def test_decode_loop_on_a_mesh_is_never_ahead_and_serves_the_same_tokens():
    """On a mesh the engine's dispatch waits for the device inside the run
    lock, so the scheduler's pass reads the step it has just dispatched:
    every step counts `ahead="0"`, a stop id is still found a step late
    (the first token is read a pass after its prefill) and the tokens are
    the one-device engine's."""
    from deeplearning4j_tpu.decode.scheduler import DecodeScheduler
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

    ctx = MeshContext({"n_data": 4, "n_model": 2, "rules": "tensor_parallel"})
    reg = ModelRegistry(adapter=ctx.wrap)
    reg.register("v1", _graph_lm(seed=5))
    reg.deploy("v1")
    mreg = MetricsRegistry()
    sched = DecodeScheduler(reg, mreg, slots=2, max_len=32)
    alone = DecodeEngine(_graph_lm(seed=5), slots=2, max_len=32)
    prompts, budgets = [[1, 2, 3], [4, 5], [6]], [6, 1, 4]
    futs = [sched.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    for _ in range(40):
        if all(f.done() for f in futs):
            break
        sched._pass()
        assert sched._flight is None        # read in the pass it was sent
    assert [f.result(timeout=0)["tokens"] for f in futs] \
        == [alone.generate(p, n) for p, n in zip(prompts, budgets)]
    assert mreg.get("decode_steps_ahead_total").series() == [
        ({"ahead": "0"}, mreg.get("decode_step_sync_ms").count())]
    assert mreg.get("decode_discarded_slot_steps_total").get() == 0
    assert all(v == 1 for v in sched._engine.executable_counts().values())


# ------------------------------------------------------------- OOM proxy

def test_model_that_overflows_one_chip_serves_tp_sharded():
    """The capacity claim as a measurement: a dense model whose weight
    bytes exceed a per-chip budget fits per-chip once TP-sharded — and a
    forward actually runs under that placement. (Real OOM cannot be forced
    on a shared-host CPU mesh; the byte ledger is the honest proxy.)"""
    from deeplearning4j_tpu import (DenseLayer, InputType,
                                    MultiLayerNetwork,
                                    NeuralNetConfiguration, OutputLayer, Sgd)
    hidden = 512
    conf = (NeuralNetConfiguration.builder()
            .seed(0).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=8, activation="softmax", loss="MCXENT"))
            .input_type(InputType.feed_forward(64))
            .build())
    m = MultiLayerNetwork(conf).init()
    ctx = MeshContext({"n_data": 1, "n_model": 8, "rules": "tensor_parallel"})
    w = ctx.wrap(m)
    per, total = w.param_shard_bytes()
    budget = total // 3               # a chip one-third the model's size
    assert total > budget, "model must overflow the unsharded budget"
    assert per < budget, (per, budget, total)
    out = np.asarray(w.output(np.zeros((2, 64), np.float32)))
    assert out.shape == (2, 8)


# --------------------------------------------------- zero-recompile serving

def test_mesh_server_steady_state_compiles_flat():
    from deeplearning4j_tpu.serving.server import ServingServer
    srv = ServingServer(_mln(seed=31), max_batch_size=4,
                        mesh={"n_data": 4, "n_model": 2,
                              "rules": "tensor_parallel"}).start()
    try:
        x = np.random.default_rng(3).normal(size=(2, 6)).astype(np.float32)
        srv.submit(x).result(timeout=120)            # warm the (2, 6) bucket
        reg = srv.metrics.registry
        c0 = reg.get("compiles_total").get()
        jit = reg.get("jit_compiles_total")
        j0 = jit.get() if jit is not None else 0.0
        for _ in range(3):                           # steady-state waves
            out = srv.submit(x).result(timeout=120)
            assert len(out["prediction"]) == 2
        assert reg.get("compiles_total").get() == c0
        if jit is not None:
            assert jit.get() == j0
        assert srv.mesh.chips == 8
        assert reg.get("mesh_dispatch_chips").get() == 8.0
    finally:
        srv.stop()


# ------------------------------------------------------------- fleet plane

def test_fleet_counts_groups_not_chips_in_mixed_pool():
    from deeplearning4j_tpu.elastic import AutoscaleController, AutoscalePolicy
    from deeplearning4j_tpu.serving.frontend import FleetFrontend
    from deeplearning4j_tpu.serving.server import ServingServer

    mesh_srv = ServingServer(_mln(seed=41), mesh=True).start()
    solo_srv = ServingServer(_mln(seed=41)).start()
    fe = FleetFrontend([mesh_srv.url, solo_srv.url],
                       names=["mesh", "solo"], health_interval_s=0.0).start()
    try:
        fe.poll_health(force=True)
        by_name = {r.name: r for r in fe.replicas}
        # ONE handle for the 8-chip group; chips is display info on it
        assert len(fe.replicas) == 2
        assert by_name["mesh"].chips == 8 and by_name["solo"].chips == 1
        assert by_name["mesh"].to_dict()["chips"] == 8
        _, pool = fe._probe_pool()
        assert pool["replicas"] == 2 and pool["chips"] == 9

        class _NoLauncher:
            def launch(self, name):
                raise AssertionError("no scaling expected")
            terminate = launch

            def names(self):
                return []

        ctl = AutoscaleController(
            fe, _NoLauncher(),
            AutoscalePolicy(min_replicas=1, max_replicas=4, step=1),
            interval_s=0.0)
        sig = ctl.collect_signals()
        # policy math counts GROUPS (2), chips is the capacity gauge (9)
        assert sig["replicas"] == 2 and sig["chips"] == 9
        assert fe.registry.get("autoscale_replicas").get() == 2.0
        assert fe.registry.get("autoscale_chips").get() == 9.0

        # the never-empty guard counts handles: with solo removed, the mesh
        # group alone is "the last replica" no matter its 8 chips
        fe.remove_replica("solo")
        with pytest.raises(ValueError):
            fe.remove_replica("mesh")
    finally:
        fe.stop()
        mesh_srv.stop()
        solo_srv.stop()


# ------------------------------------------------------------- smoke tool

def test_smoke_mesh_tool():
    """Tier-1 wiring for tools/smoke_mesh.py: multi-device mesh deploy,
    concurrent /predict + /generate waves with single-chip parity, zero
    steady-state recompiles, canary rollback on the mesh replica as one
    unit, zero client 5xx (mirrors the smoke_decode/smoke_fleet wiring)."""
    import tools.smoke_mesh as smoke
    out = smoke.run(n_predict=6, n_generate=3, max_new_tokens=4)
    assert out["steady_state_compiles"] == 0
    assert out["donation_warnings"] == 0
    assert out["client_errors"] == 0
    assert out["gen_parity"]
    assert out["devices"] == 8
    assert out["pool"] == {"replicas": 2, "routable": 2, "chips": 9}
