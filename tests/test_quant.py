"""Bytes diet (ROADMAP item 3, ISSUE 15): low-bit optimizer moments riding
inside the ZeRO flatten-pad layout, and int8 weight-quantized serving
executables — both through nn/quant.py, the one designated quant module.

Contracts under test:
- MomentCodec round-trips are EXACT-idempotent (pow2 scales), so conversion
  chains (checkpoint -> restore -> re-shard -> re-shard) replay codes
  bit-for-bit, at any shard count;
- q8/bf16 moments train to parity-tolerance vs f32 moments with per-device
  moment bytes cut >= 3.5x (q8) / 2x (bf16) at the same shard count, with
  donation intact and zero steady-state recompiles on every train path;
- int8 weight quantization serves within the accuracy-parity gate, HBM
  param bytes cut ~4x, zips stay f32, training refuses quantized weights,
  and the deploy gate fails CLOSED (breach -> f32 restored, old version
  keeps serving).
"""
import os
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (NeuralNetConfiguration, InputType, DenseLayer,
                                OutputLayer, MultiLayerNetwork, DataSet, Adam)
from deeplearning4j_tpu.datasets.iterator.base import ListDataSetIterator
from deeplearning4j_tpu.nn.quant import (MomentCodec, QuantGate,
                                         QuantParityError, WeightQuant,
                                         quantize_model_weights)
from deeplearning4j_tpu.parallel.sharding import make_mesh, ShardedTrainer
from deeplearning4j_tpu.parallel.zero import (ZeroUpdater, moment_bytes,
                                              per_device_bytes)


def _toy(n=64, nin=8, nout=3, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, nin)).astype(np.float32)
    w = rng.normal(size=(nin, nout))
    y = np.argmax(X @ w, axis=1)
    return X, np.eye(nout, dtype=np.float32)[y]


def _conf(nin=8, nout=3, seed=42, hidden=16, updater=None):
    return (NeuralNetConfiguration.builder()
            .seed(seed).updater(updater or Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=nout, activation="softmax", loss="MCXENT"))
            .input_type(InputType.feed_forward(nin))
            .build())


def _canonical_moments(net):
    st = net.opt_state
    z = getattr(net, "_zero", None)
    if z is not None:
        st = z.to_canonical(st, net.params)
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(st)[0]:
        if hasattr(leaf, "shape"):
            out["/".join(str(k) for k in path)] = np.asarray(leaf)
    return out


def _reshard(net, n, moment_dtype="q8"):
    return ShardedTrainer(net, mesh=make_mesh(n_data=n,
                                              devices=jax.devices()[:n]),
                          shard_update=True, moment_dtype=moment_dtype)


# ----------------------------------------------------------------- codec

def test_moment_codec_q8_roundtrip_exact_idempotent():
    """decode(encode(decode(x))) == decode(x) BIT-FOR-BIT: pow2 scales make
    every decode an exact float op and every re-encode reproduce the same
    scale — the property that keeps re-shard chains drift-free without
    stochastic rounding."""
    c = MomentCodec("q8", n_shards=8, block=128)
    rng = np.random.default_rng(3)
    v = np.concatenate([rng.normal(0, 1e-4, 300), np.zeros(130),
                        rng.normal(0, 7.0, 96), [1e-30, -1e-30]])
    L = -(-len(v) // 8) * 8
    v = jnp.asarray(np.pad(v, (0, L - len(v))).astype(np.float32))
    e1 = c.encode(v)
    d1 = c.decode(e1, L)
    e2 = c.encode(d1)
    np.testing.assert_array_equal(np.asarray(e1["qcodes"]),
                                  np.asarray(e2["qcodes"]))
    np.testing.assert_array_equal(np.asarray(e1["qscale"]),
                                  np.asarray(e2["qscale"]))
    np.testing.assert_array_equal(np.asarray(c.decode(e2, L)),
                                  np.asarray(d1))


def test_moment_codec_q8_no_small_value_annihilation():
    """The reason the codes are fp8-e4m3 and not linear int8: entries many
    orders below the block absmax must survive (a zeroed second moment
    divides the update by eps and the run detonates). Entries down to
    absmax/1e4 keep ~6% relative error."""
    c = MomentCodec("q8", n_shards=1, block=128)
    v = np.zeros(128, np.float32)
    v[0] = 1.0                     # block absmax
    v[1] = 1e-4                    # 4 orders below
    v[2] = -3e-3
    d = np.asarray(c.decode(c.encode(jnp.asarray(v)), 128))
    assert d[1] != 0.0 and abs(d[1] - 1e-4) / 1e-4 < 0.07
    assert abs(d[2] + 3e-3) / 3e-3 < 0.07
    assert abs(d[0] - 1.0) < 0.07


def test_moment_codec_bf16_roundtrip():
    c = MomentCodec("bf16", n_shards=4)
    v = jnp.asarray(np.random.default_rng(0).normal(size=64).astype(np.float32))
    e = c.encode(v)
    assert e.dtype == jnp.bfloat16
    d = c.decode(e, 64)
    assert d.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(c.decode(c.encode(d), 64)),
                                  np.asarray(d))


# ------------------------------------------------- training with low-bit

@pytest.mark.parametrize("md,tol", [("bf16", 5e-3), ("q8", 5e-2)])
def test_low_bit_moment_training_parity_tolerance(md, tol):
    """ISSUE satellite: a quantized-moment run reaches parity-tolerance vs
    f32 moments on a small model — same data, same seed, final params and
    score track the f32-moment run."""
    X, Y = _toy()
    ds = DataSet(X, Y)
    a = MultiLayerNetwork(_conf()).init()
    tra = ShardedTrainer(a, mesh=make_mesh(n_data=8), shard_update=True)
    b = MultiLayerNetwork(_conf()).init()
    trb = ShardedTrainer(b, mesh=make_mesh(n_data=8), shard_update=True,
                         moment_dtype=md)
    for _ in range(12):
        tra.fit_batch(ds)
        trb.fit_batch(ds)
    np.testing.assert_allclose(a.get_flat_params(), b.get_flat_params(),
                               atol=tol, rtol=0)
    assert abs(a.score_value - b.score_value) < tol
    assert np.isfinite(b.score_value)


def test_q8_moment_bytes_at_least_3p5x_smaller_and_gauge_reports():
    """ISSUE acceptance: `opt_moment_bytes_per_device` drops >= 3.5x with
    8-bit moments vs f32 at the SAME shard count (and >= 2x for bf16), and
    the gauge carries the dtype attribution."""
    def conf():
        # two hidden-256 layers: weight leaves big enough that the q8
        # codes' block*n_shards pad granule is noise, as in a real model
        return (NeuralNetConfiguration.builder()
                .seed(1).updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_out=256, activation="relu"))
                .layer(DenseLayer(n_out=256, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="MCXENT"))
                .input_type(InputType.feed_forward(8)).build())

    f = MultiLayerNetwork(conf()).init()
    ShardedTrainer(f, mesh=make_mesh(n_data=8), shard_update=True)
    mf = moment_bytes(f.opt_state)

    q = MultiLayerNetwork(conf()).init()
    ShardedTrainer(q, mesh=make_mesh(n_data=8), shard_update=True,
                   moment_dtype="q8")
    mq = moment_bytes(q.opt_state)
    assert mq * 3.5 <= mf, (mf, mq)

    h = MultiLayerNetwork(conf()).init()
    ShardedTrainer(h, mesh=make_mesh(n_data=8), shard_update=True,
                   moment_dtype="bf16")
    assert moment_bytes(h.opt_state) * 2 <= mf

    from deeplearning4j_tpu.telemetry.registry import get_registry
    series = {}
    for labels, value in get_registry().gauge(
            "opt_moment_bytes_per_device").series():
        series[(labels.get("mode"), labels.get("dtype"))] = value
    assert series[("zero", "q8")] == mq
    assert series[("zero", "f32")] == mf


def test_q8_every_train_path_donation_clean_no_retrace():
    """ISSUE acceptance: zero new donation warnings AND zero steady-state
    recompiles on the quantized paths — std jit step, scanned multistep,
    and both TBPTT paths all run with q8 moments; re-running each
    executable leaves its XLA cache size flat."""
    sets = [DataSet(*_toy(n=32, seed=s)) for s in range(8)]
    net = MultiLayerNetwork(_conf()).init()
    tr = ShardedTrainer(net, mesh=make_mesh(n_data=8), shard_update=True,
                        moment_dtype="q8")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        tr.fit_batch(sets[0])                              # std jit step
        tr.fit(ListDataSetIterator(sets), steps_per_execution=4)  # scanned
        sizes0 = {k: f._cache_size() for k, f in net._jit_cache.items()
                  if hasattr(f, "_cache_size")}
        tr.fit_batch(sets[0])
        tr.fit(ListDataSetIterator(sets), steps_per_execution=4)
        sizes1 = {k: f._cache_size() for k, f in net._jit_cache.items()
                  if hasattr(f, "_cache_size")}
    donation = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert donation == [], donation
    assert sizes0 == sizes1, (sizes0, sizes1)

    # both TBPTT paths (per-window + scanned multi_tbptt)
    from deeplearning4j_tpu.zoo.models import char_rnn_lstm
    rnn = char_rnn_lstm(vocab_size=12, hidden=16, layers=2, tbptt=5).init()
    rnn.set_update_sharding(ZeroUpdater(make_mesh(n_data=8),
                                        moment_dtype="q8"))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 12, size=(8, 21))
    x = np.eye(12, dtype=np.float32)[ids[:, :-1]]
    y = np.eye(12, dtype=np.float32)[ids[:, 1:]]
    dsr = DataSet(jnp.asarray(x), jnp.asarray(y))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        rnn.fit_batch(dsr)
        plan = rnn.prepare_steps([dsr] * 2)
        assert plan is not None and plan[0] == "tbptt"
        rnn.fit_prepared(plan)
    donation = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert donation == [], donation
    assert np.isfinite(float(rnn.score_value))


# ------------------------------------------------------- re-shard chains

def test_q8_reshard_chain_8_4_8_bitwise():
    """ISSUE satellite: quantized state converts through the canonical
    layout across re-shard chains with ZERO drift — 8 -> 4 -> 8 leaves
    every canonical moment bit-identical (exact-idempotent codec + blocks
    anchored at canonical offset 0)."""
    X, Y = _toy()
    ds = DataSet(X, Y)
    net = MultiLayerNetwork(_conf()).init()
    tr = _reshard(net, 8)
    for _ in range(4):
        tr.fit_batch(ds)
    before = _canonical_moments(net)
    tr = _reshard(net, 4)          # elastic shrink...
    tr = _reshard(net, 8)          # ...and regrow
    after = _canonical_moments(net)
    assert before.keys() == after.keys()
    for k in before:
        np.testing.assert_array_equal(before[k], after[k], err_msg=k)
    # degenerate single-shard hop too
    tr = _reshard(net, 1)
    tr = _reshard(net, 8)
    final = _canonical_moments(net)
    for k in before:
        np.testing.assert_array_equal(before[k], final[k], err_msg=k)


def test_q8_elastic_shrink_grow_with_training_bounded_drift():
    """The full elastic arc WITH steps at each topology (8 -> 4 -> 8):
    params track a fixed-8-shard q8 oracle within tolerance — momentum is
    carried through both hops, not reset."""
    X, Y = _toy()
    ds = DataSet(X, Y)
    oracle = MultiLayerNetwork(_conf()).init()
    otr = _reshard(oracle, 8)
    net = MultiLayerNetwork(_conf()).init()
    tr = _reshard(net, 8)
    for _ in range(3):
        otr.fit_batch(ds)
        tr.fit_batch(ds)
    tr = _reshard(net, 4)
    for _ in range(3):
        otr.fit_batch(ds)
        tr.fit_batch(ds)
    tr = _reshard(net, 8)
    for _ in range(2):
        otr.fit_batch(ds)
        tr.fit_batch(ds)
    np.testing.assert_allclose(oracle.get_flat_params(),
                               net.get_flat_params(), atol=5e-2, rtol=0)
    a, b = _canonical_moments(net), _canonical_moments(oracle)
    assert a.keys() == b.keys()
    for k in a:
        assert np.all(np.isfinite(a[k])), k


def test_elastic_trainer_preserves_q8_codec_across_reshard(tmp_path):
    """ElasticTrainer(moment_dtype="q8"): a chaos preemption re-shards the
    live run and the NEW ShardedTrainer keeps the q8 codec — the bytes diet
    survives topology changes."""
    from deeplearning4j_tpu.elastic import ElasticTrainer
    from deeplearning4j_tpu.resilience.chaos import FaultPlan, FaultRule
    from deeplearning4j_tpu.telemetry.health import HealthMonitor
    from deeplearning4j_tpu.train.fault_tolerance import CheckpointConfig

    X, Y = _toy()
    it = ListDataSetIterator([DataSet(X, Y)] * 8)
    plan = FaultPlan([FaultRule("preempt", target="w3", at_step=4,
                                name="kill-w3")])
    trainer = ElasticTrainer(lambda: MultiLayerNetwork(_conf()).init(),
                             CheckpointConfig(tmp_path / "ck", frequency=0),
                             devices=jax.devices()[:4], plan=plan,
                             monitor=HealthMonitor(), moment_dtype="q8")
    trainer.fit(it, epochs=1)
    assert trainer.reshards == 1 and trainer._alive == ["w0", "w1", "w2"]
    net = trainer._net()
    assert net._zero is not None and net._zero.moment_dtype == "q8"
    assert np.isfinite(net.score_value)


def test_fault_tolerant_trainer_resumes_q8_run_on_fewer_replicas(tmp_path):
    """The async snapshot-then-write checkpoint path canonicalizes q8
    moments (to_canonical decodes before the host snapshot): an 8-shard
    q8 run's checkpoint resumes in a 4-shard q8 trainer with the codec
    re-applied."""
    from deeplearning4j_tpu.train.fault_tolerance import (CheckpointConfig,
                                                          FaultTolerantTrainer)
    X, Y = _toy()
    ds = DataSet(X, Y)
    ckdir = str(tmp_path / "ck")
    t1 = FaultTolerantTrainer(
        lambda: _reshard(MultiLayerNetwork(_conf()).init(), 8),
        CheckpointConfig(ckdir, frequency=2))
    t1.fit(ListDataSetIterator([ds] * 4), epochs=1)
    t2 = FaultTolerantTrainer(
        lambda: _reshard(MultiLayerNetwork(_conf()).init(), 4),
        CheckpointConfig(ckdir, frequency=2))
    assert t2.resumed
    t2.fit(ListDataSetIterator([ds] * 4), epochs=2)
    net = t2._net()
    assert net.iteration_count == 8
    assert net._zero is not None and net._zero.moment_dtype == "q8"
    assert np.isfinite(net.score_value)


def test_q8_checkpoint_restores_at_different_shard_count(tmp_path):
    """Canonical checkpoint format UNCHANGED: a q8-moment run writes the
    same per-param f32 updater state every serializer stores; the restore
    re-shards AND re-quantizes at a different replica count and resumes
    with momentum intact (near-bitwise: the restore replays the exact
    decoded moments)."""
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer
    X, Y = _toy()
    ds = DataSet(X, Y)
    net = MultiLayerNetwork(_conf()).init()
    tr = _reshard(net, 8)
    for _ in range(4):
        tr.fit_batch(ds)
    path = str(tmp_path / "q8.zip")
    ModelSerializer.write_model(net, path)

    restored = ModelSerializer.restore(path)
    # canonical layout: every >=1-D opt leaf has a param's exact shape/f32
    pshapes = {tuple(l.shape) for l in
               jax.tree_util.tree_leaves(restored.params)}
    for leaf in jax.tree_util.tree_leaves(restored.opt_state):
        if getattr(leaf, "ndim", 0) >= 1:
            assert tuple(leaf.shape) in pshapes
            assert leaf.dtype == jnp.float32
    tr4 = _reshard(restored, 4)
    for _ in range(3):
        tr4.fit_batch(ds)
        tr.fit_batch(ds)
    np.testing.assert_allclose(net.get_flat_params(),
                               restored.get_flat_params(),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------------ int8 weights

def _trained_net(seed=7, steps=25, hidden=64, nin=16, nout=5):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(128, nin)).astype(np.float32)
    w = rng.normal(size=(nin, nout))
    Y = np.eye(nout, dtype=np.float32)[np.argmax(X @ w, axis=1)]
    net = MultiLayerNetwork(_conf(nin=nin, nout=nout, seed=seed,
                                  hidden=hidden)).init()
    for _ in range(steps):
        net.fit_batch(DataSet(X, Y))
    return net, X, Y


def test_weight_quant_parity_and_bytes():
    """Per-channel int8: top-1 preserved, outputs within the default gate,
    per-device param bytes cut >= 3x (weights dominate this model)."""
    net, X, _ = _trained_net()
    ref = np.asarray(net.output(X))
    b_f32 = per_device_bytes(net.params)
    net.quantize_weights("int8")
    q = np.asarray(net.output(X))
    b_q = per_device_bytes(net.params)
    assert b_q * 3 <= b_f32, (b_f32, b_q)
    assert np.mean(np.argmax(ref, 1) == np.argmax(q, 1)) >= 0.99
    assert np.max(np.abs(ref - q)) / np.max(np.abs(ref)) < 0.05
    # int8 codes really are the executable operands (HBM-resident narrow)
    assert net.params["0"]["W"].dtype == jnp.int8
    # biases/norm leaves stay f32
    assert net.params["0"]["b"].dtype != jnp.int8


def test_weight_quant_refuses_training_and_dequantize_restores():
    net, X, Y = _trained_net()
    ref = np.asarray(net.output(X))
    net.quantize_weights("int8")
    with pytest.raises(RuntimeError, match="serving-only"):
        net.fit_batch(DataSet(X, Y))
    with pytest.raises(RuntimeError, match="serving-only"):
        net.prepare_steps([DataSet(X, Y)] * 2)
    net.dequantize_weights()
    np.testing.assert_allclose(np.asarray(net.output(X)), ref, rtol=1e-6)
    net.fit_batch(DataSet(X, Y))    # trains again after restore


def test_weight_quant_zip_stays_f32(tmp_path):
    """Serializers write the f32 backup, never the codes: a restore of a
    quantized model's zip is a plain full-precision model."""
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer
    net, X, _ = _trained_net()
    f32_params = {k: {kk: np.asarray(vv) for kk, vv in v.items()}
                  for k, v in net.params.items()}
    net.quantize_weights("int8")
    path = str(tmp_path / "q.zip")
    ModelSerializer.write_model(net, path)
    r = ModelSerializer.restore(path)
    for lk, sub in r.params.items():
        for k, leaf in sub.items():
            assert jnp.issubdtype(leaf.dtype, jnp.floating), (lk, k)
            np.testing.assert_allclose(np.asarray(leaf), f32_params[lk][k],
                                       rtol=1e-6)


def test_weight_quant_zero_steady_state_recompiles():
    """The quantized output executable compiles once per (shape, mask)
    family and never again — the serving no-recompile invariant holds for
    int8 weights."""
    net, X, _ = _trained_net()
    net.quantize_weights("int8")
    net.output(X)
    key = ("output", False, False)
    size0 = net._jit_cache[key]._cache_size()
    for _ in range(3):
        net.output(X)
    assert net._jit_cache[key]._cache_size() == size0 == 1


def test_weight_quant_computation_graph_and_decode_parity():
    """ComputationGraph quantizes through the same mixin, and the decode
    engine consumes the narrow weights: greedy KV decode on the quantized
    transformer matches the naive quantized full-forward token-for-token."""
    from deeplearning4j_tpu.zoo.models import transformer_lm
    net = transformer_lm(vocab_size=32, d_model=32, n_layers=2, n_heads=2)
    net.init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 32, size=(8, 13))
    x = np.eye(32, dtype=np.float32)[ids[:, :-1]]
    y = np.eye(32, dtype=np.float32)[ids[:, 1:]]
    for _ in range(8):
        net.fit_batch(DataSet(x, y))
    net.quantize_weights("int8")
    prompt = list(rng.integers(0, 32, 6))
    toks = net.generate(prompt, max_new_tokens=5)
    seq = list(prompt)
    for t in toks:
        out = np.asarray(net.output(
            np.eye(32, dtype=np.float32)[np.asarray(seq)][None]))
        assert int(np.argmax(out[0, -1])) == t
        seq.append(t)


def test_quantize_model_weights_gate_fails_closed():
    """A breached gate restores the f32 weights and raises — the model
    never serves half-quantized."""
    net, X, _ = _trained_net()
    ref = np.asarray(net.output(X))
    with pytest.raises(QuantParityError):
        quantize_model_weights(net, parity_inputs=X[:16],
                               gate=QuantGate(max_rel_delta=0.0))
    assert net._wq is None
    np.testing.assert_allclose(np.asarray(net.output(X)), ref, rtol=1e-6)
    # and a passing gate reports parity
    report = quantize_model_weights(net, parity_inputs=X[:16])
    assert report["gated"] and report["top1_agreement"] >= 0.97


# ----------------------------------------------------------- serving

def test_serving_deploy_quantize_int8_end_to_end(tmp_path):
    """POST /deploy {"quantize": "int8"}: parity-gated quantization before
    the warm-up, /predict parity vs the f32 deploy, /models carries the
    quantized+parity attribution, and a strict-gate breach fails the
    deploy with the old version still serving f32."""
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.util.http import get_json, post_json
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    net, X, _ = _trained_net()
    ModelSerializer.write_model(net, os.path.join(tmp_path, "v1.zip"))
    ModelSerializer.write_model(net, os.path.join(tmp_path, "v2.zip"))
    srv = ServingServer(scan_dir=str(tmp_path), alert_interval_s=0).start()
    try:
        url = srv.url
        post_json(url + "/deploy", {"version": "v1"})
        r1 = post_json(url + "/predict", {"data": X[:4].tolist()})
        r = post_json(url + "/deploy",
                      {"version": "v2", "quantize": "int8",
                       "parity_inputs": X[:32].tolist()})
        assert r["quantized"] == "int8" and r["parity"]["gated"]
        assert r["parity"]["top1_agreement"] >= 0.97
        r2 = post_json(url + "/predict", {"data": X[:4].tolist()})
        d = np.max(np.abs(np.asarray(r1["prediction"])
                          - np.asarray(r2["prediction"])))
        assert d < 0.05 and r2["version"] == "v2"
        info = {m["version"]: m for m in get_json(url + "/models")["models"]}
        assert info["v2"]["quantized"] == "int8"
        assert info["v1"]["quantized"] is None
    finally:
        srv.stop()


def test_serving_deploy_quantize_breach_keeps_old_version(tmp_path):
    """Gate breach on deploy: 400 to the caller, the candidate version is
    restored to f32, the previously active version keeps serving."""
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.util.http import post_json
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer
    import urllib.error

    net, X, _ = _trained_net()
    ModelSerializer.write_model(net, os.path.join(tmp_path, "v1.zip"))
    ModelSerializer.write_model(net, os.path.join(tmp_path, "v2.zip"))
    srv = ServingServer(scan_dir=str(tmp_path), alert_interval_s=0,
                        quant_gate=QuantGate(max_rel_delta=0.0)).start()
    try:
        url = srv.url
        post_json(url + "/deploy", {"version": "v1"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            post_json(url + "/deploy",
                      {"version": "v2", "quantize": "int8",
                       "parity_inputs": X[:16].tolist()})
        assert ei.value.code == 400
        assert srv.registry.active_version == "v1"
        mv2 = srv.registry.get("v2")
        assert mv2.quantized is None and mv2.model._wq is None
        r = post_json(url + "/predict", {"data": X[:4].tolist()})
        assert r["version"] == "v1"
    finally:
        srv.stop()


def test_smoke_quant_tool():
    """ISSUE satellite wired as tier-1: train with 8-bit moments ->
    checkpoint -> restore at a different shard count -> deploy the zip
    int8-quantized -> /predict parity within the gate, zero steady-state
    recompiles, zero donation warnings (tools/smoke_quant.py, mirroring
    the smoke_ingest wiring)."""
    import tools.smoke_quant as smoke
    out = smoke.run(steps=25)
    assert out["moment_bytes_reduction_x"] >= 3.5
    assert out["q8_train_accuracy"] > 0.9
    assert out["parity"]["top1_agreement"] >= 0.97
    assert out["predict_rel_delta"] < 0.1
    assert out["steady_state_recompiles"] == 0
    assert out["donation_warnings"] == 0


def test_serving_quantized_deploy_by_name_synthesizes_parity(tmp_path):
    """Deploy-by-name + quantize with NO explicit parity rows: the zip in
    scan_dir is not registered yet, so the parity-input synthesis must
    resolve it (the same by-name load registry.deploy would do later)
    instead of KeyError-ing — quantized by-name deploys work like plain
    ones."""
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    net, X, _ = _trained_net()
    srv = ServingServer(scan_dir=str(tmp_path), alert_interval_s=0)
    # lands AFTER the startup scan -> unregistered until deploy-by-name
    ModelSerializer.write_model(net, os.path.join(tmp_path, "late.zip"))
    srv.deploy("late", quantize="int8")        # parity rows synthesized
    mv = srv.registry.get("late")
    assert mv.quantized == "int8" and mv.parity["gated"]
    assert srv.registry.active_version == "late"


def test_deploy_warmup_failure_unquantizes(tmp_path):
    """A warm-up failure AFTER a successful quantize must restore the f32
    weights: otherwise a later plain deploy(v) silently serves int8 weights
    that deploy never asked for."""
    from deeplearning4j_tpu.serving.registry import ModelRegistry

    net, X, _ = _trained_net()
    reg = ModelRegistry()
    reg.register("v1", net)

    def bad_warmup(model):
        raise RuntimeError("warm-up exploded")

    with pytest.raises(RuntimeError, match="warm-up exploded"):
        reg.deploy("v1", warmup=bad_warmup, quantize="int8",
                   parity_inputs=X[:16])
    mv = reg.get("v1")
    assert mv.quantized is None and mv.parity is None
    assert net._wq is None                      # f32 restored
    reg.deploy("v1")                            # plain deploy serves f32
    assert net.params["0"]["W"].dtype != jnp.int8


def test_sharded_trainer_refuses_quantized_model():
    """The 'serving-only' contract holds through ShardedTrainer too — the
    clear RuntimeError, not a jax.grad dtype error from int8 leaves."""
    net, X, Y = _trained_net()
    net.quantize_weights("int8")
    tr = ShardedTrainer(net, mesh=make_mesh(n_data=8))
    with pytest.raises(RuntimeError, match="serving-only"):
        tr.fit_batch(DataSet(X, Y))


def test_registry_subscriber_applies_quantized_deploy(tmp_path):
    """Fleet half: a broker-fanned deploy event carrying quantize="int8"
    (what FleetFrontend's /deploy broadcast publishes) brings a
    late-joining replica up with the SAME int8 executables, its own parity
    gate included."""
    from deeplearning4j_tpu.serving.frontend import RegistrySubscriber
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    net, X, _ = _trained_net()
    ModelSerializer.write_model(net, os.path.join(tmp_path, "v1.zip"))
    srv = ServingServer(scan_dir=str(tmp_path), alert_interval_s=0)
    sub = RegistrySubscriber(srv)        # apply-only (no broker loop)
    assert sub.apply({"kind": "deploy", "version": "v1",
                      "quantize": "int8",
                      "parity_inputs": X[:16].tolist()})
    assert srv.registry.active_version == "v1"
    mv = srv.registry.get("v1")
    assert mv.quantized == "int8" and mv.parity["gated"]


def test_model_version_quantize_idempotent_and_conflicts():
    from deeplearning4j_tpu.serving.registry import ModelVersion
    net, X, _ = _trained_net()
    mv = ModelVersion("v1", net)
    rep = mv.quantize("int8", parity_inputs=X[:16])
    assert mv.quantized == "int8"
    assert mv.quantize("int8") == rep      # idempotent per dtype
    with pytest.raises(ValueError):
        mv.quantize("int4")
