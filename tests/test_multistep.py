"""steps_per_execution: K train steps compiled into one executable
(nn/multistep.py) must be SEMANTICALLY IDENTICAL to K fit_batch calls —
same rng chain, same per-layer state threading, same scores — with
listeners firing on the documented K-step cadence, and graceful per-batch
fallback whenever a group can't scan."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import (NeuralNetConfiguration, InputType, DenseLayer,
                                OutputLayer, BatchNormalization,
                                MultiLayerNetwork, DataSet,
                                ListDataSetIterator, Sgd, Adam)
from deeplearning4j_tpu.optimize.listeners import IterationListener


def _mk_net(seed=5, dropout=None, bn=False, tbptt=False):
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2)).list()
    b = b.layer(DenseLayer(n_out=16, activation="tanh",
                           dropout=dropout))
    if bn:
        b = b.layer(BatchNormalization())
    b = b.layer(OutputLayer(n_out=3, activation="softmax", loss="MCXENT"))
    conf = b.input_type(InputType.feed_forward(8)).build()
    return MultiLayerNetwork(conf).init()


def _batches(n, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.normal(size=(batch, 8)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)]
        out.append(DataSet(x, y))
    return out


@pytest.mark.parametrize("dropout,bn", [(None, False), (0.3, False),
                                        (None, True)])
def test_multi_step_matches_per_batch(dropout, bn):
    """K-step scan == K singles: params, BN running state, and the rng
    chain (dropout masks) all line up."""
    sets = _batches(8)
    a = _mk_net(dropout=dropout, bn=bn)
    b = _mk_net(dropout=dropout, bn=bn)
    a.fit(ListDataSetIterator(sets))
    b.fit(ListDataSetIterator(sets), steps_per_execution=4)
    np.testing.assert_allclose(a.get_flat_params(), b.get_flat_params(),
                               rtol=1e-5, atol=1e-6)
    for sa, sb in zip(jax.tree_util.tree_leaves(a.states),
                      jax.tree_util.tree_leaves(b.states)):
        np.testing.assert_allclose(np.asarray(sa), np.asarray(sb),
                                   rtol=1e-5, atol=1e-6)
    assert a.iteration_count == b.iteration_count == 8
    # per-step scores surface from the scan
    assert b.last_scores.shape == (4,)
    assert np.isclose(float(b.last_scores[-1]), b.score_value)


def test_multi_step_listener_cadence_and_ragged_tail():
    """10 batches at K=4: two scanned groups fire listeners at iterations 4
    and 8; the ragged tail of 2 runs per-batch at 9 and 10."""
    seen = []

    class Recorder(IterationListener):
        def iteration_done(self, model, iteration):
            seen.append(iteration)

    net = _mk_net()
    net.set_listeners(Recorder())
    net.fit(ListDataSetIterator(_batches(10)), steps_per_execution=4)
    assert seen == [4, 8, 9, 10]
    assert net.iteration_count == 10


def test_multi_step_mixed_mask_group_falls_back():
    """A group mixing masked and unmasked batches can't stack into one scan
    pytree — it must quietly run per-batch and still train correctly."""
    from deeplearning4j_tpu import RnnOutputLayer, GravesLSTM
    conf = (NeuralNetConfiguration.builder().seed(5).updater(Sgd(0.1)).list()
            .layer(GravesLSTM(n_out=8, activation="tanh"))
            .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                  loss="MCXENT"))
            .input_type(InputType.recurrent(4)).build())
    rng = np.random.default_rng(1)
    sets = []
    for i in range(4):
        x = rng.normal(size=(2, 6, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (2, 6))]
        m = np.ones((2, 6), np.float32) if i % 2 else None
        sets.append(DataSet(x, y, features_mask=m, labels_mask=m))
    a = MultiLayerNetwork(conf).init()
    b = MultiLayerNetwork(conf).init()
    a.fit(ListDataSetIterator(sets))
    b.fit(ListDataSetIterator(sets), steps_per_execution=4)
    np.testing.assert_allclose(a.get_flat_params(), b.get_flat_params(),
                               rtol=1e-6, atol=1e-7)
    assert b.iteration_count == 4


def _tbptt_conf(T_unused=None):
    from deeplearning4j_tpu import RnnOutputLayer, GravesLSTM
    return (NeuralNetConfiguration.builder().seed(5).updater(Sgd(0.1))
            .list()
            .layer(GravesLSTM(n_out=8, activation="tanh"))
            .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                  loss="MCXENT"))
            .input_type(InputType.recurrent(4))
            .backprop_type("truncated_bptt").tbptt_fwd_length(4)
            .tbptt_back_length(4)
            .build())


def _tbptt_sets(T, n=4, seed=2):
    rng = np.random.default_rng(seed)
    sets = []
    for _ in range(n):
        x = rng.normal(size=(2, T, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (2, T))]
        sets.append(DataSet(x, y))
    return sets


def test_multi_step_tbptt_scans_with_parity():
    """TBPTT batches whose windows tile the sequence scan too: K batches x W
    windows flatten into one executable with carry resets at batch
    boundaries and a replayed rng table — params, carried-state semantics,
    and the window-mean scores all match per-batch TBPTT."""
    seen = []

    class Recorder(IterationListener):
        def iteration_done(self, model, iteration):
            seen.append(iteration)

    sets = _tbptt_sets(T=12)   # W = 3 windows of L=4
    a = MultiLayerNetwork(_tbptt_conf()).init()
    b = MultiLayerNetwork(_tbptt_conf()).init()
    b.set_listeners(Recorder())
    a.fit(ListDataSetIterator(sets))
    b.fit(ListDataSetIterator(sets), steps_per_execution=2)
    np.testing.assert_allclose(a.get_flat_params(), b.get_flat_params(),
                               rtol=1e-6, atol=1e-7)
    assert seen == [2, 4]          # K-step cadence, 2 groups of K=2
    assert b.last_scores.shape == (2,)
    # per-batch score = mean over that batch's windows == singles' score
    a2 = MultiLayerNetwork(_tbptt_conf()).init()
    for ds in sets:
        a2.fit_batch(ds)
    np.testing.assert_allclose(float(b.last_scores[-1]), a2.score_value,
                               rtol=1e-5)


def test_multi_step_tbptt_ragged_windows_fall_back():
    """T=10 does not tile into L=4 windows: the group must quietly run
    per-batch TBPTT and still match plain fit."""
    sets = _tbptt_sets(T=10, seed=3)
    a = MultiLayerNetwork(_tbptt_conf()).init()
    b = MultiLayerNetwork(_tbptt_conf()).init()
    a.fit(ListDataSetIterator(sets))
    b.fit(ListDataSetIterator(sets), steps_per_execution=2)
    np.testing.assert_allclose(a.get_flat_params(), b.get_flat_params(),
                               rtol=1e-6, atol=1e-7)


def _mk_graph():
    from deeplearning4j_tpu import ComputationGraph
    conf = (NeuralNetConfiguration.builder().seed(9).updater(Adam(1e-2))
            .graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="MCXENT"), "d")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(8)).build())
    return ComputationGraph(conf).init()


def test_multi_step_computation_graph_parity():
    """ComputationGraph shares the mixin: scanned groups == singles."""
    sets = _batches(6)
    a, b = _mk_graph(), _mk_graph()
    a.fit(ListDataSetIterator(sets))
    b.fit(ListDataSetIterator(sets), steps_per_execution=3)
    for pa, pb in zip(jax.tree_util.tree_leaves(a.params),
                      jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=1e-5, atol=1e-6)
    assert a.iteration_count == b.iteration_count == 6
    assert b.last_scores.shape == (3,)


def _in_row_chunks(sets, n_chunks=4):
    """The same batches as a DevicePrefetcher(transfer_streams=n_chunks)
    hands them over: features in row chunks on the device, not joined."""
    from deeplearning4j_tpu.etl.prefetch import RowChunks
    return [DataSet(RowChunks(jax.device_put(c) for c in
                              np.array_split(ds.features, n_chunks)),
                    ds.labels) for ds in sets]


@pytest.mark.parametrize("kind", ["multilayer", "graph", "tbptt"])
def test_prepare_steps_joins_row_chunks_inside_the_plan(kind, monkeypatch):
    """Features that arrive as RowChunks are joined by the program that
    stacks the plan (no join program of their own, no second copy of the
    batch), the plan and the training it drives equal the host arrays';
    a TBPTT group and fit_batch, which need the array, join them first."""
    from deeplearning4j_tpu.etl.prefetch import RowChunks
    if kind == "tbptt":
        build = lambda: MultiLayerNetwork(_tbptt_conf()).init()
        sets = [DataSet(np.repeat(d.features, 4, 0), np.repeat(d.labels, 4, 0))
                for d in _tbptt_sets(T=12)]          # 8 rows: 4 chunks of 2
    else:
        build, sets = (_mk_graph if kind == "graph" else _mk_net), _batches(4)
    chunked = _in_row_chunks(sets)
    joins = []                      # was a join traced (in a program) or run?
    real = RowChunks.__jax_array__
    monkeypatch.setattr(RowChunks, "__jax_array__", lambda c: (
        joins.append(isinstance(c.parts[0], jax.core.Tracer)), real(c))[1])
    a, b = build(), build()
    plan_a, plan_b = a.prepare_steps(sets[:2]), b.prepare_steps(chunked[:2])
    assert plan_a[0] == plan_b[0] == ("tbptt" if kind == "tbptt" else "std")
    # std: both batches' chunks joined while the ONE plan program was
    # traced; tbptt: two joins of their own before its window plan
    assert joins == [kind != "tbptt"] * 2
    for la, lb in zip(jax.tree_util.tree_leaves(plan_a[1]),
                      jax.tree_util.tree_leaves(plan_b[1])):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    a.fit(ListDataSetIterator(sets), steps_per_execution=2)
    b.fit(ListDataSetIterator(chunked), steps_per_execution=2)
    for pa, pb in zip(jax.tree_util.tree_leaves(a.params),
                      jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    # the per-batch path takes them too, joined on its own
    del joins[:]
    b.fit_batch(chunked[0])
    a.fit_batch(sets[0])
    assert joins == [False]
    np.testing.assert_allclose(float(a.score_value), float(b.score_value),
                               rtol=1e-6)


def test_prepare_steps_reusable_executable():
    """prepare_steps + fit_prepared: the train loop's hot path — one prepared stack
    can run repeatedly (inputs are NOT donated) and each run advances
    training by K steps."""
    net = _mk_net()
    sets = _batches(4)
    prepared = net.prepare_steps(sets)
    assert prepared is not None
    s0 = None
    for i in range(3):
        net.fit_prepared(prepared)
        if i == 0:
            s0 = float(net.last_scores[-1])
    assert net.iteration_count == 12
    assert float(net.last_scores[-1]) < s0


def test_sharded_trainer_steps_per_execution_parity():
    """K sharded steps inside one scanned executable (collectives inside the
    scan) must equal K per-batch sharded steps AND K single-device steps —
    the multi-chip hot path loses its per-step host dispatch without
    changing semantics."""
    from deeplearning4j_tpu.parallel.sharding import ShardedTrainer, make_mesh

    sets = _batches(8, batch=32, seed=4)
    single = _mk_net()
    for ds in sets:
        single.fit_batch(ds)

    sharded_1 = _mk_net()
    tr1 = ShardedTrainer(sharded_1, mesh=make_mesh(n_data=8))
    tr1.fit(ListDataSetIterator(sets))
    np.testing.assert_allclose(single.get_flat_params(),
                               sharded_1.get_flat_params(),
                               rtol=1e-5, atol=1e-6)

    sharded_k = _mk_net()
    trk = ShardedTrainer(sharded_k, mesh=make_mesh(n_data=8))
    trk.fit(ListDataSetIterator(sets), steps_per_execution=4)
    np.testing.assert_allclose(single.get_flat_params(),
                               sharded_k.get_flat_params(),
                               rtol=1e-5, atol=1e-6)
    assert sharded_k.iteration_count == 8
    assert sharded_k.last_scores.shape == (4,)


def test_sharded_trainer_grouped_padding_falls_back():
    """A group containing a batch that needs wrap-padding (not divisible by
    the data axis) must quietly run per-batch — no example dropped, params
    still match the single-device run."""
    from deeplearning4j_tpu.parallel.sharding import ShardedTrainer, make_mesh

    sets = _batches(4, batch=32, seed=5)
    odd = _batches(1, batch=27, seed=6)  # 27 % 8 != 0
    mixed = sets[:2] + odd + sets[2:]
    single = _mk_net()
    for ds in mixed:
        single.fit_batch(ds)
    sharded = _mk_net()
    tr = ShardedTrainer(sharded, mesh=make_mesh(n_data=8))
    tr.fit(ListDataSetIterator(mixed), steps_per_execution=5)
    np.testing.assert_allclose(single.get_flat_params(),
                               sharded.get_flat_params(),
                               rtol=1e-5, atol=1e-6)
    assert sharded.examples_fit == 32 * 4 + 27


def test_lstm_tbptt_carry_donation_no_warnings_both_paths():
    """ISSUE-7 satellite: the char_rnn/LSTM TBPTT carries must donate
    cleanly on BOTH training paths — the scanned multi_tbptt executable
    (fixed in PR 6: final carries are scan outputs) and the per-window
    fit_batch path (carries are donate_argnums=8 of the tbptt train step).
    JAX computes donation aliasing platform-independently at lowering, so
    this CPU test catches a donated-but-unusable carry buffer ("Some
    donated buffers were not usable: float32[64,256] x4") exactly as a TPU
    run would."""
    import warnings
    from deeplearning4j_tpu.zoo.models import char_rnn_lstm

    def mk():
        net = char_rnn_lstm(vocab_size=12, hidden=16, layers=2, tbptt=5)
        return net.init()

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 12, size=(4, 21))
    x = np.eye(12, dtype=np.float32)[ids[:, :-1]]
    y = np.eye(12, dtype=np.float32)[ids[:, 1:]]
    ds = DataSet(jnp.asarray(x), jnp.asarray(y))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        per_window = mk()
        per_window.fit_batch(ds)             # per-window tbptt train step
        per_window.fit_batch(ds)
        scanned = mk()
        plan = scanned.prepare_steps([ds] * 2)
        assert plan is not None and plan[0] == "tbptt"
        scanned.fit_prepared(plan)           # scanned multi_tbptt executable
        scanned.fit_prepared(plan)
    donation = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert donation == [], donation
    # both paths still train to finite scores
    assert np.isfinite(float(per_window.score_value))
    assert np.isfinite(float(scanned.score_value))


def test_char_rnn_bench_call_sequence_donation_clean():
    """ISSUE-9 satellite: a timing loop's call sequence over the char-RNN
    (an eligibility-probe prepare_steps, then K- and 2K-deep plans each
    fit_prepared twice, interleaved) must lower with zero "Some donated
    buffers were not usable" warnings — this path's carries drew one
    before they became scan outputs. Donation aliasing is computed
    platform-independently at lowering, so the CPU run guards the TPU."""
    import warnings
    from deeplearning4j_tpu.zoo.models import char_rnn_lstm

    net = char_rnn_lstm(vocab_size=12, hidden=16, layers=2, tbptt=5).init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 12, size=(8, 21))
    x = np.eye(12, dtype=np.float32)[ids[:, :-1]]
    y = np.eye(12, dtype=np.float32)[ids[:, 1:]]
    ds = DataSet(jnp.asarray(x), jnp.asarray(y))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        plan = net.prepare_steps([ds] * 2)         # eligibility probe
        assert plan is not None and plan[0] == "tbptt"
        K = 3
        p1 = net.prepare_steps([ds] * K)
        p2 = net.prepare_steps([ds] * (2 * K))
        net.fit_prepared(p1)                       # compile + warm both
        net.fit_prepared(p2)
        net.fit_prepared(p1)                       # timed-loop re-runs
        net.fit_prepared(p2)
    donation = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert donation == [], donation
    assert np.isfinite(float(net.score_value))


def test_bench_r05_exact_geometry_donation_clean():
    """ISSUE-15 satellite: the warning once named EXACTLY
    `float32[64,256] x4` — a char-RNN at batch 64, hidden 256, 2 LSTM
    layers x (h, c) carries. The small-geometry tests above guard the code
    path; this one pins the literal buffer shapes, so a donation
    regression reproduces that warning VERBATIM and can never be dismissed
    as a different workload. Every [64,256]-shaped candidate (scanned
    TBPTT, per-window TBPTT, generate, rnn_time_step) lowers clean; the
    original emitter was the TBPTT carries before they were donated as
    scan outputs."""
    import warnings
    from deeplearning4j_tpu.zoo.models import char_rnn_lstm

    net = char_rnn_lstm(vocab_size=20, hidden=256, layers=2, tbptt=5).init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 20, size=(64, 11))    # batch 64 -> [64,256] carries
    x = np.eye(20, dtype=np.float32)[ids[:, :-1]]
    y = np.eye(20, dtype=np.float32)[ids[:, 1:]]
    ds = DataSet(jnp.asarray(x), jnp.asarray(y))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        net.fit_batch(ds)                      # per-window tbptt path
        plan = net.prepare_steps([ds] * 2)     # scanned multi_tbptt path
        assert plan is not None and plan[0] == "tbptt"
        net.fit_prepared(plan)
    donation = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert donation == [], donation
    # the historical shape string must appear in NO warning of any kind
    offender = [str(w.message) for w in caught if "64,256" in str(w.message)]
    assert offender == [], offender
    assert np.isfinite(float(net.score_value))
