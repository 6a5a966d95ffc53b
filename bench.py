#!/usr/bin/env python
"""Benchmark harness: trains the BASELINE configs on the real chip and prints
ONE JSON line {"metric", "value", "unit", "vs_baseline", ...extras}.

Headline metric: ResNet-50 ComputationGraph.fit() samples/sec/chip (BASELINE
config #2 / north star), bf16 mixed precision (f32 master params/BN/loss).
Extras carry the other four BASELINE configs (LeNet #1, GravesLSTM char-RNN
#3, multi-replica scaling #4 REHEARSED on a virtual CPU mesh subprocess,
Word2Vec #5), an END-TO-END number through fit(DataSetIterator) with
uint8-on-the-wire input and device prefetch, the transformer LM (tokens/sec)
and the Pallas flash-attention kernel fwd/bwd vs the reference einsum path.

Roofline methodology (PERF.md carries the full dossier):
 - Ceilings are measured with the probe INSIDE one executable (lax.scan of
   chained matmuls / elementwise passes), so per-launch dispatch and readback
   cost cannot enter the number.
 - Per-step work is XLA's own accounting of the compiled train step:
   Compiled.cost_analysis() flops and bytes-accessed (fusions count external
   operands/outputs only, so bytes-accessed is an upper bound on HBM
   traffic that ignores any cache reuse).
 - roofline_util = max(flops/tf_ceiling, bytes/bw_ceiling) / measured step
   time: utilization of the BINDING resource (`roofline_binding` names it).
   A value near (or above) 1.0 means the step extracts the hardware's
   measured ceiling for its dominant resource; >1.0 is possible because
   bytes-accessed overestimates true traffic.
 - Published peaks come from ONE table keyed by device_kind
   (telemetry.cost.DEVICE_PEAKS); a device the table does not list is an
   error here, not a default.

Timing methodology: dispatch is async, so every timed region ends in a
device->host readback. Small signals are DIFFERENCE-TIMED (`_diff_time`:
interleaved K- vs 2K-deep executables, min-vs-min, self-check) so whatever
constant cost a call carries cancels instead of being subtracted with error;
only the long-signal ResNet loop uses plain fenced timing. The train step
itself never syncs (score stays on device). The training benches run the
loop INSIDE one executable — `fit(steps_per_execution=K)` compiles K
optimizer steps into a single lax.scan (nn/multistep.py), so one dispatch
covers K steps. Whether the per-call floor this was built around exists on
today's machine has not been measured (ROADMAP D1).

The JSON also carries a session-health block (readback floor, measured
ceilings, a fixed-size probe step) and a `regressions` list comparing headline
metrics against the best prior BENCH_r*.json.

The e2e bench goes through the DEVICE-SIDE INGEST path (BENCH_r05 measured
`e2e_binding=host_link`, e2e_vs_compute=0.077): narrow uint8 pixels + int32
ids on the wire with the one-hot/widening fused into the scanned step
(etl.device_transform + net.set_ingest), multi-stream chunked h2d
(DevicePrefetcher transfer_streams), and `h2d_bytes_per_sample` /
`ingest_dtype` attribution fields.

Virtual-mesh rehearsals: bench_scaling_subprocess and bench_mesh_serving run
in CHILD processes pinned to the CPU with 8 virtual devices (the children
never ask for the chip this process holds). What they return is a rehearsal
of partitioning, not a device measurement: it is printed under
`cpu_virtual_mesh_rehearsal`, apart from the device metrics.

A phase that fails is reported and the run exits non-zero; there is no
substitute headline.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np


ASSUMED_BASELINE_SAMPLES_PER_SEC = 1000.0
RESNET50_FLOPS_PER_SAMPLE = 3 * 4.09e9  # fwd+bwd ~= 3x fwd @224^2


def _sync(x):
    import jax
    return np.asarray(jax.device_get(x))


def _readback_floor_ms(reps=5):
    import jax.numpy as jnp
    t = []
    for _ in range(reps):
        z = jnp.zeros(())
        t0 = time.perf_counter()
        _sync(z)
        t.append(time.perf_counter() - t0)
    return min(t) * 1e3


def _best_of(trials, timed_run):
    """Min over `trials` invocations of timed_run() -> elapsed seconds: the
    host's interference only ever adds time, so the min is the estimate of
    the step cost itself."""
    return min(timed_run() for _ in range(trials))


def _time_steps(run_step, steps, fence, trials=3):
    """Best-of-`trials` seconds for `steps` calls of run_step(i), each trial
    fenced by a device->host readback (`fence`)."""
    def timed():
        t0 = time.perf_counter()
        for i in range(steps):
            run_step(i)
        fence()
        return time.perf_counter() - t0
    return _best_of(trials, timed)


def _diff_time(run_k, run_2k, trials=5):
    """Floor-FREE seconds for K extra iterations, robust to a per-call cost
    (dispatch + readback) that is constant in depth but varies from call to
    call. Subtracting a separately measured floor is unsafe then, and so is
    any mean/median-of-differences scheme (an unbalanced draw of floor
    values between the two depth groups shifts the median). Estimator:
    INTERLEAVE the K- and 2K-deep runs so both groups sample the same
    conditions, then take min(t_2K) − min(t_K) — each min converges to
    signal·depth + the SAME lowest floor, which cancels whatever the floor
    distribution is, needing only one low-floor sample per group.

    Self-check: under the model t = signal*depth + floor with floor >= 0,
    the true difference can never exceed half of min(t_2K); an estimate
    violating that means a stall swallowed one whole sample group —
    resample up to twice before accepting the least-bad round."""
    positives = []
    for _ in range(3):
        t1s, t2s = [], []
        for _ in range(trials):
            t1s.append(run_k())
            t2s.append(run_2k())
        est = min(t2s) - min(t1s)
        if 0 < est <= 0.55 * min(t2s):
            return est
        if est > 0:
            positives.append(est)
    if positives:
        return min(positives)   # least-bad round that at least went forward
    # every round inverted (K-group outages): no defensible number exists —
    # surface the failure instead of publishing signal/1e-9 absurdities
    raise RuntimeError("_diff_time: stalls corrupted all sample rounds; "
                       "measurement aborted")


def _scanned_fit_step_s(net, ds, K, trials=5):
    """Per-train-step seconds via two scanned executions (K and 2K steps
    inside one executable each; see nn/multistep.py), difference-timed.
    trials=5 keeps the chance that one depth group never samples the low
    floor (biasing the min-difference by the gap) under ~6% even for
    adversarially i.i.d. floors."""
    p1 = net.prepare_steps([ds] * K)
    p2 = net.prepare_steps([ds] * (2 * K))
    net.fit_prepared(p1)
    net.fit_prepared(p2)            # compile + warm both
    _sync(net._score_dev)

    def timed(prepared):
        def run():
            t0 = time.perf_counter()
            net.fit_prepared(prepared)
            _sync(net._score_dev)
            return time.perf_counter() - t0
        return run
    return _diff_time(timed(p1), timed(p2), trials=trials) / K


def _measure_ceilings():
    """Measured roofline ceilings of this chip: bf16 matmul TFLOP/s and
    elementwise HBM GB/s. Each probe runs inside ONE executable (lax.scan)
    at TWO depths (K and 2K) and the per-iteration cost is the DIFFERENCE —
    the session-dependent 70-110 ms dispatch+readback floor cancels exactly
    instead of being subtracted with ± several-ms error (the r04 floor
    subtraction is how a 541 GB/s "ceiling", and the roofline_util = 1.49 it
    implied, got recorded in a bad session)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    M, KM = 8192, 40
    A = jnp.ones((M, M), jnp.bfloat16)

    def make_mm(K):
        @jax.jit
        def mm_scan(a):
            def body(c, _):
                c = jnp.dot(c, a, preferred_element_type=jnp.bfloat16)
                return (c * 1e-4).astype(jnp.bfloat16), ()
            out, _ = lax.scan(body, a, None, length=K)
            return out[0, 0]
        return mm_scan

    def timed(fn, arg):
        _sync(fn(arg))  # compile + warm

        def run():
            t0 = time.perf_counter()
            _sync(fn(arg))
            return time.perf_counter() - t0
        return run

    tf = 2 * M ** 3 * KM / _diff_time(timed(make_mm(KM), A),
                                      timed(make_mm(2 * KM), A))

    x = jnp.ones((256, 1024, 1024), jnp.bfloat16)  # 512 MiB
    KB = 150

    def make_ew(K):
        @jax.jit
        def ew_scan(x):
            def body(c, _):
                return c * 1.0001 + 1.0, ()
            out, _ = lax.scan(body, x, None, length=K)
            return out.ravel()[0]
        return ew_scan

    bw = 2 * x.nbytes * KB / _diff_time(timed(make_ew(KB), x),
                                        timed(make_ew(2 * KB), x))
    return tf, bw


def _step_cost(net, inputs, labels):
    """XLA's flops + bytes-accessed for the compiled ComputationGraph train
    step (the arithmetic behind roofline_util; see PERF.md), read through the
    SAME telemetry.cost helper the live /profile/cost plane uses, and
    cross-checked against an ExecutableCostRegistry capture of the same
    executable: the offline bench numbers and the live serving telemetry
    must agree exactly (one extraction path) or the bench fails loudly."""
    from deeplearning4j_tpu.telemetry.cost import (ExecutableCostRegistry,
                                                   compiled_costs)
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
    step = net._jit_cache["std"]
    comp = step.lower(net.params, net.opt_state, net.states, net._rng,
                      inputs, labels, None, None, None).compile()
    costs = compiled_costs(comp)
    batch = int(inputs[0].shape[0])
    live = ExecutableCostRegistry(MetricsRegistry()).capture_compiled(
        "bench:train_step", comp, family="bench", samples=batch)
    for key in ("flops", "hbm_bytes"):
        got, want = live[key + "_per_sample"] * batch, costs[key]
        if abs(got - want) > 0.05 * max(abs(want), 1.0):
            raise AssertionError(
                f"live/offline {key} disagree: {got} vs {want}")
    return costs["flops"], costs["hbm_bytes"]


def bench_resnet50(batch=256, image=224, steps=20, K=5,
                   compute_dtype="bfloat16"):
    """BASELINE #2: compute-only samples/sec. K train steps run inside one
    scanned executable (fit(steps_per_execution=K)); the timed loop spans
    steps/K executions, so per-dispatch cost divides away by K."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.models import resnet50
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.updaters import Nesterovs

    net = resnet50(num_classes=1000, image_size=image,
                   updater=Nesterovs(learning_rate=0.05, momentum=0.9),
                   compute_dtype=compute_dtype)
    net.init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, image, image, 3)).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
    ds = DataSet(jnp.asarray(x), jnp.asarray(y))

    net.fit_batch(ds)   # compiles the single-step executable (cost analysis)
    if os.environ.get("BENCH_PROFILE"):
        # capture an XLA per-step profile (ui/stats.py ProfilerListener;
        # TensorBoard trace under $BENCH_PROFILE) in a separate per-step
        # phase so the trace has real iteration boundaries
        from deeplearning4j_tpu.ui.stats import ProfilerListener
        net.set_listeners(ProfilerListener(os.environ["BENCH_PROFILE"],
                                           start_iteration=3, n_iterations=5))
        for _ in range(8):
            net.fit_batch(ds)
        net.set_listeners()
    prepared = net.prepare_steps([ds] * K)
    net.fit_prepared(prepared)          # compile the scanned loop + warm
    _sync(net._score_dev)
    floor_ms = _readback_floor_ms()
    n_exec = max(1, steps // K)
    total_ms = _time_steps(lambda i: net.fit_prepared(prepared), n_exec,
                           lambda: _sync(net._score_dev),
                           trials=2) * 1e3 - floor_ms
    step_ms = max(total_ms, 1e-6) / (n_exec * K)
    sps = batch / (step_ms / 1e3)
    try:
        flops, nbytes = _step_cost(net, [ds.features], [ds.labels])
    except Exception as e:
        print(f"cost_analysis failed: {type(e).__name__}: {e}", file=sys.stderr)
        flops = nbytes = None
    return sps, step_ms, flops, nbytes


def bench_resnet50_end_to_end(compute_step_ms, batch=256, image=224,
                              n_batches=8, compute_dtype="bfloat16",
                              steps_per_execution=4, prefetch=3, streams=8):
    """End-to-end fit(DataSetIterator) through the DEVICE-SIDE INGEST path
    (ROADMAP item 3 / BENCH_r05 `e2e_binding=host_link`):

    - uint8 NHWC pixels + int32 class ids on the wire — the 1000-wide
      one-hot label matrix (1 MB/batch) never crosses the link; it expands
      on device inside the compiled step (DeviceIngest.apply_labels fused
      via net.set_ingest, ImageScalerPreProcessor widening the pixels
      on-chip as before).
    - DevicePrefetcher(transfer_streams=S): each batch's DMA is S concurrent
      row-chunk puts; `h2d_mb_per_sec_streamed` vs `h2d_mb_per_sec` shows
      in the JSON whether chunking raises sustained h2d on this machine
      (not measured on today's code — ROADMAP S2).
    - fit(steps_per_execution=K): K steps per compiled dispatch, so per-step
      dispatch cost divides away by K while transfers overlap the scanned
      compute.

    Reports per-batch link_ms (measured single-put h2d of one uint8 batch)
    and compute_ms next to the per-batch wall so the overlap claim stays
    checkable (`e2e_overlap` = fraction of the smaller leg hidden; None when
    the legs differ >10x and the ratio would be noise — the hard overlap
    assertion lives in tests/test_iterators.py on the CPU backend). New
    attribution fields: `h2d_bytes_per_sample` and `ingest_dtype`, so an
    e2e_vs_compute move is attributable to narrower transfers."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.models import resnet50
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator.base import ListDataSetIterator
    from deeplearning4j_tpu.etl.device_transform import DeviceIngest
    from deeplearning4j_tpu.etl.prefetch import DevicePrefetcher
    from deeplearning4j_tpu.nn.updaters import Nesterovs

    net = resnet50(num_classes=1000, image_size=image,
                   updater=Nesterovs(learning_rate=0.05, momentum=0.9),
                   compute_dtype=compute_dtype)
    net.init()
    net.set_ingest(DeviceIngest(one_hot_labels=1000))
    rng = np.random.default_rng(0)
    sets = []
    for _ in range(n_batches):
        x = rng.integers(0, 256, size=(batch, image, image, 3), dtype=np.uint8)
        y = rng.integers(0, 1000, batch).astype(np.int32)
        sets.append(DataSet(x, y))
    bytes_per_sample = image * image * 3 + 4          # uint8 pixels + int32 id

    # measured h2d link legs on one uint8 batch, best of 3:
    # single put (the historical h2d_mb_per_sec) vs `streams` concurrent
    # chunk puts (what the prefetcher actually does now)
    xh = sets[0].features
    _sync(jnp.sum(jax.device_put(xh).astype(jnp.float32)))
    link_s, streamed_s = [], []
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=streams) as pool:
        for _ in range(3):
            t0 = time.perf_counter()
            dev = jax.device_put(xh)
            _sync(dev.ravel()[0])
            link_s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            parts = [f.result() for f in
                     [pool.submit(jax.device_put, c)
                      for c in np.array_split(xh, streams)]]
            _sync(jnp.concatenate(parts, axis=0).ravel()[0])
            streamed_s.append(time.perf_counter() - t0)
    link_ms = min(link_s) * 1e3
    link_ms_streamed = min(streamed_s) * 1e3
    h2d_mb_s = xh.nbytes / 1e6 / (link_ms / 1e3)
    h2d_mb_s_streamed = xh.nbytes / 1e6 / min(streamed_s)

    K = max(1, int(steps_per_execution))
    net.fit(ListDataSetIterator(sets[:K]), steps_per_execution=K)  # compile
    _sync(net._score_dev)
    t0 = time.perf_counter()
    it = DevicePrefetcher(ListDataSetIterator(sets), queue_size=prefetch,
                          transfer_streams=streams)
    net.fit(it, steps_per_execution=K)
    _sync(net._score_dev)
    wall_ms = (time.perf_counter() - t0) * 1e3 / n_batches
    it.close()
    e2e_sps = batch / (wall_ms / 1e3)
    # overlap/binding judge the STREAMED leg — the transfer path the
    # measured fit actually takes (single-put link_ms stays reported for
    # continuity with BENCH_r05)
    legs = sorted((link_ms_streamed, compute_step_ms))
    if legs[1] > 10 * legs[0]:
        overlap = None
    else:
        overlap = (link_ms_streamed + compute_step_ms - wall_ms) \
            / max(legs[0], 1e-9)
    return {"e2e_sps": e2e_sps, "h2d_mb_s": h2d_mb_s,
            "h2d_mb_s_streamed": h2d_mb_s_streamed, "link_ms": link_ms,
            "link_ms_streamed": link_ms_streamed,
            "wall_ms": wall_ms, "overlap": overlap,
            "bytes_per_sample": bytes_per_sample, "ingest_dtype": "uint8",
            "streams": streams, "steps_per_execution": K}


def bench_lenet(batch=128, K=400, trials=5):
    """BASELINE #1, via the compiled K-step loop (one executable per K train
    steps) with difference timing, so neither per-dispatch cost nor the
    readback floor touches the number."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.models import lenet_mnist
    from deeplearning4j_tpu.datasets.dataset import DataSet

    net = lenet_mnist()
    net.init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((batch, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)])
    step_s = _scanned_fit_step_s(net, DataSet(x, y), K, trials=trials)
    return batch / step_s, step_s * 1e3


def bench_mnist_real_accuracy(epochs=6):
    """BASELINE #1 on REAL digits (committed fixture, tests/fixtures/
    mnist_real): full fit() run -> held-out accuracy, f32 AND int8-weight-
    quantized (the serving parity number behind
    `quantized_vs_f32_accuracy_delta`). Returns (acc, acc_int8) — acc_int8
    None if quantization fails — or None when only the synthetic fallback
    is available (fixture deleted)."""
    from deeplearning4j_tpu.datasets.fetchers.mnist import (
        MnistDataSetIterator, load_mnist)
    from deeplearning4j_tpu.zoo.models import lenet_mnist

    from deeplearning4j_tpu.datasets.fetchers.mnist import _find_mnist_files
    if _find_mnist_files(train=True)[0] is None:
        return None  # synthetic fallback engaged; accuracy would be bogus
    net = lenet_mnist()
    net.init()
    net.fit(MnistDataSetIterator(batch_size=64, train=True, seed=3),
            epochs=epochs)
    test_it = MnistDataSetIterator(batch_size=250, train=False,
                                   shuffle=False)
    acc = net.evaluate(test_it).accuracy()
    acc_q = None
    try:
        net.quantize_weights("int8")
        acc_q = net.evaluate(test_it).accuracy()
    except Exception as e:
        print(f"ucidigits int8 eval failed: {e}", file=sys.stderr)
    return acc, acc_q


def bench_real32_accuracy(epochs=10):
    """Real-photo 32x32 gate (VERDICT r4 next #7): the shared recipe in
    datasets/fetchers/standard.py (small convnet + flips on the committed
    cifar_real fixture — real photograph crops, CIFAR binary layout, spatial
    train/test split, NOT the CIFAR-10 classes). Returns (accuracy,
    int8-quantized accuracy), or None when only synthetic data is found."""
    from deeplearning4j_tpu.datasets.fetchers.standard import (
        real32_gate_accuracy)
    return real32_gate_accuracy(epochs=epochs, quantized_delta=True)


def bench_char_rnn(batch=64, seq=200, vocab=80, steps=20, trials=5):
    """BASELINE #3: GravesLSTM char-RNN TBPTT training throughput
    (chars/sec; the reference hot loop is LSTMHelpers.java:172-174 per-step
    gemms — here one lax.scan over fused gemms). The K batches x 4 TBPTT
    windows now ALL run inside one executable (the tbptt window scan in
    nn/multistep.py), so no per-window dispatch touches the number. f32 by
    MEASUREMENT, not fear: compute_dtype="bfloat16" runs safely (f32 carry,
    bf16 gemms) but benched SLOWER on the v5e at hidden 256 (222k vs 298k
    chars/s) and 1024 (179k vs 193k) — the per-step carry casts outweigh
    the MXU win at scan-sized recurrent gemms."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.models import char_rnn_lstm
    from deeplearning4j_tpu.datasets.dataset import DataSet

    net = char_rnn_lstm(vocab_size=vocab, hidden=256, layers=2, tbptt=50)
    net.init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(batch, seq + 1))
    x = np.eye(vocab, dtype=np.float32)[ids[:, :-1]]
    y = np.eye(vocab, dtype=np.float32)[ids[:, 1:]]
    ds = DataSet(jnp.asarray(x), jnp.asarray(y))
    plan = net.prepare_steps([ds] * 2)
    assert plan is not None and plan[0] == "tbptt", \
        "char-RNN bench expects the scanned TBPTT path"
    step_s = _scanned_fit_step_s(net, ds, steps, trials=trials)
    return batch * seq / step_s


def bench_transformer_lm(batch=16, seq=512, vocab=256, steps=10, trials=5):
    """Flagship-adjacent transformer LM: tokens/sec through the full
    ComputationGraph train step (4 layers, d_model 256, 4 heads, causal,
    Pallas flash attention, bf16 compute), all `steps` steps inside one
    scanned executable."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.models import transformer_lm
    from deeplearning4j_tpu.datasets.dataset import DataSet

    net = transformer_lm(vocab_size=vocab, d_model=256, n_layers=4, n_heads=4,
                         use_pallas=True, compute_dtype="bfloat16")
    net.init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(batch, seq + 1))
    x = np.eye(vocab, dtype=np.float32)[ids[:, :-1]]
    y = np.eye(vocab, dtype=np.float32)[ids[:, 1:]]
    ds = DataSet(jnp.asarray(x), jnp.asarray(y))
    step_s = _scanned_fit_step_s(net, ds, steps, trials=trials)
    return batch * seq / step_s


def bench_flash_attention(B=4, H=8, T=4096, D=64, K=8):
    """Pallas flash-attention kernel vs the einsum reference, fwd+bwd on the
    real chip (compiled, not interpret), every path difference-timed inside
    scanned executables in the SAME run. T=4096 is
    where the long-context story lives: the reference materializes a 2.1 GB
    [T,T] score temp, flash holds 236 MB of block tiles + the LSE residual.
    Also times ring_attention on a 1-device mesh (VERDICT r4 next #4
    done-criterion: the ring's per-shard update IS the kernel now, and the
    degenerate 1-shard ring short-circuits to exactly one kernel call)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from deeplearning4j_tpu.kernels.flash_attention import flash_attention
    from deeplearning4j_tpu.parallel.ring_attention import attention_reference
    from deeplearning4j_tpu.parallel.sharding import make_mesh
    from deeplearning4j_tpu.parallel.ring_attention import ring_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32),
                    jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32),
                    jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32),
                    jnp.bfloat16)
    mesh = make_mesh(n_data=1, n_seq=1, devices=jax.devices()[:1])

    def ring_fn(q, k, v, causal=True):
        return ring_attention(q, k, v, mesh, causal=causal)

    def make_scan(fn, K):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32))
        g = jax.grad(loss, argnums=(0, 1, 2))

        @jax.jit
        def run(q, k, v):
            def body(c, _):
                # q + c makes each iteration data-depend on the last so XLA
                # can't hoist the loop-invariant grad out of the scan; the
                # 1e-20-scaled carry keeps the values unchanged in bf16
                dq, _, _ = g(q + c.astype(q.dtype), k, v)
                return dq.ravel()[0].astype(jnp.float32) * 1e-20, ()
            c, _ = lax.scan(body, jnp.float32(0.0), None, length=K)
            return c
        return run

    def timed(fn):
        _sync(fn(q, k, v))  # compile + warm

        def run():
            t0 = time.perf_counter()
            _sync(fn(q, k, v))
            return time.perf_counter() - t0
        return run

    # masked entry (VERDICT r4 next #3 done-criterion): a ragged batch —
    # every sequence a different valid length — through the SAME kernel;
    # the win must survive masking, not evaporate on padded batches
    key_mask = jnp.asarray(
        (np.arange(T)[None, :] < np.linspace(T // 2, T, B)[:, None]),
        jnp.float32)

    def masked_fn(q, k, v, causal=True):
        return flash_attention(q, k, v, causal=causal, key_mask=key_mask)

    out = {}
    for name, fn in (("flash", flash_attention),
                     ("flash_masked", masked_fn),
                     ("reference", attention_reference),
                     ("ring_1dev", ring_fn)):
        out[name + "_ms"] = _diff_time(timed(make_scan(fn, K)),
                                       timed(make_scan(fn, 2 * K))) / K * 1e3
        if name in ("flash", "reference"):

            def loss(q, k, v, fn=fn):
                return jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32))
            from deeplearning4j_tpu.telemetry.cost import compiled_costs
            comp = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                q, k, v).compile()
            out[name + "_temp_mb"] = compiled_costs(comp)["temp_bytes"] / 1e6
    out["speedup"] = out["reference_ms"] / out["flash_ms"]
    return out


def bench_word2vec(n_pairs=65536, dim=128, vocab=10000, K=20, n_neg=5):
    """BASELINE #5: skip-gram negative-sampling training pairs/sec through
    the jitted batched scatter-add kernel (reference hot loop: SkipGram.java
    iterateSample + InMemoryLookupTable axpy updates). K steps run inside
    one scanned executable (the table carry makes iterations naturally
    data-dependent), difference-timed like every other small signal."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from deeplearning4j_tpu.nlp.embeddings import skipgram_ns_step

    rng = np.random.default_rng(0)
    syn0 = jnp.asarray(rng.normal(0, 0.1, (vocab, dim)).astype(np.float32))
    syn1 = jnp.zeros((vocab, dim), jnp.float32)
    # unigram sampling table (word ids drawn proportional to freq^0.75)
    unigram = jnp.asarray(rng.integers(0, vocab, 1 << 20, dtype=np.int32))
    centers = jnp.asarray(rng.integers(0, vocab, n_pairs, dtype=np.int32))
    contexts = jnp.asarray(rng.integers(0, vocab, n_pairs, dtype=np.int32))
    valid = jnp.ones((n_pairs,), jnp.float32)
    key = jax.random.PRNGKey(0)

    def make(K):
        @jax.jit
        def run(s0, s1, k):
            def body(c, _):
                s0, s1, k = c
                k, sub = jax.random.split(k)
                s0, s1 = skipgram_ns_step(s0, s1, unigram, centers, contexts,
                                          valid, 0.025, sub, n_neg)
                return (s0, s1, k), ()
            (s0, s1, k), _ = lax.scan(body, (s0, s1, k), None, length=K)
            return s0[0, 0]
        return run

    def timed(fn):
        _sync(fn(syn0, syn1, key))  # compile + warm

        def run():
            t0 = time.perf_counter()
            _sync(fn(syn0, syn1, key))
            return time.perf_counter() - t0
        return run

    step_s = _diff_time(timed(make(K)), timed(make(2 * K))) / K
    return n_pairs / step_s


def _session_probe(steps=320, trials=5):
    """Fixed-size health probe: per-step ms of a FIXED MLP train step (batch
    512, hidden 2048 — ~11 GFLOP/step, ≈0.2 ms on a healthy v5e, so the
    K-vs-2K difference signal is tens of ms, well above pair noise) run
    `steps`-deep inside one scanned executable, difference-timed. The
    workload never changes across rounds, so this number separates 'the rig
    is slow today' from 'the code got slower' in BENCH_r*.json."""
    from deeplearning4j_tpu.zoo.models import mlp_mnist
    from deeplearning4j_tpu.datasets.dataset import DataSet

    import jax.numpy as jnp
    net = mlp_mnist(hidden=2048)
    net.init()
    rng = np.random.default_rng(0)
    # device arrays up front: prepare_steps preps each group element, and a
    # numpy-backed DataSet would re-transfer the same batch K times
    x = jnp.asarray(rng.random((512, 784)).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, 512)])
    return _scanned_fit_step_s(net, DataSet(x, y), steps,
                               trials=trials) * 1e3


def bench_decode(slots=8, max_len=256, prompt_len=64, steps=48, vocab=256,
                 trials=3):
    """Autoregressive decode serving (the /generate plane, ROADMAP item 2):
    transformer_lm through the KV-cache decode engine at FULL slot
    occupancy — `slots` co-batched requests advanced one token per
    fixed-shape step executable (decode/engine.py), exactly what the
    DecodeScheduler dispatches in steady state. Reports:
      - decode_tokens_per_sec: slots*steps / best trial wall (per chip),
        the release-over-release throughput guard;
      - ttft_ms_p50: median WARM prefill wall (prompt_len tokens through
        the masked flash prefill leg — the compile-paying first prefill is
        excluded, same convention as every steady-state number here);
      - decode_itl_ms: per-token inter-token latency at full occupancy.
    The engine's step donates the multi-MB cache, so the run rides inside
    main()'s donation-warning net like every other workload."""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.zoo.models import transformer_lm
    import jax

    net = transformer_lm(vocab_size=vocab, d_model=256, n_layers=4,
                         n_heads=4)
    net.init()
    eng = DecodeEngine(net, slots=slots, max_len=max_len)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, vocab, size=(slots, prompt_len))

    def fill():
        cache = eng.init_cache()
        walls = []
        for s in range(slots):
            t0 = time.perf_counter()
            cache, nid, _ = eng.prefill(cache, s, prompts[s])
            jax.block_until_ready(cache["lengths"])
            walls.append((time.perf_counter() - t0) * 1e3)
        return cache, walls

    cache, first_walls = fill()                 # first prefill = compile
    ttfts = first_walls[1:]
    ids = np.zeros((slots,), np.int32)
    cache, nxt, _ = eng.step(cache, ids)        # compile the step
    best_s = None
    for _ in range(trials):
        cache, walls = fill()
        ttfts.extend(walls)
        nxt = np.zeros((slots,), np.int32)
        t0 = time.perf_counter()
        for _ in range(steps):
            cache, nxt, _ = eng.step(cache, nxt)
        jax.block_until_ready(cache["lengths"])
        wall = time.perf_counter() - t0
        best_s = wall if best_s is None else min(best_s, wall)
    tokens_per_sec = slots * steps / best_s
    return {"tokens_per_sec": tokens_per_sec,
            "itl_ms": best_s / steps * 1e3,
            "ttft_ms_p50": float(np.median(ttfts)),
            "slots": slots, "prompt_len": prompt_len, "max_len": max_len,
            "cache_mb": eng.cache_bytes() / 1e6}


def bench_decode_paged(slots=4, max_len=128, block_size=16, prompt_len=24,
                       max_new=24, n_requests=12):
    """Decode v2 paged-KV serving (ROADMAP item 2): the SAME request set
    through the DecodeScheduler twice — slab cache fully backed (1x), then
    the paged BlockPool at 2x OVERSUBSCRIPTION (half the allocatable
    blocks a fully-backed pool would hold), where admission bets requests
    finish short and the preempt/requeue path covers the losses. Reports
    tokens/sec for both (the paged number is guarded: block-table
    indirection + allocation churn must not tax steady-state decode),
    the pool's high-water utilization, the preempt count, and token
    parity (oversubscription must be invisible in the token streams)."""
    from deeplearning4j_tpu.decode.paged import blocks_for
    from deeplearning4j_tpu.decode.scheduler import DecodeScheduler
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
    from deeplearning4j_tpu.zoo.models import transformer_lm

    net = transformer_lm(vocab_size=256, d_model=128, n_layers=2, n_heads=4,
                         seed=3)
    net.init()
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, 256, size=prompt_len))
               for _ in range(n_requests)]
    full = slots * blocks_for(max_len, block_size)    # fully backed
    pool_2x = full // 2 + 1                           # + scratch block

    def run(paged, pool_blocks=None):
        registry = ModelRegistry()
        registry.register("v1", net)
        registry.deploy("v1")
        sched = DecodeScheduler(registry, MetricsRegistry(), slots=slots,
                                max_len=max_len, paged=paged,
                                block_size=block_size,
                                pool_blocks=pool_blocks)
        sched.start()
        try:
            warm = [sched.submit(p, max_new_tokens=max_new)
                    for p in prompts[:slots]]         # compile + warm
            for f in warm:
                f.result(timeout=600)
            t0 = time.perf_counter()
            futs = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
            res = [f.result(timeout=600) for f in futs]
            wall = time.perf_counter() - t0
            toks = sum(len(r["tokens"]) for r in res)
            return toks / wall, [r["tokens"] for r in res], sched.snapshot()
        finally:
            sched.stop()

    tps_slab, toks_slab, _ = run(paged=False)
    tps_paged, toks_paged, snap = run(paged=True, pool_blocks=pool_2x)
    pg = snap["paged"]
    return {"tokens_per_sec_slab": tps_slab,
            "tokens_per_sec_paged": tps_paged,
            "paged_vs_slab": tps_paged / tps_slab,
            "pool_blocks": pg["pool_blocks"],
            "pool_blocks_full": full,
            "kv_pool_utilization": pg["high_water"] / max(pg["pool_blocks"],
                                                          1),
            "preempted": pg["preempted"],
            "token_parity": toks_slab == toks_paged}


def bench_spec(vocab=24, k=4, prompt_len=8, gen=64, train_steps=120,
               trials=3):
    """Speculative decoding (decode/speculative.py): char_rnn_lstm draft
    proposes K tokens, transformer_lm target verifies all K in ONE batched
    pass. Acceptance is what sets the speedup, and untrained random models
    agree on ~nothing — so BOTH models first train briefly on a cyclic
    next-token corpus (next = cur + 1 mod V) until they agree, then greedy
    speculative decode races target-only decode on the same prompt.
    Reports acceptance rate, wall-clock speedup, and the greedy parity
    bit (speculative output must be token-for-token the target-only
    stream). The >=1.2x speedup guard arms only OFF-RIG: speculation wins
    by amortizing the target's HBM traffic across K verified tokens, and
    on CPU the verify pass is COMPUTE-bound (a W-token window costs ~W
    steps of flops), so no CPU speedup exists even at acceptance 1.0 —
    measured 0.77x here at acceptance 1.0, mesh_serving_rig_bound
    style."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.decode.speculative import SpeculativeEngine
    from deeplearning4j_tpu.zoo.models import char_rnn_lstm, transformer_lm

    target = transformer_lm(vocab_size=vocab, d_model=64, n_layers=2,
                            n_heads=2, seed=3)
    target.init()
    draft = char_rnn_lstm(vocab_size=vocab, hidden=48, layers=1, seed=5)
    draft.init()
    rng = np.random.default_rng(0)
    for _ in range(train_steps):
        starts = rng.integers(0, vocab, size=(16, 1))
        ids = (starts + np.arange(49)) % vocab
        x = np.eye(vocab, dtype=np.float32)[ids[:, :-1]]
        y = np.eye(vocab, dtype=np.float32)[ids[:, 1:]]
        ds = DataSet(jnp.asarray(x), jnp.asarray(y))
        target.fit_batch(ds)
        draft.fit_batch(ds)

    max_len = prompt_len + gen + k + 8
    prompt = list((np.arange(prompt_len) + 3) % vocab)
    tgt_eng = DecodeEngine(target, slots=1, max_len=max_len)
    ref = tgt_eng.generate(prompt, gen)                 # warm + reference
    spec = SpeculativeEngine(draft, target, k=k, max_len=max_len)
    out = spec.generate(prompt, gen)                    # warm + parity

    def best(fn):
        b = None
        for _ in range(trials):
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            b = dt if b is None else min(b, dt)
        return b

    t_tgt = best(lambda: tgt_eng.generate(prompt, gen))
    t_spec = best(lambda: spec.generate(prompt, gen))
    return {"acceptance_rate": spec.acceptance_rate(),
            "speedup_x": t_tgt / t_spec,
            "greedy_parity": out == ref,
            "k": k, "gen": gen,
            "target_only_ms": t_tgt * 1e3, "spec_ms": t_spec * 1e3,
            "platform": jax.default_backend()}


def bench_loadgen(rate=300.0, duration_s=2.0, n_replicas=3, seed=0):
    """Elastic-fleet serving capacity, measured the loadgen way (ROADMAP
    item 4): an OPEN-LOOP Poisson client (tools/loadgen.py — fixed offered
    rate, no coordinated omission) drives a FleetFrontend at 1 replica and
    then at `n_replicas`, same offered load. Reported: achieved rate and
    p99 latency at both pool sizes — the scale claim as a measurement. The
    N-replica numbers carry the release-over-release regression guard
    (loadgen_achieved_rate / loadgen_p99_ms in the watched sets)."""
    from tools.loadgen import predict_body, run_loadgen
    from deeplearning4j_tpu.elastic import InProcessLauncher
    from deeplearning4j_tpu.serving import FleetFrontend
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer
    from deeplearning4j_tpu.zoo.models import mlp_mnist

    net = mlp_mnist(hidden=256)
    net.init()
    body = predict_body(nin=784)
    out = {}
    with tempfile.TemporaryDirectory() as d:
        ModelSerializer.write_model(net, os.path.join(d, "v1.zip"))
        launcher = InProcessLauncher(
            scan_dir=d, max_replicas=n_replicas,
            server_opts=dict(max_batch_size=32, queue_capacity=64,
                             alert_interval_s=0),
            deploy_event={"kind": "deploy", "version": "v1"})
        fe = None
        try:
            urls = [launcher.launch(f"b{i}") for i in range(n_replicas)]
            fe = FleetFrontend(urls[:1], names=["b0"],
                               health_interval_s=1e9,
                               alert_interval_s=0).start()
            run_loadgen(fe.url, body, rate=50.0, duration_s=0.5,
                        seed=seed)                      # warm both paths
            r1 = run_loadgen(fe.url, body, rate=rate,
                             duration_s=duration_s, seed=seed)
            for i in range(1, n_replicas):
                fe.add_replica(urls[i], name=f"b{i}")
            run_loadgen(fe.url, body, rate=50.0, duration_s=0.5,
                        seed=seed)                      # warm new replicas
            rn = run_loadgen(fe.url, body, rate=rate,
                             duration_s=duration_s, seed=seed + 1)
            out = {"offered_rate": rate, "replicas": n_replicas,
                   "achieved_rate_1": r1["achieved_rate"],
                   "p99_ms_1": r1["p99_ms"],
                   "shed_ratio_1": r1["shed_ratio"],
                   "achieved_rate_n": rn["achieved_rate"],
                   "p99_ms_n": rn["p99_ms"],
                   "shed_ratio_n": rn["shed_ratio"],
                   "errors_5xx": r1["errors_5xx"] + rn["errors_5xx"]}
        finally:
            if fe is not None:
                fe.stop()
            launcher.close()
    return out


def bench_mesh_serving(batch=64, steps=30, trials=3):
    """Mesh-sharded serving dispatch (serving/mesh.py, ROADMAP item 1): the
    SAME coalesced /predict batch through one chip vs a MeshDispatcher on
    the 8-virtual-device mesh — replica-parallel (batch split over the data
    axis) and tensor-parallel (weights split over the model axis, the
    serve-models-that-OOM-one-chip mode, reported with its measured
    per-chip param bytes). Runs in a subprocess (bench_scaling_subprocess
    style) so the forced device count can't leak into the other workloads.
    The 8 virtual devices share ONE physical CPU, so the speedup is
    rig-bound here (`mesh_serving_rig_bound`); the >=1.5x acceptance guard
    arms only on a real multi-chip platform."""
    code = f"BATCH, STEPS, TRIALS = {batch}, {steps}, {trials}\n" + r"""
import os, time, json
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from deeplearning4j_tpu.zoo.models import mlp_mnist
from deeplearning4j_tpu.serving.mesh import MeshContext

rng = np.random.default_rng(0)
x = rng.random((BATCH, 784)).astype(np.float32)

def sps(call):
    jax.block_until_ready(call(x))      # compile + place outside the clock
    best = float("inf")
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = call(x)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return BATCH * STEPS / best

sps_1 = sps(mlp_mnist(hidden=512).init().output)
dp = MeshContext({"n_data": 8}).wrap(mlp_mnist(hidden=512).init())
sps_dp = sps(dp.output)
tp = MeshContext({"n_data": 4, "n_model": 2,
                  "rules": "tensor_parallel"}).wrap(
    mlp_mnist(hidden=512).init())
per_chip, total = tp.param_shard_bytes()
sps_tp = sps(tp.output)
print(json.dumps({
    "sps_single": sps_1, "sps_mesh": sps_dp, "sps_mesh_tp": sps_tp,
    "chips": dp.mesh_context.chips,
    "platform": jax.devices()[0].platform,
    "tp_param_bytes_per_chip": per_chip,
    "tp_param_bytes_total": total}))
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         env=env, timeout=600, cwd=os.path.dirname(
                             os.path.abspath(__file__)))
    import warnings
    for wline in out.stderr.decode(errors="replace").splitlines():
        if "donated buffers were not usable" in wline:
            warnings.warn(wline)
    line = out.stdout.decode().strip().splitlines()[-1]
    return json.loads(line)


def bench_ckpt(hidden=1024, reps=7):
    """Durable-checkpoint cost (the robustness PR's measurable win): what
    the TRAINING THREAD pays per checkpoint, async (one host device-get
    snapshot, serialize+fsync+publish on the writer thread) vs sync (the
    whole write inline). `ckpt_blocking_ms` p50 must sit strictly below the
    synchronous write time — the regression guard in main(). Writer-side
    cost reported as `ckpt_write_ms` from the registry histogram."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    from deeplearning4j_tpu.train import CheckpointConfig, FaultTolerantTrainer
    from deeplearning4j_tpu.zoo.models import mlp_mnist

    def run(async_write, d):
        t = FaultTolerantTrainer(
            lambda: mlp_mnist(hidden=hidden),
            CheckpointConfig(d, frequency=0, keep_last=2,
                             async_write=async_write),
            monitor=False)
        # prime optimizer state so the checkpoint carries realistic bytes
        rng = np.random.default_rng(0)
        x = rng.random((64, 784)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 64)]
        from deeplearning4j_tpu.datasets.dataset import DataSet
        t.model.fit_batch(DataSet(x, y))
        times = []
        for i in range(reps):
            t.state["iteration"] = i + 1     # distinct dirs, no dedupe
            t0 = time.perf_counter()
            t.checkpoint()
            times.append((time.perf_counter() - t0) * 1e3)
            # untimed: in a real run the checkpoint interval dwarfs the
            # write, so the writer idles by the next checkpoint() — without
            # this the timed call would just join the previous write
            t.drain_checkpoints()
        return float(np.median(times))

    with tempfile.TemporaryDirectory() as d:
        sync_ms = run(False, os.path.join(d, "sync"))
        blocking_ms = run(True, os.path.join(d, "async"))
    hist = get_registry().get("ckpt_write_ms")
    write_ms = hist.percentile(0.5) if hist is not None else None
    return {"ckpt_blocking_ms": blocking_ms, "ckpt_sync_ms": sync_ms,
            "ckpt_write_ms": write_ms}


# metrics compared against the best prior BENCH_r*.json (higher is better);
# >30% drops surface in the "regressions" list
WATCHED_METRICS = ("value", "lenet_samples_per_sec", "char_rnn_chars_per_sec",
                   "transformer_lm_tokens_per_sec", "word2vec_pairs_per_sec",
                   "flash_speedup", "e2e_samples_per_sec", "e2e_vs_compute",
                   "ucidigits_test_acc", "real32_test_acc",
                   "decode_tokens_per_sec", "decode_tokens_per_sec_paged",
                   "spec_acceptance_rate", "loadgen_achieved_rate")
# lower-is-better latency metrics: best prior = the MINIMUM, and a >50%
# degradation (1.5x the best) lands in "regressions" (wider margin than the
# throughput 30%: single-request latency is noisier)
WATCHED_LOWER_METRICS = ("ttft_ms_p50", "decode_itl_ms", "loadgen_p99_ms",
                         "ckpt_blocking_ms")
_RENAMED = {"mnist_real_test_acc": "ucidigits_test_acc"}


def _regressions_vs_prior(current):
    import glob
    here = os.path.dirname(os.path.abspath(__file__))
    best = {}
    for path in sorted(glob.glob(os.path.join(here, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                prior = json.load(f)
        except Exception:
            continue
        if prior.get("metric") != current.get("metric"):
            prior = dict(prior)
            prior.pop("value", None)  # headline not comparable across metrics
        for old, new in _RENAMED.items():
            if old in prior:
                prior[new] = prior.pop(old)
        for k in WATCHED_METRICS:
            v = prior.get(k)
            if isinstance(v, (int, float)) and (k not in best or v > best[k]):
                best[k] = float(v)
        for k in WATCHED_LOWER_METRICS:
            v = prior.get(k)
            if isinstance(v, (int, float)) and (k not in best or v < best[k]):
                best[k] = float(v)
    out = []
    for k in WATCHED_METRICS:
        now = current.get(k)
        if k in best and isinstance(now, (int, float)) and best[k] > 0 \
                and now < 0.7 * best[k]:
            out.append({"metric": k, "best_prior": round(best[k], 2),
                        "now": round(float(now), 2),
                        "ratio": round(float(now) / best[k], 3)})
    for k in WATCHED_LOWER_METRICS:
        now = current.get(k)
        if k in best and isinstance(now, (int, float)) and best[k] > 0 \
                and now > 1.5 * best[k]:
            out.append({"metric": k, "best_prior": round(best[k], 2),
                        "now": round(float(now), 2),
                        "ratio": round(float(now) / best[k], 3)})
    return out


def bench_scaling_subprocess():
    """BASELINE #4: SPMD overhead on the virtual 8-device CPU mesh
    (ShardedTrainer = ParallelWrapper semantics, gradients all-reduced
    in-step). The 8 virtual devices SHARE one physical CPU, so throughput
    cannot scale here; what IS measurable is SPMD overhead, reported two
    ways, both with ideal 1.0 on this rig:
      - spmd_strong_ratio: fixed GLOBAL batch 512 — sharded-8-way wall vs
        unsharded wall (same total work; partitioning/collective overhead
        only).
      - spmd_weak_ratio: fixed PER-DEVICE batch 512 — 8-way at global 4096
        does 8x the work of 1-dev at 512 on the same CPU, so ideal wall is
        8x and the ratio normalizes that away; real meshes would scale
        throughput ~8x here.
    Compile time is reported separately (spmd_compile_s) instead of being
    smeared into throughput."""
    code = r"""
import os, time, json
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
from deeplearning4j_tpu.zoo.models import mlp_mnist
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.parallel.sharding import ShardedTrainer, make_mesh

def run(n_dev, batch, steps=20, zero=False, moment=None, want_bytes=False):
    net = mlp_mnist(hidden=1024)
    net.init()
    mesh = make_mesh(n_data=n_dev, devices=jax.devices()[:n_dev])
    tr = ShardedTrainer(net, mesh=mesh, shard_update=zero,
                        moment_dtype=moment)
    rng = np.random.default_rng(0)
    x = rng.random((batch, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    ds = DataSet(x, y)
    t0 = time.perf_counter()
    tr.fit_batch(ds)
    compile_s = time.perf_counter() - t0
    step_bytes = None
    if want_bytes:
        # XLA's own bytes-accessed accounting of the compiled sharded step:
        # the headline xla_step_gb delta, measured on the fixed workload
        from deeplearning4j_tpu.telemetry.cost import compiled_costs
        comp = tr._step.lower(net.params, net.opt_state, net.states,
                              net._rng, jnp.asarray(x), jnp.asarray(y),
                              None, None, None).compile()
        step_bytes = compiled_costs(comp)["hbm_bytes"]
    for _ in range(2):
        tr.fit_batch(ds)
    t0 = time.perf_counter()
    for _ in range(steps):
        tr.fit_batch(ds)
    sps = batch * steps / (time.perf_counter() - t0)
    return (sps, compile_s, step_bytes) if want_bytes else (sps, compile_s)

sps_1, compile_1 = run(1, 512)
sps_8s, compile_8 = run(8, 512)
sps_8w, _ = run(8, 4096)

# ZeRO-1 sharded update (parallel/zero.py, ROADMAP item 4): step-time guard
# on the same fixed workload (all 8 virtual devices share ONE physical CPU,
# so the per-shard update does the same total arithmetic — the ratio
# isolates the reduce-scatter/all-gather overhead the transform adds), and
# per-device state bytes for the HEADLINE model (resnet50 + Nesterovs
# momentum, the BENCH config #2 updater) replicated vs sharded.
zero_step_ratio = zero_bytes = None
try:
    sps_8z, _ = run(8, 512, zero=True)
    zero_step_ratio = sps_8s / sps_8z    # >1: the ZeRO step is SLOWER
    from deeplearning4j_tpu.zoo.models import resnet50
    from deeplearning4j_tpu.parallel.zero import ZeroUpdater, per_device_bytes
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    rn = resnet50(num_classes=1000, image_size=32,
                  updater=Nesterovs(learning_rate=0.05, momentum=0.9))
    rn.init()   # state bytes depend on params only, not image size/batch
    repl_opt = per_device_bytes(rn.opt_state)
    param_b = per_device_bytes(rn.params)
    zu = ZeroUpdater(make_mesh(n_data=8))
    sharded_opt = per_device_bytes(zu.from_canonical(rn.opt_state, rn.params))
    zero_bytes = {"opt_state_bytes_per_device_replicated": repl_opt,
                  "opt_state_bytes_per_device": sharded_opt,
                  "param_bytes_per_device": param_b,
                  "zero_state_reduction_x": repl_opt / max(sharded_opt, 1)}
except Exception as e:
    import sys as _sys
    print(f"zero sharded-update bench failed: {e}", file=_sys.stderr)

# Bytes diet (ROADMAP item 3 / ISSUE 15): 8-bit block-wise moments riding
# inside the ZeRO layout. Three measured claims on the SAME workloads the
# ZeRO numbers use: (a) per-device MOMENT bytes on the headline resnet50
# state at 8 shards, q8 vs f32 (the opt_moment_bytes_per_device guard);
# (b) the fixed-MLP sharded step's XLA bytes-accessed with q8 vs f32
# moments (the headline xla_step_gb delta, rig-independent); (c) the q8
# step's throughput ratio vs the f32-moment ZeRO step (decode/encode are
# elementwise on 1/N shards — must be ~free).
moment_quant = None
try:
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel.zero import moment_bytes
    zu8 = ZeroUpdater(make_mesh(n_data=8), moment_dtype="q8")
    m_f32 = moment_bytes(zu.from_canonical(rn.opt_state, rn.params))
    m_q8 = moment_bytes(zu8.from_canonical(rn.opt_state, rn.params))
    sps_8q, _, q8_step_bytes = run(8, 512, zero=True, moment="q8",
                                   want_bytes=True)
    _, _, f32_step_bytes = run(8, 512, steps=2, zero=True, want_bytes=True)
    moment_quant = {
        "opt_moment_bytes_per_device": int(m_q8),
        "opt_moment_bytes_per_device_f32": int(m_f32),
        "moment_quant_reduction_x": m_f32 / max(m_q8, 1),
        "moment_quant_step_bytes_ratio": q8_step_bytes / f32_step_bytes,
        "moment_quant_step_gb": q8_step_bytes / 1e9,
        "moment_quant_step_ratio": sps_8z / sps_8q}   # >1: q8 SLOWER
except Exception as e:
    import sys as _sys
    print(f"moment-quant bench failed: {e}", file=_sys.stderr)

# pipeline 1F1B: wall of the async-enqueued schedule vs the same compiled
# stage executables host-fenced after every op (<1.0 = stages overlap).
# Guarded so a pipeline failure cannot take the SPMD numbers down with it.
pipe_ratio = None
try:
    from deeplearning4j_tpu import (NeuralNetConfiguration, InputType,
                                    DenseLayer, OutputLayer,
                                    MultiLayerNetwork, Sgd)
    from deeplearning4j_tpu.parallel.pipeline import PipelineTrainer
    b = NeuralNetConfiguration.builder().seed(11).updater(Sgd(0.05)).list()
    for _ in range(8):
        b = b.layer(DenseLayer(n_out=512, activation="tanh"))
    conf = (b.layer(OutputLayer(n_out=8, activation="softmax", loss="MCXENT"))
            .input_type(InputType.feed_forward(512)).build())
    rng = np.random.default_rng(0)
    Xp = rng.normal(size=(256, 512)).astype(np.float32)
    Yp = np.eye(8, dtype=np.float32)[rng.integers(0, 8, 256)]
    dsp = DataSet(Xp, Yp)
    pt = PipelineTrainer(MultiLayerNetwork(conf).init(), n_stages=4,
                         n_microbatches=8, devices=jax.devices()[:4])

    def pipe_wall(fenced, reps=3):
        pt._fence_every_op = fenced
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            pt.fit_batch(dsp)
            jax.block_until_ready(pt.model.params)
            best = min(best, time.perf_counter() - t0)
        return best

    pipe_wall(False); pipe_wall(True)
    pipe_ratio = pipe_wall(False) / pipe_wall(True)
except Exception as e:
    import sys as _sys
    print(f"pipeline overlap bench failed: {e}", file=_sys.stderr)

# schedule accounting (VERDICT r4 next #6): replay the enqueued 1F1B order
# with measured per-op durations; bubble vs the (S-1)/(M+S-1) ideal is
# rig-independent (the shared-core wall clock never enters)
pipe_bubble = pipe_ideal = None
try:
    pt._fence_every_op = False
    prof = pt.profile_schedule(dsp)
    pipe_bubble, pipe_ideal = prof["bubble_fraction"], prof["ideal_bubble"]
except Exception as e:
    import sys as _sys
    print(f"pipeline schedule accounting failed: {e}", file=_sys.stderr)

print(json.dumps({
    "sps_1dev": sps_1, "sps_8dev_strong": sps_8s, "sps_8dev_weak": sps_8w,
    "strong_ratio": sps_8s / sps_1, "weak_ratio": sps_8w / sps_1,
    "compile_s_1dev": compile_1, "compile_s_8dev": compile_8,
    "pipeline_overlap_ratio": pipe_ratio,
    "pipeline_bubble_fraction": pipe_bubble,
    "pipeline_bubble_ideal": pipe_ideal,
    "zero_step_ratio": zero_step_ratio,
    "zero_bytes": zero_bytes,
    "moment_quant": moment_quant}))
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         env=env, timeout=900, cwd=os.path.dirname(
                             os.path.abspath(__file__)))
    # the workload runs in a child process, outside main()'s warnings net —
    # re-emit any donation warning from its captured stderr so the net still
    # counts it against the zero-donation-warnings guarantee
    import warnings
    for wline in out.stderr.decode(errors="replace").splitlines():
        if "donated buffers were not usable" in wline:
            warnings.warn(wline)
    line = out.stdout.decode().strip().splitlines()[-1]
    return json.loads(line)


def main():
    # the whole run records under one warnings net: ANY workload that trips
    # XLA's "Some donated buffers were not usable" lowering warning (donation
    # silently not sticking = fresh HBM allocations per step at
    # roofline_util~1.0) fails the bench via donation_warnings/regressions —
    # the per-path fixes (scanned multistep PR 6, tbptt window carries here)
    # stay fixed
    import warnings
    _warn_net = warnings.catch_warnings(record=True)
    _caught = _warn_net.__enter__()
    warnings.simplefilter("always")
    import jax
    from deeplearning4j_tpu.telemetry.cost import classify, device_peaks
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    peaks = device_peaks(device["kind"])
    if peaks is None:
        # a measurement path that finds no chip it knows fails; it does not
        # print a CPU timing under a device metric's name
        sys.exit(f"bench.py: no published peaks for {device} in "
                 "telemetry.cost.DEVICE_PEAKS; this benchmark runs on a "
                 "listed accelerator only")
    extras = {"device": device}
    failed = []          # phases that raised: reported, and the run exits 1

    def report_failure(name, e):
        failed.append(name)
        print(f"{name} bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)

    def phase(name, fn):
        try:
            return fn()
        except Exception as e:
            report_failure(name, e)
            return None

    def probe():
        extras["readback_floor_ms"] = round(_readback_floor_ms(), 2)
        extras["session_probe_ms"] = round(_session_probe(), 4)
    phase("session_probe", probe)
    tf_ceiling, bw_ceiling = phase("ceilings", _measure_ceilings) \
        or (None, None)
    if tf_ceiling:
        extras["matmul_tflops_ceiling"] = round(tf_ceiling / 1e12, 1)
        extras["hbm_gbps_ceiling"] = round(bw_ceiling / 1e9, 1)

    # the headline: no substitute — if ResNet-50 cannot run, the bench fails
    value, step_ms, flops, nbytes = bench_resnet50()
    metric = "resnet50_train_samples_per_sec_per_chip"
    mfu = value * RESNET50_FLOPS_PER_SAMPLE / peaks["flops"]
    extras.update(step_ms=round(step_ms, 2), mfu=round(float(mfu), 4),
                  dtype="bfloat16", batch=256, image=224)
    if flops is not None:
        extras["xla_step_tflop"] = round(flops / 1e12, 2)
        extras["xla_step_gb"] = round(nbytes / 1e9, 2)
        extras["hbm_gbps_achieved"] = round(nbytes / (step_ms / 1e3) / 1e9, 1)
        if tf_ceiling:
            # HBM leg vs NOMINAL bandwidth: the best elementwise stream
            # this chip sustains (hbm_gbps_ceiling, diff-timed, stable
            # ~650-710) is BELOW what the step's conv DMA patterns move
            # the (upper-bound) cost_analysis byte count at (~820 =
            # nominal), so a stream-probe denominator can only yield
            # util > 1 — re-stating that bytes-accessed is an upper
            # bound, not measuring headroom. Against nominal, util ≈ 1.0
            # says: even the UPPER-BOUND byte count would need the full
            # nominal HBM rate to finish in the measured step time —
            # there is no bandwidth headroom left. Matmul leg uses the
            # measured (stable) MXU ceiling.
            cls = classify(flops, nbytes, tflops_ceiling=tf_ceiling,
                           hbm_bps_ceiling=peaks["hbm_bps"],
                           measured_ms=step_ms)
            extras["roofline_compute_ms"] = round(
                cls["roofline_compute_ms"], 1)
            extras["roofline_hbm_ms"] = round(cls["roofline_hbm_ms"], 1)
            extras["roofline_binding"] = cls["roofline_binding"]
            extras["roofline_util"] = round(cls["roofline_util"], 3)
            extras["roofline_note"] = (
                f"hbm leg vs nominal {peaks['hbm_bps'] / 1e9:.0f} GB/s; "
                "the measured elementwise "
                "stream ceiling (hbm_gbps_ceiling) underruns conv DMA, "
                "and xla_step_gb is an upper bound — util ~1.0 means "
                "no bandwidth headroom within measurement resolution")
    benches = [("e2e", lambda: bench_resnet50_end_to_end(step_ms)),
               ("lenet", lambda: bench_lenet()),
               ("mnist_real", lambda: bench_mnist_real_accuracy()),
               ("real32", lambda: bench_real32_accuracy()),
               ("char_rnn", lambda: bench_char_rnn()),
               ("transformer", lambda: bench_transformer_lm()),
               ("flash", lambda: bench_flash_attention()),
               ("decode", lambda: bench_decode()),
               ("decode_paged", lambda: bench_decode_paged()),
               ("spec", lambda: bench_spec()),
               ("word2vec", lambda: bench_word2vec()),
               ("loadgen", lambda: bench_loadgen()),
               ("mesh", lambda: bench_mesh_serving()),
               ("ckpt", lambda: bench_ckpt()),
               ("scaling", lambda: bench_scaling_subprocess())]
    # what the CPU children (8 virtual devices) return: a rehearsal of
    # partitioning, kept apart from the device metrics above
    mesh = {"platform": "cpu", "virtual_devices": 8,
            "note": "children pinned to the CPU with 8 virtual devices that "
                    "share the host's cores: counts and overhead ratios of "
                    "partitioning, never a device rate"}
    for name, fn in benches:
        try:
            r = fn()
            if name == "e2e":
                extras["e2e_samples_per_sec"] = round(r["e2e_sps"], 1)
                extras["h2d_mb_per_sec"] = round(r["h2d_mb_s"], 1)
                extras["h2d_mb_per_sec_streamed"] = round(
                    r["h2d_mb_s_streamed"], 1)
                extras["h2d_bytes_per_sample"] = r["bytes_per_sample"]
                extras["ingest_dtype"] = r["ingest_dtype"]
                extras["e2e_transfer_streams"] = r["streams"]
                extras["e2e_steps_per_execution"] = r["steps_per_execution"]
                extras["e2e_link_ms"] = round(r["link_ms"], 1)
                extras["e2e_link_ms_streamed"] = round(
                    r["link_ms_streamed"], 1)
                extras["e2e_wall_ms_per_batch"] = round(r["wall_ms"], 1)
                if r["overlap"] is not None:
                    extras["e2e_overlap"] = round(r["overlap"], 2)
                extras["e2e_vs_compute"] = round(r["e2e_sps"] / value, 3)
                # which leg binds the e2e wall on this rig (VERDICT r4 #6),
                # judged on the STREAMED transfer leg — the path the
                # measured fit actually uses
                extras["e2e_binding"] = ("host_link"
                                         if r["link_ms_streamed"] > step_ms
                                         else "compute")
            elif name == "lenet":
                extras["lenet_samples_per_sec"] = round(r[0], 1)
            elif name == "mnist_real":
                if r is not None:
                    # UCI pen-stroke digits upsampled to 28x28 — real digits,
                    # NOT LeCun MNIST (tools/make_mnist_fixture.py); named so
                    # the number can't be miscited as MNIST accuracy
                    acc, acc_q = r
                    extras["ucidigits_test_acc"] = round(float(acc), 4)
                    if acc_q is not None:
                        extras["ucidigits_test_acc_int8"] = round(
                            float(acc_q), 4)
                        # the int8 serving-parity number (guarded below):
                        # negative = quantized LOST accuracy
                        extras["quantized_vs_f32_accuracy_delta"] = round(
                            float(acc_q) - float(acc), 4)
            elif name == "real32":
                if r is not None:
                    # real photograph crops, NOT the CIFAR-10 classes
                    acc, acc_q = r
                    extras["real32_test_acc"] = round(float(acc), 4)
                    if acc_q is not None:
                        extras["real32_test_acc_int8"] = round(
                            float(acc_q), 4)
                        extras["real32_quantized_accuracy_delta"] = round(
                            float(acc_q) - float(acc), 4)
            elif name == "char_rnn":
                extras["char_rnn_chars_per_sec"] = round(r, 1)
            elif name == "transformer":
                extras["transformer_lm_tokens_per_sec"] = round(r, 1)
            elif name == "flash":
                extras["flash_fwdbwd_ms"] = round(r["flash_ms"], 2)
                extras["flash_ref_fwdbwd_ms"] = round(r["reference_ms"], 2)
                extras["flash_speedup"] = round(r["speedup"], 2)
                extras["flash_temp_mb"] = round(r["flash_temp_mb"], 1)
                extras["flash_ref_temp_mb"] = round(r["reference_temp_mb"], 1)
                extras["flash_masked_fwdbwd_ms"] = round(
                    r["flash_masked_ms"], 2)
                extras["ring_1dev_fwdbwd_ms"] = round(r["ring_1dev_ms"], 2)
                extras["ring_vs_flash"] = round(
                    r["ring_1dev_ms"] / r["flash_ms"], 2)
            elif name == "decode":
                extras["decode_tokens_per_sec"] = round(r["tokens_per_sec"],
                                                        1)
                extras["ttft_ms_p50"] = round(r["ttft_ms_p50"], 2)
                extras["decode_itl_ms"] = round(r["itl_ms"], 3)
                extras["decode_slots"] = r["slots"]
                extras["decode_prompt_len"] = r["prompt_len"]
                extras["decode_cache_mb"] = round(r["cache_mb"], 1)
            elif name == "decode_paged":
                # 2x-oversubscribed paged admission vs the fully-backed
                # slab, same request set (the paged number is the guarded
                # one; parity says oversubscription stayed invisible)
                extras["decode_tokens_per_sec_paged"] = round(
                    r["tokens_per_sec_paged"], 1)
                extras["decode_tokens_per_sec_slab_1x"] = round(
                    r["tokens_per_sec_slab"], 1)
                extras["decode_paged_vs_slab"] = round(r["paged_vs_slab"], 3)
                extras["kv_pool_utilization"] = round(
                    r["kv_pool_utilization"], 3)
                extras["decode_paged_pool_blocks"] = r["pool_blocks"]
                extras["decode_paged_pool_blocks_full"] = \
                    r["pool_blocks_full"]
                extras["decode_paged_preempted"] = r["preempted"]
                extras["decode_paged_token_parity"] = r["token_parity"]
            elif name == "spec":
                extras["spec_acceptance_rate"] = round(
                    r["acceptance_rate"], 3)
                extras["spec_speedup_x"] = round(r["speedup_x"], 3)
                extras["spec_greedy_parity"] = r["greedy_parity"]
                extras["spec_target_only_ms"] = round(r["target_only_ms"], 2)
                extras["spec_ms"] = round(r["spec_ms"], 2)
                extras["spec_rig_bound"] = r["platform"] == "cpu"
                extras["spec_note"] = (
                    "rig-bound: CPU verify is COMPUTE-bound (a W-token "
                    "window costs ~W steps of flops), so speculation's "
                    "HBM-amortization win cannot show here; the >=1.2x "
                    "speedup guard arms on accelerator platforms")
            elif name == "word2vec":
                extras["word2vec_pairs_per_sec"] = round(r, 1)
            elif name == "loadgen":
                # serving capacity at 1 vs N replicas under the SAME
                # open-loop offered rate; the N-replica numbers are the
                # guarded ones (watched sets)
                extras["loadgen_offered_rate"] = round(r["offered_rate"], 1)
                extras["loadgen_replicas"] = r["replicas"]
                extras["loadgen_achieved_rate_1"] = round(
                    r["achieved_rate_1"], 1)
                extras["loadgen_p99_ms_1"] = round(r["p99_ms_1"], 2)
                extras["loadgen_shed_ratio_1"] = round(r["shed_ratio_1"], 3)
                extras["loadgen_achieved_rate"] = round(
                    r["achieved_rate_n"], 1)
                extras["loadgen_p99_ms"] = round(r["p99_ms_n"], 2)
                extras["loadgen_shed_ratio"] = round(r["shed_ratio_n"], 3)
                extras["loadgen_errors_5xx"] = r["errors_5xx"]
                extras["loadgen_note"] = (
                    "in-process replicas share ONE host CPU (like "
                    "spmd_strong_ratio): achieved-vs-offered and p99 are "
                    "the guarded capacity numbers, not a linear-scaling "
                    "claim")
            elif name == "mesh":
                # mesh-sharded serving: one dispatch, all chips. The
                # speedup guard arms only off-rig (real multi-chip
                # platform); here the 8 virtual devices share one CPU
                mesh["serving_samples_per_sec"] = round(r["sps_single"], 1)
                mesh["serving_samples_per_sec_mesh"] = round(
                    r["sps_mesh"], 1)
                mesh["serving_samples_per_sec_mesh_tp"] = round(
                    r["sps_mesh_tp"], 1)
                mesh["mesh_serving_speedup"] = round(
                    r["sps_mesh"] / r["sps_single"], 2)
                mesh["mesh_serving_chips"] = r["chips"]
                mesh["mesh_tp_param_bytes_per_chip"] = int(
                    r["tp_param_bytes_per_chip"])
                mesh["mesh_tp_param_bytes_total"] = int(
                    r["tp_param_bytes_total"])
                mesh["mesh_serving_rig_bound"] = (
                    r["platform"] == "cpu")
                mesh["mesh_serving_note"] = (
                    "rig-bound: 8 virtual devices share ONE physical CPU "
                    "(spmd_strong_ratio style) — the speedup here measures "
                    "partitioning overhead only; the >=1.5x mesh dispatch "
                    "guard arms on real multi-chip platforms")
            elif name == "ckpt":
                extras["ckpt_blocking_ms"] = round(r["ckpt_blocking_ms"], 2)
                extras["ckpt_sync_ms"] = round(r["ckpt_sync_ms"], 2)
                if r["ckpt_write_ms"] is not None:
                    extras["ckpt_write_ms"] = round(r["ckpt_write_ms"], 2)
            else:
                mesh["spmd_strong_ratio"] = round(r["strong_ratio"], 2)
                mesh["spmd_strong_note"] = (
                    "rig-bound: 8 virtual devices share ONE physical CPU, so"
                    " strong scaling measures partitioning overhead only —"
                    " not a throughput claim")
                mesh["spmd_weak_ratio"] = round(r["weak_ratio"], 2)
                mesh["spmd_compile_s_8dev"] = round(r["compile_s_8dev"], 1)
                if r.get("pipeline_overlap_ratio") is not None:
                    mesh["pipeline_overlap_ratio"] = round(
                        r["pipeline_overlap_ratio"], 2)
                if r.get("pipeline_bubble_fraction") is not None:
                    mesh["pipeline_bubble_fraction"] = round(
                        r["pipeline_bubble_fraction"], 3)
                if r.get("pipeline_bubble_ideal") is not None:
                    mesh["pipeline_bubble_ideal"] = round(
                        r["pipeline_bubble_ideal"], 3)
                # ZeRO-1 sharded update: the state reduction as a measured
                # number on the headline model, plus the step-time guard
                if r.get("zero_step_ratio") is not None:
                    mesh["zero_step_ratio"] = round(r["zero_step_ratio"], 2)
                    mesh["zero_step_note"] = (
                        "sharded-update wall / replicated-update wall on the"
                        " 8-virtual-device mesh (one shared CPU: per-shard"
                        " update work doesn't shrink here, so ~1.0 = the"
                        " added collectives are free; real meshes also cut"
                        " the update FLOPs 8x)")
                zb = r.get("zero_bytes")
                if zb:
                    mesh["opt_state_bytes_per_device"] = int(
                        zb["opt_state_bytes_per_device"])
                    mesh["opt_state_bytes_per_device_replicated"] = int(
                        zb["opt_state_bytes_per_device_replicated"])
                    mesh["param_bytes_per_device"] = int(
                        zb["param_bytes_per_device"])
                    mesh["zero_state_reduction_x"] = round(
                        zb["zero_state_reduction_x"], 2)
                mq = r.get("moment_quant")
                if mq:
                    # bytes diet: 8-bit moments inside the ZeRO layout —
                    # headline resnet50 moment bytes at 8 shards, the fixed
                    # MLP step's bytes-accessed delta, and the throughput
                    # ratio (all guarded below, zero_step_ratio style)
                    mesh["opt_moment_bytes_per_device"] = int(
                        mq["opt_moment_bytes_per_device"])
                    mesh["opt_moment_bytes_per_device_f32"] = int(
                        mq["opt_moment_bytes_per_device_f32"])
                    mesh["moment_quant_reduction_x"] = round(
                        mq["moment_quant_reduction_x"], 2)
                    mesh["moment_quant_step_bytes_ratio"] = round(
                        mq["moment_quant_step_bytes_ratio"], 3)
                    mesh["moment_quant_step_gb"] = round(
                        mq["moment_quant_step_gb"], 3)
                    mesh["moment_quant_step_ratio"] = round(
                        mq["moment_quant_step_ratio"], 2)
                    mesh["moment_quant_note"] = (
                        "reduction_x = resident moment bytes, the "
                        "guaranteed win; step_bytes_ratio ~1.0 = traffic "
                        "break-even (requantize materializes one f32 "
                        "moment copy); step_ratio is rig-bound (virtual "
                        "CPU mesh emulates fp8 converts)")
        except Exception as e:
            report_failure(name, e)
    extras["cpu_virtual_mesh_rehearsal"] = mesh
    extras["failed_phases"] = failed

    out = {
        "metric": metric,
        "value": round(float(value), 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(float(value) / ASSUMED_BASELINE_SAMPLES_PER_SEC, 3),
    }
    out.update(extras)
    out["regressions"] = _regressions_vs_prior(out)
    # ZeRO guard: the sharded update must not slow the step down at 8
    # virtual devices (10% margin over shared-core scheduler noise)
    zr = mesh.get("zero_step_ratio")
    if isinstance(zr, (int, float)) and zr > 1.1:
        out["regressions"].append(
            {"metric": "zero_step_ratio", "best_prior": 1.0,
             "now": round(float(zr), 2),
             "detail": "ZeRO-sharded step slower than replicated at 8 "
                       "virtual devices"})
    # bytes-diet guards (ISSUE 15, zero_step_ratio style):
    # (a) 8-bit moments must cut per-device moment bytes >= 3.5x vs f32 at
    # the same shard count — the diet's headline claim
    mr = mesh.get("moment_quant_reduction_x")
    if isinstance(mr, (int, float)) and mr < 3.5:
        out["regressions"].append(
            {"metric": "moment_quant_reduction_x", "best_prior": 3.5,
             "now": round(float(mr), 2),
             "detail": "8-bit moments cut per-device moment bytes by less "
                       "than the 3.5x acceptance floor"})
    # (b) the q8-moment step must stay ~byte-neutral on PER-STEP traffic
    # (XLA bytes-accessed on the fixed MLP workload). Measured ~1.00: the
    # moment reads/writes shrink 4x but the re-quantize absmax reduction
    # materializes one f32 copy of the fresh moments, so traffic breaks
    # even — the diet's guaranteed win is RESIDENT HBM (3.9x above), not
    # step traffic. The guard catches a codec regression that starts
    # materializing everything (ratio drifting past 5%).
    sbr = mesh.get("moment_quant_step_bytes_ratio")
    if isinstance(sbr, (int, float)) and sbr > 1.05:
        out["regressions"].append(
            {"metric": "moment_quant_step_bytes_ratio", "best_prior": 1.0,
             "now": round(float(sbr), 3),
             "detail": "q8-moment step accesses >5% more bytes than the "
                       "f32-moment step (codec temps regressed)"})
    # (c) int8 serving weights must hold accuracy within the parity gate on
    # the real-data benches (2 points of accuracy = the deploy-gate spirit)
    for key in ("quantized_vs_f32_accuracy_delta",
                "real32_quantized_accuracy_delta"):
        qd = extras.get(key)
        if isinstance(qd, (int, float)) and qd < -0.02:
            out["regressions"].append(
                {"metric": key, "best_prior": 0.0,
                 "now": round(float(qd), 4),
                 "detail": "int8-quantized serving accuracy dropped beyond "
                           "the parity gate"})
    # mesh-serving guard (rig-aware): on a REAL multi-chip platform the
    # replica-parallel dispatch must clear 1.5x over one chip at 8 chips;
    # on this rig's virtual CPU mesh (one shared core) the guard stays
    # disarmed — the number measures partitioning overhead, not scaling
    msp = mesh.get("mesh_serving_speedup")
    if mesh.get("mesh_serving_rig_bound") is False \
            and isinstance(msp, (int, float)) \
            and mesh.get("mesh_serving_chips", 0) >= 8 and msp < 1.5:
        out["regressions"].append(
            {"metric": "mesh_serving_speedup", "best_prior": 1.5,
             "now": round(float(msp), 2),
             "detail": "mesh dispatch under 1.5x of single-chip serving "
                       "throughput on a real multi-chip platform"})
    # speculative-decode guards (ISSUE 18): greedy parity is correctness
    # and always armed — speculative output must BE the target-only
    # stream. The >=1.2x speedup guard is rig-aware (mesh_serving_speedup
    # style): CPU verify is compute-bound, so the HBM-amortization win
    # only exists on accelerator platforms (measured 0.77x on this rig at
    # acceptance 1.0 — disarmed, recorded).
    if extras.get("spec_greedy_parity") is False:
        out["regressions"].append(
            {"metric": "spec_greedy_parity", "best_prior": True,
             "now": False,
             "detail": "greedy speculative output diverged from the "
                       "target-only token stream"})
    ssx = extras.get("spec_speedup_x")
    if extras.get("spec_rig_bound") is False \
            and isinstance(ssx, (int, float)) and ssx < 1.2:
        out["regressions"].append(
            {"metric": "spec_speedup_x", "best_prior": 1.2,
             "now": round(float(ssx), 2),
             "detail": "speculative decode under 1.2x of target-only "
                       "decoding on an accelerator platform"})
    # paged-decode guards: token parity (oversubscription must stay
    # invisible) always armed; throughput at 2x-oversubscribed admission
    # must hold >= 0.85x of the fully-backed slab (measured 0.97 — the
    # 15% margin covers shared-core scheduler noise, zero_step_ratio
    # style)
    if extras.get("decode_paged_token_parity") is False:
        out["regressions"].append(
            {"metric": "decode_paged_token_parity", "best_prior": True,
             "now": False,
             "detail": "paged 2x-oversubscribed token streams diverged "
                       "from the slab run"})
    pvs = extras.get("decode_paged_vs_slab")
    if isinstance(pvs, (int, float)) and pvs < 0.85:
        out["regressions"].append(
            {"metric": "decode_paged_vs_slab", "best_prior": 0.85,
             "now": round(float(pvs), 3),
             "detail": "paged decode at 2x oversubscription below 0.85x "
                       "of slab-at-1x throughput"})
    # durable-checkpoint guard: the async path's blocking time must sit
    # STRICTLY below the synchronous write — otherwise the background
    # writer is buying nothing and the training thread re-pays the fsync
    cb, cs = extras.get("ckpt_blocking_ms"), extras.get("ckpt_sync_ms")
    if isinstance(cb, (int, float)) and isinstance(cs, (int, float)) \
            and cb >= cs:
        out["regressions"].append(
            {"metric": "ckpt_blocking_ms", "best_prior": round(cs, 2),
             "now": round(cb, 2),
             "detail": "async checkpoint blocking time not below the "
                       "synchronous write time"})
    donation = [str(w.message).splitlines()[0] for w in _caught
                if "donated buffers were not usable" in str(w.message)]
    _warn_net.__exit__(None, None, None)
    out["donation_warnings"] = len(donation)
    if donation:
        for msg in donation:
            print(f"DONATION WARNING: {msg}", file=sys.stderr)
        out["regressions"].append({"metric": "donation_warnings",
                                   "best_prior": 0, "now": len(donation),
                                   "detail": donation[:4]})
    print(json.dumps(out))
    if failed:
        sys.exit(f"bench.py: phases failed: {failed}")


if __name__ == "__main__":
    main()
