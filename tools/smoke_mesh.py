"""Mesh-sharded serving smoke test: one dispatch, all chips, end to end.

Boots a MESH ServingServer (serving/mesh.py — every model the registry
hands out is wrapped in a MeshDispatcher, the decode KV cache is
head-sharded over the mesh model axis) next to a single-chip reference
server over the SAME ModelSerializer zip, then:

1. deploys BY NAME on both, warms every /predict bucket and /generate
   prefill bucket, and fires CONCURRENT /predict + /generate waves at the
   mesh server — asserting bit-level parity (f32 tolerance on logits,
   token-exact on /generate) against the single-chip reference;
2. asserts ZERO steady-state recompiles across the whole concurrent wave
   (compiles_total + jit_compiles_total flat, every decode executable's
   XLA cache size exactly 1) and ZERO XLA donation warnings — the sharded
   cache still donates;
3. checks the mesh is VISIBLE where it should be (healthz `mesh_chips`,
   the `mesh_dispatch_chips` gauge, `mesh_dispatch` trace spans with
   per-axis detail) and INVISIBLE where it must be: in a FleetFrontend the
   whole N-chip group is ONE ReplicaHandle (pool counts handles, chips is
   display), and a canary started ON the mesh replica rolls back as one
   unit — one cohort member, the whole group back to stable, zero client
   5xx throughout.

Usage (needs 8 devices: 8 virtual CPU devices unless the environment names
another platform, e.g. JAX_PLATFORMS=tpu on a host with 8 chips):
    python tools/smoke_mesh.py [-n 12] [-g 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

VOCAB = 24


def _model(seed=7):
    from deeplearning4j_tpu.zoo.models import transformer_lm
    net = transformer_lm(vocab_size=VOCAB, d_model=32, n_layers=2,
                         n_heads=2, seed=seed)
    return net.init()


def run(n_predict=12, n_generate=4, max_new_tokens=5, slots=3, max_len=64):
    import numpy as np
    import jax
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.serving.frontend import FleetFrontend
    from deeplearning4j_tpu.util.http import get_json, post_json
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    n_dev = len(jax.devices())
    assert n_dev >= 2, \
        f"mesh smoke needs >=2 devices (XLA_FLAGS force host count); got {n_dev}"
    n_model = 2                       # transformer heads=2: TP divides evenly
    mesh_spec = {"n_data": n_dev // n_model, "n_model": n_model,
                 "rules": "tensor_parallel"}

    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(0, VOCAB,
                                             int(rng.integers(2, 7)))]
               for _ in range(n_generate)]
    # two seq lengths only, so the warm-up can cover the FULL observed key
    # set {row bucket} x {seq len} deterministically (the concurrent wave
    # coalesces into arbitrary pow2 row buckets up to max_batch_size)
    pred_lens = [(3, 6)[i % 2] for i in range(n_predict)]
    eye = np.eye(VOCAB, dtype=np.float32)
    pred_xs = [eye[rng.integers(0, VOCAB, L)][None].tolist()
               for L in pred_lens]    # one-hot [1, L, vocab] token rows

    # both planes (mesh + solo) and the frontend run on sanitized locks;
    # the mesh run_lock serializing one wave per mesh (PR 16) is exactly
    # the kind of lock whose ordering this arc now checks at runtime
    from deeplearning4j_tpu.util.concurrency import lock_sanitizer
    lock_sanitizer.reset()
    lock_sanitizer.install()
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            with tempfile.TemporaryDirectory() as tmp:
                ModelSerializer.write_model(_model(),
                                            os.path.join(tmp, "lm.zip"),
                                            save_updater=False)
                ModelSerializer.write_model(_model(seed=8),
                                            os.path.join(tmp, "lm2.zip"),
                                            save_updater=False)
                mesh_srv = ServingServer(scan_dir=tmp, decode=True,
                                         decode_slots=slots,
                                         decode_max_len=max_len,
                                         max_batch_size=4,
                                         mesh=mesh_spec).start()
                ref_srv = ServingServer(scan_dir=tmp, decode=True,
                                        decode_slots=slots,
                                        decode_max_len=max_len,
                                        max_batch_size=4).start()
                fe = FleetFrontend([ref_srv.url, mesh_srv.url],
                                   names=["solo", "mesh"],
                                   health_interval_s=0.0).start()
                try:
                    out = _drive(mesh_srv, ref_srv, fe, prompts, pred_xs,
                                 max_new_tokens, get_json, post_json, np)
                finally:
                    fe.stop()
                    mesh_srv.stop()
                    ref_srv.stop()
    finally:
        lock_report = lock_sanitizer.report()
        lock_sanitizer.uninstall()
    donation = [w for w in caught
                if "donated buffers were not usable" in str(w.message)]
    out["donation_warnings"] = len(donation)
    assert out["donation_warnings"] == 0, \
        [str(w.message).splitlines()[0] for w in donation]
    out["lock_sanitizer"] = lock_report
    assert lock_report["violations"] == 0, \
        f"lock sanitizer: {lock_sanitizer.table()['violations']}"
    return out


def _drive(mesh_srv, ref_srv, fe, prompts, pred_xs, max_new_tokens,
           get_json, post_json, np):
    for url in (mesh_srv.url, ref_srv.url):
        post_json(url + "/deploy", {"version": "lm"}, timeout=120)

    # ---- 1. warm every bucket both planes will see --------------------------
    lm = mesh_srv.registry.get("lm").model       # the MeshDispatcher wrapper
    eng = mesh_srv.decode.engine_for(lm)
    for L in sorted({eng.prefill_bucket(len(p)) for p in prompts}):
        for url in (mesh_srv.url, ref_srv.url):
            post_json(url + "/generate",
                      {"prompt": [0] * (L - 1), "max_new_tokens": 1},
                      timeout=120)
    for L in sorted({len(x[0]) for x in pred_xs}):
        for rows in (1, 2, 4):      # every pow2 row bucket the wave can hit
            zeros = np.zeros((rows, L, VOCAB), np.float32).tolist()
            for url in (mesh_srv.url, ref_srv.url):
                post_json(url + "/predict", {"data": zeros}, timeout=120)

    reg = mesh_srv.metrics.registry
    compiles0 = reg.get("compiles_total").get()
    jit = reg.get("jit_compiles_total")
    jit0 = jit.get() if jit is not None else 0.0

    # ---- 2. concurrent /predict + /generate waves at the mesh ---------------
    results, errors = {}, []

    def fire(kind, i):
        try:
            if kind == "p":
                results[("p", i)] = post_json(
                    mesh_srv.url + "/predict", {"data": pred_xs[i]},
                    timeout=120)
            else:
                results[("g", i)] = post_json(
                    mesh_srv.url + "/generate",
                    {"prompt": prompts[i], "max_new_tokens": max_new_tokens},
                    timeout=120)
        except Exception as e:          # collected, asserted below: zero 5xx
            errors.append((kind, i, repr(e)))

    threads = [threading.Thread(target=fire, args=("p", i), daemon=True)
               for i in range(len(pred_xs))]
    threads += [threading.Thread(target=fire, args=("g", i), daemon=True)
                for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors

    # parity vs the single-chip reference (same zip, same weights)
    for i, x in enumerate(pred_xs):
        want = post_json(ref_srv.url + "/predict", {"data": x}, timeout=120)
        got = np.asarray(results[("p", i)]["prediction"], np.float32)
        np.testing.assert_allclose(
            got, np.asarray(want["prediction"], np.float32),
            rtol=2e-4, atol=2e-5)
    gen_parity = all(
        results[("g", i)]["tokens"] == post_json(
            ref_srv.url + "/generate",
            {"prompt": prompts[i], "max_new_tokens": max_new_tokens},
            timeout=120)["tokens"]
        for i in range(len(prompts)))
    assert gen_parity

    # zero steady-state recompiles across the whole concurrent wave
    steady = (reg.get("compiles_total").get() - compiles0) + (
        (jit.get() - jit0) if jit is not None else 0.0)
    assert steady == 0, f"steady-state recompiles: {steady}"
    counts = mesh_srv.decode._engine.executable_counts()
    assert all(v == 1 for v in counts.values()), counts

    # ---- 3. mesh visibility -------------------------------------------------
    hz = get_json(mesh_srv.url + "/healthz", timeout=30)
    chips = mesh_srv.mesh.chips
    assert hz["mesh_chips"] == chips, hz
    snap = get_json(mesh_srv.url + "/metrics", timeout=30)
    assert snap["mesh_dispatch_chips"] == chips, snap.get("mesh_dispatch_chips")
    trace = get_json(mesh_srv.url + "/trace", timeout=30)
    spans = [e for e in trace["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "mesh_dispatch"]
    assert spans and all(e["args"]["chips"] == chips for e in spans)

    # ---- 4. fleet: N chips, ONE handle; canary rolls back as one unit -------
    fe.poll_health(force=True)
    handles = {r.name: r for r in fe.replicas}
    assert len(handles) == 2, "a mesh group must register as ONE handle"
    assert handles["mesh"].chips == chips and handles["solo"].chips == 1
    _, pool = fe._probe_pool()
    assert pool["replicas"] == 2 and pool["chips"] == chips + 1, pool

    fe.canary.start("lm2", 0.5, replica="mesh")
    canary_members = [r.name for r in fe.replicas if r.cohort == "canary"]
    assert canary_members == ["mesh"], canary_members
    assert mesh_srv.registry.active_version == "lm2"
    # traffic keeps flowing THROUGH the frontend during the canary: zero 5xx
    for i in range(4):
        got = post_json(fe.url + "/predict", {"data": pred_xs[0]},
                        timeout=120)
        assert "prediction" in got, got
    fe.canary.rollback(reason="smoke")
    assert [r.cohort for r in fe.replicas] == ["stable", "stable"]
    assert mesh_srv.registry.active_version == "lm"   # the WHOLE group back
    assert len(fe.replicas) == 2
    snap_fe = fe.registry.snapshot()

    return {
        "devices": chips,
        "mesh": mesh_srv.mesh.describe(),
        "predict_requests": len(pred_xs),
        "generate_requests": len(prompts),
        "steady_state_compiles": int(steady),
        "executable_cache_sizes": counts,
        "gen_parity": bool(gen_parity),
        "mesh_dispatch_spans": len(spans),
        "pool": pool,
        "canary_rollbacks": snap_fe.get("canary_rollbacks_total"),
        "client_errors": len(errors),
    }


def main(argv=None):
    # before JAX is imported: with nothing said, rehearse on the CPU
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-n", "--predict-requests", type=int, default=12)
    ap.add_argument("-g", "--generate-requests", type=int, default=4)
    args = ap.parse_args(argv)
    out = run(n_predict=args.predict_requests,
              n_generate=args.generate_requests)
    print(json.dumps(out, indent=2))
    print("SMOKE MESH: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
