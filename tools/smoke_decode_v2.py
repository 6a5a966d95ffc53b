"""Decode v2 smoke test: sampled decoding, paged oversubscription, and
speculative verify, end to end — three arcs over one serving stack:

1. SAMPLING: seeded temperature/top-k/top-p requests through POST
   /generate are byte-reproducible across repeat calls AND across a
   same-weights hot-swap (the per-slot `fold_in(PRNGKey(seed), step)`
   stream is request state, not server state), different seeds diverge,
   and the whole parameter-diverse wave — every request its own
   temperature/top_p/seed — causes ZERO steady-state recompiles: sampling
   params ride as array operands of the ONE decode executable (graftlint
   GL016), so the registry compile counters stay flat and every decode
   executable's XLA cache size is exactly 1.

2. PAGED OVERSUBSCRIPTION: the same server runs its KV cache as a
   BlockPool at 2x oversubscription (half the blocks a fully-backed pool
   would hold). A concurrent staggered wave admits more context than the
   pool physically holds; admission + preempt/requeue must absorb it with
   every request answering 200 (zero 5xx), token parity against isolated
   runs, and the pool drained back to zero used blocks afterwards.

3. SPECULATIVE: a trained-for-agreement char_rnn_lstm draft proposes K
   tokens per round, the transformer target verifies them in one batched
   pass, and the greedy speculative stream is token-for-token identical
   to target-only decoding, with executable cache sizes of exactly 1.

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_decode_v2.py [-n 8]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

VOCAB = 24


def _model(seed=7):
    from deeplearning4j_tpu.zoo.models import transformer_lm
    net = transformer_lm(vocab_size=VOCAB, d_model=32, n_layers=2,
                         n_heads=2, seed=seed)
    return net.init()


def _sampling_arc(n_requests):
    """Arc 1: seeded sampling — reproducible, seed-sensitive, hot-swap
    stable, compile-flat under parameter-diverse traffic."""
    import numpy as np
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.util.http import post_json
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    rng = np.random.default_rng(1)
    net = _model()
    with tempfile.TemporaryDirectory() as tmp:
        # two zips of the SAME weights: v2 deploys as a hot-swap that must
        # not disturb any seeded stream
        ModelSerializer.write_model(net, os.path.join(tmp, "lm.zip"),
                                    save_updater=False)
        ModelSerializer.write_model(net, os.path.join(tmp, "lm2.zip"),
                                    save_updater=False)
        server = ServingServer(scan_dir=tmp, decode=True, decode_slots=3,
                               decode_max_len=64).start()
        url = f"http://{server.host}:{server.port}"
        try:
            post_json(url + "/deploy", {"version": "lm"}, timeout=120)
            body = {"prompt": [3, 1, 4, 1, 5], "max_new_tokens": 8,
                    "temperature": 0.8, "top_k": 12, "top_p": 0.9,
                    "seed": 42}
            first = post_json(url + "/generate", body, timeout=120)
            repeat = post_json(url + "/generate", body, timeout=120)
            other = post_json(url + "/generate", dict(body, seed=43),
                              timeout=120)
            reg = server.metrics.registry
            compiles0 = reg.get("compiles_total").get()
            jit = reg.get("jit_compiles_total")
            jit0 = jit.get() if jit is not None else 0
            # parameter-diverse wave: every request novel temperature /
            # top_p / seed — the recompile trap GL016 exists to catch
            results, errors = {}, []

            def fire(i):
                try:
                    results[i] = post_json(
                        url + "/generate",
                        {"prompt": [3, 1, 4, 1, 5], "max_new_tokens": 6,
                         "temperature": 0.5 + 0.07 * i,
                         "top_p": 0.85 + 0.01 * (i % 8),
                         "top_k": int(rng.integers(4, VOCAB)),
                         "seed": 1000 + i}, timeout=120)
                except Exception as e:          # collected, asserted below
                    errors.append((i, repr(e)))

            threads = []
            for i in range(n_requests):
                t = threading.Thread(target=fire, args=(i,))
                t.start()
                threads.append(t)
                if i % 2:
                    time.sleep(0.01)
            for t in threads:
                t.join()
            assert not errors, errors
            steady = (reg.get("compiles_total").get() - compiles0) + (
                (jit.get() - jit0) if jit is not None else 0)
            counts = server.decode._engine.executable_counts()
            # hot-swap to identical weights: the seeded stream replays
            post_json(url + "/deploy", {"version": "lm2"}, timeout=120)
            swapped = post_json(url + "/generate", body, timeout=120)
        finally:
            server.stop()
    assert first["tokens"] == repeat["tokens"], (first, repeat)
    assert first["tokens"] != other["tokens"], \
        "different seeds produced identical streams"
    assert swapped["tokens"] == first["tokens"], (first, swapped)
    assert steady == 0, f"{steady} steady-state recompiles"
    assert all(v == 1 for v in counts.values()), counts
    return {"seeded_reproducible": True, "seed_sensitive": True,
            "hot_swap_stable": True, "steady_state_compiles": int(steady),
            "executable_cache_sizes": counts}


def _paged_arc(n_requests):
    """Arc 2: 2x-oversubscribed paged admission — zero 5xx, token parity,
    pool drained."""
    import numpy as np
    from deeplearning4j_tpu.decode.paged import blocks_for
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.util.http import post_json
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    slots, max_len, bs = 3, 64, 8
    full = slots * blocks_for(max_len, bs)
    pool = full // 2 + 1                      # 2x oversubscribed + scratch
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, VOCAB,
                                             int(rng.integers(4, 12)))]
               for _ in range(n_requests)]
    budgets = [int(rng.integers(6, 14)) for _ in range(n_requests)]
    net = _model()
    with tempfile.TemporaryDirectory() as tmp:
        ModelSerializer.write_model(net, os.path.join(tmp, "lm.zip"),
                                    save_updater=False)
        server = ServingServer(scan_dir=tmp, decode=True,
                               decode_slots=slots, decode_max_len=max_len,
                               decode_paged=True, decode_block_size=bs,
                               decode_pool_blocks=pool).start()
        url = f"http://{server.host}:{server.port}"
        try:
            post_json(url + "/deploy", {"version": "lm"}, timeout=120)
            lm = server.registry.get("lm").model
            solo = [lm.generate(p, n) for p, n in zip(prompts, budgets)]
            results, errors = {}, []

            def fire(i):
                try:
                    results[i] = post_json(
                        url + "/generate",
                        {"prompt": prompts[i],
                         "max_new_tokens": budgets[i]}, timeout=120)
                except Exception as e:
                    errors.append((i, repr(e)))

            threads = []
            for i in range(n_requests):
                t = threading.Thread(target=fire, args=(i,))
                t.start()
                threads.append(t)
                if i % 2:
                    time.sleep(0.01)
            for t in threads:
                t.join()
            snap = server.decode.snapshot()
        finally:
            server.stop()
    assert not errors, f"5xx/errors under oversubscription: {errors}"
    parity = all(results[i]["tokens"] == solo[i]
                 for i in range(n_requests))
    assert parity, "oversubscribed token streams diverged from solo runs"
    pg = snap["paged"]
    assert pg["used_blocks"] == 0, f"pool leaked blocks: {pg}"
    assert snap["active_slots"] == 0, snap
    return {"requests": n_requests, "errors_5xx": 0, "parity_ok": True,
            "pool_blocks": pg["pool_blocks"], "pool_blocks_full": full,
            "pool_high_water": pg["high_water"],
            "preempted": pg["preempted"], "pool_drained": True}


def _spec_arc():
    """Arc 3: greedy speculative parity with a trained-for-agreement
    draft (cyclic corpus, few steps — the smoke wants a nonzero
    acceptance rate, not a speedup claim)."""
    import numpy as np
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.decode.speculative import SpeculativeEngine
    from deeplearning4j_tpu.zoo.models import char_rnn_lstm

    target = _model(seed=11)
    draft = char_rnn_lstm(vocab_size=VOCAB, hidden=32, layers=1, seed=13)
    draft.init()
    rng = np.random.default_rng(3)
    for _ in range(90):
        starts = rng.integers(0, VOCAB, size=(16, 1))
        ids = (starts + np.arange(25)) % VOCAB
        x = np.eye(VOCAB, dtype=np.float32)[ids[:, :-1]]
        y = np.eye(VOCAB, dtype=np.float32)[ids[:, 1:]]
        ds = DataSet(jnp.asarray(x), jnp.asarray(y))
        target.fit_batch(ds)
        draft.fit_batch(ds)
    prompt = [5, 6, 7, 8]
    ref = DecodeEngine(target, slots=1, max_len=64).generate(prompt, 16)
    spec = SpeculativeEngine(draft, target, k=3, max_len=64)
    out = spec.generate(prompt, 16)
    counts = spec.executable_counts()
    assert out == ref, (out, ref)
    assert all(v == 1 for v in counts.values()), counts
    assert spec.acceptance_rate() > 0, \
        "draft/target never agreed — speculation exercised nothing"
    return {"greedy_parity": True,
            "acceptance_rate": round(spec.acceptance_rate(), 3),
            "rounds": spec.rounds,
            "executable_cache_sizes": counts}


def run(n_requests=8):
    # all three arcs run with the lock sanitizer live: the scheduler loop,
    # paged KV pool, and speculative verify all juggle locks across threads
    from deeplearning4j_tpu.util.concurrency import lock_sanitizer
    lock_sanitizer.reset()
    lock_sanitizer.install()
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            sampling = _sampling_arc(n_requests)
            paged = _paged_arc(n_requests)
            spec = _spec_arc()
    finally:
        lock_report = lock_sanitizer.report()
        lock_sanitizer.uninstall()
    donation = [w for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert not donation, \
        [str(w.message).splitlines()[0] for w in donation]
    assert lock_report["violations"] == 0, \
        f"lock sanitizer: {lock_sanitizer.table()['violations']}"
    return {"sampling": sampling, "paged": paged, "speculative": spec,
            "donation_warnings": 0, "lock_sanitizer": lock_report}


def main():
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-n", "--requests", type=int, default=8)
    args = ap.parse_args()
    out = run(n_requests=args.requests)
    print(json.dumps(out, indent=2))
    print("SMOKE DECODE V2: OK")


if __name__ == "__main__":
    main()
