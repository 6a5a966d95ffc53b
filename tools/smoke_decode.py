"""Autoregressive decode smoke test: the /generate plane end to end —

  train-shaped transformer_lm -> ModelSerializer zip -> ServingServer(
  scan_dir=..., decode=True) -> deploy BY NAME from the persistent registry
  -> one warm-up request (compiles the decode step + the prompt's length
  bucket + the /predict path stays untouched) -> N concurrent /generate
  requests with STAGGERED arrivals and varying prompt/output lengths, so
  requests join and leave the in-flight continuous batch per token.

Asserts (a) ZERO steady-state recompiles — the serving registry's
compiles_total and jit_compiles_total are flat across the whole concurrent
wave, and every decode executable's XLA cache size is exactly 1; (b) ZERO
XLA donation warnings ("Some donated buffers were not usable" — the decode
step donates the multi-MB KV cache every token, so a silently-undonated
cache would double decode HBM traffic); (c) the decode_ttft_ms histogram is
populated with exemplar-ready observations; (d) token-for-token parity:
every concurrent request's output equals the model's own isolated
net.generate run (per-request independence from co-batched neighbors).

Usage (on the CPU; without JAX_PLATFORMS it runs on the default platform):
    JAX_PLATFORMS=cpu python tools/smoke_decode.py [-n 8] [-t 6]
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


def run(n_requests=8, max_new_tokens=6, slots=3, max_len=64):
    import numpy as np
    from deeplearning4j_tpu.serving.server import ServingServer
    from deeplearning4j_tpu.util.http import get_json, post_json
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(0, VOCAB,
                                             int(rng.integers(1, 7)))]
               for _ in range(n_requests)]
    budgets = [int(rng.integers(2, max_new_tokens + 1))
               for _ in range(n_requests)]

    net = _model()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with tempfile.TemporaryDirectory() as tmp:
            ModelSerializer.write_model(net, os.path.join(tmp, "lm.zip"),
                                        save_updater=False)
            server = ServingServer(scan_dir=tmp, decode=True,
                                   decode_slots=slots,
                                   decode_max_len=max_len).start()
            url = f"http://{server.host}:{server.port}"
            try:
                post_json(url + "/deploy", {"version": "lm"}, timeout=120)
                # expected outputs from the RESTORED model (isolated runs —
                # the parity oracle for per-request independence)
                lm = server.registry.get("lm").model
                solo = [lm.generate(p, n) for p, n in zip(prompts, budgets)]
                # warm-up: every prompt length bucket + the decode step
                for L in sorted({server.decode.engine_for(
                        lm).prefill_bucket(len(p)) for p in prompts}):
                    post_json(url + "/generate",
                              {"prompt": [0] * (L - 1), "max_new_tokens": 1},
                              timeout=120)
                reg = server.metrics.registry
                compiles0 = reg.get("compiles_total").get()
                jit0 = reg.get("jit_compiles_total").get() \
                    if reg.get("jit_compiles_total") is not None else 0

                # the concurrent wave: staggered joins, varying lengths
                results, errors = {}, []

                def fire(i):
                    try:
                        results[i] = post_json(
                            url + "/generate",
                            {"prompt": prompts[i],
                             "max_new_tokens": budgets[i]}, timeout=120)
                    except Exception as e:      # collected, asserted below
                        errors.append((i, repr(e)))

                threads = []
                for i in range(n_requests):
                    t = threading.Thread(target=fire, args=(i,))
                    t.start()
                    threads.append(t)
                    if i % 2:
                        import time
                        time.sleep(0.01)
                for t in threads:
                    t.join()
                assert not errors, errors

                parity_ok = all(results[i]["tokens"] == solo[i]
                                for i in range(n_requests))
                steady = (reg.get("compiles_total").get() - compiles0) + (
                    (reg.get("jit_compiles_total").get() - jit0)
                    if reg.get("jit_compiles_total") is not None else 0)
                counts = server.decode._engine.executable_counts()
                metrics = get_json(url + "/metrics", timeout=30)
                decode_snap = metrics["decode"]
            finally:
                server.stop()
    donation = [w for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert all(v == 1 for v in counts.values()), counts
    out = {
        "requests": n_requests,
        "steady_state_compiles": int(steady),
        "executable_cache_sizes": counts,
        "donation_warnings": len(donation),
        "parity_ok": bool(parity_ok),
        "tokens_total": decode_snap["tokens"],
        "ttft_ms_p50": decode_snap["ttft_ms"]["p50"],
        "itl_ms_p50": decode_snap["itl_ms"]["p50"],
        "prefill_buckets": decode_snap["prefill_buckets"],
    }
    assert out["steady_state_compiles"] == 0, out
    assert out["donation_warnings"] == 0, \
        [str(w.message).splitlines()[0] for w in donation]
    assert out["parity_ok"], out
    assert out["ttft_ms_p50"] is not None, out
    return out


def main():
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-n", "--requests", type=int, default=8)
    ap.add_argument("-t", "--max-new-tokens", type=int, default=6)
    args = ap.parse_args()
    out = run(n_requests=args.requests, max_new_tokens=args.max_new_tokens)
    print(json.dumps(out, indent=2))
    print("SMOKE DECODE: OK")


if __name__ == "__main__":
    main()
