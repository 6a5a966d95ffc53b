"""Drives a serving cell: the configuration's model behind
ServingServer(decode=True), registered in memory, in this one process; the
load generator's threads post /generate over HTTP to it.

Workload parameters (workloads/<cell>.json, "serve"): loop ("closed" with
`clients`, or "open" with `rate_per_s` and `workers`), slots, decode_max_len,
queue_capacity, the mix (cycle, prompt_tokens, new_tokens), ramp_s,
check_requests, trace_delay_s, trace_seconds.

`serve_tokens_per_s` is taken over all the work and all the time of the
window: every answered request (200) is credited its tokens times the share
of its life [sent, answered] that lies inside the window, so a request that
straddles an edge counts for what was generated inside. (Counting only
requests answered inside the window reads the same work as 310 or 367
tokens/s depending on which of ~75 long requests the seed's order lets
finish in time: PERF.md section 6.) Per request answered in full inside the
window the readers under layer_metrics/ get: time to first token, the
response's `ttft_ms` (the scheduler's clock, enqueue to first token;
/generate does not stream) plus how late the generator sent it; and time per
output token, (client latency - ttft_ms) / (tokens - 1). In a closed loop
with as many clients as slots the server is at capacity, where tails belong
among the per-layer metrics; no end-to-end tail is defined yet.
"""
from __future__ import annotations

import gc
import http.client
import json
import statistics
import threading
import time

import numpy as np

from .. import observe, traffic
from ..seeds import key_of, rng_of
from .train import build_net, place


def model_dims(config):
    a = config["args"]
    return {"vocab": a["vocab_size"], "d_model": a["d_model"],
            "layers": a["n_layers"], "heads": a["n_heads"],
            "ffn": a["d_model"] * a.get("ffn_mult", 4)}


def sender(host, port, timeout, ends_at=None):
    """POSTs one /generate. With `ends_at` (a perf_counter time, in a list so
    it can be set later) each request carries the server's own `timeout_ms`
    up to then: a request that would outlive the window is answered with
    what it has (finish_reason "deadline") once the window has closed,
    instead of holding its slot for up to a minute more."""
    def send(prompt, new):
        import jax
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            req = {"prompt": prompt, "max_new_tokens": new}
            if ends_at:
                req["timeout_ms"] = max(
                    500.0, (ends_at[0] - time.perf_counter()) * 1e3)
            body = json.dumps(req)
            with jax.profiler.TraceAnnotation("bench:generate_request"):
                conn.request("POST", "/generate", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                data = resp.read()
            return resp.status, (json.loads(data) if resp.status == 200
                                 else {"error": data[:200].decode("latin1")})
        finally:
            conn.close()
    return send


def served_gaps(ref, params, dims, samples, dtype="float32",
                own_first=False):
    """For each (prompt, tokens) sample, the reference's logits over prompt +
    tokens in one pass, and per served token the gap by which its logit lies
    below the reference's best at that position. With `own_first` (the
    control) the token judged at each position is the one that `dtype`
    arithmetic puts first there, not the served one. Returns the gaps of all
    samples, concatenated. Sequences are padded to a multiple of 256 (causal
    attention: the padding changes nothing before it), and the gap is taken
    at every position on the device, so only four shapes ever compile."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps_of(logits, judged):
        picked = jnp.take_along_axis(logits, judged[:, None], axis=1)[:, 0]
        return jnp.max(logits, axis=-1) - picked

    out = []
    for prompt, tokens in samples:
        n_p, n_t = len(prompt), len(tokens)
        pad_to = -(-(n_p + n_t) // 256) * 256
        ids = np.zeros((pad_to,), np.int32)
        ids[:n_p + n_t] = prompt + tokens
        ids = jnp.asarray(ids)
        want = ref.logits(params, ids, heads=dims["heads"],
                          layers=dims["layers"])
        if own_first:
            judged = jnp.argmax(ref.logits(
                params, ids, heads=dims["heads"], layers=dims["layers"],
                dtype=dtype), axis=-1).astype(jnp.int32)
        else:                   # position i predicts the token at i + 1
            judged = jnp.roll(ids, -1)
        gaps = np.asarray(gaps_of(want, judged), np.float64)
        out.append(gaps[n_p - 1:n_p - 1 + n_t])
    return np.concatenate(out)


def run(run):
    import jax
    from deeplearning4j_tpu.serving.server import ServingServer

    cfg, p = run.config, run.cell["serve"]
    ref, dims = run.reference(), model_dims(cfg)
    key = key_of(run.seed)

    run.mark("imports")
    net = build_net(cfg)
    net.init()
    run.mark("program_init")
    params = ref.init_params(key, dims["vocab"], dims["d_model"],
                             dims["layers"], dims["ffn"])
    place(net, params)
    jax.block_until_ready(params)
    run.mark("weights_placed")
    server = ServingServer(decode=True, decode_slots=p["slots"],
                           decode_max_len=p["decode_max_len"],
                           decode_queue_capacity=p["queue_capacity"],
                           decode_max_new_tokens=16)
    server.registry.register("bench", net)
    server.deploy("bench")
    server.start()
    run.mark("deployed")
    registry = server.metrics.registry
    ends_at = []
    send = sender(server.host, server.port, p.get("timeout_s", 120), ends_at)
    try:
        # warm the prefill buckets this mix uses, through the served path
        engine = server.decode.engine_for(
            server.registry.get("bench").model)
        lens = traffic.length_grid(p["mix"]["prompt_tokens"],
                                   p["mix"]["cycle"])
        for L in sorted({engine.prefill_bucket(n) for n in lens}):
            status, body = send([0] * min(L, p["decode_max_len"] - 3), 2)
            if status != 200:
                raise RuntimeError(f"warm-up of bucket {L} failed: {body}")

        run.mark("buckets_warm")
        n_req = int(p["requests_drawn"])
        reqs = traffic.requests(run.seed, p["mix"], dims["vocab"], n_req)
        stop = threading.Event()
        t_begin = time.perf_counter()
        ends_at.append(t_begin + p["ramp_s"] + run.seconds + 1.0)
        if p["loop"] == "closed":
            threads, outcomes = traffic.closed_loop(send, reqs, p["clients"],
                                                    stop)
        else:
            times = traffic.arrivals(run.seed, p["rate_per_s"], n_req)
            threads, outcomes = traffic.open_loop(send, reqs, times, t_begin,
                                                  stop, p["workers"])
        time.sleep(p["ramp_s"])
        setup_s = run.setup_seconds()

        # the window
        slice_ = observe.TraceSlice(run.trace_dir, run.trace)
        poll = observe.GaugePoll(registry, ["decode_active_slots",
                                            "decode_queue_depth"]).start()
        before = observe.snapshot(registry)
        t0 = time.perf_counter()
        if run.trace:       # this thread launches nothing on the device
            time.sleep(p["trace_delay_s"])
            with slice_:
                time.sleep(p["trace_seconds"])
        time.sleep(max(0.0, t0 + run.seconds - time.perf_counter()))
        t1 = time.perf_counter()
        after = observe.snapshot(registry)
        polled = poll.stop()
        stop.set()
        reduced = slice_.reduce()
        peak_bytes = observe.memory_peak_bytes(server.cost.table())
        for t in threads:                  # requests in flight run out
            t.join()
    finally:
        server.stop()

    done = [o for o in outcomes if t0 <= o.done <= t1]
    ok = [o for o in done if o.status == 200
          and o.body["finish_reason"] == "length"]
    answered = [o for o in outcomes if o.status == 200 and o.done > t0
                and o.sent < t1]
    tokens = sum(len(o.body["tokens"])
                 * (min(o.done, t1) - max(o.sent, t0)) / (o.done - o.sent)
                 for o in answered)
    ttft = [o.body["ttft_ms"] + (o.sent - o.due) * 1e3 for o in ok]
    tpot = [((o.done - o.sent) * 1e3 - o.body["ttft_ms"])
            / (len(o.body["tokens"]) - 1)
            for o in ok if len(o.body["tokens"]) > 1]
    late = [(o.sent - o.due) * 1e3 for o in done]
    elapsed = t1 - t0
    observe.note(window_seconds=elapsed, completed=len(done), ok=len(ok),
                 ttft_samples=len(ttft), tpot_samples=len(tpot),
                 ttft_p50_ms=statistics.median(ttft) if ttft else None,
                 tpot_p50_ms=statistics.median(tpot) if tpot else None,
                 generator_late_ms_p50=statistics.median(late) if late
                 else None, generator_late_ms_max=max(late, default=None),
                 errors=[o.body or o.error for o in done
                         if o.status != 200][:3])

    # the server and its cache are freed; the reference reads a sample
    del server, engine, net
    gc.collect()
    finished = [o for o in outcomes if o.status == 200
                and o.body["finish_reason"] == "length"]
    ck = run.check
    if finished:
        t_ref = time.perf_counter()
        longest = max(finished, key=lambda o: len(reqs[o.index][0])
                      + len(o.body["tokens"]))
        rng = rng_of(run.seed, 4)
        rest = [o for o in finished if o is not longest]
        picks = [longest] + [rest[i] for i in rng.permutation(len(rest))
                             [:max(0, p["check_requests"] - 1)]]
        samples = [(reqs[o.index][0], o.body["tokens"]) for o in picks]
        gaps = served_gaps(ref, params, dims, samples)
        ck.at_most("served_token_logit_gap_max", float(gaps.max()),
                   run.cell["limits"]["served_token_logit_gap_max"])
        info = {"checked_requests": len(samples),
                "checked_tokens": int(gaps.size),
                "gap_mean": float(gaps.mean()),
                "tokens_not_reference_first": int((gaps > 0).sum()),
                "distinct_served_tokens": len({t for _, ts in samples
                                               for t in ts})}
        if run.control:
            low = served_gaps(ref, params, dims, samples,
                              dtype=cfg["control_precision"], own_first=True)
            run.control_rows.append({"check": "served_token_logit_gap_max",
                                     "value": float(low.max()),
                                     "gap_mean": float(low.mean())})
        info["reference_seconds"] = time.perf_counter() - t_ref
        observe.note(**info)
    short = sum(1 for o in ok if len(o.body["tokens"]) != o.asked)
    ck.at_most("responses_of_wrong_length", float(short)
               + float(not finished), 0.0)
    compiles = sum(after.get(n, 0) - before.get(n, 0)
                   for n in ("jit_compiles_total", "compiles_total"))
    ck.at_most("compiles_in_window", float(compiles), 0.0)

    end = {"setup_s": setup_s,
           "serve_tokens_per_s": tokens / elapsed}
    return {
        "attempted": len(done), "failed": len(done) - len(ok),
        "memory_peak_bytes": peak_bytes, "end_to_end": end,
        "counts": {"completed": len(done), "ok": len(ok), "tokens": tokens},
        "obs": {"before": before, "after": after, "trace": reduced,
                "polled": polled, "requests": {"ttft_ms": ttft, "tpot_ms": tpot},
                "cell": run.cell, "config": cfg,
                "peak": run.peak,
                "window": {"seconds": elapsed, "requests": len(ok),
                           "tokens": tokens, "chips": run.cell["chips"]}},
    }
