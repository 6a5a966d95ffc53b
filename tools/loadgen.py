"""Open-loop load generator: fixed offered rate, latency SLO report.

Scale claims should be measured, not asserted — and measured honestly. A
*closed-loop* client (fire, wait, fire again) suffers coordinated omission:
when the server stalls, the client stops offering load, so the stall never
shows in the latency distribution. This generator is *open-loop*: arrival
times are drawn up front from a Poisson process (exponential interarrivals
off the seeded RNG seam — deterministic schedule per seed) and every
request fires at its scheduled time on its own thread, whether or not
earlier ones returned. A slow server faces the same offered rate and the
tail shows up where it belongs: in p99 and in shed/error ratios.

Pacing waits go through resilience's advance-aware sleep, so a ManualClock
run (the autoscale smoke) collapses the schedule deterministically with
zero real sleeps, while a real-clock run offers the true rate.

In-flight threads are bounded (`max_inflight`, the GL012 spawn guard);
arrivals past the bound are *counted* as `dropped_inflight` — dropped load
is reported, never silently reshaped into a lower offered rate.

Report (all ratios over arrivals):

    {"offered_rate", "achieved_rate", "duration_s", "arrivals", "ok",
     "shed", "errors_5xx", "transport_errors", "dropped_inflight",
     "shed_ratio", "error_ratio", "p50_ms", "p99_ms", "mean_ms"}

Usage:
    JAX_PLATFORMS=cpu python tools/loadgen.py http://HOST:PORT \
        --rate 100 --duration 5 [--path /predict] [--nin 6] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import random
import sys
import threading
import urllib.error
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from deeplearning4j_tpu.resilience.policy import advance_aware_sleep  # noqa: E402
from deeplearning4j_tpu.util.http import post_json                   # noqa: E402
from deeplearning4j_tpu.util.time_source import monotonic_s          # noqa: E402


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i]


def run_loadgen(url, body, path="/predict", rate=50.0, duration_s=2.0,
                seed=0, timeout_s=30.0, max_inflight=256):
    """Drive `url + path` with POST `body` at `rate` req/s for `duration_s`
    (open loop; see module docstring); returns the SLO report dict."""
    rng = random.Random(seed)
    rate = float(rate)
    arrivals = []
    t = 0.0
    while True:
        t += rng.expovariate(rate)
        if t >= float(duration_s):
            break
        arrivals.append(t)

    lock = threading.Lock()
    latencies = []       # guarded by: lock — ms, completed requests only
    counts = {"ok": 0, "shed": 0, "errors_5xx": 0, "transport_errors": 0,
              "other_4xx": 0}    # guarded by: lock
    inflight = threading.Semaphore(int(max_inflight))
    threads = []
    dropped = 0
    target = url.rstrip("/") + path

    def one():
        t0 = monotonic_s()
        key = "ok"
        try:
            post_json(target, body, timeout=timeout_s)
        except urllib.error.HTTPError as e:
            key = ("shed" if e.code == 429
                   else "errors_5xx" if e.code >= 500 else "other_4xx")
        except Exception:
            key = "transport_errors"
        ms = (monotonic_s() - t0) * 1000.0
        with lock:
            counts[key] += 1
            latencies.append(ms)
        inflight.release()

    start = monotonic_s()
    for at in arrivals:
        wait = at - (monotonic_s() - start)
        if wait > 0:
            advance_aware_sleep(wait)
        # bounded spawn (GL012): over the in-flight cap the arrival is
        # DROPPED AND COUNTED — open-loop honesty — not queued (queueing
        # here would re-create the closed loop this tool exists to avoid)
        if not inflight.acquire(blocking=False):
            dropped += 1
            continue
        th = threading.Thread(target=one, daemon=True, name="loadgen")
        th.start()
        threads.append(th)
    # the offered window ends when the schedule does; the join below only
    # DRAINS stragglers. Rating completions over schedule+drain would let
    # one wedged request crater achieved_rate
    # while the server sustained the offered rate the whole window — the
    # straggler's cost belongs in p99/mean, and drain_s reports the wait.
    schedule_s = max(monotonic_s() - start, float(duration_s), 1e-9)
    for th in threads:
        th.join(timeout_s + 5.0)
    drain_s = monotonic_s() - start - schedule_s

    with lock:
        lat = sorted(latencies)
        c = dict(counts)
    n = len(arrivals)
    report = {
        "offered_rate": rate,
        "achieved_rate": c["ok"] / schedule_s,
        "duration_s": schedule_s,
        "drain_s": max(drain_s, 0.0),
        "arrivals": n,
        "ok": c["ok"], "shed": c["shed"], "errors_5xx": c["errors_5xx"],
        "other_4xx": c["other_4xx"],
        "transport_errors": c["transport_errors"],
        "dropped_inflight": dropped,
        "shed_ratio": c["shed"] / n if n else 0.0,
        "error_ratio": (c["errors_5xx"] + c["transport_errors"]) / n
        if n else 0.0,
        "p50_ms": _percentile(lat, 0.50),
        "p99_ms": _percentile(lat, 0.99),
        "mean_ms": sum(lat) / len(lat) if lat else None,
    }
    return report


def predict_body(nin=6):
    return {"data": [[0.1] * int(nin)]}


def generate_body(prompt_len=8, max_new_tokens=8, vocab=16):
    return {"prompt": [i % int(vocab) for i in range(int(prompt_len))],
            "max_new_tokens": int(max_new_tokens)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("url", help="server base URL (ServingServer or "
                                "FleetFrontend)")
    ap.add_argument("--path", default="/predict",
                    choices=["/predict", "/generate"])
    ap.add_argument("--rate", type=float, default=50.0,
                    help="offered rate, requests/second")
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=30.0)
    ap.add_argument("--max-inflight", type=int, default=256)
    ap.add_argument("--nin", type=int, default=6,
                    help="/predict feature width")
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="/generate prompt length")
    ap.add_argument("--body", default=None,
                    help="explicit JSON request body (overrides --nin/"
                         "--prompt-len)")
    args = ap.parse_args(argv)
    if args.body is not None:
        body = json.loads(args.body)
    elif args.path == "/generate":
        body = generate_body(prompt_len=args.prompt_len)
    else:
        body = predict_body(nin=args.nin)
    report = run_loadgen(args.url, body, path=args.path, rate=args.rate,
                         duration_s=args.duration, seed=args.seed,
                         timeout_s=args.timeout,
                         max_inflight=args.max_inflight)
    print(json.dumps(report, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
