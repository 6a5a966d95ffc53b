"""95th percentile of the time to first token in a closed loop at capacity:
the response's `ttft_ms` (scheduler clock) of every request answered in full
inside the window. With as many clients as slots a slot is always free, so
this is the wait for the running step plus one prefill. At capacity a tail
swings too much to be an end-to-end metric (4.7-6.1 % between runs)."""
UNIT = "ms"
LAYER = "decode scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def read(obs):
    from benchmarks.observe import nearest_rank
    ttft = (obs.get("requests") or {}).get("ttft_ms")
    if not ttft or obs["cell"]["serve"].get("loop") != "closed":
        return None
    return nearest_rank(ttft, 0.95)
