"""95th percentile of the time per output token in a closed loop at capacity:
per request with 2 tokens or more that was answered in full inside the
window, (client-clock latency - `ttft_ms`) / (tokens - 1). The sample is
small and censored, which is why this is no end-to-end metric: a request
lives `tokens` decode steps, so only requests short enough to start and end
inside ramp + window are in it (in `opt350m_batch_decode`, 105 ms a step and
51 s: under ~485 tokens, some 78 requests, 4 beyond the 95th percentile)."""
UNIT = "ms"
LAYER = "decode engine"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def read(obs):
    from benchmarks.observe import nearest_rank
    tpot = (obs.get("requests") or {}).get("tpot_ms")
    if not tpot or obs["cell"]["serve"].get("loop") != "closed":
        return None
    return nearest_rank(tpot, 0.95)
