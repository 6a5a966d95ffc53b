"""Median time a request waited for a decode slot: the median of the
program's `decode_queue_wait_ms` reservoir (enqueue to popped with a free
slot, one observation per request) at the end of the window."""
UNIT = "ms"
LAYER = "decode scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    h = obs["after"].get("decode_queue_wait_ms")
    if not h or h["p50"] is None:
        return None
    return float(h["p50"])
