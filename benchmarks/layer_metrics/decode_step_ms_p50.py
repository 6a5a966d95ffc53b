"""Median wall time of one decode step as the scheduler times it: the median
of the program's `decode_itl_ms` reservoir (recent observations, one per
active slot per step) at the end of the window."""
UNIT = "ms"
LAYER = "decode engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    h = obs["after"].get("decode_itl_ms")
    if not h or h["p50"] is None:
        return None
    return float(h["p50"])
