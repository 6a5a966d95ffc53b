"""Median of the /generate handler's own time per request (parse, sampler
config, submit, answer; its wall less the wait on the scheduler's future):
the median of the program's `generate_front_ms` reservoir at the end of the
window."""
UNIT = "ms"
LAYER = "serving front"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    h = obs["after"].get("generate_front_ms")
    if not h or h["p50"] is None:
        return None
    return float(h["p50"])
