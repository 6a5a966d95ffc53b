"""Milliseconds a decode step spends copying its [slots, vocab] float32
probabilities to the host: the sum of `decode_probs_read_ms` over the window
/ its count."""
UNIT = "ms"
LAYER = "decode engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    b, a = obs["before"].get("decode_probs_read_ms"), \
        obs["after"].get("decode_probs_read_ms")
    if not a:
        return None
    n = a["count"] - (b["count"] if b else 0)
    if n <= 0:
        return None
    return (a["sum"] - (b["sum"] if b else 0.0)) / n
