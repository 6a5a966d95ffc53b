"""Share of the window's wall time the scheduler thread spent inside
synchronous `engine.prefill` calls: 100 x the sum of `decode_prefill_ms` over
the window / (1000 x window seconds). While it prefills, no slot steps."""
UNIT = "%"
LAYER = "decode engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    b, a = obs["before"].get("decode_prefill_ms"), \
        obs["after"].get("decode_prefill_ms")
    seconds = obs["window"].get("seconds")
    if not a or not seconds:
        return None
    return 100.0 * (a["sum"] - (b["sum"] if b else 0.0)) / (1000.0 * seconds)
