"""Milliseconds a pass of the decode loop waits for step N's ids with step
N + 1 already enqueued: the sum of the program's `decode_step_sync_ms` over
the window / its count. It is what is left of a step program after the
host's own work of the pass (emit, retire, admit, build, dispatch): the room
the host has before the device would wait for it."""
UNIT = "ms"
LAYER = "decode scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    b, a = obs["before"].get("decode_step_sync_ms"), \
        obs["after"].get("decode_step_sync_ms")
    if not a:
        return None
    steps = a["count"] - (b["count"] if b else 0)
    if steps <= 0:
        return None
    return (a["sum"] - (b["sum"] if b else 0.0)) / steps
