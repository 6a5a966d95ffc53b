"""Milliseconds per step that fit() blocked waiting for the prefetcher's next
batch: the sum of the program's `etl_consumer_wait_ms` over the window / steps."""
UNIT = "ms"
LAYER = "input path"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(obs):
    b, a = obs["before"].get("etl_consumer_wait_ms"), \
        obs["after"].get("etl_consumer_wait_ms")
    steps = obs["window"].get("steps")
    if not a or not steps:
        return None
    return (a["sum"] - (b["sum"] if b else 0.0)) / steps
