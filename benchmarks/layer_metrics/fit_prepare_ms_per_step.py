"""Milliseconds per step that fit(steps_per_execution=K) spent stacking the
next group of K device batches into an execution plan: the sum of the
program's `fit_prepare_ms` over the window / steps."""
UNIT = "ms"
LAYER = "step builder"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(obs):
    b, a = obs["before"].get("fit_prepare_ms"), \
        obs["after"].get("fit_prepare_ms")
    steps = obs["window"].get("steps")
    if not a or not steps:
        return None
    return (a["sum"] - (b["sum"] if b else 0.0)) / steps
