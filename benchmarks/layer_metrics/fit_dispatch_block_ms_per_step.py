"""Milliseconds per step that fit(steps_per_execution=K) spent inside the
call of the multi-step executable until it returned (not until ready): the
sum of the program's `fit_dispatch_ms` over the window / steps. Near the
step's device time when that call is what holds the host to one execution
ahead of the device; near zero when something else does."""
UNIT = "ms"
LAYER = "step builder"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(obs):
    b, a = obs["before"].get("fit_dispatch_ms"), \
        obs["after"].get("fit_dispatch_ms")
    steps = obs["window"].get("steps")
    if not a or not steps:
        return None
    return (a["sum"] - (b["sum"] if b else 0.0)) / steps
