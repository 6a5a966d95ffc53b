"""Device-busy milliseconds per optimizer step: the busy time of the traced
slice (every program, the input path's small ones too) over the optimizer
steps in it - the executions of the program with most device time, each
`steps_per_execution` steps."""
UNIT = "ms"
LAYER = "step builder"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(obs):
    t = obs.get("trace")
    per = (obs["cell"].get("train") or {}).get("steps_per_execution")
    if not t or not t.get("programs") or not per:
        return None
    return t["busy_s"] / (t["programs"][0][1] * per) * 1e3
