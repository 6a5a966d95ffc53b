"""Milliseconds the decode step's program runs on the device: the median
duration of the `jit_step_fn` events that lie whole in the traced slice
(`program_p50_s` of trace_reduce.reduce). With a step kept in flight
(`decode_steps_ahead_pct` 100) this is what `decode_step_ms_p50` times from
the host."""
UNIT = "ms"
LAYER = "decode engine"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

STEP_PROGRAM = "jit_step_fn"


def read(obs):
    p50 = (obs.get("trace") or {}).get("program_p50_s") or {}
    steps = [s for name, s in p50.items() if name.startswith(STEP_PROGRAM)]
    if not steps:
        return None
    return steps[0] * 1e3
