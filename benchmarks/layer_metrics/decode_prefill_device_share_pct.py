"""Share of the device's busy time in the traced slice that the prefill
programs take: the seconds of the `jit_prefill_fn*` events, clipped to the
slice (`program_busy` of trace_reduce.reduce), over `busy_s`. With the host
hidden under a running step the rate is slots / (step program + this share of
a pass), so it is what several prompts in one prefill, or chunks riding a
step, would shrink. A slice of the loop that holds no prefill reads 0; one
that holds no decode program at all has nothing to read."""
UNIT = "%"
LAYER = "decode engine"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

PREFILL_PROGRAM = "jit_prefill_fn"
STEP_PROGRAM = "jit_step_fn"


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("busy_s") or "serve" not in obs["cell"]:
        return None
    busy = t.get("program_busy") or ()
    if not any(name.startswith((STEP_PROGRAM, PREFILL_PROGRAM))
               for name, _ in busy):
        return None
    return 100.0 * sum(s for name, s in busy
                       if name.startswith(PREFILL_PROGRAM)) / t["busy_s"]
