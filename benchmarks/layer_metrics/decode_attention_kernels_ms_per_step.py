"""Milliseconds of a decode step spent in the attention layers' two kernels:
the self time of `flash_decode` and `kv_append` in the `kernels` of the
`jit_step_fn` program (every call site, counted inside the program events
that lie whole in the slice) over those events (`kernels_cover`). Where the append is not that
kernel (head_dim >= 128: XLA's scatter) only `flash_decode` is in it. None
when the step program holds neither."""
UNIT = "ms"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

STEP_PROGRAM = "jit_step_fn"
KERNELS = ("flash_decode", "kv_append")


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("kernels") or "serve" not in obs["cell"]:
        return None
    events = sum(c for name, c, _, _ in t["kernels_cover"]
                 if name.startswith(STEP_PROGRAM))
    seconds = [s for prog, name, _, s in t["kernels"]
               if prog.startswith(STEP_PROGRAM) and name in KERNELS]
    if not seconds or not events > 0:
        return None
    return sum(seconds) / events * 1e3
