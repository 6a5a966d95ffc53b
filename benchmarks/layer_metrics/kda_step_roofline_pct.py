"""Share of the HBM roofline the `kda_step` kernel reaches in the decode step:
the bytes one call has to move (`kda_step_bytes(slots, d_model)` of the
configuration's reference: the float32 delta-rule state read once and written
once, and its row operands) over the device's peak HBM bytes/s, over the
seconds a call takes: the kernel's self time in the `kernels` of the
`jit_step_fn` program (every call site, counted inside the program events
that lie whole in the slice) over its calls there. None when the step
program holds no such kernel (the parent, a model without KDA layers), or the
reference has no such byte count."""
import importlib

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

STEP_PROGRAM = "jit_step_fn"
KERNEL = "kda_step"


def read(obs):
    t = obs.get("trace")
    if not t or "serve" not in obs["cell"] or "reference" not in obs["config"]:
        return None
    rows = [(calls, s) for prog, name, calls, s in t.get("kernels") or ()
            if prog.startswith(STEP_PROGRAM) and name == KERNEL]
    calls = sum(c for c, _ in rows)
    ref = importlib.import_module(
        "benchmarks.reference." + obs["config"]["reference"])
    if not calls > 0 or not hasattr(ref, "kda_step_bytes"):
        return None
    seconds = sum(s for _, s in rows) / calls
    floor = ref.kda_step_bytes(obs["cell"]["serve"]["slots"],
                               obs["config"]["args"]["d_model"]) \
        / obs["peak"]["hbm_bytes_per_s"]
    return 100.0 * floor / seconds
