"""Share of the HBM roofline the `ssm_step` kernel reaches in the decode step:
the bytes one call has to move (`ssm_step_bytes(slots)` of the configuration's
reference: the float32 state read once and written once, and its row and
column operands) over the device's peak HBM bytes/s, over the seconds a call
takes in the traced slice.

The slice's `device_ops` are the ten operations with most time; each
`ssm_step*` among them is ONE layer's call site, so its seconds in the slice
over the executions of the step program in the slice are that layer's seconds
a call, and the metric is taken from their mean. (Operations are clipped to
the slice and executions counted only when wholly inside it, so the seconds
a call read slightly high and the share slightly low, never the other way.)
None when no such operation is listed."""
import importlib

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

STEP_PROGRAM = "jit_step_fn"


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("programs") or "serve" not in obs["cell"]:
        return None
    calls = [s for name, s in t["device_ops"] if name.startswith("ssm_step")]
    steps = [c for name, c, _ in t["programs"]
             if name.startswith(STEP_PROGRAM)]
    if not calls or not steps or not steps[0] > 0:
        return None
    ref = importlib.import_module(
        "benchmarks.reference." + obs["config"]["reference"])
    seconds = sum(calls) / len(calls) / steps[0]
    floor = ref.ssm_step_bytes(obs["cell"]["serve"]["slots"],
                               obs["config"]["args"]["d_model"]) \
        / obs["peak"]["hbm_bytes_per_s"]
    return 100.0 * floor / seconds
