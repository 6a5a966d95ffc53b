"""Share of the HBM roofline the `expert_gmm` kernel reaches in the decode
step: the bytes one layer's expert products have to move
(`expert_layer_bytes(slots)` of the configuration's reference: the held
experts' matrices once, the rows routed to them in and out) over the device's
peak HBM bytes/s, over the seconds a call takes in the traced slice.

A layer is ONE kernel call (both products and the gate fused), named after
the leading shape of the layer's input: `expert_gmm_<slots>x1` in the step
program, `expert_gmm_1x<bucket>` in a prefill's, so only the step's call
sites are read here although prefills run the same kernel inside the slice.
The slice's `device_ops` are the ten operations with most time; each such
name among them is one layer's call site, its seconds in the slice over the
executions of the step program in the slice are that layer's seconds a call,
and the metric is taken from their mean. (Operations are clipped to the slice
and executions counted only when wholly inside it, so the seconds a call read
slightly high and the share slightly low; a step in which a held expert got
no row reads less than is counted, 1/18 of the bytes at most once in some
hundred steps: never over 100 at the kernel's efficiency.) None when no such
operation is listed, or the program has no such kernel."""
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
    slots = obs["cell"]["serve"]["slots"]
    site = f"expert_gmm_{slots}x1"
    calls = [s for name, s in t["device_ops"]
             if name == site or name.startswith(site + ".")]
    steps = [c for name, c, _ in t["programs"]
             if name.startswith(STEP_PROGRAM)]
    if not calls or not steps or not steps[0] > 0:
        return None
    ref = importlib.import_module(
        "benchmarks.reference." + obs["config"]["reference"])
    if not hasattr(ref, "expert_layer_bytes"):
        return None
    seconds = sum(calls) / len(calls) / steps[0]
    floor = ref.expert_layer_bytes(slots, obs["config"]["args"]["d_model"]) \
        / obs["peak"]["hbm_bytes_per_s"]
    return 100.0 * floor / seconds
