"""Share of the HBM roofline the `expert_gmm` kernel reaches in the decode
step, read from the STEP PROGRAM's own kernels: the bytes one layer's expert
products have to move (`expert_layer_bytes(slots, d_model)` of the
configuration's reference: the held experts' matrices once, the rows routed
to them in and out) over the device's peak HBM bytes/s, over the seconds a
call takes: the self time of the kernel `expert_gmm_<slots>x1` in the
`kernels` of the `jit_step_fn` program over its calls there (whole program
events only, every layer's call site merged under the name).

`expert_gmm_roofline_pct` reads the same kernel from the slice's ten longest
operations of the program with MOST device time; where a prefill is a tenth
of a second and more, a 0.5 s slice that holds two is the prefill's and that
reader finds no step call site. This one asks for the step program by name,
so it reads whenever the slice holds one whole step. A prefill's call sites
(`expert_gmm_1x<bucket>`) carry another name and another program and are not
read. A step in which a held expert got no row moves less than is counted:
the share then reads high by that expert's 1 / held of the bytes. None when
the step program holds no such kernel or the reference has no such count."""
import importlib

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

STEP_PROGRAM = "jit_step_fn"


def read(obs):
    t = obs.get("trace")
    if not t or "serve" not in obs["cell"] or "reference" not in obs["config"]:
        return None
    slots = obs["cell"]["serve"]["slots"]
    rows = [(calls, s) for prog, name, calls, s in t.get("kernels") or ()
            if prog.startswith(STEP_PROGRAM)
            and name == f"expert_gmm_{slots}x1"]
    calls = sum(c for c, _ in rows)
    ref = importlib.import_module(
        "benchmarks.reference." + obs["config"]["reference"])
    if not calls > 0 or not hasattr(ref, "expert_layer_bytes"):
        return None
    floor = ref.expert_layer_bytes(slots, obs["config"]["args"]["d_model"]) \
        / obs["peak"]["hbm_bytes_per_s"]
    return 100.0 * floor * calls / sum(s for _, s in rows)
