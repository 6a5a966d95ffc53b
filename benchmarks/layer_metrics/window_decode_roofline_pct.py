"""Share of the HBM roofline the `flash_decode_window` kernel — the decode
step's attention call of a SLIDING-WINDOW layer, on its ring — reaches: the
bytes one call has to move (`window_decode_bytes(slots, window_tokens,
d_model)` of the configuration's reference: the K and V rows of the
positions the active slots' rings hold, the step's token rows in, the query
rows in and the context rows out) over the device's peak HBM bytes/s, over
the seconds a call takes: the kernel's self time in the `kernels` of the
`jit_step_fn` program over its calls there.

`window_tokens` is active slots x the window (`args.window` of the
configuration), the active slots the SMALLER of the gauge
`decode_active_slots` in the window's two snapshots. That a ring is FULL
holds because no prompt of the cell is shorter than the window: every
window layer's ring is full from a request's first step. The kernel also
reads an idle slot's first block, whole key blocks, and — the ring wrapped —
the place of the token it is about to overwrite, none of which is counted:
the share can only read low. The full layers' calls carry another name
(`flash_decode`) and are not read here; `flash_decode_roofline_pct` reads
those and none of these. None when the step program holds no such kernel
(the parent, a model without window layers), the gauge was not read, or the
reference has no such byte count."""
import importlib

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

STEP_PROGRAM = "jit_step_fn"
KERNEL = "flash_decode_window"
GAUGE = "decode_active_slots"


def read(obs):
    t = obs.get("trace")
    if not t or "serve" not in obs["cell"] or "reference" not in obs["config"]:
        return None
    rows = [(calls, s) for prog, name, calls, s in t.get("kernels") or ()
            if prog.startswith(STEP_PROGRAM) and name == KERNEL]
    calls = sum(c for c, _ in rows)
    active = [snap.get(GAUGE) for snap in (obs["before"], obs["after"])]
    ref = importlib.import_module(
        "benchmarks.reference." + obs["config"]["reference"])
    args = obs["config"]["args"]
    if not calls > 0 or None in active or not args.get("window") \
            or not hasattr(ref, "window_decode_bytes"):
        return None
    serve = obs["cell"]["serve"]
    slots = min(min(active), serve["slots"])
    # a ring holds the window, or the capacity where a slot cannot outgrow it
    ring = min(args["window"], serve["decode_max_len"])
    floor = ref.window_decode_bytes(slots, slots * ring, args["d_model"]) \
        / obs["peak"]["hbm_bytes_per_s"]
    return 100.0 * floor * calls / sum(s for _, s in rows)
