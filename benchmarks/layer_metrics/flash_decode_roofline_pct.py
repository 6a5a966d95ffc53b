"""Share of the HBM roofline the `flash_decode` kernel reaches in the decode
step: the bytes one call has to move (`flash_decode_bytes(slots, live_tokens,
d_model)` of the configuration's reference: the K and V rows of every live
token once for all the query heads of a K/V head, the step's token rows in,
the query rows in and the context rows out) over the device's peak HBM
bytes/s, over the seconds a call takes: the kernel's self time in the
`kernels` of the `jit_step_fn` program over its calls there.

`live_tokens` is the SMALLER of the gauge `decode_kv_live_pct` in the
window's two snapshots (the positions the active requests have filled, % of
slots x capacity) times slots x capacity — never the capacity: the kernel
reads whole key blocks of live rows, so it moves more than is counted and
the share can only read low. None when the step program holds no such
kernel, the gauge was not read, or the reference has no such byte count (a
configuration whose decode kernel reads another layout)."""
import importlib

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

STEP_PROGRAM = "jit_step_fn"
KERNEL = "flash_decode"
GAUGE = "decode_kv_live_pct"


def read(obs):
    t = obs.get("trace")
    if not t or "serve" not in obs["cell"] or "reference" not in obs["config"]:
        return None
    rows = [(calls, s) for prog, name, calls, s in t.get("kernels") or ()
            if prog.startswith(STEP_PROGRAM) and name == KERNEL]
    calls = sum(c for c, _ in rows)
    live = [snap.get(GAUGE) for snap in (obs["before"], obs["after"])]
    ref = importlib.import_module(
        "benchmarks.reference." + obs["config"]["reference"])
    if not calls > 0 or None in live \
            or not hasattr(ref, "flash_decode_bytes"):
        return None
    serve = obs["cell"]["serve"]
    tokens = min(live) / 100.0 * serve["slots"] * serve["decode_max_len"]
    floor = ref.flash_decode_bytes(serve["slots"], tokens,
                                   obs["config"]["args"]["d_model"]) \
        / obs["peak"]["hbm_bytes_per_s"]
    return 100.0 * floor * calls / sum(s for _, s in rows)
