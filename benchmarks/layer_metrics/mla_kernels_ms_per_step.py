"""Milliseconds of a decode step spent in the latent-attention layers' two
kernels: the self time of `mla_decode` (a slot's live latent rows read once
for all heads) and `latent_append` (the step's row into the cache) in the
`kernels` of the `jit_step_fn` program (every call site, counted inside the
program events that lie whole in the slice) over those events
(`kernels_cover`). Where every mixer is latent attention this is what says
that the mechanism does most of the step's work; beside
`decode_step_program_ms` it is its share. None when the step program holds
neither kernel."""
UNIT = "ms"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

STEP_PROGRAM = "jit_step_fn"
KERNELS = ("mla_decode", "latent_append")


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("kernels") or "serve" not in obs["cell"]:
        return None
    events = sum(c for name, c, _, _ in t.get("kernels_cover") or ()
                 if name.startswith(STEP_PROGRAM))
    seconds = [s for prog, name, _, s in t["kernels"]
               if prog.startswith(STEP_PROGRAM) and name in KERNELS]
    if not seconds or not events > 0:
        return None
    return sum(seconds) / events * 1e3
