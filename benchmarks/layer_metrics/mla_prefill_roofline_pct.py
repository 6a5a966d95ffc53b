"""Share of the MXU's peak the `mla_prefill` kernel reaches in the prefill
programs: the operations a layer's causal attention over a prefill bucket has
to do in the plain form of latent attention (`mla_prefill_flops(bucket,
d_model)` of the configuration's reference: 2 x heads x (192 + 128) for every
(query, key) pair at or under the diagonal) over the device's bfloat16 peak,
over the seconds a call takes: the kernel's self time in the `kernels` of the
`jit_prefill_fn*` programs over its calls there.

The kernel carries its bucket in its name (`mla_prefill_<tokens>`), which
tells it from every other call site and says what each call had to do; the
buckets the slice ran are summed, operations and seconds apart. A program
event counts only where it lies whole in the slice. The kernel also computes
the rest of the diagonal's blocks and a bucket's padding, so the share can
only read low. None where the slice holds no whole prefill event, the
programs hold no such kernel, or the reference has no such count."""
import importlib

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

PREFILL_PROGRAM = "jit_prefill_fn"
KERNEL = "mla_prefill_"


def read(obs):
    t = obs.get("trace")
    if not t or "serve" not in obs["cell"] or "reference" not in obs["config"]:
        return None
    rows = [(int(name[len(KERNEL):]), calls, s)
            for prog, name, calls, s in t.get("kernels") or ()
            if prog.startswith(PREFILL_PROGRAM) and name.startswith(KERNEL)
            and name[len(KERNEL):].isdigit()]
    seconds = sum(s for _, _, s in rows)
    ref = importlib.import_module(
        "benchmarks.reference." + obs["config"]["reference"])
    if not seconds > 0 or not hasattr(ref, "mla_prefill_flops"):
        return None
    d_model = obs["config"]["args"]["d_model"]
    flops = sum(calls * ref.mla_prefill_flops(tokens, d_model)
                for tokens, calls, _ in rows)
    return 100.0 * flops / seconds / obs["peak"]["flops_per_s"]["bfloat16"]
