"""Model FLOP/s utilisation of training: the operations forward and backward
require (flops.py: 2 per multiply-accumulate, 3 x forward, nothing recomputed;
the forward count is `forward_macs(args)` of the configuration's own
reference/<config>.py) times samples per second, over chips times the bf16
peak of peaks.json."""
import importlib

UNIT = "%"
LAYER = "step builder"
MOVES = "samples_per_s_per_chip"
SOURCE = "host_clock"


def read(obs):
    from benchmarks import flops
    w, config = obs["window"], obs["config"]
    if not w.get("samples") or "reference" not in config:
        return None
    ref = importlib.import_module("benchmarks.reference."
                                  + config["reference"])
    if not hasattr(ref, "forward_macs"):
        return None
    per_sample = flops.train_flops_per_sample(ref.forward_macs(config["args"]))
    rate = w["samples"] / w["seconds"] / w["chips"]
    return 100.0 * per_sample * rate / obs["peak"]["flops_per_s"]["bfloat16"]
