"""Model FLOP/s utilisation of serving: the operations a generated token
needs in the weights' products (`decode_macs_per_token` of the configuration's
own reference/<config>.py, 2 per multiply-accumulate) times the window's
tokens per second, over chips times the bf16 peak of peaks.json. The whole
step's share of the chip's arithmetic peak, beside the kernels' roofline
shares: a decode step is bound by HBM, so it is small, and it still falls
when a kernel taken off the path made the step no faster."""
import importlib

UNIT = "%"
LAYER = "decode engine"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def read(obs):
    from benchmarks.kinds.serve import model_dims
    w, config = obs["window"], obs["config"]
    if not w.get("tokens") or "reference" not in config \
            or not obs.get("peak"):
        return None
    ref = importlib.import_module("benchmarks.reference."
                                  + config["reference"])
    if not hasattr(ref, "decode_macs_per_token"):
        return None
    d = model_dims(config)
    macs = ref.decode_macs_per_token(d["vocab"], d["d_model"], d["layers"],
                                     d["ffn"])
    rate = w["tokens"] / w["seconds"] / w["chips"]
    return 100.0 * 2 * macs * rate / obs["peak"]["flops_per_s"]["bfloat16"]
