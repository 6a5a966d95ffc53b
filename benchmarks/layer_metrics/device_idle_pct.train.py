"""Share of the training window in which no program ran on the device: 1 -
device-busy time per optimizer step (the traced slice, as
train_step_device_ms) / wall time per optimizer step (the window). Not the
idle share of the slice itself: under the profiler the input path delivers a
batch per 0.5 s and the slice reads 76-80 % idle whatever the loop does."""
UNIT = "%"
LAYER = "device"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(obs):
    t, w = obs.get("trace"), obs["window"]
    per = (obs["cell"].get("train") or {}).get("steps_per_execution")
    if not t or not t.get("programs") or not per or not w.get("steps"):
        return None
    busy_per_step = t["busy_s"] / (t["programs"][0][1] * per)
    return 100.0 * (1.0 - busy_per_step / (w["seconds"] / w["steps"]))
