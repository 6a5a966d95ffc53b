"""Share of the traced slice of a serving window in which no operation ran
on the device."""
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    t = obs.get("trace")
    if not t or "requests" not in obs["window"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
