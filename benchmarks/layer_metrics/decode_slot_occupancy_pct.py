"""Share of the decode slots that held a request, averaged over the window:
the program's `decode_active_slots` gauge polled every 20 ms, over `slots`."""
UNIT = "%"
LAYER = "decode scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    samples = (obs.get("polled") or {}).get("decode_active_slots")
    if not samples:
        return None
    return 100.0 * sum(samples) / len(samples) / obs["cell"]["serve"]["slots"]
