"""Milliseconds of a decode step's pass that are neither the wait for the
device nor a prefill: (sum of `decode_wave_ms` - sum of `decode_step_sync_ms`
- sum of `decode_prefill_ms`) over the window / steps (the count of
`decode_step_sync_ms`). The host's own work between two device programs:
admission bookkeeping, building the operands, the dispatch, the probabilities'
read, emitting tokens."""
UNIT = "ms"
LAYER = "decode scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    b, a = obs["before"], obs["after"]
    names = ("decode_wave_ms", "decode_step_sync_ms", "decode_prefill_ms")
    if any(not a.get(n) for n in names):
        return None
    d = {n: a[n]["sum"] - (b.get(n) or {"sum": 0.0})["sum"] for n in names}
    steps = a["decode_step_sync_ms"]["count"] \
        - (b.get("decode_step_sync_ms") or {"count": 0})["count"]
    if steps <= 0:
        return None
    return (d["decode_wave_ms"] - d["decode_step_sync_ms"]
            - d["decode_prefill_ms"]) / steps
