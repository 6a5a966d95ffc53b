"""Share of the decode programs' time over the whole window that the prefill
programs took: the program's own ledger, the counter
`decode_program_ms_total{program}` (the sum of `decode_program_ms`: the wall
of every warm execution from the previous result reaching the host to this
one — with the device kept fed, the program's device time); the window's
growth of the `prefill:<bucket>` series over the growth of every series. The
whole window's answer to what `decode_prefill_device_share_pct` draws from a
0.5 s slice. Time can shift between a program and its neighbour (the host
reads results in device order, not the instant they land); the sum over
programs is conserved."""
UNIT = "%"
LAYER = "decode engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"

SERIES = 'decode_program_ms_total{program="'


def read(obs):
    before, after = obs["before"], obs["after"]
    grown = {name: ms - before.get(name, 0.0) for name, ms in after.items()
             if name.startswith(SERIES)}
    total = sum(grown.values())
    if total <= 0:
        return None
    return 100.0 * sum(ms for name, ms in grown.items()
                       if name.startswith(SERIES + "prefill:")) / total
