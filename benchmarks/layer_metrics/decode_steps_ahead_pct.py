"""Share of the window's decode steps that were dispatched while another was
still unread: the program's `decode_steps_ahead_total{ahead="1"}` over both
of its labels. A step counts `ahead="0"` when it goes into a drained loop
(the first after idle, a hot-swap, a failure; on a mesh every step)."""
UNIT = "%"
LAYER = "decode scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"

AHEAD, DRAINED = ('decode_steps_ahead_total{ahead="1"}',
                  'decode_steps_ahead_total{ahead="0"}')


def read(obs):
    b, a = obs["before"], obs["after"]
    if AHEAD not in a and DRAINED not in a:
        return None
    ahead, drained = (a.get(n, 0) - b.get(n, 0) for n in (AHEAD, DRAINED))
    if ahead + drained <= 0:
        return None
    return 100.0 * ahead / (ahead + drained)
