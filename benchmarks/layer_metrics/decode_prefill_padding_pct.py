"""Share of the rows the window's prefill programs computed that held no
prompt token: the growth of the program's
`decode_prefill_rows_total{kind="padding"}` (bucket less context, counted
where a prefill is dispatched) over the growth of both kinds."""
UNIT = "%"
LAYER = "decode engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"

PROMPT, PADDING = ('decode_prefill_rows_total{kind="prompt"}',
                   'decode_prefill_rows_total{kind="padding"}')


def read(obs):
    b, a = obs["before"], obs["after"]
    if PROMPT not in a and PADDING not in a:
        return None
    prompt, padding = (a.get(n, 0) - b.get(n, 0) for n in (PROMPT, PADDING))
    if prompt + padding <= 0:
        return None
    return 100.0 * padding / (prompt + padding)
