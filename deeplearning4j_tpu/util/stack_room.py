"""Call a function with room on the Python data stack.

CPython (3.11+) keeps a thread's interpreter frames in chunks of 16 KiB and
frees a chunk the moment the frame at its base returns. A call site that
happens to sit on a chunk boundary therefore pays an allocation and a free
on EVERY call — a call that costs 50 ns costs 5 us there (a 100x cliff at
one recursion depth in ~125, `tests/test_stack_room.py`). Tracing and
lowering a large program makes millions of calls some hundred frames deep,
so WHERE THE CALLER'S FRAMES HAPPEN TO END decides how long that takes: the
5-step ResNet-50 program lowered in 17 s or in 29 s on the v5e host
depending on one closure more or less between `fit()` and the jitted call
(PERF.md section 6, PR 39; PR 24 had met it as "every `with` block around
the lowering call costs 3-9 s" — a `with` deepens its frame's stack).

`call_with_stack_room(fn, *args, **kwargs)` calls `fn` from a frame with
thousands of unused local slots. Such a frame never fits the current chunk,
so the interpreter gives it a chunk of its own — sized to the next power of
two, with well over a hundred KiB to spare — and keeps that chunk while the
frame is on the stack: everything `fn` calls runs in the chunk's free tail
and crosses no boundary, whatever lies above. Costs one 256 KiB allocation
and clearing the slots, once a call: for calls that compile, not for hot
loops. On an interpreter that lays frames out differently it is a plain
call.
"""
from __future__ import annotations

# (15,500 + the interpreter's 1,000-slot guard) * 8 bytes is just over
# 128 KiB, so the chunk is 256 KiB: about 17,000 slots (some 400 ordinary
# frames) stay free below this one
ROOM_SLOTS = 15500


def _build():
    names = " = ".join(f"_{i}" for i in range(ROOM_SLOTS))
    ns = {}
    exec("def call_with_stack_room(fn, *args, **kwargs):\n"
         "    if fn is None:          # never: the names only size the frame\n"
         f"        {names} = None\n"
         "    return fn(*args, **kwargs)\n", ns)
    return ns["call_with_stack_room"]


call_with_stack_room = _build()
call_with_stack_room.__doc__ = ("fn(*args, **kwargs), called from a frame "
                                "that owns a data-stack chunk (see module).")
