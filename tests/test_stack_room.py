"""util/stack_room.py: a call made with room on the Python data stack does
not pay for where its caller's frames happen to end."""
import sys
import time

import pytest

from deeplearning4j_tpu.util.stack_room import (ROOM_SLOTS,
                                                call_with_stack_room)


def test_call_with_stack_room_is_a_call():
    def f(a, b=2, *rest, **kw):
        return a, b, rest, kw
    assert call_with_stack_room(f, 1) == (1, 2, (), {})
    assert call_with_stack_room(f, 1, 3, 4, k=5) == (1, 3, (4,), {"k": 5})
    assert call_with_stack_room.__code__.co_nlocals >= ROOM_SLOTS
    with pytest.raises(ZeroDivisionError):
        call_with_stack_room(lambda: 1 / 0)


def _leaf(x):
    return x + 1


def _hot(n):
    s = 0
    for _ in range(n):
        s = _leaf(s)
    return s


def _deep(d, n):
    """Seconds `n` calls take from `d` frames further down."""
    if d == 0:
        t = time.perf_counter()
        _hot(n)
        return time.perf_counter() - t
    return _deep(d - 1, n)


def test_the_boundary_cliff_is_gone_with_stack_room():
    """Somewhere in any ~130 consecutive depths the loop's call site sits on
    a chunk boundary and every call there allocates: ~100x. From a frame
    with stack room the same depth costs what every other depth costs."""
    if sys.version_info < (3, 11):
        pytest.skip("frames are not laid out in chunks before 3.11")
    n = 20000
    times = [_deep(d, n) for d in range(300)]
    usual = sorted(times)[len(times) // 2]
    cliff = max(range(300), key=times.__getitem__)
    if times[cliff] < 20 * usual:
        pytest.skip(f"no cliff on this interpreter (worst {times[cliff]:.4f} "
                    f"s at depth {cliff}, usual {usual:.4f} s)")
    # one frame is the roomy one, so the loop runs cliff + 1 frames down:
    # either side of the cliff depth must be fine too
    with_room = max(call_with_stack_room(_deep, d, n)
                    for d in (cliff - 1, cliff, cliff + 1))
    assert with_room < 5 * usual, (with_room, usual, times[cliff])
