"""Prefetch-overlap property tests (VERDICT r3 #3).

The reference's AsyncDataSetIterator exists to hide host-side data cost
behind device compute (AsyncDataSetIterator.java:38-76: prefetch thread +
bounded queue + device affinity). The testable form of that claim: with a
producer that takes `t_link` per batch and a consumer that takes `t_compute`
per batch, total wall for N batches must track
startup + N*max(t_link, t_compute), NOT N*(t_link + t_compute). On the
chip the benchmark's train cell reads the same overlap as
`input_wait_ms_per_step`; the hard assertion lives here where timing is
controllable.
"""
import time

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator.base import (
    AsyncDataSetIterator, DataSetIterator, DevicePrefetchIterator,
    ListDataSetIterator)


class SlowIterator(DataSetIterator):
    """Simulates an expensive host-side pipeline (decode/augment/link)."""

    def __init__(self, n_batches, delay_s, batch=8):
        self.n = n_batches
        self.delay = delay_s
        self._i = 0
        rng = np.random.default_rng(0)
        self._x = rng.random((batch, 4)).astype(np.float32)
        self._y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, batch)]

    def next(self):
        time.sleep(self.delay)
        self._i += 1
        return DataSet(self._x, self._y)

    def has_next(self):
        return self._i < self.n

    def reset(self):
        self._i = 0


@pytest.mark.parametrize("cls", [DevicePrefetchIterator, AsyncDataSetIterator])
def test_prefetch_overlaps_producer_with_consumer(cls):
    n, t_link, t_compute = 8, 0.05, 0.05
    serial = n * (t_link + t_compute)          # what NO overlap would cost
    pipelined = t_link + n * max(t_link, t_compute)  # ideal overlap

    it = cls(SlowIterator(n, t_link), queue_size=2)
    t0 = time.perf_counter()
    seen = 0
    while it.has_next():
        it.next()
        time.sleep(t_compute)                  # stand-in for device compute
        seen += 1
    wall = time.perf_counter() - t0
    assert seen == n
    # must beat serial by a clear margin and track the pipelined ideal
    # (generous slack: CI schedulers jitter sleeps)
    assert wall < 0.80 * serial, (
        f"wall {wall:.3f}s vs serial {serial:.3f}s — no overlap happened")
    assert wall < pipelined * 1.35


def test_prefetch_draining_and_reuse():
    """Queue drains fully and reset() restarts the producer thread."""
    it = DevicePrefetchIterator(SlowIterator(3, 0.0), queue_size=2)
    got = [it.next() for _ in range(3)]
    assert not it.has_next()
    assert all(g.features.shape == (8, 4) for g in got)
    it.reset()
    assert it.has_next()
    assert sum(1 for _ in it) == 3


def test_prefetch_propagates_producer_error():
    class Boom(SlowIterator):
        def next(self):
            if self._i == 1:
                raise RuntimeError("decode failed")
            return super().next()

    it = DevicePrefetchIterator(Boom(3, 0.0), queue_size=2)
    with pytest.raises(RuntimeError, match="decode failed"):
        # already-prefetched batches are delivered first; the error then
        # surfaces from has_next() (iteration protocol) rather than being lost
        for _ in it:
            pass


class _BoomOnce(SlowIterator):
    """Reader that fails mid-stream on the first pass only."""

    def __init__(self, n_batches, boom_at=1):
        super().__init__(n_batches, 0.0)
        self.boom_at = boom_at
        self._armed = True

    def next(self):
        if self._armed and self._i == self.boom_at:
            raise RuntimeError("decode failed")
        return super().next()

    def reset(self):
        super().reset()
        self._armed = False


@pytest.mark.parametrize("cls", [AsyncDataSetIterator, DevicePrefetchIterator])
def test_prefetch_error_surfaces_on_close_exactly_once(cls):
    """A worker error raised AFTER the consumer stops calling next() used to
    be swallowed; close() must re-raise it — and exactly once."""
    import time as _time
    it = cls(_BoomOnce(4), queue_size=4)
    it.next()                       # consume one batch, then stop pulling
    deadline = _time.monotonic() + 20
    while it._error is None and _time.monotonic() < deadline:
        _time.sleep(0.01)           # worker hits the failure in background
    with pytest.raises(RuntimeError, match="decode failed"):
        it.close()
    it.close()                      # second close: clean no-op
    assert not it.has_next()        # and no third surfacing from has_next


@pytest.mark.parametrize("cls", [AsyncDataSetIterator, DevicePrefetchIterator])
def test_prefetch_error_surfaces_on_reset_exactly_once(cls):
    """reset() after a mid-stream failure re-raises the pending error once,
    and the restarted pass (underlying reset cleared the fault) runs clean."""
    import time as _time
    it = cls(_BoomOnce(4), queue_size=4)
    it.next()
    deadline = _time.monotonic() + 20
    while it._error is None and _time.monotonic() < deadline:
        _time.sleep(0.01)
    with pytest.raises(RuntimeError, match="decode failed"):
        it.reset()
    # the raise happened AFTER the restart: the iterator is usable again
    assert sum(1 for _ in it) == 4
    it.close()


@pytest.mark.parametrize("cls", [AsyncDataSetIterator, DevicePrefetchIterator])
def test_prefetch_error_not_raised_twice_across_paths(cls):
    """The iteration path (has_next raise) claims the error; reset()/close()
    afterwards must NOT raise the same error again."""
    it = cls(_BoomOnce(4), queue_size=4)
    with pytest.raises(RuntimeError, match="decode failed"):
        for _ in it:
            pass
    it.reset()                      # no second raise; restarts cleanly
    assert sum(1 for _ in it) == 4
    it.close()
