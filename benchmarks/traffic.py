"""The generators: everything a cell's inputs are made of comes from here and
from the parameters in its workload file.

Steadiness rule: a seed never changes the amount of work. Lengths and arrival
gaps are a fixed grid of quantiles of the distribution the workload file
names, so every seed gets the same multiset of sizes; the seed decides the
order, the pairing of prompt with answer length, and the token values.
"""
from __future__ import annotations

import math
import threading
import time

import numpy as np

from .seeds import rng_of


# ------------------------------------------------------------------ training
def image_pool(seed, n, batch, image, classes):
    """n host batches of uint8 pixels [batch, image, image, 3] and int32
    labels [batch]; every row differs. Each class has a coarse 7x7 colour
    pattern of its own, blown up to the image and laid under uniform noise,
    so the labels can be learnt and the gradients carry a signal."""
    rng = rng_of(seed, 1)
    cell = -(-image // 7)
    coarse = rng.integers(0, 128, size=(classes, 7, 7, 3), dtype=np.uint8)
    pool = []
    for _ in range(n):
        labels = rng.integers(0, classes, batch).astype(np.int32)
        big = np.repeat(np.repeat(coarse[labels], cell, axis=1), cell,
                        axis=2)[:, :image, :image]
        pixels = big + rng.integers(0, 128, size=big.shape, dtype=np.uint8)
        pool.append((pixels, labels))
    return pool


class TimedGroups:
    """Iterator protocol of the program's DataSetIterator over a cycled pool:
    hands out whole groups of `group` items until `seconds` have passed since
    the first call (or `max_groups` groups when seconds is None), then stops.
    The deadline is checked once per group so an execution is never ragged."""

    def __init__(self, items, group, seconds=None, max_groups=None):
        self.items, self.group = items, int(group)
        self.seconds, self.max_groups = seconds, max_groups
        self.served = 0
        self.t0 = None

    def __iter__(self):
        return self

    def has_next(self):
        if self.served % self.group:
            return True
        if self.max_groups is not None:
            return self.served < self.max_groups * self.group
        if self.t0 is None:
            self.t0 = time.perf_counter()
        return time.perf_counter() - self.t0 < self.seconds

    def __next__(self):
        if not self.has_next():
            raise StopIteration
        return self.next()

    def next(self):
        item = self.items[self.served % len(self.items)]
        self.served += 1
        return item

    def reset(self):
        pass

    def batch(self):
        return None

    def total_examples(self):
        return None

    def async_supported(self):
        return True


# ------------------------------------------------------------------- lengths
def _inverse_cdf(dist, u):
    kind = dist["kind"]
    lo, hi = dist["min"], dist["max"]
    if kind == "log_uniform":
        return math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    if kind == "log_normal":
        # median and sigma of the underlying normal, truncated to [lo, hi]
        from statistics import NormalDist
        nd = NormalDist(math.log(dist["median"]), dist["sigma"])
        a, b = nd.cdf(math.log(lo)), nd.cdf(math.log(hi))
        return math.exp(nd.inv_cdf(a + u * (b - a)))
    if kind == "fixed":
        return dist["value"]
    raise ValueError(f"unknown length distribution {kind!r}")


def length_grid(dist, n):
    """n lengths at the mid-quantiles of `dist`: the same for every seed."""
    return [int(min(dist.get("max", 1 << 30), max(dist.get("min", 1), round(
        _inverse_cdf(dist, (i + 0.5) / n))))) for i in range(n)]


def requests(seed, mix, vocab, n):
    """n requests [(prompt ids, max_new_tokens)]: cycles of mix["cycle"]
    requests, each cycle the full grid of prompt and answer lengths in an
    order and a pairing drawn from the seed."""
    rng = rng_of(seed, 2)
    cycle = int(mix["cycle"])
    plens = length_grid(mix["prompt_tokens"], cycle)
    nlens = length_grid(mix["new_tokens"], cycle)
    out = []
    while len(out) < n:
        pp, nn = rng.permutation(cycle), rng.permutation(cycle)
        for i, j in zip(pp, nn):
            prompt = rng.integers(0, vocab, plens[i]).tolist()
            out.append((prompt, nlens[j]))
    return out[:n]


def arrivals(seed, rate_per_s, n, cycle=256):
    """n send times (seconds from the start) of a Poisson process at
    `rate_per_s`: exponential gaps on a quantile grid, shuffled by the seed,
    so every seed offers the same load."""
    rng = rng_of(seed, 3)
    grid = [-math.log(1 - (i + 0.5) / cycle) / rate_per_s
            for i in range(cycle)]
    gaps = []
    while len(gaps) < n:
        gaps += [grid[i] for i in rng.permutation(cycle)]
    return np.cumsum(gaps[:n]).tolist()


# ----------------------------------------------------------------- the loops
class Outcome:
    __slots__ = ("index", "due", "asked", "sent", "done", "status", "body",
                 "error")

    def __init__(self, index, due):
        self.index, self.due, self.asked = index, due, None
        self.sent = self.done = self.status = self.body = self.error = None


def closed_loop(send, reqs, clients, stop):
    """`clients` threads, each sending its next request when its last
    returned, until `stop` is set. Requests are taken in order from `reqs`.
    The first request of client i is cut to (i+1)/clients of its answer
    length, so the clients do not march in step. Returns the outcomes, in
    completion order, once every thread has ended."""
    lock = threading.Lock()
    state = {"next": 0}
    outcomes = []

    def client(i):
        first = True
        while not stop.is_set():
            with lock:
                k = state["next"]
                state["next"] += 1
            if k >= len(reqs):
                return
            prompt, new = reqs[k]
            if first:
                new = max(1, new * (i + 1) // clients)
            first = False
            o = Outcome(k, time.perf_counter())
            o.sent = o.due
            _send_into(send, o, prompt, new)
            with lock:
                outcomes.append(o)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    return threads, outcomes


def open_loop(send, reqs, times, t0, stop, workers=64):
    """Requests sent on a schedule (times[i] seconds after t0) whether or not
    earlier ones have returned; each is timed from when it was due. A pool of
    `workers` threads does the sending; `sent - due` is how late the
    generator ran."""
    lock = threading.Lock()
    state = {"next": 0}
    outcomes = []

    def worker():
        while not stop.is_set():
            with lock:
                k = state["next"]
                if k >= len(reqs):
                    return
                state["next"] += 1
            due = t0 + times[k]
            while True:
                wait = due - time.perf_counter()
                if wait <= 0 or stop.is_set():
                    break
                time.sleep(min(wait, 0.05))
            if stop.is_set():
                return
            o = Outcome(k, due)
            o.sent = time.perf_counter()
            _send_into(send, o, *reqs[k])
            with lock:
                outcomes.append(o)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    return threads, outcomes


def _send_into(send, o, prompt, new):
    o.asked = new
    try:
        o.status, o.body = send(prompt, new)
    except Exception as e:                 # a failed request is a miss
        o.status, o.error = -1, repr(e)
    o.done = time.perf_counter()
