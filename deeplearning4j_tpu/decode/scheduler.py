"""DecodeScheduler: continuous batching over the DecodeEngine's cache slots.

One scheduler thread owns the engine, the live cache, and the slot
lifecycle; HTTP handler threads only touch the bounded admission queue.
The loop runs ONE STEP AHEAD of what it has read. The step program returns
the next ids as a [slots] int32 device array and takes its ids as one, and
the prefill program sets its slot's entry of that same vector, so the ids
of step N + 1 never pass through the host. With step N in flight, a pass
(`_pass`) does, in this order:

1. **dispatch step N + 1** — for every active slot with budget and room
   left, from the vector step N and the prefills since have left on the
   device. Who rides, the sampler's step indexes and, paged, the block each
   rider appends to follow from `GenerateRequest.scheduled`, the count of
   tokens dispatched for the request (read or not); no token value is
   needed. The device goes from step N straight on to N + 1.
2. **read step N's ids** — the pass's one wait for a step — and, under
   step N + 1, append them, retire (max_new_tokens reached, stop id emitted,
   cache capacity hit, or the per-token deadline budget spent — a deadline
   mid-generation returns the PARTIAL result with finish_reason="deadline",
   not an error) and complete futures. Then the first token of each prefill
   the last pass dispatched (behind step N on the device, so read after
   it): `decode_ttft_ms` is taken when it reaches the host, with the
   request's trace id as exemplar.
3. **admit** — free slots are filled from the queue (expired requests fail
   with DeadlineExceeded instead of burning a prefill). Each admission
   enqueues one prefill executable (compiled per pow2 prompt-length
   bucket) behind step N + 1 — the donated cache orders them — and does
   not wait for it: its first token joins the ids of step N + 2.

Never more than one step is unread: step N + 2 is not dispatched before
step N's ids are on the host. What follows from running ahead:

- An end by length or by capacity is known by count before the dispatch:
  the slot is left out of the next step, and the request that takes it
  over joins the step after. An end that needs a token's value (stop id, a
  deadline that has passed, `abandon` between a dispatch and its read) is
  found out one step late: the slot was stepped once more, and that token
  is thrown away (`decode_discarded_slot_steps_total`) — never appended,
  never in `decode_tokens_total`, never in a response. The slot's row and
  recurrent state are whatever the next prefill overwrites, as an idle
  slot's are.
- The unread step and first tokens belong to their cache generation:
  `_fail_all`, a failed prefill and a cache re-init drop them, a hot-swap
  reads the step (all its riders have ended) before the cache goes, and
  the loop exits only when nothing is unread.
- On a serving mesh the engine's dispatch waits for the device inside the
  mesh's run lock, so nothing could run under the host's work: the pass
  reads the step it has just dispatched, and every step counts
  `decode_steps_ahead_total{ahead="0"}`, as does the first step after idle,
  a hot-swap or a failure. A loop with nothing active dispatches nothing.

Every pass that dispatched, read or admitted is one `decode_wave` phase
(telemetry/trace.py `Tracer.phase`: profiler annotation "dl4j:<name>" +
histogram `<name>_ms` + ring span) whose parts fold into it, all of them
host time: `decode_step_build` (who rides, paged growth, sampling
operands), the engine's `decode_step_dispatch` (step N + 1 enqueued) and
`decode_step_sync` (the wait for step N's ids: with the device kept fed,
what is left of step N after the host's own work), `decode_prefill_sync`
(the wait for a first token), `decode_emit` (tokens appended, retirements,
futures completed — after either read) and `decode_admit` (a pass that
admitted something; holds the per-request `decode_queue_wait` and
`decode_prefill`, which now spans the prompt's placement and the enqueue,
not the program's run). The ring gets one span a pass with its parts'
durations as attributes. A request's stages hang under its own trace id
(`r.trace_ctx`), recorded from clock reads the loop makes anyway:
`decode_queue_wait`, `decode_first_token` (slot granted -> first token on
the host; histogram `decode_first_token_ms`) and `decode_generate` (first
token -> the answer), so queue wait + first token = `ttft_ms`. Every read
of a result hands its wall to the engine's program ledger
(`DecodeEngine.observe_wall` -> `decode_program_ms{program}`); a wall the
ledger calls a stall is counted (`decode_stalls_total{program}`) and
written up once (`_stall`: log record, `decode_stall` ring span and
profiler marker) with what would explain it — the host's wait against its
own phases, the collector's and the compile counts since the pass before.
`decode_itl_ms` is, per rider of a step, the wall
from the previous result's arrival on the host (a step's ids or a first
token; this step's own dispatch when that came later, as into a drained
loop) to this step's ids: in steady state the interval between two reads,
a prefill queued between two steps left out as it was when admission
waited for it. (The step's probabilities stay on the device: this loop
never reads them.)

Requests therefore join and leave the in-flight batch per token with zero
steady-state recompiles: after the step executable and a prompt-length
bucket have compiled once, no request mix recompiles anything
(counter-asserted in tests/test_decode.py and tools/smoke_decode.py via
CompileTracker / jit_compiles_total / the engine's XLA cache sizes).

Hot-swap: the scheduler pins one model version per cache generation. When
ModelRegistry's active version changes, admission pauses, in-flight
requests drain on the old engine (a step batch never mixes versions), then
the engine/cache swap. Engines are cached per model object, and
`warmup(model)` (wired into ServingServer.deploy) compiles the new
version's step + observed prefill buckets BEFORE the registry pointer
swaps — a deploy is never cold, a rollback never recompiles.

Sampling rides along per request: a SamplerConfig's temperature / top-k /
top-p / seed become batch-shaped ARRAY operands of the step wave
(decode/sampling.py), so greedy and creative requests co-batch in one
executable and per-request params never mint executables (GL016).

Paged mode (`paged=True`, decode/paged.py): the engine's slab becomes a
shared block pool and THIS loop thread owns the allocator — admission
allocates each request's prompt blocks and writes its table row, a slot
grows block-by-block as it generates, and retirement frees. The pool may
be smaller than slots x capacity (OVERSUBSCRIPTION): admission only needs
the prompt to fit NOW, betting most requests finish short. When the bet
loses — a growth allocation finds the pool dry (the watermark) — the
YOUNGEST active slot is preempted: its blocks free immediately, the
request re-queues at the FRONT with its partial tokens, and on re-admission
it re-prefills prompt+partial in one bucket pass whose sampling step index
continues the seeded stream exactly (the preemption is invisible in the
token stream: a slot preempted with a step in flight loses that step's
token, and the re-prefill emits it again). Deadline-expired and preempted
slots retire through the same `_release_slot` path, so slot ids, pool blocks, and the active_slots
gauge can never leak however a request leaves its slot.
"""
from __future__ import annotations

import collections
import gc
import threading
from typing import Any, NamedTuple

from concurrent.futures import Future, TimeoutError as FuturesTimeoutError

from ..serving.admission import (DeadlineExceeded, RejectedError,
                                 safe_set_exception, safe_set_result)
from ..serving.registry import NoModelDeployed
from ..telemetry.trace import current_span, get_tracer
from ..util.time_source import monotonic_s
from .paged import BlockPool, PoolExhausted, blocks_for, make_table
from .sampling import batch_operands


class GenerateRequest:
    __slots__ = ("prompt", "max_new_tokens", "stop_id", "future", "deadline",
                 "enqueued_at", "trace_ctx", "tokens", "slot", "version",
                 "ttft_ms", "queue_wait_ms", "finish_reason", "sampler",
                 "admit_seq", "scheduled", "admitted_at", "first_token_at")

    def __init__(self, prompt, max_new_tokens, stop_id=None, deadline=None,
                 sampler=None):
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.stop_id = stop_id
        self.future = Future()
        self.deadline = deadline          # absolute monotonic_s() or None
        self.enqueued_at = monotonic_s()
        self.trace_ctx = current_span()   # handler thread's span rides along
        self.tokens = []
        self.slot = None
        self.version = None
        self.ttft_ms = None
        self.queue_wait_ms = None         # enqueue -> popped with a slot
        # the request's stages on the loop's own clock reads: the slot
        # granted (first admission) and the first token on the host
        self.admitted_at = None
        self.first_token_at = None
        self.finish_reason = None
        self.sampler = sampler            # SamplerConfig or None (greedy)
        self.admit_seq = None             # admission order; youngest preempts
        # tokens the device has been asked for: len(tokens) + those of the
        # prefill or step the loop has dispatched and not read yet
        self.scheduled = 0

    def expired(self, now=None):
        return self.deadline is not None and \
            (now if now is not None else monotonic_s()) > self.deadline

    def complete(self):
        safe_set_result(self.future, {
            "tokens": list(self.tokens),
            "n_prompt": len(self.prompt),
            "version": self.version,
            "ttft_ms": self.ttft_ms,
            "queue_wait_ms": self.queue_wait_ms,
            "finish_reason": self.finish_reason,
        })

    def fail(self, exc):
        safe_set_exception(self.future, exc)


# the two phases of a pass in which the loop waits for the device
_WAITS = ("decode_step_sync_ms", "decode_prefill_sync_ms")


class _Flight(NamedTuple):
    """A dispatched step whose ids the host has not read."""
    ids: Any                # [slots] int32, on the device
    riders: list            # [(slot, request)] as of the dispatch
    ahead: bool             # dispatched while another step was unread
    dispatched_at: float


class _First(NamedTuple):
    """A dispatched prefill whose first token the host has not read."""
    slot: int
    request: GenerateRequest
    nid: Any                # int32 scalar, on the device
    bucket: int
    dispatched_at: float


class DecodeScheduler:
    def __init__(self, registry, metrics_registry, *, slots=4, max_len=128,
                 queue_capacity=64, default_max_new_tokens=32, tracer=None,
                 compile_tracker=None, logger=None, idle_wait_s=0.2,
                 max_engines=4, paged=False, block_size=16,
                 pool_blocks=None, cost_registry=None):
        self.registry = registry                    # ModelRegistry
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.paged = bool(paged)
        self.block_size = int(block_size)
        # allocatable pool size INCLUDING the scratch block; None = fully
        # backed (slots * ceil(max_len/bs) + 1 — no oversubscription).
        # Smaller pools oversubscribe: admission bets requests finish short
        # and the preempt/requeue path covers the losses.
        self.pool_blocks = None if pool_blocks is None else int(pool_blocks)
        self.queue_capacity = int(queue_capacity)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.tracer = tracer if tracer is not None else get_tracer()
        self.compile_tracker = compile_tracker
        self.cost_registry = cost_registry
        self.logger = logger
        self.idle_wait_s = float(idle_wait_s)
        self.max_engines = int(max_engines)
        self.metrics_registry = metrics_registry

        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._queue = collections.deque()
        self._closed = False
        self._thread = None
        # loop-thread-owned state
        self._engines = collections.OrderedDict()   # id(model) -> (model, eng)
        self._engine = None
        self._cache = None
        self._version = None
        self._active = {}                           # slot -> GenerateRequest
        self._free = list(range(self.slots))
        self._observed_buckets = set()
        self._admit_seq = 0
        # what the device has been handed and the host has not read (module
        # docstring): the [slots] vector of next ids both programs write,
        # the step in flight, the prefills whose first token is pending,
        # and when the last result reached the host
        self._ids = None
        self._flight = None                         # _Flight
        self._firsts = []                           # [_First]
        self._last_read = 0.0
        # for the stall record: the pass's own phase (its folded parts) and
        # the parts of the pass before, the collector's and the compile
        # counts at the start of this pass and of the one before
        self._wave = None
        self._last_parts = {}
        self._marks = self._marks_before = None
        # paged-mode allocator state (loop-thread-owned, rebuilt with the
        # cache each generation)
        self._pool = None                           # BlockPool
        self._table = None                          # [slots, max_blocks] i32
        self._slot_blocks = {}                      # slot -> [block ids]

        reg = metrics_registry
        self.m_requests = reg.counter("decode_requests_total",
                                      "Generate requests answered")
        self.m_tokens = reg.counter("decode_tokens_total",
                                    "Tokens generated (all requests)")
        self.m_shed = reg.counter("decode_shed_total",
                                  "Generate requests shed at admission (429)")
        self.m_expired = reg.counter(
            "decode_expired_total",
            "Generate requests whose deadline passed while queued (504)")
        self.m_errors = reg.counter("decode_errors_total",
                                    "Generate requests failed in the engine")
        self.m_preempted = reg.counter(
            "decode_preempted_total",
            "Slots preempted (blocks reclaimed, request re-queued with its "
            "partial tokens) when the KV block pool ran dry")
        self.m_ttft = reg.histogram(
            "decode_ttft_ms", "Time to first token (admission to first "
            "token), ms")
        self.m_itl = reg.histogram(
            "decode_itl_ms", "Inter-token latency, per active slot a step: "
            "from the previous result reaching the host (or this step's "
            "dispatch, into a drained loop) to this step's ids, ms")
        self.m_ahead = reg.counter(
            "decode_steps_ahead_total", "Decode steps read, by whether they "
            "were dispatched while another step was still unread "
            "(ahead=\"1\") or into a drained loop (\"0\": first step after "
            "idle, a hot-swap or a failure; on a mesh every step)")
        self.m_discarded = reg.counter(
            "decode_discarded_slot_steps_total", "Slot-steps whose token "
            "was thrown away: the request had ended (stop id, deadline, "
            "abandon) or been preempted while the step was in flight")
        self.m_first_token = reg.histogram(
            "decode_first_token_ms", "Slot granted to first token on the "
            "host, per request: admission behind a running step and the "
            "prefill itself, without the queue, ms")
        self.m_stalls = reg.counter(
            "decode_stalls_total", "Decode programs whose wall was a stall "
            "(over 250 ms and over 8 x the program's running median or mean), by "
            "program; each also writes a `decode_stall` log record")
        self.m_tps = reg.gauge("decode_tokens_per_sec",
                               "Decode throughput over the last step wave")
        # one unlabelled histogram per phase of the loop (module docstring);
        # the engine registers its three on the same registry
        self.m_wave = reg.histogram(
            "decode_wave_ms", "One scheduler pass (a step dispatched, the "
            "last one's results read, admission) that did any of it, ms")
        self.m_admit = reg.histogram(
            "decode_admit_ms", "Admission of a pass that admitted something "
            "(queue pops, slot and block bookkeeping, prefills), ms")
        self.m_queue_wait = reg.histogram(
            "decode_queue_wait_ms", "Enqueue to popped with a free slot, "
            "per request, ms")
        self.m_prefill = reg.histogram(
            "decode_prefill_ms", "One engine.dispatch_prefill call (the "
            "prompt placed and the program enqueued, not run), ms")
        self.m_prefill_sync = reg.histogram(
            "decode_prefill_sync_ms", "Host read of a prefill's first "
            "token, a pass after its dispatch: the wait for the device, ms")
        self.m_step_build = reg.histogram(
            "decode_step_build_ms", "Host work of a step before the "
            "dispatch (who rides, paged growth, sampling operands), ms")
        self.m_emit = reg.histogram(
            "decode_emit_ms", "Host work after a step's or a prefill's "
            "result (tokens appended, retirements, futures completed), ms")
        reg.gauge("decode_active_slots", "In-flight generate requests",
                  fn=lambda: float(self.active_count()))
        reg.gauge("decode_kv_live_pct",
                  "Cache positions the active requests have filled (prompt "
                  "+ tokens emitted), % of slots x capacity: what the next "
                  "step's attention has to read",
                  fn=self.kv_live_pct)
        reg.gauge("decode_queue_depth", "Generate requests awaiting a slot",
                  fn=lambda: float(self.depth()))
        # PER-SHARD cache bytes: on a mesh the KV cache partitions its head
        # axis across chips, and what admission/capacity must answer for is
        # what ONE chip holds resident — the global figure would overstate
        # per-chip pressure by n_model x (single-chip engines report the
        # same number either way)
        reg.gauge("decode_cache_mb",
                  "KV-cache bytes resident PER SHARD (MB) for the live "
                  "engine", fn=lambda: self.cache_mb())
        for c in (self.m_requests, self.m_tokens, self.m_shed,
                  self.m_expired, self.m_errors, self.m_preempted,
                  self.m_discarded, self.m_stalls):
            c.inc(0)

    # ------------------------------------------------------------ admission
    def depth(self):
        with self._lock:
            return len(self._queue)

    def active_count(self):
        # loop-thread-written dict; len() is atomic enough for a gauge
        return len(self._active)

    def kv_live_pct(self):
        # from what the loop thread already holds on the host, no device
        # read; list() of the loop-thread-written dict is atomic enough
        live = sum(len(r.prompt) + len(r.tokens)
                   for r in list(self._active.values()))
        return 100.0 * live / (self.slots * self.max_len)

    def submit(self, prompt_ids, max_new_tokens=None, timeout_ms=None,
               stop_id=None, sampler=None):
        """Admit one generate request; returns its Future (shed raises
        RejectedError, an unservable request ValueError). `sampler` is a
        sampling.SamplerConfig (None = greedy)."""
        max_new = self.default_max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        prompt = list(prompt_ids)
        if not prompt:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds the cache capacity {self.max_len}; split the "
                "request or deploy with a larger decode_max_len")
        if self.paged and self.pool_blocks is not None and \
                blocks_for(len(prompt) + 1, self.block_size) > \
                self.pool_blocks - 1:
            raise ValueError(
                f"prompt of {len(prompt)} tokens can never fit the KV "
                f"block pool ({self.pool_blocks - 1} allocatable blocks of "
                f"{self.block_size} tokens)")
        deadline = None if timeout_ms is None \
            else monotonic_s() + float(timeout_ms) / 1000.0
        req = GenerateRequest(prompt, max_new, stop_id=stop_id,
                              deadline=deadline, sampler=sampler)
        with self._work:
            if self._closed:
                self.m_shed.add(1)
                raise RejectedError("server is draining", retry_after_s=5)
            if len(self._queue) >= self.queue_capacity:
                self.m_shed.add(1)
                raise RejectedError(
                    f"decode queue full ({self.queue_capacity} pending)",
                    retry_after_s=1)
            self._queue.append(req)
            self._work.notify()
        return req.future

    def generate(self, prompt_ids, max_new_tokens=None, timeout_ms=None,
                 stop_id=None, wait_s=120.0, sampler=None):
        """Blocking convenience: submit + wait; a wait timeout abandons the
        request so it cannot burn a slot generating tokens nobody reads."""
        fut = self.submit(prompt_ids, max_new_tokens=max_new_tokens,
                          timeout_ms=timeout_ms, stop_id=stop_id,
                          sampler=sampler)
        try:
            return fut.result(timeout=wait_s)
        except FuturesTimeoutError:
            self.abandon(fut)
            raise

    def abandon(self, future):
        """Best-effort cancellation of a request whose caller gave up: a
        still-queued request is withdrawn and failed; an in-flight one has
        its token budget clamped so it retires at the next step instead of
        generating a full answer nobody will read."""
        with self._lock:
            for r in list(self._queue):
                if r.future is future:
                    self._queue.remove(r)
                    r.fail(RejectedError("abandoned by caller"))
                    return True
        for r in list(self._active.values()):   # loop-thread-owned; the
            if r.future is future:              # int write is benign
                r.max_new_tokens = 0
                return True
        return False

    # ------------------------------------------------------------ lifecycle
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return self
        with self._work:        # _closed is guarded by the work condition
            self._closed = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="decode-scheduler")
        self._thread.start()
        return self

    def stop(self, drain=True, timeout=30.0):
        """Stop admitting and exit once in-flight work finishes. drain=True
        (default) also serves what is already queued; drain=False sheds the
        queue with RejectedError (in-flight generations still run to their
        own finish — they are bounded by max_new_tokens)."""
        with self._work:
            self._closed = True
            if not drain:
                queued, self._queue = list(self._queue), collections.deque()
            else:
                queued = []
            self._work.notify_all()
        for r in queued:
            r.fail(RejectedError("server shutting down"))
        if self._thread is not None:
            self._thread.join(timeout)

    def probe(self):
        """HealthMonitor probe: unhealthy when the loop thread died."""
        t = self._thread
        if t is None:
            return "degraded", {"reason": "not started"}
        if not t.is_alive() and not self._closed:
            return "unhealthy", {"reason": "decode loop dead"}
        return "healthy", {"active": self.active_count(),
                           "queued": self.depth(),
                           "version": self._version}

    def snapshot(self):
        """JSON block for the serving /metrics snapshot."""
        with self._lock:     # _observed_buckets is written under this lock
            buckets = sorted(self._observed_buckets)
        out = {
            "requests": self.m_requests.get(),
            "tokens": self.m_tokens.get(),
            "shed": self.m_shed.get(),
            "expired": self.m_expired.get(),
            "errors": self.m_errors.get(),
            "active_slots": self.active_count(),
            "queue_depth": self.depth(),
            "tokens_per_sec": self.m_tps.get(),
            "ttft_ms": self.m_ttft.percentiles(),
            "itl_ms": self.m_itl.percentiles(),
            "version": self._version,
            "prefill_buckets": buckets,
            "cache_mb": self.cache_mb(),
        }
        if self.paged:
            pool = self._pool
            out["paged"] = {
                "block_size": self.block_size,
                "pool_blocks": pool.capacity_blocks if pool else 0,
                "used_blocks": pool.used_blocks if pool else 0,
                "high_water": pool.high_water if pool else 0,
                "utilization": self.pool_utilization(),
                "preempted": self.m_preempted.get(),
            }
        return out

    def pool_utilization(self):
        pool = self._pool
        return pool.utilization() if pool is not None else 0.0

    def cache_mb(self):
        """PER-SHARD KV-cache megabytes of the live engine (0.0 before the
        first deploy). Sharded caches divide each entry by its shard count,
        so the gauge answers "what does one chip hold", matching the
        per-chip HBM budget the capacity plane reasons about."""
        eng = self._engine
        if eng is None:
            return 0.0
        try:
            return float(eng.cache_bytes(per_shard=True)) / 1e6
        except Exception:
            return 0.0

    # ------------------------------------------------------------- engines
    def engine_for(self, model):
        """One DecodeEngine per model object, LRU-bounded — a rollback to a
        recently-served version reuses its compiled executables."""
        from .engine import DecodeEngine
        key = id(model)
        with self._lock:
            hit = self._engines.get(key)
            if hit is not None and hit[0] is model:
                self._engines.move_to_end(key)
                return hit[1]
        eng = DecodeEngine(model, slots=self.slots, max_len=self.max_len,
                           compile_tracker=self.compile_tracker,
                           registry=self.metrics_registry,
                           tracer=self.tracer, paged=self.paged,
                           block_size=self.block_size,
                           num_blocks=self.pool_blocks,
                           cost_registry=self.cost_registry)
        with self._lock:
            self._engines[key] = (model, eng)
            self._engines.move_to_end(key)
            while len(self._engines) > self.max_engines:
                self._engines.popitem(last=False)
        return eng

    def warmup(self, model):
        """Deploy-time warm-up: compile the step + every observed prompt
        bucket for `model` BEFORE the registry pointer swaps."""
        with self._lock:
            buckets = set(self._observed_buckets)
        self.engine_for(model).warmup(buckets)

    # ------------------------------------------------------------ the loop
    def _run(self):
        while True:
            with self._work:
                while not self._queue and not self._active \
                        and self._flight is None and not self._closed:
                    self._work.wait(self.idle_wait_s)
                if self._closed and not self._queue and not self._active \
                        and self._flight is None:
                    return
            self._turn()

    def _turn(self):
        """One pass as the loop thread makes it: inside its `decode_wave`
        phase, whose folded parts the stall record reads."""
        with self.tracer.phase("decode_wave", histogram=self.m_wave) as wave:
            self._wave = wave
            try:
                worked = self._pass()
            except Exception as e:      # last resort: the loop survives
                self._fail_all(e)
                worked = True
            if not worked:
                wave.cancel()
            self._last_parts = wave.attributes

    def _pass(self):
        """One turn of the loop (module docstring): dispatch the next step
        from ids that are still on the device, then read what the last pass
        left there, retire and admit while the device runs. Returns whether
        anything was dispatched, read or admitted."""
        self._marks_before, self._marks = self._marks, self._read_marks()
        prev = self._flight
        cur = self._dispatch_step(ahead=prev is not None)
        worked = prev is not None or cur is not None
        if prev is not None:
            self._emit_step(prev)
        worked = self._emit_firsts() > 0 or worked
        if cur is not None and self._engine.mesh is not None:
            # the mesh's dispatch has waited for the device inside its run
            # lock: there is nothing to run under, and nothing is ahead
            self._emit_step(cur)
            cur = None
        self._flight = cur
        return self._admit() > 0 or worked

    def _drop_unread(self):
        """What is unread dies with the cache it was computed from: nothing
        reads ids of a cache that is gone."""
        self._ids = None
        self._flight = None
        self._firsts = []

    def _drop_cache(self):
        """The end of a cache generation that cannot be stepped again: the
        allocator and whatever is unread die with it, and the next admission
        starts a fresh one."""
        self._cache = None
        self._drop_unread()
        self._pool = None
        self._table = None
        self._slot_blocks = {}

    def _fail_all(self, exc):
        self.m_errors.add(len(self._active))
        for slot, r in list(self._active.items()):
            r.fail(exc)
            self._free.append(slot)
        self._active.clear()
        self._drop_cache()                  # poisoned (possibly donated away)
        if self.logger is not None:
            self.logger.error("decode_wave_failed",
                              error=f"{type(exc).__name__}: {exc}")

    def _pop_queued(self):
        with self._lock:
            if self._queue:
                return self._queue.popleft()
            return None

    def _admit(self):
        """Fill free slots from the queue; returns how many requests got a
        slot (and a prefill). The call is one `decode_admit` phase when it
        admitted something."""
        if not self._free:
            return 0
        seq0 = self._admit_seq
        with self.tracer.phase("decode_admit", histogram=self.m_admit,
                               fold=True) as ph:
            self._fill_free_slots()
            if self._admit_seq == seq0:
                ph.cancel()
        return self._admit_seq - seq0

    def _fill_free_slots(self):
        # pin ONE (version, model) per cache generation; on a hot-swap,
        # drain in-flight work before re-pinning (a step never mixes
        # versions)
        try:
            entry = self.registry.active_entry()
        except NoModelDeployed as e:
            while True:
                r = self._pop_queued()
                if r is None:
                    return
                r.fail(e)
            return
        if self._engine is None or self._version != entry.version \
                or self._engine.model is not entry.model:
            if self._active:
                return                      # drain first, swap next wave
            if self._flight is not None:
                # every rider of the step in flight has ended: its ids
                # belong to the old engine's cache and are read (and
                # counted as discarded) before that cache goes
                flight, self._flight = self._flight, None
                self._emit_step(flight)
            try:
                self._engine = self.engine_for(entry.model)
            except Exception as e:
                # a model with no decode semantics (DecodeUnsupported) — or
                # any engine-build failure — is deterministic for this
                # version: fail EVERYTHING queued and stop, instead of
                # leaving the queue full and the loop spinning on it
                if self.logger is not None:
                    self.logger.error(
                        "decode_engine_unavailable", version=entry.version,
                        error=f"{type(e).__name__}: {e}")
                while True:
                    r = self._pop_queued()
                    if r is None:
                        return
                    self.m_errors.add(1)
                    r.fail(e)
            self._version = entry.version
            self._cache = None
        if self._cache is None:
            self._cache = self._engine.init_cache()
            self._drop_unread()
            self._reset_pool()
        while self._free:
            r = self._pop_queued()
            if r is None:
                return
            now = monotonic_s()
            if r.expired(now):
                # a preempted request that expires while re-queued holds
                # real tokens: it retires like a mid-generation deadline
                # (partial result), NOT as a 504 — same retire path either
                # way, so the accounting cannot diverge
                if r.tokens:
                    self._finish(r, "deadline", now)
                else:
                    self.m_expired.add(1)
                    r.fail(DeadlineExceeded(
                        "deadline exceeded while awaiting a decode slot"))
                continue
            # ctx is the FULL generated-so-far prefix: for a fresh request
            # just the prompt; for a preempted one prompt+partial, whose
            # re-prefill emits the next token at the sampling step index
            # the lost slot would have used (seeded streams are preemption-
            # invariant)
            ctx = r.prompt + r.tokens
            if self.paged:
                need = blocks_for(len(ctx), self.block_size)
                if need > self._pool.capacity_blocks:
                    if r.tokens:
                        # a preempted request outgrew the whole pool: what
                        # it generated is the answer, same as hitting the
                        # slab capacity wall mid-flight
                        self._finish(r, "capacity", now)
                    else:
                        self.m_errors.add(1)
                        r.fail(ValueError(
                            f"context of {len(ctx)} tokens can never fit "
                            f"the KV block pool "
                            f"({self._pool.capacity_blocks} blocks of "
                            f"{self.block_size})"))
                    continue
                if need > self._pool.free_blocks:
                    with self._lock:
                        self._queue.appendleft(r)
                    return          # wait for retirements to free blocks
            slot = self._free.pop()
            r.slot, r.version = slot, self._version
            r.admit_seq = self._admit_seq
            self._admit_seq += 1
            if r.queue_wait_ms is None:  # first admission: `now` is the pop
                r.admitted_at = now
                r.queue_wait_ms = (now - r.enqueued_at) * 1000.0
                self.tracer.record_span(
                    "decode_queue_wait", r.enqueued_at, now,
                    parent=r.trace_ctx, histogram=self.m_queue_wait,
                    slot=slot)
            if self.paged:
                blks = self._pool.alloc(need)
                self._slot_blocks[slot] = blks
                self._table[slot, :] = 0
                self._table[slot, :len(blks)] = blks
            bucket = self._engine.prefill_bucket(len(ctx))
            with self._lock:
                self._observed_buckets.add(bucket)
            with self.tracer.phase("decode_prefill",
                                   histogram=self.m_prefill,
                                   parent=r.trace_ctx, slot=slot,
                                   bucket=bucket, n_prompt=len(ctx)) as ph:
                try:
                    # queued behind the step in flight through the donated
                    # cache; the first token stays on the device as this
                    # slot's entry of the next step's ids
                    self._cache, nid, _, self._ids = \
                        self._engine.dispatch_prefill(
                            self._cache, slot, ctx, sampling=r.sampler,
                            step_index=len(r.tokens),
                            table=self._table_operand(), next_ids=self._ids)
                except Exception as e:
                    self.m_errors.add(1)
                    r.fail(e)
                    self._release_slot(slot)
                    if self.logger is not None:
                        self.logger.error(
                            "decode_prefill_failed", slot=slot,
                            error=f"{type(e).__name__}: {e}")
                    # the prefill DONATES the whole cache: after a failure
                    # mid-execution the co-batched slots' buffers may be
                    # gone too, so fail them loudly rather than stepping a
                    # poisoned cache next wave; a fresh cache re-inits on
                    # the next admission
                    if self._active:
                        self._fail_all(RuntimeError(
                            "co-batched KV cache lost to a failed prefill: "
                            f"{type(e).__name__}: {e}"))
                    else:
                        self._drop_cache()
                    return
            r.scheduled = len(r.tokens) + 1
            self._active[slot] = r
            self._firsts.append(_First(slot, r, nid, bucket, ph.start_mono))

    def _emit_firsts(self):
        """Read the first token of every prefill the last pass dispatched
        (in their order on the device) and do with it what admission did
        when it waited for it: `ttft_ms`, the token, a retirement by a
        budget of one or a stop id. Returns how many were read."""
        firsts, self._firsts = self._firsts, []
        n = 0
        for f in firsts:
            r = f.request
            if self._active.get(f.slot) is not r:
                continue        # preempted before its first token was read
            with self.tracer.phase("decode_prefill_sync",
                                   histogram=self.m_prefill_sync,
                                   fold=True) as sync:
                nid = int(f.nid)
            now = monotonic_s()
            wall_ms = (now - max(f.dispatched_at, self._last_read)) * 1000.0
            self._last_read = now
            if self._engine.observe_wall(f"decode_prefill:{f.bucket}",
                                         wall_ms):
                self._stall(f"prefill:{f.bucket}", wall_ms, sync.duration_ms)
            n += 1
            with self.tracer.phase("decode_emit", histogram=self.m_emit,
                                   fold=True):
                if r.ttft_ms is None:   # first admission only — a re-
                    r.ttft_ms = (now - r.enqueued_at) * 1000.0  # admission
                    self.m_ttft.observe(        # is not a second "first
                        r.ttft_ms, trace_id=getattr(    # token"
                            r.trace_ctx, "trace_id", None))
                    r.first_token_at = now
                    self.tracer.record_span(
                        "decode_first_token", r.admitted_at, now,
                        parent=r.trace_ctx, histogram=self.m_first_token,
                        slot=f.slot, bucket=f.bucket, n_prompt=len(r.prompt))
                r.tokens.append(nid)
                self.m_tokens.add(1)
                self._maybe_retire(f.slot, now)
        return n

    # --------------------------------------------------------- paged alloc
    def _reset_pool(self):
        """(Re)build the allocator beside a fresh cache — pool state and
        cache contents live and die together (a table pointing into a
        previous generation's pool would read garbage)."""
        if not self.paged or self._engine is None:
            self._pool = None
            self._table = None
            self._slot_blocks = {}
            return
        eng = self._engine
        self._pool = BlockPool(eng.num_blocks, eng.block_size)
        self._table = make_table(self.slots, eng.max_blocks)
        self._slot_blocks = {}

    def _grow(self, slot):
        """Back `slot`'s next append position with a physical block,
        preempting the YOUNGEST active slot whenever the pool is dry (the
        oversubscription watermark). Returns False when `slot` itself was
        the youngest and lost its own blocks."""
        r = self._active[slot]
        # when the step runs the cache holds the prompt and all but the last
        # of the `scheduled` tokens; the step appends that last one
        need = blocks_for(len(r.prompt) + r.scheduled, self.block_size)
        row = self._slot_blocks[slot]
        while len(row) < need:
            try:
                blk = self._pool.alloc(1)[0]
            except PoolExhausted:
                victim = max(self._active,
                             key=lambda s: self._active[s].admit_seq)
                self._preempt(victim)
                if victim == slot:
                    return False
                continue
            row.append(blk)
            self._table[slot, len(row) - 1] = blk
        return True

    def _preempt(self, slot):
        """Reclaim a slot's blocks mid-flight: the request keeps the tokens
        the host has read and re-queues at the FRONT (it was admitted
        before anything queued behind it); re-admission re-prefills
        prompt+partial, which emits the token of the step in flight again
        (that step's own is discarded when it is read)."""
        r = self._active.pop(slot)
        self._release_slot(slot)
        self.m_preempted.add(1)
        with self._lock:
            self._queue.appendleft(r)
        if self.logger is not None:
            self.logger.info("decode_preempted", slot=slot,
                             n_tokens=len(r.tokens),
                             pool_free=self._pool.free_blocks)

    # ------------------------------------------------------------ stepping
    def _table_operand(self):
        """The block table as an operand of a program that runs after this
        call returns: a copy, because the loop goes on writing the table
        and a dispatched program may read its host operands late."""
        return self._table.copy() if self.paged else None

    def _dispatch_step(self, ahead):
        """Enqueue one decode step for every active slot with budget and
        room left, its ids the vector the last step and the prefills since
        have left on the device. Who rides, the block each rider appends to
        and the sampler's step indexes follow from counts the host holds;
        no token value is needed. Returns the _Flight, or None when no slot
        rides."""
        if not self._active:
            return None
        with self.tracer.phase("decode_step_build",
                               histogram=self.m_step_build, fold=True):
            # an end by length or by capacity is known before the dispatch
            # (the last token is in flight): that slot is stepped no more.
            # An end that needs the token's value is found out a step late.
            riders = [s for s, r in self._active.items()
                      if r.scheduled < r.max_new_tokens
                      and len(r.prompt) + r.scheduled < self.max_len]
            if self.paged:
                # oldest-first: seniority keeps its blocks, the youngest
                # pays
                riders.sort(key=lambda s: self._active[s].admit_seq)
                for slot in riders:
                    if slot in self._active:    # not preempted as a victim
                        self._grow(slot)
                riders = [s for s in riders if s in self._active]
            if not riders:
                return None
            samp = None
            if any(self._active[s].sampler is not None for s in riders):
                # per-slot sampling params + fold_in step indexes as ARRAY
                # operands — swinging every request never recompiles (GL016)
                samp = batch_operands(
                    self.slots,
                    {s: self._active[s].sampler for s in riders},
                    {s: self._active[s].scheduled for s in riders})
            table = self._table_operand()
        t0 = monotonic_s()
        self._cache, self._ids, _ = self._engine.dispatch_step(
            self._cache, self._ids, sampling=samp, table=table)
        riders = [(slot, self._active[slot]) for slot in riders]
        for _, r in riders:
            r.scheduled += 1
        return _Flight(self._ids, riders, ahead, t0)

    def _emit_step(self, flight):
        """Read a dispatched step's ids — the pass's one wait for a step —
        and append, retire and complete with them. A rider that has left
        its slot since the dispatch (ended by a token's value or a deadline
        one step earlier, or preempted) has its token thrown away."""
        nxt = self._engine.read_ids(flight.ids)
        now = monotonic_s()
        # from the previous result reaching the host when the device was
        # kept fed, from this step's own dispatch otherwise: never a span
        # in which the device ran something else for the whole of it
        wall = now - max(flight.dispatched_at, self._last_read)
        self._last_read = now
        if self._engine.observe_wall("decode_step", wall * 1000.0):
            self._stall("step", wall * 1000.0, self._engine.last_sync_ms)
        self.m_ahead.inc(1, ahead="1" if flight.ahead else "0")
        with self.tracer.phase("decode_emit", histogram=self.m_emit,
                               fold=True):
            self.m_tps.set(len(flight.riders) / max(wall, 1e-9))
            for slot, r in flight.riders:
                # a request leaves its slot before a read and takes one
                # again only after it (`_admit` ends the pass): the slot
                # still holding it is the admission that was dispatched
                if self._active.get(slot) is not r:
                    self.m_discarded.add(1)
                    continue
                r.tokens.append(int(nxt[slot]))
                self.m_tokens.add(1)
                self.m_itl.observe(wall * 1000.0,
                                   trace_id=getattr(r.trace_ctx, "trace_id",
                                                    None))
                self._maybe_retire(slot, now)

    # ------------------------------------------------------------- stalls
    def _read_marks(self):
        """What a stall record gives as a delta: the garbage collector's
        collections by generation and the server's compiles, read once a
        pass."""
        ct = self.compile_tracker
        return ([g["collections"] for g in gc.get_stats()],
                0 if ct is None else ct.total())

    def _stall(self, program, wall_ms, waited_ms):
        """A program's wall was a stall by the engine's rule (`observe_wall`):
        count it and say which of the suspects it was. `waited_ms` is the
        host's wait for this result, so wall less it is the time the loop
        thread spent between the two reads — its own phases (`phases_ms`:
        the parts of this pass and of the one before, whose tail the wall
        covers; `host_phases_ms` their sum without the two waits) or
        whatever else held it: a collection, a compile, another thread."""
        self.m_stalls.inc(1, program=program)
        parts = dict(self._last_parts)
        if self._wave is not None:
            for k, v in self._wave.attributes.items():
                parts[k] = parts.get(k, 0.0) + v
        then = self._marks_before or self._marks     # a loop's first pass
        now_gc, now_compiles = self._read_marks()
        record = {
            "program": program, "wall_ms": round(wall_ms, 3),
            "waited_ms": round(waited_ms, 3),
            "host_phases_ms": round(sum(
                v for k, v in parts.items() if k not in _WAITS), 3),
            "phases_ms": {k: round(v, 3) for k, v in parts.items()},
            "gc_collections": [a - b for a, b in zip(now_gc, then[0])],
            "compiles": now_compiles - then[1],
            "queue_depth": self.depth(), "active_slots": self.active_count()}
        # a phase, so that a profiler session shows `dl4j:decode_stall` at
        # the instant the long result reached the host; its ring span
        # (tracer on) carries the record
        with self.tracer.phase("decode_stall", **record):
            if self.logger is not None:
                self.logger.warning("decode_stall", **record)

    # ----------------------------------------------------------- retiring
    def _release_slot(self, slot):
        """The ONE place a slot id (and, paged, its pool blocks + table
        row) returns to the free state — retire, preempt, and prefill-
        failure all route through here, so no exit path can leak a slot or
        strand blocks. When the last active slot leaves, the free list is
        re-sorted so future allocations pack low block ids (defrag)."""
        self._free.append(slot)
        if self._pool is not None:
            blks = self._slot_blocks.pop(slot, None)
            if blks:
                self._pool.free(blks)
            self._table[slot, :] = 0
            if not self._active:
                self._pool.defrag()

    def _finish(self, r, reason, now):
        r.finish_reason = reason
        self.m_requests.add(1)
        if r.first_token_at is not None:
            # the request's last stage, under its trace id: first token on
            # the host -> the answer (a re-queue after preemption included)
            self.tracer.record_span(
                "decode_generate", r.first_token_at, now, parent=r.trace_ctx,
                n_tokens=len(r.tokens), finish_reason=reason)
        r.complete()

    def _retire(self, slot, r, reason, now):
        self._active.pop(slot, None)
        self._release_slot(slot)
        self._finish(r, reason, now)
        if self.logger is not None:
            self.logger.debug("generate_done", slot=slot, reason=reason,
                              n_tokens=len(r.tokens), version=r.version)

    def _maybe_retire(self, slot, now):
        r = self._active.get(slot)
        if r is None:
            return
        reason = None
        if r.stop_id is not None and r.tokens and r.tokens[-1] == r.stop_id:
            reason = "stop"
        elif len(r.tokens) >= r.max_new_tokens:
            reason = "length"
        elif len(r.prompt) + len(r.tokens) >= self.max_len:
            reason = "capacity"
        elif r.expired(now):
            # the per-token deadline budget: the client gets what was
            # generated before the budget ran out, marked as such
            reason = "deadline"
        if reason is None:
            return
        self._retire(slot, r, reason, now)
