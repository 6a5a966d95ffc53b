"""DecodeEngine: fixed-shape decode executables over the nn types' layers.

The engine owns the plan (a MultiLayerNetwork or a one-in/one-out
ComputationGraph flattened to nodes), the cache pytree and three executable
families; what a layer keeps per decode slot and how each leg advances it is
the layer's own business — the decode contract of nn/layers/base.py
(`decode_unsupported`, `decode_entry`, `decode_prefill` / `decode_step` /
`decode_verify`, `decode_rewindable`; nn/layers/recurrent.py has attention's
K/V cache and the LSTM carry). No layer class is named here.

- ``step``: ONE compiled function of fixed shape — [slots] token ids in,
  [slots] next ids out — that advances EVERY in-flight request by one token.
  Because every shape is a function of (slots, capacity) only — never of how
  many tokens any request has generated — steady-state decoding NEVER
  recompiles, no matter how requests join and leave the batch.
- ``prefill``: one compiled function per power-of-two prompt-length bucket.
  The prompt runs as a normal full-sequence forward under the same
  padded+masked length-bucket discipline the serving batcher applies to
  /predict, and every stateful layer writes the slot's state.
- ``verify`` (speculative decoding, decode/speculative.py): one compiled
  function per window size W — appends a W-token window at a dynamic
  `start` offset of one slot and returns ALL W next-token distributions in
  one batched pass (prefill-shaped work: it spends the compute the
  HBM-bound step leaves idle). Rollback after the accept decision is a
  host-side length reset — which is why verify requires every layer's state
  to be rewindable.

Both legs emit SAMPLED token ids (decode/sampling.py): temperature /
top-k / top-p / seed arrive as batch-shaped ARRAY OPERANDS, with
temperature <= 0 short-circuiting to argmax in-trace, so greedy and
creative requests co-batch in the same executable and per-request sampling
params never become recompile keys (graftlint GL016). The sampling leg does,
and hands the host, only what those operands ask for: a batch with no
sampled slot runs an argmax (the sort and the draw sit behind a traced
conditional; `decode_steps_total{sampler}` counts which way each step went)
and the host reads [slots] int32 ids. The [slots, vocab] distribution comes
back as the device array the program produced: `read_probs` is the host
copy, for a caller that uses rows of it.

Each leg is two phases, and each phase a method: `dispatch_step` /
`dispatch_prefill` enqueue the program and return what it will produce,
still on the device; `read_ids` is the host copy of a step's next ids. The
next ids are one [slots] int32 vector that both programs write (the step
all of it, the prefill its slot's entry) and the step takes as its ids, so a
caller can dispatch step N + 1 from step N's ids before it has read them
(decode/scheduler.py keeps one step in flight that way). `step` / `prefill`
are the two phases in a row, for callers that want the token at once.

The cache is a plain pytree ``{"lengths": int32[slots], "layers": {name:
entry}}`` threaded functionally through the executables and DONATED, so
steady state re-uses the cache buffers in place instead of allocating a
fresh multi-MB cache per token. With ``paged=True`` the layers are handed a
``[slots, max_blocks]`` int32 block-table operand (decode/paged.py) and
capacity is whatever the scheduler's allocator backs; the table replicates
on a mesh while every entry keeps the sharding its layer declared.

Decode runs in the model's param dtype (no mixed-precision cast): decode is
bound by streaming cache bytes, not MXU throughput, and greedy parity with
``model.output`` is the contract the tests pin.
"""
from __future__ import annotations

import math
import threading
from types import SimpleNamespace
from typing import Any, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..telemetry.trace import get_tracer
from ..telemetry.xla import record_jit_compile
from ..util.time_source import monotonic_s
from . import sampling as _sampling


class DecodeUnsupported(TypeError):
    """The model contains a construct with no token-streaming semantics
    (bidirectional recurrence, non-causal attention, temporal pooling...)."""


STALL_FLOOR_MS = 250.0    # a program's wall is a stall over this AND over
STALL_FACTOR = 8.0        # this many times its own running median

MIN_PREFILL_BUCKET = 16   # floor the prompt buckets: bounds the executable
                          # set at log2(capacity/16)+1 without measurable
                          # padding waste at serving prompt sizes


def _ledger_instruments(registry):
    """The program ledger on `registry` (get-or-create: every engine of a
    server shares them): the histogram `observe_wall` writes, the same
    milliseconds as a counter by program (a window's share of a program is
    the growth of its series over the growth of all), and the prefill's
    rows counter."""
    program = registry.histogram(
        "decode_program_ms", "Wall of every warm execution of a decode "
        "program as its caller measured it (observe_wall: result to result "
        "in a loop that keeps the device fed, so the program's device "
        "time), by program: step, prefill:<bucket>, verify:<W>, ms")
    total = registry.counter(
        "decode_program_ms_total", "Sum of decode_program_ms by program, ms")
    rows = registry.counter(
        "decode_prefill_rows_total", "Rows the prefill programs computed, "
        "by kind: \"prompt\" (the context's tokens) or \"padding\" (the "
        "rest of the bucket)")
    return program, total, rows


def bucket_for_len(n, capacity):
    """Smallest power-of-two >= n (floored at MIN_PREFILL_BUCKET, capped at
    the cache capacity) — the prefill executable key."""
    b = MIN_PREFILL_BUCKET
    while b < n:
        b <<= 1
    return min(b, capacity)


class _Node:
    __slots__ = ("name", "kind", "inputs", "module", "vertex")

    def __init__(self, name, kind, inputs=(), module=None, vertex=None):
        self.name = name
        self.kind = kind            # "input" | "layer" | "vertex"
        self.inputs = tuple(inputs)
        self.module = module
        self.vertex = vertex


class _Ctx(NamedTuple):
    """The operands of one leg that a layer's `decode_<leg>` reads (the
    contract's docstring, nn/layers/base.py, says which leg sets which)."""
    mask: Any = None
    slot: Any = None
    length: Any = None
    pos: Any = None
    kv_valid: Any = None
    start: Any = None
    table: Any = None
    row: Any = None
    blk: Any = None
    off: Any = None


def _layer_node(name, inputs, module):
    reason = module.decode_unsupported()
    if reason is not None:
        raise DecodeUnsupported(f"layer {name!r}: {reason}")
    return _Node(name, "layer", inputs, module=module)


def build_plan(model):
    """(nodes, input_name, output_name, vocab) for a MultiLayerNetwork or a
    single-input/single-output ComputationGraph. A mesh-serving wrapper
    (serving/mesh.MeshDispatcher) is planned through the model it wraps —
    duck-typed on `mesh_inner` so decode/ never imports serving/."""
    from ..nn.graph.graph import ComputationGraph
    from ..nn.multilayer.network import MultiLayerNetwork
    model = getattr(model, "mesh_inner", model)
    if isinstance(model, MultiLayerNetwork):
        it = getattr(model.conf, "input_type", None)
        vocab = int(it.size) if it is not None and hasattr(it, "size") \
            else int(model.conf.layers[0].n_in)
        if getattr(model.conf, "input_preprocessors", None):
            if any(model.conf.input_preprocessors.get(i) is not None
                   for i in range(len(model.layers))):
                raise DecodeUnsupported(
                    "input preprocessors have no per-token semantics")
        nodes = [_Node("__in__", "input")]
        prev = "__in__"
        for i, module in enumerate(model.layers):
            nodes.append(_layer_node(str(i), (prev,), module))
            prev = str(i)
        return nodes, "__in__", prev, vocab
    if isinstance(model, ComputationGraph):
        conf = model.conf
        if len(conf.network_inputs) != 1 or len(conf.network_outputs) != 1:
            raise DecodeUnsupported(
                "decode requires a single-input/single-output graph")
        vocab = int(conf.input_types[0].size) if conf.input_types \
            else int(conf.vertices[model.order[1]].layer_conf.n_in)
        nodes = []
        for name in model.order:
            spec = conf.vertices[name]
            if spec.kind == "input":
                nodes.append(_Node(name, "input"))
            elif spec.kind == "layer":
                if spec.preprocessor is not None:
                    raise DecodeUnsupported(
                        f"vertex {name!r}: preprocessors have no per-token "
                        "semantics")
                nodes.append(_layer_node(name, spec.inputs,
                                         model.layers[name]))
            else:
                vc = spec.vertex_conf
                if not vc.positionwise:
                    raise DecodeUnsupported(
                        f"vertex {name!r} ({type(vc).__name__}) is not a "
                        "per-position map")
                nodes.append(_Node(name, "vertex", spec.inputs, vertex=vc))
        return nodes, conf.network_inputs[0], conf.network_outputs[0], vocab
    raise DecodeUnsupported(f"cannot decode a {type(model).__name__}")


class DecodeEngine:
    def __init__(self, model, *, slots=4, max_len=128, compile_tracker=None,
                 registry=None, paged=False, block_size=16, num_blocks=None,
                 cost_registry=None, tracer=None):
        self.model = model
        self.slots = int(slots)
        self.capacity = int(max_len)
        self.paged = bool(paged)
        self.block_size = int(block_size)
        if self.paged:
            if self.block_size < 1 or (self.block_size
                                       & (self.block_size - 1)):
                raise ValueError(f"block_size must be a power of two, got "
                                 f"{self.block_size}")
            # capacity in whole blocks: the table addresses nothing finer
            bs = self.block_size
            self.capacity = -(-self.capacity // bs) * bs
            self.max_blocks = self.capacity // bs
            # default pool: every slot fully backed, +1 for the scratch
            # block — byte-parity with the slab, so paged-vs-slab parity
            # tests compare equal capacity (the scheduler passes a smaller
            # pool to actually oversubscribe)
            self.num_blocks = (self.slots * self.max_blocks + 1
                               if num_blocks is None else int(num_blocks))
            if self.num_blocks < 2:
                raise ValueError("paged cache needs >= 2 blocks "
                                 "(block 0 is scratch)")
        else:
            self.max_blocks = 0
            self.num_blocks = 0
        self.nodes, self.input_name, self.output_name, self.vocab = \
            build_plan(model)
        if model.params is None:
            model.init()
        self._dtype = model._dtype
        # what each stateful layer declared it keeps: {name: {leaf: (shape,
        # dtype, model axis)}}; the entries a length reset cannot rewind
        # are the carries
        # mesh-sharded decode (serving/mesh.py): a wrapped model carries the
        # serving MeshContext; the KV cache partitions its head axis over
        # the mesh model axis and the step/prefill executables pin the
        # cache's out_shardings so donation survives partitioning
        self.mesh = getattr(model, "mesh_context", None)
        geom = SimpleNamespace(
            slots=self.slots, capacity=self.capacity, dtype=self._dtype,
            paged=self.paged, block_size=self.block_size,
            num_blocks=self.num_blocks,
            model_shards=1 if self.mesh is None else self.mesh.model_size)
        self._entries = {}
        self._carries = set()
        for node in self.nodes:
            entry = node.kind == "layer" and node.module.decode_entry(geom)
            if entry:
                self._entries[node.name] = entry
                if not node.module.decode_rewindable:
                    self._carries.add(node.name)
        self.compile_tracker = compile_tracker
        self.registry = registry            # MetricsRegistry for jit counters
        # live cost attribution (telemetry/cost.py): each decode executable
        # family (step / prefill:L / verify:W) is captured at first call and
        # its wall time sampled every Nth dispatch (the sync is paid only on
        # sampled dispatches — decode steps are otherwise async)
        self.cost_registry = cost_registry
        # the step's three host phases (Tracer.phase): profiler annotation
        # + histogram (with a registry) + a `<phase>_ms` attribute folded
        # into the scheduler's decode_wave span
        self.tracer = tracer if tracer is not None else get_tracer()
        self._m_dispatch = self._m_sync = self._m_probs_read = None
        self._m_steps = None        # decode_steps_total{sampler}
        # the program ledger: histogram, its sums as a counter, prefill rows
        self._m_program = self._m_program_total = self._m_rows = None
        self.last_sync_ms = 0.0     # the last read_ids' wait for the device
        if registry is not None:
            self._m_program, self._m_program_total, self._m_rows = \
                _ledger_instruments(registry)
            self._m_dispatch = registry.histogram(
                "decode_step_dispatch_ms", "The jitted decode step call "
                "until it returns (enqueue cost; on a mesh it waits for "
                "the device inside the run lock), ms")
            self._m_sync = registry.histogram(
                "decode_step_sync_ms", "Host read of the step's next ids: "
                "the wait for the device, ms")
            self._m_probs_read = registry.histogram(
                "decode_probs_read_ms", "Host read of rows of a step's "
                "[slots, vocab] probabilities (read_probs: only a caller "
                "that uses them pays it), ms")
            self._m_steps = registry.counter(
                "decode_steps_total", "Decode steps by what their sampling "
                "operands asked for: sampler=\"greedy\" (no slot with a "
                "positive temperature: argmax only) or \"sampled\"")
        self._cache_shardings = None        # lazily built pytree
        self._step_fn = None
        self._prefill_fns = {}              # length bucket -> jitted fn
        self._verify_fns = {}               # window size W -> jitted fn
        self._compiled = set()              # labels whose first call was timed
        self._cold = set()      # compiled by a call whose wall is still owed
        self._jit_lock = threading.Lock()
        # default (greedy) sampling operands, built once: callers that never
        # sample pay zero per-call operand construction
        self._greedy_step_ops = _sampling.batch_operands(self.slots)
        self._greedy_slot_ops = _sampling.slot_operands(None, 0)

    # ------------------------------------------------------------ cache
    def _map_cache(self, fn):
        """The cache pytree with fn(shape, dtype, model_axis) at every leaf
        the layers declared."""
        # `lengths` is allocated BEFORE the entries: after them (PR 28's order)
        # the same step program served ~2 % fewer tokens/s (PERF.md §6, PR 29)
        return {"lengths": fn((self.slots,), jnp.int32, None),
                "layers": {name: {k: fn(*leaf) for k, leaf in entry.items()}
                           for name, entry in self._entries.items()}}

    def _cache_zeros(self):
        """Abstract cache construction (placement is `init_cache`'s job)."""
        return self._map_cache(lambda shape, dtype, _: jnp.zeros(shape, dtype))

    def init_cache(self):
        """Fresh all-zero cache pytree (slot lengths all 0); on a serving
        mesh every entry is placed under its declared NamedSharding."""
        cache = self._cache_zeros()
        if self.mesh is None:
            return cache
        return jax.tree_util.tree_map(
            lambda leaf, s: jax.device_put(leaf, s), cache,
            self.cache_shardings())

    def cache_shardings(self):
        """NamedSharding pytree matching the cache (mesh only): each leaf
        split over the model axis on the axis its layer declared (attention
        K/V: heads; recurrent carries: features), lengths replicated."""
        if self._cache_shardings is None:
            self._cache_shardings = self._map_cache(
                lambda shape, _, axis: self.mesh.cache_sharding(shape, axis))
        return self._cache_shardings

    def cache_bytes(self, per_shard=False):
        """Bytes of the cache, no device allocation. per_shard: what ONE
        chip holds resident — the honest capacity number for admission and
        gauges on a mesh (a head-sharded entry puts 1/n_model of its bytes
        on each chip; uneven entries stay replicated and count whole)."""
        split = per_shard and self.mesh is not None

        def nbytes(shape, dtype, axis):
            if split:
                shape = self.mesh.cache_sharding(shape, axis) \
                    .shard_shape(shape)
            return math.prod(shape) * jnp.dtype(dtype).itemsize
        return sum(jax.tree_util.tree_leaves(self._map_cache(nbytes)))

    # ------------------------------------------------------------- walk
    def _node_scope(self, node):
        """jax.named_scope of one walked node: its name on its operations
        in the lowered text and in a device trace, the output node's under
        `lm_head`. Within an attention layer a step's append and read are
        one kernel under `attention` (`kv_append` where they are two calls,
        and for a prefill's write)."""
        return jax.named_scope(node.name if node.name != self.output_name
                               else "lm_head/" + node.name)

    def _walk(self, leg, params, states, x0, cache, ctx):
        """One forward over the plan for one leg — "prefill": a [1, L, f]
        prompt into `ctx.slot`'s state; "step": [slots, 1, f], one token a
        slot against the cache; "verify": a [1, W, f] window at `ctx.start`.
        Each layer's `decode_<leg>` advances its own cache entry."""
        acts = {self.input_name: x0}
        layers = dict(cache["layers"])
        for node in self.nodes:
            if node.kind == "input":
                continue
            with self._node_scope(node):
                if node.kind == "vertex":
                    acts[node.name] = node.vertex.apply(
                        [acts[i] for i in node.inputs])
                    continue
                name = node.name
                acts[name], entry = getattr(node.module, "decode_" + leg)(
                    params[name], states[name], acts[node.inputs[0]],
                    layers.get(name), ctx)
                if name in layers:
                    layers[name] = entry
        return acts[self.output_name], layers

    # ------------------------------------------------------- executables
    def _one_hot(self, ids):
        return jax.nn.one_hot(ids, self.vocab, dtype=self._dtype)

    def _build_step(self):
        C = self.capacity
        paged = self.paged

        def step_fn(params, states, cache, ids, samp, table):
            # int8 serving weights: decode executables consume the narrow
            # codes too; the fused dequant is the same one output() traces
            params = self.model._dequant_params(params)
            lengths = cache["lengths"]
            pos = jnp.clip(lengths, 0, C - 1)
            x0 = self._one_hot(ids[:, None])              # [S, 1, V]
            # slot s appends at pos[s] and then holds pos[s] + 1 tokens
            ctx = _Ctx(pos=pos, kv_valid=pos + 1)
            if paged:
                # physical (block, offset) of each slot's append position;
                # an unallocated logical block maps to 0 = scratch, so a
                # slot the scheduler hasn't backed writes where nobody reads
                bs = self.block_size
                blk = jnp.take_along_axis(table, (pos // bs)[:, None],
                                          axis=1)[:, 0]
                ctx = ctx._replace(table=table, blk=blk, off=pos % bs)
            y, layers = self._walk("step", params, states, x0, cache, ctx)
            probs = y[:, -1].astype(jnp.float32)          # [S, V]
            new_cache = {"lengths": jnp.minimum(lengths + 1, C),
                         "layers": layers}
            with jax.named_scope("sample"):
                nxt = _sampling.sample_tokens(probs, samp)
            return new_cache, nxt, probs

        return jax.jit(step_fn, donate_argnums=(2,), **self._jit_sharding())

    def _build_prefill(self, L):
        paged = self.paged

        def prefill_fn(params, states, cache, slot, ids, length, samp,
                       table, next_ids):
            params = self.model._dequant_params(params)
            x0 = self._one_hot(ids[None, :])              # [1, L, V]
            valid = (jnp.arange(L, dtype=jnp.int32)
                     < length).astype(self._dtype)[None]  # [1, L]
            ctx = _Ctx(mask=valid, slot=slot, length=length)
            if paged:
                ctx = ctx._replace(table=table, row=lax.dynamic_index_in_dim(
                    table, slot, 0, keepdims=False))
            y, layers = self._walk("prefill", params, states, x0, cache, ctx)
            z = jnp.zeros((), length.dtype)
            probs = lax.dynamic_slice(
                y, (z, length - 1, z), (1, 1, self.vocab))[0, 0]
            probs = probs.astype(jnp.float32)
            new_cache = {"lengths": cache["lengths"].at[slot].set(length),
                         "layers": layers}
            with jax.named_scope("sample"):
                nid = _sampling.sample_tokens(probs[None], samp)[0]
            # the slot's entry of the vector the next step takes as its ids
            return new_cache, nid, probs, next_ids.at[slot].set(nid)

        return jax.jit(prefill_fn, donate_argnums=(2,),
                       **self._jit_sharding(n_repl=3))

    def _build_verify(self, W):
        def verify_fn(params, states, cache, slot, ids, start):
            params = self.model._dequant_params(params)
            x0 = self._one_hot(ids[None, :])              # [1, W, V]
            y, layers = self._walk("verify", params, states, x0, cache,
                                   _Ctx(slot=slot, start=start))
            probs = y[0].astype(jnp.float32)              # [W, V]
            # lengths unchanged: the accept decision is host-side, and the
            # host commits the accepted length via set_length afterwards
            new_cache = {"lengths": cache["lengths"], "layers": layers}
            return new_cache, probs

        return jax.jit(verify_fn, donate_argnums=(2,),
                       **self._jit_sharding(n_repl=1))

    def _jit_sharding(self, n_repl=2):
        """Extra jit kwargs on a mesh: pin the output cache to the SAME
        head-sharded placement as the donated input cache, so GSPMD's
        propagation can never pick a layout that breaks buffer donation —
        the zero-fresh-allocation steady state (GL011's sibling invariant)
        holds sharded exactly as it does on one chip. Token ids and probs
        replicate (they're host-read every step); `n_repl` is how many such
        trailing outputs the executable returns."""
        if self.mesh is None:
            return {}
        repl = self.mesh.cache_sharding(())     # replicated NamedSharding
        return {"out_shardings":
                (self.cache_shardings(),) + (repl,) * n_repl}

    def _ensure_placed(self):
        """A mesh-wrapped model keeps its params placed (TP specs or
        replicated) — re-checked per call because quantize/dequantize swap
        the params object; identity-cached so steady state pays nothing."""
        placer = getattr(self.model, "ensure_placed", None)
        if placer is not None:
            placer()

    def _run(self, fn, label, bucket, *args, sample=True):
        """Invoke a decode executable. On a mesh, the call takes the
        context's run_lock and blocks until ready inside it: one
        partitioned wave in flight per mesh, or concurrently-launched
        collectives (this step vs the batcher's /predict dispatch)
        interleave their rendezvous participants and deadlock XLA's CPU
        runtime. Single-chip engines skip both."""
        if self.mesh is None:
            return self._timed(fn, label, bucket, *args, sample=sample)
        # set_mesh: the decode kernels run per shard of the ambient mesh
        # (kernels/flash_attention._per_shard); the cost plane's shadow
        # lower inside _timed sees the same mesh
        with self.mesh.run_lock, jax.set_mesh(self.mesh.mesh):
            out = self._timed(fn, label, bucket, *args, sample=sample)
            jax.block_until_ready(out)
            return out

    def _timed(self, fn, label, bucket, *args, sample=True):
        """Invoke a decode executable; the first call per label is the XLA
        compile and is timed into the compile accounting (CompileTracker
        phase="decode" + jit_compiles_total), same discipline as the
        batcher's observed buckets. With a cost registry attached, the first
        call also captures the executable's XLA costs (from an abstract-arg
        snapshot taken BEFORE the donating call) and every Nth later call is
        wall-timed into the sampled dispatch_ms histogram (`sample=False`:
        the call is only enqueued and whoever reads its result hands the
        wall to `observe_wall`, as step(), prefill() and the scheduler
        do)."""
        cr = self.cost_registry
        if label in self._compiled:
            if sample and cr is not None and cr.dispatch_due(label):
                t0 = monotonic_s()
                out = fn(*args)
                jax.block_until_ready(out[1])
                cr.observe_dispatch(label, (monotonic_s() - t0) * 1000.0)
                return out
            return fn(*args)
        if cr is not None:
            from ..telemetry.cost import abstractify
            abs_args = abstractify(args)
        t0 = monotonic_s()
        out = fn(*args)
        jax.block_until_ready(out[1])
        ms = (monotonic_s() - t0) * 1000.0
        self._compiled.add(label)
        if not sample:
            self._cold.add(label)
        record_jit_compile(label, ms, registry=self.registry)
        if self.compile_tracker is not None:
            self.compile_tracker.record(ms, bucket=bucket, phase="decode")
        if cr is not None:
            cr.capture(label, fn, abs_args, family="decode",
                       samples=self._cost_samples(label))
            if sample:
                cr.dispatch_due(label)
                cr.observe_dispatch(label, ms)
        return out

    def observe_wall(self, label, ms):
        """The wall of one execution of `label` as its caller measured it
        (call to result on the host, or result to result in a loop that
        keeps the device fed). Every warm one is an observation of the
        program ledger, `decode_program_ms{program}` (the label less its
        "decode_"); the call that compiled is `jit_compile_ms`'s and is left
        out. Every Nth is the cost plane's dispatch sample. Returns whether
        the wall is a STALL by the ledger's own record: over STALL_FLOOR_MS
        and over STALL_FACTOR times the program's median before it — or its
        mean where that is the larger: the host reads results in device
        order, not as they land, so a burst's time can sit in its first
        reading (48 prefills enqueued into a drained loop: one wall of
        2.7 s, then 47 of 0.01 ms); the sum is conserved, so the mean stays
        true where the median does not."""
        cr = self.cost_registry
        if cr is not None and cr.dispatch_due(label):
            cr.observe_dispatch(label, ms)
        if label in self._cold:
            self._cold.discard(label)
            return False
        if self._m_program is None:
            return False
        program = label[len("decode_"):]
        stalled = False
        if ms > STALL_FLOOR_MS:     # rare: the median is a sort of <= 4096
            median = self._m_program.percentile(0.5, program=program)
            stalled = median is not None and ms > STALL_FACTOR * max(
                median, self._m_program.sum(program=program)
                / self._m_program.count(program=program))
        self._m_program.observe(ms, program=program)
        self._m_program_total.inc(ms, program=program)
        return stalled

    def _cost_samples(self, label):
        """Tokens one execution of this executable serves — the per-token
        normalizer for the cost table: a step advances every slot one
        token; prefill:L ingests L tokens; verify:W scores a W-token
        window."""
        if label == "decode_step":
            return self.slots
        tail = label.rsplit(":", 1)
        if len(tail) == 2 and tail[1].isdigit():
            return int(tail[1])
        return 1

    def prefill_bucket(self, n):
        return bucket_for_len(n, self.capacity)

    def observed_buckets(self):
        with self._jit_lock:
            return sorted(self._prefill_fns)

    def executable_counts(self):
        """{label: XLA cache size} for the compiled decode executables — the
        hard recompile assertion (a retrace would grow a count past 1).
        On a mesh these are PER-SHARD sizes in the only honest sense: one
        partitioned executable per label serves all chips, so a sharded
        cache must still report 1 per label — a mesh engine that minted a
        per-chip executable family would show up here as a count of
        n_chips, and the smoke/tests pin it at 1."""
        out = {}
        with self._jit_lock:
            fns = [("decode_step", self._step_fn)] + \
                [(f"decode_prefill:{L}", f)
                 for L, f in sorted(self._prefill_fns.items())] + \
                [(f"decode_verify:{W}", f)
                 for W, f in sorted(self._verify_fns.items())]
        for label, fn in fns:
            if fn is None:
                continue
            size = getattr(fn, "_cache_size", None)
            if callable(size):
                out[label] = int(size())
        return out

    # ------------------------------------------------------------- api
    def full_table(self, slots=None):
        """Fully-provisioned block table (paged mode): slot s owns blocks
        [1 + s*max_blocks, ...) contiguously. This is the static layout
        engine-level callers (generate, warmup, parity tests) use — the
        scheduler builds real tables block-by-block from its BlockPool.
        Requires the default full-size pool."""
        if not self.paged:
            raise ValueError("full_table() is paged-mode only")
        n = self.slots if slots is None else int(slots)
        nb = self.max_blocks
        table = np.zeros((self.slots, nb), np.int32)
        for s in range(min(n, self.slots)):
            want = 1 + s * nb + np.arange(nb, dtype=np.int32)
            # a smaller-than-default pool can't back every slot: leave the
            # overflow on scratch (warmup tolerates garbage K/V)
            table[s] = np.where(want < self.num_blocks, want, 0)
        return table

    def _step_operands(self, sampling):
        return self._greedy_step_ops if sampling is None else sampling

    def _place_ids(self, ids):
        """`ids` as the [slots] int32 device array the executables take and
        return: a step's or a prefill's own output passes through, a host
        vector is placed like one (replicated on a mesh), so either way a
        call has the one signature its executable was compiled for."""
        if getattr(ids, "sharding", None) is not None:
            return ids
        return jax.device_put(
            np.asarray(ids, np.int32).reshape(self.slots),
            None if self.mesh is None else self.mesh.cache_sharding(()))

    def dispatch_prefill(self, cache, slot, prompt_ids, sampling=None,
                         step_index=0, table=None, next_ids=None):
        """Enqueue the prefill of `prompt_ids` (python ints / 1-D array) into
        cache slot `slot` and return without waiting for it: (cache, first
        generated id, last-position probs [vocab], next ids [slots]), all
        still on the device. `next_ids` is the vector the next step takes
        as its ids (a step's or another prefill's output; zeros when None):
        it comes back with `slot`'s entry set to the first id, so a caller
        that runs ahead of its reads hands it to `dispatch_step` as it is.

        `sampling`: a SamplerConfig (greedy when None); `step_index` is the
        fold_in counter of the emitted token — 0 on a fresh admission,
        len(partial) on a post-preemption re-prefill. `table`: the paged
        block table (defaults to the static full table)."""
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        n = ids.shape[0]
        if n < 1:
            raise ValueError("empty prompt")
        if n >= self.capacity:
            raise ValueError(
                f"prompt of {n} tokens does not fit the cache "
                f"(capacity {self.capacity}, needs room for >=1 new token)")
        self._ensure_placed()
        L = self.prefill_bucket(n)
        padded = np.zeros((L,), np.int32)
        padded[:n] = ids
        if sampling is None and step_index == 0:
            samp = self._greedy_slot_ops
        else:
            samp = _sampling.slot_operands(sampling, step_index)
        if self.paged and table is None:
            table = self.full_table()
        if next_ids is None:
            next_ids = np.zeros((self.slots,), np.int32)
        with self._jit_lock:
            fn = self._prefill_fns.get(L)
            if fn is None:
                fn = self._prefill_fns[L] = self._build_prefill(L)
        if self._m_rows is not None:
            self._m_rows.inc(n, kind="prompt")
            self._m_rows.inc(L - n, kind="padding")
        return self._run(
            fn, f"decode_prefill:{L}", L, self.model.params,
            self.model.states, cache, np.int32(slot), padded, np.int32(n),
            samp, table if self.paged else None, self._place_ids(next_ids),
            sample=False)

    def prefill(self, cache, slot, prompt_ids, sampling=None, step_index=0,
                table=None):
        """`dispatch_prefill` and the read of its first id, in a row — the
        synchronous prefill of `generate`, the speculative decoder and the
        warm-up. Returns (cache, first generated id as an int, last-position
        probs [vocab] as the device array the program produced: `read_probs`
        for a host copy)."""
        t0 = monotonic_s()
        cache, nid, probs, _ = self.dispatch_prefill(
            cache, slot, prompt_ids, sampling=sampling,
            step_index=step_index, table=table)
        nid = int(nid)
        self.observe_wall(
            f"decode_prefill:{self.prefill_bucket(len(prompt_ids))}",
            (monotonic_s() - t0) * 1000.0)
        return cache, nid, probs

    def dispatch_step(self, cache, last_ids, sampling=None, table=None):
        """Enqueue one step (every slot advances one token) and return
        without waiting for it: (cache, next ids [slots] int32, probs
        [slots, vocab]), all still on the device. `last_ids`: [slots] token
        ids, on the host or — a previous `dispatch_step`'s or
        `dispatch_prefill`'s next ids — on the device, which is how a loop
        dispatches step N + 1 before it has read step N (inactive slots may
        carry any id; their outputs are ignored and their cache rows are
        reset by the next prefill). `sampling`: the operand dict from
        sampling.batch_operands (greedy when None — per-request sampling
        params are ARRAY operands here, never jit keys). The ids are not
        donated: `read_ids` gives their host copy before or after they have
        been another call's operand. On a mesh the call waits for the
        device inside the run lock (`_run`), so nothing is ever ahead."""
        self._ensure_placed()
        if self.paged and table is None:
            table = self.full_table()
        with self._jit_lock:
            if self._step_fn is None:
                self._step_fn = self._build_step()
            fn = self._step_fn
        label = "decode_step"
        warm = label in self._compiled
        samp = self._step_operands(sampling)
        with self.tracer.phase("decode_step_dispatch",
                               histogram=self._m_dispatch,
                               fold=True) as dispatch:
            out = self._run(
                fn, label, "step", self.model.params, self.model.states,
                cache, self._place_ids(last_ids), samp,
                table if self.paged else None, sample=False)
            if not warm:            # the compile: _timed has accounted it
                dispatch.cancel()
        if self._m_steps is not None:
            # the same question the traced conditional asks of the operand
            self._m_steps.inc(1, sampler="sampled" if np.any(
                samp["temperature"] > 0) else "greedy")
        return out

    def read_ids(self, next_ids):
        """Host copy ([slots] np.int32) of a dispatched step's next ids: the
        one wait for the device of a step, the phase `decode_step_sync`."""
        with self.tracer.phase("decode_step_sync", histogram=self._m_sync,
                               fold=True) as sync:
            ids = np.asarray(next_ids)
        self.last_sync_ms = sync.duration_ms
        return ids

    def step(self, cache, last_ids, sampling=None, table=None):
        """`dispatch_step` and `read_ids` in a row: the synchronous step of
        `generate`, the speculative decoder and the warm-up. Returns (cache,
        next_ids [slots] np.int32, probs [slots, vocab] still on the device:
        `read_probs` for a host copy of the rows a caller uses)."""
        t0 = monotonic_s()
        cache, nxt, probs = self.dispatch_step(cache, last_ids,
                                               sampling=sampling, table=table)
        nxt = self.read_ids(nxt)
        # the wall of the call and of the wait for the ids is the cost
        # plane's dispatch sample
        self.observe_wall("decode_step", (monotonic_s() - t0) * 1000.0)
        return cache, nxt, probs

    def read_probs(self, probs):
        """Host copy of a distribution `step` / `prefill` returned, or of
        the rows of it a caller indexed out on the device: the phase
        `decode_probs_read` and its histogram, whose count is how often some
        caller moved probabilities to the host at all."""
        with self.tracer.phase("decode_probs_read",
                               histogram=self._m_probs_read, fold=True):
            return np.asarray(probs)

    def has_recurrent(self):
        """Some layer keeps a carry: state a length reset cannot rewind."""
        return bool(self._carries)

    def verify(self, cache, slot, tokens, start):
        """Speculative verify: append the W-token window `tokens` at row
        offset `start` of `slot` and return (cache, probs [W, vocab]) — the
        next-token distribution AFTER each window position, all W in ONE
        batched pass. The caller owns the accept decision and commits the
        surviving length via `set_length` (rollback = not advancing it).
        One executable per W; rewindable state only, slab-layout only."""
        if self.paged:
            raise DecodeUnsupported(
                "speculative verify runs on the slab layout (the paged "
                "scheduler path and the verify window are separate tiers)")
        if self.has_recurrent():
            raise DecodeUnsupported(
                "verify needs rewind-free state: recurrent carries cannot "
                "roll back to `start` after a rejected draft")
        ids = np.asarray(tokens, np.int32).reshape(-1)
        W = ids.shape[0]
        if W < 1:
            raise ValueError("empty verify window")
        if int(start) + W > self.capacity:
            raise ValueError(
                f"verify window [{int(start)}, {int(start) + W}) exceeds "
                f"capacity {self.capacity}")
        self._ensure_placed()
        with self._jit_lock:
            fn = self._verify_fns.get(W)
            if fn is None:
                fn = self._verify_fns[W] = self._build_verify(W)
        t0 = monotonic_s()
        cache, probs = self._run(
            fn, f"decode_verify:{W}", W, self.model.params,
            self.model.states, cache, np.int32(slot), ids, np.int32(start),
            sample=False)
        probs = np.asarray(probs)
        self.observe_wall(f"decode_verify:{W}", (monotonic_s() - t0) * 1000.0)
        return cache, probs

    def set_length(self, cache, slot, n):
        """Host-side length commit for `slot` (the speculative accept /
        rollback primitive: cache rows beyond the new length become dead
        weight the causal mask hides)."""
        lengths = np.asarray(cache["lengths"]).copy()
        lengths[int(slot)] = int(n)
        out = dict(cache)
        if self.mesh is not None:
            out["lengths"] = jax.device_put(
                jnp.asarray(lengths), self.cache_shardings()["lengths"])
        else:
            out["lengths"] = jnp.asarray(lengths)
        return out

    def carry_snapshot(self, cache):
        """Host copy of the carries + lengths — tiny ([slots, n_out] per
        LSTM layer, no K/V). The speculative engine snapshots a recurrent
        DRAFT before proposing and restores on rollback; rewindable entries
        don't need it (rollback is a length reset)."""
        return {"lengths": np.asarray(cache["lengths"]).copy(),
                "layers": {name: {k: np.asarray(v).copy()
                                  for k, v in cache["layers"][name].items()}
                           for name in self._carries}}

    def carry_restore(self, cache, snap):
        """Rewind the carries (and lengths) to a snapshot; every other
        entry stays the array it is (placed where it is placed already)."""
        out = {"lengths": jnp.asarray(snap["lengths"]),
               "layers": {**cache["layers"], **jax.tree_util.tree_map(
                   jnp.asarray, snap["layers"])}}
        if self.mesh is None:
            return out
        return jax.tree_util.tree_map(jax.device_put, out,
                                      self.cache_shardings())

    def warmup(self, buckets=()):
        """Compile the step and the given prefill buckets on a scratch cache
        (deploy-time warm-up: a hot-swapped model is never cold)."""
        cache = self.init_cache()
        for L in sorted(set(int(b) for b in buckets)):
            L = min(max(L, MIN_PREFILL_BUCKET), self.capacity)
            # a (L-1)-token prompt maps to bucket L
            cache, _, _ = self.prefill(cache, 0, np.zeros((max(L - 1, 1),),
                                                          np.int32))
        cache, _, _ = self.step(cache, np.zeros((self.slots,), np.int32))
        return self

    def generate(self, prompt_ids, max_new_tokens=20, stop_id=None,
                 sampler=None):
        """Single-request decode on slot 0 (the host loop behind
        `network.generate`); greedy unless `sampler` (a SamplerConfig)
        says otherwise. Returns the list of generated token ids."""
        if int(max_new_tokens) < 1:
            # same contract as DecodeScheduler.submit: the prefill always
            # emits one token, so 0 is unservable, not "empty result"
            raise ValueError("max_new_tokens must be >= 1")
        cache = self.init_cache()
        table = self.full_table() if self.paged else None
        cache, nid, _ = self.prefill(cache, 0, prompt_ids, sampling=sampler,
                                     table=table)
        out = [nid]
        ids = np.zeros((self.slots,), np.int32)
        while len(out) < int(max_new_tokens) and out[-1] != stop_id \
                and len(np.asarray(prompt_ids).reshape(-1)) + len(out) \
                < self.capacity:
            ids[0] = out[-1]
            samp = None
            if sampler is not None:
                # fold_in counter = index of the token being emitted
                samp = _sampling.batch_operands(
                    self.slots, {0: sampler}, {0: len(out)})
            cache, nxt, _ = self.step(cache, ids, sampling=samp,
                                      table=table)
            out.append(int(nxt[0]))
        return out
