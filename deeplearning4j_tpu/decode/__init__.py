"""Autoregressive decode subsystem: KV-cache continuous batching, sampled
decoding, paged KV, and speculative verify.

The LLM-style workloads this repo trains (`zoo.transformer_lm`,
`zoo.char_rnn_lstm`) are served token-by-token here, with the same
zero-steady-state-recompile discipline the serving batcher and device-side
ingest established:

- `DecodeEngine` compiles a fixed-shape decode step (every token, every
  mix of co-batched requests), one prefill per power-of-two prompt-length
  bucket, and one speculative-verify pass per window size, over a cache
  pytree with a per-slot length vector. What a layer keeps there (an
  attention layer's K/V, a recurrent layer's carry) and how each leg
  advances it is the layer's decode contract (nn/layers/base.py).
- `sampling.SamplerConfig` carries a request's temperature / top-k /
  top-p / seed; they enter the step executable as BATCH-SHAPED ARRAY
  OPERANDS (never jit keys — graftlint GL016), with per-slot
  `fold_in(PRNGKey(seed), step)` keys making every sampled stream
  reproducible across runs, hot-swaps, and preemptions.
- `paged.BlockPool` + a `[slots, max_blocks]` block-table operand replace
  the slab with pow2-token pool blocks (`DecodeEngine(paged=True)`,
  `kernels.flash_attention.flash_decode_paged`): capacity is allocated
  block-by-block as requests generate, so admission can OVERSUBSCRIBE and
  reclaim via preempt-and-requeue instead of stranding slab bytes.
- `SpeculativeEngine` pairs a cheap draft with the serving target: the
  draft proposes K tokens, the target scores all K in one batched verify,
  and greedy speculative output is token-for-token identical to
  target-only decoding.
- `DecodeScheduler` owns slot lifecycle: requests join free slots and
  retire PER TOKEN (continuous batching), with admission shedding,
  per-token deadline budgets, TTFT/ITL histograms with trace exemplars,
  block allocation/preemption in paged mode, and ModelRegistry hot-swap
  (drain-then-swap, engines cached per model so a rollback never
  recompiles).

`ServingServer(decode=True)` exposes this as POST /generate, routed through
the same FleetFrontend failover/canary layer as /predict.
"""
from .engine import DecodeEngine, DecodeUnsupported
from .paged import BlockPool, PoolExhausted, blocks_for
from .sampling import SamplerConfig
from .scheduler import DecodeScheduler, GenerateRequest
from .speculative import SpeculativeEngine

__all__ = ["BlockPool", "DecodeEngine", "DecodeScheduler",
           "DecodeUnsupported", "GenerateRequest", "PoolExhausted",
           "SamplerConfig", "SpeculativeEngine", "blocks_for"]
