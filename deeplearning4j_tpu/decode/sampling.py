"""Sampled decoding: temperature / top-k / top-p with per-request seeds,
as BATCH-SHAPED OPERANDS of the one decode step executable.

The recompile trap this module exists to avoid: the obvious way to add
sampling to a compiled decode step is to close over (or pass as jit static
args) the request's temperature / top_k / top_p / seed — and then every
creative-workload request with a new temperature mints a new executable,
exactly the per-shape explosion GL011 banned for shapes. Here every
sampling parameter is an ARRAY operand of the step:

  temperature f32[slots]   <= 0 means greedy (argmax) for that slot
  top_k       i32[slots]   <= 0 means off (full vocab)
  top_p       f32[slots]   >= 1 means off; always keeps the top-1 token
  seed        u32[slots]   per-request RNG seed
  step        i32[slots]   index of the token being sampled (0 = the
                           prefill's first token), the fold_in counter

so one executable serves every mix of greedy and sampled slots, and the
graftlint GL016 rule (`sampling-recompile-key`) flags any hot-path code
that demotes these back to static args or dict-key components.

What a batch pays follows what its operands ask for: the filter and the
draw below (a sort, a cumulative sum and a Gumbel draw, each over
[slots, vocab]) sit in one branch of a `lax.cond` whose predicate is
`any(temperature > 0)` — a traced value of the operand, nothing static.
A batch of greedy slots runs the argmax alone; a batch with one sampled
slot runs what it always ran and draws the same tokens.

Determinism: slot s draws token t from
``jax.random.categorical(fold_in(PRNGKey(seed[s]), step[s]), ...)`` — a
pure function of (seed, token index). The sequence therefore reproduces
across runs, across hot-swaps of the same weights, and across a paged-pool
preemption that re-prefills prompt+partial (the re-prefill passes the
SAME step index the lost step would have used).

Top-k / top-p run INSIDE the trace via sort+cumsum (no dynamic shapes):
top-k keeps probs >= the k-th largest (ties may keep a few extra — the
standard tie-handling caveat), top-p keeps the smallest prefix of the
descending-sorted probs whose *exclusive* cumulative sum is < p (so the
top-1 token always survives, even at p=0). Masked tokens are excluded at
the LOGIT level (finite NEG_INF after the temperature divide), not by
renormalizing probabilities, so high temperatures cannot leak mass back
into masked tokens.

`filter_probs_np` is the numpy mirror of the same filter (parity-tested)
for host-side consumers — the speculative engine's accept/rollback math
needs the filtered target/draft distributions without another executable.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30

_FIELDS = ("temperature", "top_k", "top_p", "seed", "step")


class SamplerConfig:
    """One request's sampling parameters (host-side, JSON round-trip).

    The default config IS greedy decoding: temperature 0 short-circuits to
    argmax inside the trace, so greedy and sampled requests co-batch in the
    same step executable.
    """

    __slots__ = ("temperature", "top_k", "top_p", "seed")

    def __init__(self, temperature=0.0, top_k=0, top_p=1.0, seed=0):
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed) & 0xFFFFFFFF
        if not np.isfinite(self.temperature):
            raise ValueError("temperature must be finite")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0 (0 = off)")
        if not (0.0 <= self.top_p):
            raise ValueError("top_p must be >= 0")

    @property
    def is_greedy(self):
        return self.temperature <= 0.0

    @classmethod
    def from_request(cls, d):
        """Build from a /generate JSON body; None when the body carries no
        sampling field (the greedy fast path skips operand building)."""
        if not any(k in d for k in ("temperature", "top_k", "top_p", "seed")):
            return None
        return cls(temperature=d.get("temperature", 0.0),
                   top_k=d.get("top_k", 0),
                   top_p=d.get("top_p", 1.0),
                   seed=d.get("seed", 0))

    def to_dict(self):
        return {"temperature": self.temperature, "top_k": self.top_k,
                "top_p": self.top_p, "seed": self.seed}

    def __repr__(self):
        return (f"SamplerConfig(temperature={self.temperature}, "
                f"top_k={self.top_k}, top_p={self.top_p}, seed={self.seed})")


GREEDY = SamplerConfig()


def batch_operands(slots, configs=None, steps=None):
    """The step executable's sampling operand dict: numpy [slots] arrays.

    configs: {slot: SamplerConfig} (missing slots decode greedily);
    steps: {slot: token index} for the fold_in counter. Plain arrays in,
    plain arrays out — nothing here is ever a hashable jit key.
    """
    ops = {"temperature": np.zeros((slots,), np.float32),
           "top_k": np.zeros((slots,), np.int32),
           "top_p": np.ones((slots,), np.float32),
           "seed": np.zeros((slots,), np.uint32),
           "step": np.zeros((slots,), np.int32)}
    for slot, cfg in (configs or {}).items():
        if cfg is None:
            continue
        ops["temperature"][slot] = cfg.temperature
        ops["top_k"][slot] = cfg.top_k
        ops["top_p"][slot] = cfg.top_p
        ops["seed"][slot] = cfg.seed
    for slot, t in (steps or {}).items():
        ops["step"][slot] = int(t)
    return ops


def slot_operands(config, step):
    """[1]-shaped operand dict for the prefill leg (one slot at a time).
    `step` is the index of the token this prefill emits — 0 on a fresh
    admission, len(partial tokens) on a post-preemption re-prefill, so the
    seeded stream continues exactly where the preempted request left off."""
    cfg = config if config is not None else GREEDY
    return batch_operands(1, {0: cfg}, {0: step})


def keep_mask(probs, top_k, top_p):
    """Traced [S, V] bool mask of tokens that survive top-k AND top-p.

    top-k: token survives when its prob >= the k-th largest of its row
    (k <= 0 or k >= V disables). top-p: survives when its prob >= the
    smallest prob kept by the nucleus — the descending-sorted prefix whose
    EXCLUSIVE cumsum is < p, top-1 always kept (p >= 1 disables). Both are
    fixed-shape sort/cumsum/threshold chains: no dynamic slicing, so the
    mask composes into the one decode executable."""
    V = probs.shape[-1]
    sorted_p = jnp.sort(probs, axis=-1)[:, ::-1]              # descending
    # ---- top-k: threshold at the k-th largest probability
    k = jnp.clip(top_k, 1, V)
    kth = jnp.take_along_axis(sorted_p, (k - 1)[:, None], axis=-1)   # [S,1]
    k_on = ((top_k > 0) & (top_k < V))[:, None]
    keep_k = jnp.where(k_on, probs >= kth, True)
    # ---- top-p: exclusive cumsum over the sorted row; map the boundary
    # back to prob space as "the minimum kept probability"
    csum = jnp.cumsum(sorted_p, axis=-1)
    excl = csum - sorted_p
    pos0 = jnp.arange(V, dtype=jnp.int32)[None, :] == 0
    keep_sorted = (excl < top_p[:, None]) | pos0              # top-1 stays
    min_kept = jnp.min(jnp.where(keep_sorted, sorted_p, jnp.inf),
                       axis=-1, keepdims=True)
    keep_p = jnp.where((top_p < 1.0)[:, None], probs >= min_kept, True)
    return keep_k & keep_p


def sample_tokens(probs, operands):
    """Traced per-slot token choice: [S, V] f32 probs + the operand dict
    from `batch_operands` -> [S] int32 ids.

    Greedy slots (temperature <= 0) take the argmax; sampled slots draw
    from categorical(logits/T) with the top-k/top-p mask applied at the
    LOGIT level (NEG_INF) and a per-slot key
    fold_in(PRNGKey(seed), step). The filter and the draw are one branch
    of a `lax.cond` on "some slot samples" (module docstring)."""
    temperature = operands["temperature"]
    greedy_ids = jnp.argmax(probs, axis=-1).astype(jnp.int32)

    def filter_and_draw():
        keep = keep_mask(probs, operands["top_k"], operands["top_p"])
        t = jnp.maximum(temperature, 1e-6)[:, None]
        logits = jnp.log(jnp.clip(probs, 1e-30, None)) / t
        logits = jnp.where(keep, logits, NEG_INF)

        def draw(seed, step, row):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            return jax.random.categorical(key, row)

        sampled = jax.vmap(draw)(operands["seed"].astype(jnp.uint32),
                                 operands["step"], logits).astype(jnp.int32)
        return jnp.where(temperature > 0, sampled, greedy_ids)

    return lax.cond(jnp.any(temperature > 0), filter_and_draw,
                    lambda: greedy_ids)


def filter_probs_np(probs, config):
    """Host-side numpy mirror of the traced filter+temperature chain:
    returns the NORMALIZED distribution a sampled slot draws from (greedy
    configs return a one-hot argmax row). The speculative engine's
    accept/rollback math runs on these without minting an executable;
    parity with `keep_mask`/`sample_tokens` is pinned in tests."""
    p = np.asarray(probs, np.float64).reshape(-1)
    V = p.shape[0]
    if config is None or config.is_greedy:
        out = np.zeros_like(p)
        out[int(np.argmax(p))] = 1.0
        return out
    order = np.argsort(-p, kind="stable")
    sorted_p = p[order]
    keep = np.ones((V,), bool)
    if 0 < config.top_k < V:
        keep &= p >= sorted_p[config.top_k - 1]
    if config.top_p < 1.0:
        excl = np.cumsum(sorted_p) - sorted_p
        keep_sorted = excl < config.top_p
        keep_sorted[0] = True
        keep &= p >= sorted_p[keep_sorted].min()
    logits = np.log(np.clip(p, 1e-30, None)) / max(config.temperature, 1e-6)
    logits[~keep] = -np.inf
    logits -= logits.max()
    e = np.exp(logits)
    return e / e.sum()
