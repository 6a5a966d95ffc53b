"""Runtime layer SPI.

Mirrors the reference's Layer contract (nn/api/Layer.java:37 — activate :202,
backpropGradient :119, feedForwardMaskArray :309) with a TPU-first twist:
layers are pure functions of (params, state, input); the backward pass is
derived by JAX autodiff instead of hand-written backpropGradient, and the whole
network's forward+backward+update traces into a single XLA computation.

A custom layer can still provide its own gradient by wrapping its forward in
jax.custom_vjp — that is the analog of the reference's hand-written layers.

State = non-trainable per-layer variables (e.g. batch-norm running stats,
center-loss centers). Mask = per-timestep validity [batch, time] for
variable-length sequences (reference: Layer.feedForwardMaskArray).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..activations import get_activation

LAYER_IMPL_REGISTRY: dict = {}


def register_impl(conf_cls_name):
    def deco(cls):
        LAYER_IMPL_REGISTRY[conf_cls_name] = cls
        return cls
    return deco


def create_layer(conf):
    cls = LAYER_IMPL_REGISTRY.get(type(conf).__name__)
    if cls is None:
        raise ValueError(f"No runtime implementation for layer config {type(conf).__name__}")
    return cls(conf)


def apply_dropout(x, rate, train, rng):
    """Inverted dropout on the layer *input*, matching the reference
    (nn/conf dropout semantics, util/Dropout.java: applied to input at train time)."""
    if not train or rate is None or rate <= 0.0 or rng is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


class CacheLeaf(NamedTuple):
    """One array of a layer's decode-cache entry: its shape, its dtype, and
    the axis a serving mesh splits over its model axis (None: replicated)."""
    shape: tuple
    dtype: Any
    model_axis: int | None = None


def note_cache_entry(geom, kind, entry):
    """Count a layer's decode-cache entry in the gauges
    `decode_cache_state_bytes` (kind "state": a fixed size a slot, whatever
    the sequence), `decode_cache_kv_bytes` (kind "kv": grows with the
    capacity) or `decode_cache_window_bytes` (kind "window": a sliding
    window's ring, its window's size whatever the capacity). A layer's
    `decode_entry` calls this with what it returns; the running totals ride
    on the engine's `geom`, so the gauges read the cache of the engine built
    last."""
    import math
    from ...telemetry.registry import get_registry
    totals = vars(geom).setdefault("cache_bytes",
                                   {"state": 0, "kv": 0, "window": 0})
    totals[kind] += sum(math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
                        for leaf in entry.values())
    for k, doc in (("state", "fixed-size per-slot state (recurrent carries, "
                    "state-space states, conv tails)"),
                   ("kv", "entries with a capacity axis (K/V rows)"),
                   ("window", "sliding-window layers' rings (K/V rows of "
                    "the window's positions)")):
        get_registry().gauge(f"decode_cache_{k}_bytes",
                             "Bytes of the decode cache held as "
                             + doc).set(totals[k])
    return entry


class BaseLayerModule:
    """One instantiated layer: shape-aware param init + pure forward.

    The decode contract (walked by decode/engine.py, which names no layer
    class) is four answers: may the layer stream (`decode_unsupported`),
    what it keeps per decode slot (`decode_entry`), how a prompt fills that
    and one token or a verify window advances it (`decode_prefill`,
    `decode_step`, `decode_verify`), and whether a length reset rewinds it
    (`decode_rewindable`). SelfAttentionLayerModule and _BaseLSTMModule
    (recurrent.py) are the two worked examples."""

    #: forward is a per-position map ([b,t,f] -> [b,t,g], position i from
    #: position i alone): it streams with no state of its own
    positionwise = False
    #: rolling a slot back is a reset of its length: nothing the layer keeps
    #: has to be restored (False: verify refuses the plan, and the
    #: speculative draft snapshots the entry instead)
    decode_rewindable = True

    def __init__(self, conf):
        self.conf = conf

    # -- init ---------------------------------------------------------------
    def init(self, rng, input_type, dtype=jnp.float32):
        """Returns (params: dict, state: dict, output_type)."""
        raise NotImplementedError

    # -- forward ------------------------------------------------------------
    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        """Returns (activations, new_state, out_mask)."""
        raise NotImplementedError

    # -- decode contract ----------------------------------------------------
    def decode_unsupported(self):
        """None when the layer can decode token by token, else the reason."""
        if self.positionwise:
            return None
        return f"{type(self).__name__} has no per-token decode semantics"

    def decode_entry(self, geom):
        """{leaf name: CacheLeaf} the layer keeps in the decode cache, from
        the engine's geometry: `slots`, `capacity`, `dtype`, `paged` with
        `block_size` / `num_blocks` for the block-pool layout, and
        `model_shards`, the size of the serving mesh's model axis (1 with no
        mesh). Empty: no state."""
        return {}

    def decode_prefill(self, params, state, x, entry, ctx):
        """(y, entry) of one leg. prefill: x [1, L, f] is a padded prompt
        for cache slot `ctx.slot`, `ctx.mask` [1, L] marks its `ctx.length`
        real tokens (paged: `ctx.row` is the slot's block-table row).
        step: x [slots, 1, f], slot s appends at `ctx.pos[s]` and then
        holds `ctx.kv_valid[s]` tokens (paged: at block `ctx.blk[s]`, offset
        `ctx.off[s]` of the pool behind `ctx.table`). verify: x [1, W, f] is
        a window appended at position `ctx.start` of slot `ctx.slot`; only
        asked of rewindable layers. Stateless default: the forward."""
        return self.forward(params, state, x, train=False, rng=None,
                            mask=ctx.mask)[0], entry

    decode_step = decode_verify = decode_prefill

    # -- optional: output-layer protocol -------------------------------------
    def is_output_layer(self):
        return False

    # -- optional: pretrainable protocol (AE/RBM/VAE) -------------------------
    def is_pretrainable(self):
        return False

    def activation_fn(self):
        return get_activation(self.conf.activation or "identity")

    def num_params(self, params):
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
