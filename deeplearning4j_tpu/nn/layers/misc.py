"""ActivationLayer and DropoutLayer runtime (reference:
nn/layers/ActivationLayer.java, nn/layers/DropoutLayer.java)."""
from __future__ import annotations

import jax.numpy as jnp

from .base import BaseLayerModule, register_impl, apply_dropout


@register_impl("ActivationLayer")
class ActivationLayerModule(BaseLayerModule):
    positionwise = True

    def init(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.activation_fn()(x), state, mask


@register_impl("DropoutLayer")
class DropoutLayerModule(BaseLayerModule):
    positionwise = True

    def init(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return apply_dropout(x, self.conf.dropout, train, rng), state, mask
