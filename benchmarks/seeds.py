"""One place where --seed becomes random state. The driver's seeds run to a
little over 2**31, more than an int32 holds, so the JAX key is folded from two
halves and numpy gets the whole number."""
from __future__ import annotations

import numpy as np


def key_of(seed, stream=0):
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, int(stream))


def rng_of(seed, stream=0):
    return np.random.default_rng([int(seed), int(stream)])
