"""Keys and generators from ``--seed``, which may exceed 32 bits."""
from __future__ import annotations

import numpy as np


def key(seed: int, salt: int = 0):
    """A raw ``uint32[2]`` JAX PRNG key from ``seed`` (any non-negative
    int)."""
    import jax.numpy as jnp

    return jnp.asarray(np.random.SeedSequence([seed, salt]).generate_state(
        2, np.uint32))


def rng(seed: int, salt: int = 0) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, salt]))
