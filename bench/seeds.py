"""Seeds: every input, weight and choice of a run comes from ``--seed``,
any whole number up to 2**64."""

from __future__ import annotations

import jax
import numpy as np


def key(seed: int) -> jax.Array:
    """A JAX PRNG key for the seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for the seed, one independent stream per use."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])
