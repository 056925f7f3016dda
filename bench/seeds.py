"""Everything a run draws comes from its ``--seed`` through these streams.

Seeds are any whole number; ``jax.random.PRNGKey`` keeps only 32 bits of
one, so the upper half is folded in.
"""
from __future__ import annotations

import jax
import numpy as np


def seed_key(seed: int) -> jax.Array:
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def weight_key(seed: int) -> jax.Array:
    """The key the weights are drawn from, by the program and the reference."""
    return jax.random.fold_in(seed_key(seed), 0)


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream for each use of the seed."""
    return np.random.default_rng(
        np.random.SeedSequence(int(seed) % (1 << 64), spawn_key=(stream,))
    )
