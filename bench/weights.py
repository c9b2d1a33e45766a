"""The key every seeded random draw of the benchmark's weights starts from.

Each model family (``bench/families``) makes its weights from it."""

from __future__ import annotations

import jax


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also past 32 bits."""
    if seed < 0:
        raise ValueError("seed must be >= 0")
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)),
                              seed >> 32)
