"""Seeds: one PRNG key per ``--seed``, for seeds wider than 32 bits."""

from __future__ import annotations

import jax


def seed_key(seed: int) -> jax.Array:
    """A key that depends on all 64 bits of ``seed`` (seeds may
    pass 2**31, which ``jax.random.key`` alone would reject)."""
    if seed < 0 or seed >= 2**64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    key = jax.random.key(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
