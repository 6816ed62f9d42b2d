"""Inputs made on the device from the seed, in one jitted call.

A generator module (``chipbench/generators/<name>.py``, named by the
configuration's ``generator``) provides ``tables(data)`` (host-made
arrays), ``prepare(data, tables, k_truth, p_max)`` (what every block
shares, such as the ground truth) and ``block(data, tables, prep, k_data,
n, p_max)`` (``n`` examples from the block's own key).  Block ``b`` of a
seed is the same whatever the number of blocks, so a short run and a long
one see the same first rounds.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from chipbench.rng import seed_key


def generator(name: str):
    return importlib.import_module(f"chipbench.generators.{name}")


def blocks(config: dict, seed: int, n_blocks: int, shape: tuple, first: int = 0):
    """Blocks ``first .. first+n_blocks-1`` of the seed's stream, each of
    ``shape`` examples (a tuple such as ``(round_len, batch)``): a dict of
    device arrays ``idx``/``val`` ``[n_blocks, *shape, p_max]`` and ``y``
    ``[n_blocks, *shape]``."""
    gen = generator(config["generator"])
    data, p_max = config["data"], config["p_max"]
    n = 1
    for s in shape:
        n *= s
    k_truth, k_data = jax.random.split(seed_key(seed))

    @jax.jit
    def make(tables, k_truth, k_data):
        prep = gen.prepare(data, tables, k_truth, p_max)

        def one(b):
            idx, val, y = gen.block(data, tables, prep, jax.random.fold_in(k_data, b), n, p_max)
            return {
                "idx": idx.reshape(*shape, p_max),
                "val": val.reshape(*shape, p_max),
                "y": y.reshape(shape),
            }

        return jax.lax.map(one, first + jnp.arange(n_blocks))

    tables = {k: jnp.asarray(v) for k, v in gen.tables(data).items()}
    return make(tables, k_truth, k_data)
