"""Plain FTRL-Proximal (``ftrl_proximal``) over only the ids the rounds touch.

At ``dim`` = 2^30 the dense ``z`` and ``n`` of ``ftrl_proximal`` take 8 GiB,
and its weight read 4 GiB more, on one chip.  Here the rounds' distinct ids
are mapped onto ``[0, u)`` in order (``np.unique``) and ``ftrl_proximal``
runs at dim ``u`` on the remapped batches: at most ``rounds * R * B * p``
ids, 1,966,080 for three rounds of 2048 x 8 x 40.

It is the same arithmetic.  Each touched id keeps its own ``(z, n)`` and
sees the same updates in the same order, duplicates in a batch included.
An untouched row keeps ``z = n = 0`` and so reads ``w = 0``: it adds
nothing to a margin, and nothing to the norm of ``w``, ``z`` or ``n``.  So
the losses and the leaf norms are those of the full-``dim`` reference.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from chipbench.references import ftrl_proximal


def train(hp: dict, dim: int, rounds: list, dtype=jnp.float32) -> list:
    """As ``ftrl_proximal.train``; ``dim`` only bounds the ids."""
    rounds = [{k: np.asarray(v) for k, v in r.items()} for r in rounds]
    idx = [r["idx"] for r in rounds]
    ids, flat = np.unique(np.concatenate([i.reshape(-1) for i in idx]), return_inverse=True)
    if ids.size and (ids[0] < 0 or ids[-1] >= dim):
        raise ValueError(f"ids outside [0, {dim})")
    out, at = [], 0
    for r, i in zip(rounds, idx):
        local = flat[at : at + i.size].reshape(i.shape).astype(np.int32)
        at += i.size
        out.append({**r, "idx": local})
    return ftrl_proximal.train(hp, int(ids.size), out, dtype)
