"""Plain FTRL-Proximal logistic regression with L1 + L2 (McMahan et al.,
KDD 2013, Algorithm 1), per-coordinate AdaGrad rates.

State per coordinate: ``z`` (the linearized-loss sum) and ``n`` (the sum of
squared gradients).  The weight is read from them:

    w = 0                                                  if |z| <= lam1
        (sign(z) * lam1 - z) / ((beta + sqrt(n)) / alpha + lam2)  otherwise

Step ``t`` on a batch ``(idx, val, y)``: read ``w`` and ``n`` at the ids,
``z_e = sum_s w_es * val_es + b``, ``g_es = (sigmoid(z_e) - y_e) * val_es``;
then per occurrence, against the values read before the step,

    sigma = (sqrt(n + g**2) - sqrt(n)) / alpha
    z    += g - sigma * w          (repeated ids add up)
    n    += g**2

and the bias, which every example touches, takes a plain step
``b -= eta_t * sum_e (sigmoid(z_e) - y_e)`` on the global schedule.
``alpha`` is the schedule's ``eta0``.  ``dtype`` is the precision of the
whole computation: float32 is the reference, bfloat16 its control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.references.common import Static, eta, logistic, norm


def read(hp: dict, z, n):
    alpha = hp["schedule"]["eta0"]
    lam1, lam2, beta = hp["lam1"], hp["lam2"], hp["beta"]
    w = (jnp.sign(z) * lam1 - z) / ((beta + jnp.sqrt(n)) / alpha + lam2)
    return jnp.where(jnp.abs(z) <= lam1, 0, w).astype(z.dtype)


def _step(hp: dict, dtype, carry, batch):
    z, n, b, t = carry
    idx, val, y = batch["idx"], batch["val"].astype(dtype), batch["y"].astype(dtype)
    zg, ng = z[idx], n[idx]
    w = read(hp, zg, ng)
    m = jnp.sum(w * val, axis=-1) + b
    loss, gm = logistic(m, y)
    g = gm[:, None] * val
    sigma = (jnp.sqrt(ng + g * g) - jnp.sqrt(ng)) / jnp.asarray(hp["schedule"]["eta0"], dtype)
    flat = idx.reshape(-1)
    z = z.at[flat].add((g - sigma * w).reshape(-1))
    n = n.at[flat].add((g * g).reshape(-1))
    b = b - eta(hp["schedule"], t, dtype) * jnp.sum(gm)
    return (z, n, b, t + 1), jnp.mean(loss.astype(jnp.float32))


def init(dim: int, dtype):
    zeros = jnp.zeros((dim,), dtype)
    return (zeros, zeros, jnp.zeros((), dtype), jnp.zeros((), jnp.int32))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _round(hp, dtype, carry, batches):
    return jax.lax.scan(functools.partial(_step, hp.d, dtype), carry, batches)


@functools.partial(jax.jit, static_argnums=0)
def _leaves(hp, carry):
    z, n, b, _ = carry
    return {"w": norm(read(hp.d, z, n)), "z": norm(z), "n": norm(n), "b": norm(b)}


def train(hp: dict, dim: int, rounds: list, dtype=jnp.float32) -> list:
    """The rounds' batches (dicts of ``[R, B, p]`` arrays) in order; after
    each round, its mean loss and the norms of the state's leaves."""
    carry = init(dim, dtype)
    out = []
    for batches in rounds:
        carry, losses = _round(Static(hp), dtype, carry, batches)
        leaves = {k: float(v) for k, v in _leaves(Static(hp), carry).items()}
        out.append({"loss": float(jnp.mean(losses)), "leaves": leaves})
    return out
