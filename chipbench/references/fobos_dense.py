"""Plain FoBoS elastic-net logistic regression: every coordinate, every step.

Step ``t`` on a batch of examples ``(idx, val, y)``, with ``eta = eta_t``:

    z_e    = sum_s w[idx_es] * val_es + b
    g_e    = sigmoid(z_e) - y_e
    w[idx_es] -= eta * g_e * val_es          (repeated ids add up)
    w      = sign(w) * max(|w| - eta * lam1, 0) / (1 + eta * lam2)   (all d)
    b     -= eta * sum_e g_e

which is the paper's FoBoS update (section 6) done densely, with no
delayed catch-up and no caches.  ``dtype`` is the precision of the whole
computation: float32 is the reference, bfloat16 its control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.references.common import Static, eta, logistic, norm


def _step(hp: dict, dtype, carry, batch):
    w, b, t = carry
    e = eta(hp["schedule"], t, dtype)
    idx, val, y = batch["idx"], batch["val"].astype(dtype), batch["y"].astype(dtype)
    z = jnp.sum(w[idx] * val, axis=-1) + b
    loss, g = logistic(z, y)
    w = w.at[idx.reshape(-1)].add((-e * g[:, None] * val).reshape(-1))
    mag = (jnp.abs(w) - e * jnp.asarray(hp["lam1"], dtype)) / (1 + e * jnp.asarray(hp["lam2"], dtype))
    w = jnp.sign(w) * jnp.maximum(mag, 0)
    return (w, b - e * jnp.sum(g), t + 1), jnp.mean(loss.astype(jnp.float32))


def init(dim: int, dtype, t: int = 0):
    return (jnp.zeros((dim,), dtype), jnp.zeros((), dtype), jnp.asarray(t, jnp.int32))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _round(hp, dtype, carry, batches):
    return jax.lax.scan(functools.partial(_step, hp.d, dtype), carry, batches)


@jax.jit
def _leaves(carry):
    w, b, _ = carry
    return {"w": norm(w), "b": norm(b)}


def train(hp: dict, dim: int, rounds: list, dtype=jnp.float32) -> list:
    """The rounds' batches (dicts of ``[R, B, p]`` arrays) in order; after
    each round, its mean loss and the norms of the state's leaves."""
    carry = init(dim, dtype)
    out = []
    for batches in rounds:
        carry, losses = _round(Static(hp), dtype, carry, batches)
        leaves = {k: float(v) for k, v in _leaves(carry).items()}
        out.append({"loss": float(jnp.mean(losses)), "leaves": leaves})
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _online(hp, dtype, carry, stream):
    step = functools.partial(_step, hp.d, dtype)

    def one(carry, ex):
        w, b, _ = carry
        z = jnp.sum(w[ex["idx"]] * ex["val"].astype(dtype)) + b
        p = jax.nn.sigmoid(z.astype(jnp.float32))
        carry, _ = step(carry, jax.tree.map(lambda a: a[None], ex))
        return carry, p

    return jax.lax.scan(one, carry, stream)


def online(hp: dict, dim: int, stream: dict, t: int = 0, dtype=jnp.float32):
    """Progressive validation, one example at a time from zero weights at
    step ``t``: predict example k with the weights after examples ``< k``,
    then learn it.  Returns the predictions ``[N]`` and the final
    ``(w, b)``."""
    (w, b, _), preds = _online(Static(hp), dtype, init(dim, dtype, t), stream)
    return preds, w, b

