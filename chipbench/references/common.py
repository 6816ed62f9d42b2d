"""Arithmetic the plain references share: the rate schedule and the loss."""

from __future__ import annotations

import json

import jax.numpy as jnp


class Static:
    """A dict of hyperparameters as a static (hashable) jit argument."""

    def __init__(self, d: dict):
        self.d = d
        self._key = json.dumps(d, sort_keys=True)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Static) and self._key == other._key


def eta(schedule: dict, t, dtype):
    """The global rate at step ``t`` (``schedule`` as in a configuration
    file: ``kind`` constant | inv_t | inv_sqrt, ``eta0``, ``t0``)."""
    t = jnp.asarray(t, jnp.float32)
    eta0, t0 = schedule["eta0"], schedule.get("t0", 1.0)
    if schedule["kind"] == "constant":
        e = jnp.float32(eta0)
    elif schedule["kind"] == "inv_t":
        e = eta0 * t0 / (t0 + t)
    elif schedule["kind"] == "inv_sqrt":
        e = eta0 * jnp.sqrt(jnp.float32(t0)) / jnp.sqrt(t0 + t)
    else:
        raise ValueError(f"unknown schedule kind {schedule['kind']!r}")
    return e.astype(dtype)


def logistic(z, y):
    """Per-example log loss (from the logit) and its derivative in ``z``."""
    loss = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return loss, 1 / (1 + jnp.exp(-z)) - y


def norm(x) -> jnp.ndarray:
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
