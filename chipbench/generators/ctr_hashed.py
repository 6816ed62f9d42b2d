"""Criteo-shaped click examples, hashed into ``dim`` features, made on the
device from a key.

Layout of one example (``p_max`` slots):

* slots ``0 .. n_int-1``: the integer fields, one feature per field (a fixed
  id per field), value ``log1p(count)`` with ``count = floor(exp(int_log_mu +
  int_log_sigma * N(0, 1)))``, and value 0 where the field is missing
  (probability ``int_missing``);
* the next ``len(cardinalities)`` slots: the categorical fields, value 1.
  Field ``f`` draws a rank in ``[0, cardinalities[f])`` from a power law of
  exponent ``zipf_s`` by continuous inversion (:func:`rank_cdf` gives the
  law), and hashes ``(f, rank)`` into ``[0, dim)``;
* the rest: padding (id 0, value 0).

Labels: ``z`` sums one effect per categorical ``(f, rank)`` and per integer
field, each ``truth_scale * U(-1, 1)`` from a hash salted by the key, plus
``noise * N(0, 1)``; ``y = 1[z > q]``, where ``q`` is the
``1 - positive_rate`` quantile of ``z`` over a fixed calibration sample, so
that about ``positive_rate`` of the examples are clicks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CALIBRATION = 65536  # examples in the sample that fixes the label threshold


def fmix32(h):
    """MurmurHash3's 32-bit finalizer, on uint32 arrays."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def hash2(a, b, salt):
    """A 32-bit hash of two uint32 arrays and a salt."""
    return fmix32(fmix32(a * jnp.uint32(0x9E3779B1) ^ salt) ^ (b * jnp.uint32(0x27D4EB2F)))


def rank_cdf(rank, card: int, s: float):
    """P(drawn rank < ``rank``) under :func:`ranks`' law (float64, host)."""
    top = (card + 1.0) ** (1.0 - s)
    return ((np.asarray(rank, np.float64) + 1.0) ** (1.0 - s) - 1.0) / (top - 1.0)


def ranks(u, cards, s: float):
    """Inverse of :func:`rank_cdf` for uniforms ``u [n, F]``."""
    top = (cards.astype(jnp.float32) + 1.0) ** (1.0 - s)
    x = (1.0 + u * (top - 1.0)) ** (1.0 / (1.0 - s))
    r = jnp.floor(x).astype(jnp.int32) - 1
    return jnp.clip(r, 0, cards - 1)


def tables(data: dict) -> dict:
    return {"cards": np.asarray(data["cardinalities"], np.int32)}


def _effect(salt, a, b, scale):
    u = hash2(a.astype(jnp.uint32), b.astype(jnp.uint32), salt).astype(jnp.float32)
    return scale * (u * (2.0 / 2**32) - 1.0)


def _raw(data: dict, tables: dict, salt, key, n: int, p_max: int):
    n_int, cards = data["n_int"], tables["cards"]
    n_cat = cards.shape[0]
    dim = data["dim"]
    k_miss, k_count, k_rank, k_noise = jax.random.split(key, 4)
    fields = jnp.arange(n_int, dtype=jnp.uint32)
    int_ids = (hash2(fields, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)) % dim).astype(jnp.int32)
    count = jnp.floor(
        jnp.exp(data["int_log_mu"] + data["int_log_sigma"] * jax.random.normal(k_count, (n, n_int)))
    )
    present = jax.random.uniform(k_miss, (n, n_int)) >= data["int_missing"]
    int_val = jnp.where(present, jnp.log1p(count), 0.0)
    rank = ranks(jax.random.uniform(k_rank, (n, n_cat)), cards, data["zipf_s"])
    cat_f = jnp.arange(n_cat, dtype=jnp.uint32)[None, :]
    cat_ids = (hash2(cat_f + 1, rank.astype(jnp.uint32), jnp.uint32(0)) % dim).astype(jnp.int32)
    pad = p_max - n_int - n_cat
    idx = jnp.concatenate(
        [jnp.broadcast_to(int_ids, (n, n_int)), cat_ids, jnp.zeros((n, pad), jnp.int32)], axis=1
    )
    val = jnp.concatenate(
        [int_val, jnp.ones((n, n_cat), jnp.float32), jnp.zeros((n, pad), jnp.float32)], axis=1
    )
    scale = data["truth_scale"]
    z = jnp.sum(_effect(salt, cat_f + 1, rank, scale), axis=-1)
    z = z + jnp.sum(int_val * _effect(salt, fields, jnp.uint32(0), scale), axis=-1)
    z = z + jax.random.normal(k_noise, (n,)) * data["noise"]
    return idx, val, z


def prepare(data: dict, tables: dict, k_truth, p_max: int) -> dict:
    """The run's hash salt and label threshold, shared by every block."""
    k_salt, k_calib = jax.random.split(k_truth)
    salt = jax.random.bits(k_salt, (), jnp.uint32)
    _, _, z_cal = _raw(data, tables, salt, k_calib, CALIBRATION, p_max)
    return {"salt": salt, "q": jnp.quantile(z_cal, 1.0 - data["positive_rate"])}


def block(data: dict, tables: dict, prep: dict, k_data, n: int, p_max: int):
    """``n`` examples: ``(idx [n, p_max] i32, val [n, p_max] f32, y [n] f32)``."""
    idx, val, z = _raw(data, tables, prep["salt"], k_data, n, p_max)
    return idx, val, (z > prep["q"]).astype(jnp.float32)
