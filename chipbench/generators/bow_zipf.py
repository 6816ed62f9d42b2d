"""Medline-shaped bag-of-words examples, made on the device from a key.

The statistics are those of ``repro.data.synthetic_bow`` (copied here, not
imported, so that the benchmark's inputs do not move with the program):

* document lengths ``min(Poisson(p_mean), p_max)``;
* feature ids drawn from a Zipf law over ``dim`` ranks, ``p(r) ~ r**-s``, by
  inverse CDF (the CDF is summed in float64 on the host, once);
* values ``log1p(1 + Poisson(count_poisson))``;
* labels ``1[x . w_true + noise * N(0, 1) > 0]``, where ``w_true`` holds
  ``n_informative`` weights ``N(0, truth_scale**2)`` on ids drawn without
  replacement from the ``informative_pool`` most popular ones.

Padding slots carry id 0 and value 0, the trainer's inert convention.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def zipf_cdf(dim: int, s: float) -> np.ndarray:
    """CDF of the Zipf law over ranks 1..dim, summed in float64."""
    p = np.arange(1, dim + 1, dtype=np.float64) ** (-s)
    return np.cumsum(p / p.sum())


def truth(data: dict, key) -> jnp.ndarray:
    """The ground-truth weight vector ``[dim]``."""
    k_support, k_values = jax.random.split(key)
    support = jax.random.choice(
        k_support, data["informative_pool"], (data["n_informative"],), replace=False
    )
    values = jax.random.normal(k_values, (data["n_informative"],)) * data["truth_scale"]
    return jnp.zeros((data["dim"],), jnp.float32).at[support].set(values)


def tables(data: dict) -> dict:
    """Host-made tables that :func:`block` takes as arguments."""
    return {"cdf": zipf_cdf(data["dim"], data["zipf_s"]).astype(np.float32)}


def prepare(data: dict, tables: dict, k_truth, p_max: int) -> dict:
    """The run's ground truth, shared by every block."""
    return {"w_true": truth(data, k_truth)}


def block(data: dict, tables: dict, prep: dict, k_data, n: int, p_max: int):
    """``n`` examples: ``(idx [n, p_max] i32, val [n, p_max] f32, y [n] f32)``
    from the block's own key ``k_data``."""
    w_true, cdf = prep["w_true"], tables["cdf"]
    k_len, k_id, k_count, k_noise = jax.random.split(k_data, 4)
    lens = jnp.minimum(jax.random.poisson(k_len, data["p_mean"], (n,)), p_max)
    mask = jnp.arange(p_max)[None, :] < lens[:, None]
    u = jax.random.uniform(k_id, (n, p_max))
    # "sort" gives the ids that the default binary search gives, and on a TPU
    # makes the Medline corpus in a fifth of the time
    idx = jnp.searchsorted(cdf, u, method="sort")
    idx = jnp.minimum(idx, data["dim"] - 1).astype(jnp.int32)
    counts = 1.0 + jax.random.poisson(k_count, data["count_poisson"], (n, p_max))
    val = jnp.where(mask, jnp.log1p(counts.astype(jnp.float32)), 0.0)
    idx = jnp.where(mask, idx, 0)
    z = jnp.sum(val * w_true[idx], axis=-1)
    z = z + jax.random.normal(k_noise, (n,)) * data["noise"]
    return idx, val, (z > 0).astype(jnp.float32)
