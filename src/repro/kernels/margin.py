"""Pallas TPU kernels for the feature-sharded margin pass (DESIGN.md §16).

The sharded lazy step splits the fused whole-step kernel at the mesh
boundary: everything BEFORE the per-example margin psum is shard-local and
elementwise over the gathered ``[B, p]`` slab — catch-up (cache solvers) or
apply-at-read (FTRL) plus the per-slot margin contribution ``w_cur * val``.
These kernels are that pre-psum half; the caller psums the contributions
across shards and finishes the loss gradient in jnp (identical arithmetic
to the unsharded fused step, so the reference twin stays bitwise).

Two elementwise passes, mirroring kernels/lazy_enet.py / kernels/ftrl.py:

* ``dp_margin_rows_kernel``   — ``w_cur = sgn(w) * max(|w|*ratio - shift, 0)``,
  ``contrib = w_cur * val``.
* ``ftrl_margin_rows_kernel`` — the FTRL apply-at-read weight and the same
  contribution product.

TPU mapping: grid = (R/block_rows, D/block_cols) over zero-padded tiles
(padded w=val=0 / z=n=val=0 entries produce 0 outputs), hypers DYNAMIC
(1, 1) f32 tiles — a new lam/alpha must never recompile.  Off-shard slots
arrive with ``val = 0`` (the routing mask), so their contributions vanish
inside the same pass that computes them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import SCALAR_SPEC, dynamic_hypers, tile_spec


def _dp_margin_kernel(w_ref, ratio_ref, shift_ref, val_ref, wcur_ref, contrib_ref):
    w = w_ref[...].astype(jnp.float32)
    mag = jnp.abs(w) * ratio_ref[...].astype(jnp.float32) - shift_ref[...].astype(jnp.float32)
    w_cur = jnp.sign(w) * jnp.maximum(mag, 0.0)
    wcur_ref[...] = w_cur.astype(wcur_ref.dtype)
    contrib_ref[...] = (w_cur * val_ref[...].astype(jnp.float32)).astype(contrib_ref.dtype)


def _ftrl_margin_kernel(z_ref, n_ref, val_ref, alpha_ref, beta_ref, lam1_ref, lam2_ref,
                        wcur_ref, contrib_ref):
    z = z_ref[...].astype(jnp.float32)
    n = n_ref[...].astype(jnp.float32)
    # reciprocal-of-alpha form, matching ReferenceBackend.ftrl_read exactly
    inv_alpha = 1.0 / alpha_ref[0, 0].astype(jnp.float32)
    lam1 = lam1_ref[0, 0].astype(jnp.float32)
    denom = (beta_ref[0, 0].astype(jnp.float32) + jnp.sqrt(n)) * inv_alpha + lam2_ref[
        0, 0
    ].astype(jnp.float32)
    w = (jnp.sign(z) * lam1 - z) / denom
    w_cur = jnp.where(jnp.abs(z) <= lam1, 0.0, w)
    wcur_ref[...] = w_cur.astype(wcur_ref.dtype)
    contrib_ref[...] = (w_cur * val_ref[...].astype(jnp.float32)).astype(contrib_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def dp_margin_rows_kernel(
    w: jnp.ndarray,  # [R, D] gathered weights
    ratio: jnp.ndarray,  # [R, D] per-element catch-up factors
    shift: jnp.ndarray,  # [R, D]
    val: jnp.ndarray,  # [R, D] (masked) feature values
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool = False,
):
    """Raw pallas_call returning ``(w_cur, contrib)`` tiles; shapes must be
    padded to block multiples (repro.kernels.ops.dp_margin wraps this)."""
    R, D = w.shape
    assert w.shape == ratio.shape == shift.shape == val.shape, (w.shape, val.shape)
    assert R % block_rows == 0 and D % block_cols == 0, (w.shape, block_rows, block_cols)
    grid = (R // block_rows, D // block_cols)
    return pl.pallas_call(
        _dp_margin_kernel,
        grid=grid,
        in_specs=[tile_spec(block_rows, block_cols)] * 4,
        out_specs=(tile_spec(block_rows, block_cols), tile_spec(block_rows, block_cols)),
        out_shape=(
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
        ),
        name="dp_margin_rows",
        interpret=interpret,
    )(w, ratio, shift, val)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def ftrl_margin_rows_kernel(
    z: jnp.ndarray,  # [R, D] gathered FTRL accumulators
    n: jnp.ndarray,  # [R, D] gathered AdaGrad sums
    val: jnp.ndarray,  # [R, D] (masked) feature values
    alpha: jnp.ndarray,  # scalar f32 hypers (dynamic)
    beta: jnp.ndarray,
    lam1: jnp.ndarray,
    lam2: jnp.ndarray,
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool = False,
):
    """Raw pallas_call returning ``(w_cur, contrib)`` tiles."""
    R, D = z.shape
    assert z.shape == n.shape == val.shape, (z.shape, n.shape, val.shape)
    assert R % block_rows == 0 and D % block_cols == 0, (z.shape, block_rows, block_cols)
    grid = (R // block_rows, D // block_cols)
    return pl.pallas_call(
        _ftrl_margin_kernel,
        grid=grid,
        in_specs=[tile_spec(block_rows, block_cols)] * 3 + [SCALAR_SPEC] * 4,
        out_specs=(tile_spec(block_rows, block_cols), tile_spec(block_rows, block_cols)),
        out_shape=(
            jax.ShapeDtypeStruct(z.shape, jnp.float32),
            jax.ShapeDtypeStruct(z.shape, jnp.float32),
        ),
        name="ftrl_margin_rows",
        interpret=interpret,
    )(z, n, val, *dynamic_hypers(alpha, beta, lam1, lam2))
