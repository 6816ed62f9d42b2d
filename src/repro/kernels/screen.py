"""Pallas TPU kernel for regularization-path screening (DESIGN.md §17).

One elementwise pass per tile fuses the two per-coordinate tests the path
engine runs between lambda stages:

* the sequential strong rule's gradient bound — a coordinate survives when
  ``|g| >= thr`` (``thr = 2*lam1_k - lam1_{k-1}``) or when it is already
  active (``w != 0``, the ever-active rule);
* the KKT violation check on the complement — a discarded coordinate
  violates stationarity when ``|g| > lam_chk``.

Emitting both masks from the same tile read means the safety loop costs one
pass over the gradient bytes, not two.  Outputs are packed 0/1 f32 masks
(comparisons only — no rounding), so the reference twin is exactly equal,
not merely close.

TPU mapping: grid = (R/block_rows, D/block_cols) over zero-padded tiles;
``thr``/``lam_chk`` are DYNAMIC (1, 1) f32 tiles — a new lambda stage must
never recompile.  Padded entries (g = w = 0) are sliced off by the ops.py
wrapper; their mask values are meaningless but harmless.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import SCALAR_SPEC, dynamic_hypers, tile_spec


def _screen_kernel(g_ref, w_ref, thr_ref, chk_ref, active_ref, viol_ref):
    ag = jnp.abs(g_ref[...].astype(jnp.float32))
    w = w_ref[...].astype(jnp.float32)
    thr = thr_ref[0, 0].astype(jnp.float32)
    chk = chk_ref[0, 0].astype(jnp.float32)
    active = jnp.where((ag >= thr) | (w != 0.0), 1.0, 0.0)
    active_ref[...] = active.astype(active_ref.dtype)
    viol_ref[...] = ((1.0 - active) * jnp.where(ag > chk, 1.0, 0.0)).astype(viol_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def screen_rows_kernel(
    g: jnp.ndarray,  # [R, D] unpenalized loss gradient
    w: jnp.ndarray,  # [R, D] previous-stage weights (ever-active rule)
    thr: jnp.ndarray,  # scalar f32 strong-rule bound (dynamic)
    chk: jnp.ndarray,  # scalar f32 KKT tolerance bound (dynamic)
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool = False,
):
    """Raw pallas_call returning ``(active, viol)`` 0/1 f32 tiles; shapes
    must be padded to block multiples (repro.kernels.ops.screen_mask wraps
    this)."""
    R, D = g.shape
    assert g.shape == w.shape, (g.shape, w.shape)
    assert R % block_rows == 0 and D % block_cols == 0, (g.shape, block_rows, block_cols)
    grid = (R // block_rows, D // block_cols)
    return pl.pallas_call(
        _screen_kernel,
        grid=grid,
        in_specs=[tile_spec(block_rows, block_cols)] * 2 + [SCALAR_SPEC] * 2,
        out_specs=(tile_spec(block_rows, block_cols), tile_spec(block_rows, block_cols)),
        out_shape=(
            jax.ShapeDtypeStruct(g.shape, jnp.float32),
            jax.ShapeDtypeStruct(g.shape, jnp.float32),
        ),
        name="screen_rows",
        interpret=interpret,
    )(g, w, *dynamic_hypers(thr, chk))
