"""Pallas TPU kernel: fused lazy elastic-net catch-up + SGD update on a slab
of gathered parameter rows.

This is the inner loop the paper optimizes: when a row (embedding row / MoE
expert slice / linear-model weight group) is touched after ``n`` absent
steps, apply all ``n`` missed regularization updates in closed form AND the
current loss-gradient step, in ONE pass over the row bytes:

    out[r, c] = sgn(w[r,c]) * max(|w[r,c]| * ratio[r] - shift[r], 0)
                - eta * grad[r,c]

``ratio``/``shift`` are the per-row O(1) catch-up factors derived from the
DP caches (repro.core.lazy_enet.catchup_factors); they are tiny [R] vectors
computed outside and broadcast down the 128-wide lane dimension inside the
kernel, so the kernel stays purely memory-bound at 2 reads + 1 write per
element instead of the 3 reads + 2 writes of a split catchup-then-update.

TPU mapping
-----------
* grid = (R / block_rows, D / block_cols); each program owns a
  (block_rows, block_cols) VMEM tile of ``w`` and ``grad``.
* block_cols is a multiple of 128 (VPU lane width); block_rows a multiple
  of 8 (f32 sublanes) — asserted in ops.py, which also pads ragged shapes.
* ratio/shift ride along as (block_rows, 1) tiles: one scalar per sublane,
  broadcast across lanes by the VPU.
* eta is a (1, 1) tile mapped to every program.

Validated in interpret mode against ref.lazy_enet_update_ref for shape and
dtype sweeps (tests/kernels/test_lazy_enet_kernel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import SCALAR_SPEC, dynamic_hypers, row_tile_spec, tile_spec


def _kernel(w_ref, g_ref, ratio_ref, shift_ref, eta_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)
    ratio = ratio_ref[...].astype(jnp.float32)  # [RB, 1] or [RB, CB]; broadcasts
    shift = shift_ref[...].astype(jnp.float32)
    mag = jnp.abs(w) * ratio - shift
    cur = jnp.sign(w) * jnp.maximum(mag, 0.0)
    out = cur - eta_ref[0, 0].astype(jnp.float32) * g_ref[...].astype(jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)


def _apply_kernel(w_ref, ratio_ref, shift_ref, out_ref):
    """Catch-up apply without a gradient term (flush / pure catch-up)."""
    w = w_ref[...].astype(jnp.float32)
    mag = jnp.abs(w) * ratio_ref[...].astype(jnp.float32) - shift_ref[...].astype(jnp.float32)
    out_ref[...] = (jnp.sign(w) * jnp.maximum(mag, 0.0)).astype(out_ref.dtype)


def _factor_operand(f: jnp.ndarray, R: int, D: int, block_rows: int, block_cols: int):
    """Normalize a catch-up factor to a kernel operand + BlockSpec.

    Per-row factors ([R] or [R, 1]) ride along as (block_rows, 1) tiles — one
    scalar per sublane, broadcast across lanes by the VPU.  Per-element
    factors ([R, D], the linear trainer's gathered flat slab reshaped to
    tiles) get full (block_rows, block_cols) tiles."""
    if f.shape == (R, D) and D != 1:
        return f.astype(jnp.float32), tile_spec(block_rows, block_cols)
    assert f.shape in ((R,), (R, 1)), (f.shape, (R, D))
    return f.reshape(R, 1).astype(jnp.float32), row_tile_spec(block_rows)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def lazy_enet_rows_kernel(
    w: jnp.ndarray,  # [R, D]
    grad: jnp.ndarray,  # [R, D]
    ratio: jnp.ndarray,  # [R] (per-row) or [R, D] (per-element) f32
    shift: jnp.ndarray,  # same shape as ratio
    eta: jnp.ndarray,  # scalar f32
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call; shapes must already be padded to block multiples
    (use repro.kernels.ops.lazy_enet_update for the public padded/gathered
    wrapper)."""
    R, D = w.shape
    assert R % block_rows == 0 and D % block_cols == 0, (w.shape, block_rows, block_cols)
    grid = (R // block_rows, D // block_cols)
    ratio, ratio_spec = _factor_operand(ratio, R, D, block_rows, block_cols)
    shift, shift_spec = _factor_operand(shift, R, D, block_rows, block_cols)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            tile_spec(block_rows, block_cols),  # w
            tile_spec(block_rows, block_cols),  # grad
            ratio_spec,
            shift_spec,
            SCALAR_SPEC,  # eta
        ],
        out_specs=tile_spec(block_rows, block_cols),
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        name="lazy_enet_rows",
        interpret=interpret,
    )(w, grad, ratio, shift, *dynamic_hypers(eta))


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def enet_apply_rows_kernel(
    w: jnp.ndarray,  # [R, D]
    ratio: jnp.ndarray,  # [R] (per-row) or [R, D] (per-element) f32
    shift: jnp.ndarray,  # same shape as ratio
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Gradient-free catch-up apply: ``sgn(w) * max(|w|*ratio - shift, 0)``
    with per-row or per-element factors — one read + one write per element
    (the flush / pure-catch-up half of the fused kernel)."""
    R, D = w.shape
    assert R % block_rows == 0 and D % block_cols == 0, (w.shape, block_rows, block_cols)
    grid = (R // block_rows, D // block_cols)
    ratio, ratio_spec = _factor_operand(ratio, R, D, block_rows, block_cols)
    shift, shift_spec = _factor_operand(shift, R, D, block_rows, block_cols)
    return pl.pallas_call(
        _apply_kernel,
        grid=grid,
        in_specs=[
            tile_spec(block_rows, block_cols),  # w
            ratio_spec,
            shift_spec,
        ],
        out_specs=tile_spec(block_rows, block_cols),
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        name="enet_apply_rows",
        interpret=interpret,
    )(w, ratio, shift)
