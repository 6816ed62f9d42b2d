"""Pallas TPU kernel: dense elastic-net shrink (prox) sweep.

    out = sgn(w) * max(a * |w| - s, 0)

with scalar ``a`` (multiplicative l2^2 decay) and ``s`` (l1 shift).  This is
the O(d) inner loop of the paper's *dense-update baseline* (Eq 9 / §6.2
applied to every coordinate every step) and of the lazy trainer's
round-boundary flush when all rows share one (ratio, shift).

One read + one write per element; tiled (block_rows, block_cols) in VMEM
with 128-lane-aligned columns.  1-D inputs are reshaped to (n/128, 128) by
the ops.py wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import SCALAR_SPEC, dynamic_hypers, tile_spec


def _kernel(w_ref, a_ref, s_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)
    mag = a_ref[0, 0] * jnp.abs(w) - s_ref[0, 0]
    out_ref[...] = (jnp.sign(w) * jnp.maximum(mag, 0.0)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def enet_prox_kernel(
    w: jnp.ndarray,  # [R, D] padded to block multiples
    a: jnp.ndarray,  # scalar f32
    s: jnp.ndarray,  # scalar f32
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    R, D = w.shape
    assert R % block_rows == 0 and D % block_cols == 0, (w.shape, block_rows, block_cols)
    grid = (R // block_rows, D // block_cols)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            tile_spec(block_rows, block_cols),
            SCALAR_SPEC,
            SCALAR_SPEC,
        ],
        out_specs=tile_spec(block_rows, block_cols),
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        name="enet_prox",
        interpret=interpret,
    )(w, *dynamic_hypers(a, s))
