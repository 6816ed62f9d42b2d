"""Shared hyper-operand plumbing for the Pallas kernels.

Every kernel in this package takes its hyperparameters (lam1, eta, the prox
``a``/``s``, FTRL's alpha/beta/lams) as DYNAMIC ``(1, 1)`` f32 tiles mapped
to every program — never as trace-time constants — so a new value must not
recompile and :mod:`repro.sweeps` can pass them as traced per-config scalars
under vmap.  Before this module each kernel carried its own copy of the
``jnp.asarray(x, jnp.float32).reshape(1, 1)`` + ``BlockSpec((1, 1), ...)``
boilerplate; the fused whole-step kernels made a third copy inevitable, so
the plumbing lives here once:

* :func:`dynamic_hypers` — normalize any number of scalars to f32 ``(1, 1)``
  kernel operands in one call.
* :data:`SCALAR_SPEC` — the matching BlockSpec: a ``(1, 1)`` tile pinned to
  block ``(0, 0)`` for every program, whatever the grid rank (the index_map
  ignores its arguments, so one spec serves 1-D and 2-D grids).
* :func:`tile_spec` — the standard ``(block_rows, block_cols)`` data tile
  over a 2-D grid.
* :func:`row_tile_spec` — a ``(block_rows, 1)`` per-row operand (one scalar
  per sublane, broadcast across lanes by the VPU).
* :func:`default_interpret` — the one place a kernel call picks interpret
  mode (off the TPU) or the compiled kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def default_interpret() -> bool:
    """Interpret mode wherever the default backend is not a TPU (the CPU
    test runs), the compiled kernel on it.  A run that expects the chip
    checks its lowered programs for the compiled call (``chip_smoke.py``
    looks for ``tpu_custom_call``) rather than trusting this switch."""
    return jax.default_backend() != "tpu"


#: (1, 1) scalar operand mapped to every program of any grid rank
SCALAR_SPEC = pl.BlockSpec((1, 1), lambda *_: (0, 0))


def scalar_operand(x) -> jnp.ndarray:
    """One dynamic hyper as a ``(1, 1)`` f32 kernel operand."""
    return jnp.asarray(x, jnp.float32).reshape(1, 1)


def dynamic_hypers(*hypers):
    """Normalize scalars (Python floats or traced f32) to ``(1, 1)`` f32
    kernel operands.  Returns a tuple in argument order; pair each with
    :data:`SCALAR_SPEC` in the pallas_call's ``in_specs``."""
    return tuple(scalar_operand(h) for h in hypers)


def tile_spec(block_rows: int, block_cols: int) -> pl.BlockSpec:
    """The standard (block_rows, block_cols) data tile over a 2-D grid."""
    return pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))


def row_tile_spec(block_rows: int) -> pl.BlockSpec:
    """A (block_rows, 1) per-row operand: one scalar per sublane, broadcast
    across the 128-wide lane dimension by the VPU."""
    return pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))
