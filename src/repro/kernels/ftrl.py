"""Pallas TPU kernels for the FTRL-Proximal solver (repro.solvers.ftrl).

Two elementwise passes over gathered coordinate tiles, and the round flush:

* ``ftrl_read_rows_kernel`` — the apply-at-read elastic-net proximal step:

    w = 0                                              if |z| <= lam1
        (sgn(z)*lam1 - z) / ((beta + sqrt(n))/alpha + lam2)    otherwise

* ``ftrl_update_rows_kernel`` — the per-coordinate AdaGrad update deltas:

    sigma = (sqrt(n + g^2) - sqrt(n)) / alpha
    dz    = g - sigma * w
    dn    = g^2

  Deltas (not absolute values) come back so the caller's scatter-ADD keeps
  the additive duplicate-index semantics in XLA — the same division of
  labor as the catch-up kernels (DESIGN.md §11): tiny per-row derivations
  outside, the O(n) elementwise pass inside.

* ``ftrl_flush_tiles_kernel`` — the round flush: every row's w rebuilt by
  the read from its own ``(z, n)``, over the ``[d, 3]`` state in the chip's
  own layout.  XLA keeps that state as ``{0,1:T(4,128)}``: each (4, 128)
  tile holds the w, z, n (and one pad) rows of 128 coordinates, contiguous
  in HBM.  Viewed as ``[d/128, 3, 128]`` (a bitcast, no copy) a block of
  tiles is one contiguous DMA, z and n load as dense ``[tiles, 128]``
  rows, and the output aliases the input: one read and one write of the
  state, at the HBM roofline rather than the per-step overhead of a flat
  (8, 256) grid.

TPU mapping mirrors kernels/lazy_enet.py: grid = (R/block_rows,
D/block_cols) over zero-padded [R, D] tiles (padded w=n=g=z=0 entries
produce 0 outputs: sign(0)=0 gates the read, g=0 gates the deltas), with
every hyper a DYNAMIC (1, 1) f32 tile — a new alpha/beta/lam must never
recompile, and repro.sweeps vmaps them as traced per-config scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import SCALAR_SPEC, dynamic_hypers, tile_spec


def _read(z, n, alpha, beta, lam1, lam2):
    # reciprocal-of-alpha form, matching the reference backend exactly (see
    # ReferenceBackend.ftrl_read: keeps constant vs traced alpha bitwise)
    inv_alpha = 1.0 / alpha
    denom = (beta + jnp.sqrt(n)) * inv_alpha + lam2
    w = (jnp.sign(z) * lam1 - z) / denom
    return jnp.where(jnp.abs(z) <= lam1, 0.0, w)


def _hypers(*refs):
    return tuple(r[0, 0].astype(jnp.float32) for r in refs)


def _read_kernel(z_ref, n_ref, alpha_ref, beta_ref, lam1_ref, lam2_ref, out_ref):
    z = z_ref[...].astype(jnp.float32)
    n = n_ref[...].astype(jnp.float32)
    w = _read(z, n, *_hypers(alpha_ref, beta_ref, lam1_ref, lam2_ref))
    out_ref[...] = w.astype(out_ref.dtype)


def _flush_kernel(alpha_ref, beta_ref, lam1_ref, lam2_ref, wzn_ref, out_ref):
    # [tiles, 3, 128] blocks: row 1 of every tile is z, row 2 is n
    z, n = wzn_ref[:, 1, :], wzn_ref[:, 2, :]
    out_ref[:, 0, :] = _read(z, n, *_hypers(alpha_ref, beta_ref, lam1_ref, lam2_ref))
    out_ref[:, 1, :] = z
    out_ref[:, 2, :] = n


def _update_kernel(w_ref, n_ref, g_ref, alpha_ref, dz_ref, dn_ref):
    w = w_ref[...].astype(jnp.float32)
    n = n_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    g2 = g * g
    sigma = (jnp.sqrt(n + g2) - jnp.sqrt(n)) * (1.0 / alpha_ref[0, 0].astype(jnp.float32))
    dz_ref[...] = (g - sigma * w).astype(dz_ref.dtype)
    dn_ref[...] = g2.astype(dn_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def ftrl_read_rows_kernel(
    z: jnp.ndarray,  # [R, D]
    n: jnp.ndarray,  # [R, D]
    alpha: jnp.ndarray,  # scalar f32 (dynamic)
    beta: jnp.ndarray,
    lam1: jnp.ndarray,
    lam2: jnp.ndarray,
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call; shapes must already be padded to block multiples
    (use repro.kernels.ops.ftrl_read for the public padded wrapper)."""
    R, D = z.shape
    assert z.shape == n.shape and R % block_rows == 0 and D % block_cols == 0, (z.shape, n.shape)
    grid = (R // block_rows, D // block_cols)
    return pl.pallas_call(
        _read_kernel,
        grid=grid,
        in_specs=[tile_spec(block_rows, block_cols)] * 2 + [SCALAR_SPEC] * 4,
        out_specs=tile_spec(block_rows, block_cols),
        out_shape=jax.ShapeDtypeStruct(z.shape, jnp.float32),
        name="ftrl_read_rows",
        interpret=interpret,
    )(z, n, *dynamic_hypers(alpha, beta, lam1, lam2))


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def ftrl_update_rows_kernel(
    w: jnp.ndarray,  # [R, D] current (read) weights
    n: jnp.ndarray,  # [R, D] AdaGrad accumulators
    g: jnp.ndarray,  # [R, D] per-example loss gradients
    alpha: jnp.ndarray,  # scalar f32 (dynamic)
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool = False,
):
    """Raw pallas_call returning ``(dz, dn)`` delta tiles."""
    R, D = w.shape
    assert w.shape == n.shape == g.shape, (w.shape, n.shape, g.shape)
    assert R % block_rows == 0 and D % block_cols == 0, (w.shape, block_rows, block_cols)
    grid = (R // block_rows, D // block_cols)
    return pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[tile_spec(block_rows, block_cols)] * 3 + [SCALAR_SPEC],
        out_specs=(tile_spec(block_rows, block_cols), tile_spec(block_rows, block_cols)),
        out_shape=(
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
        ),
        name="ftrl_update_rows",
        interpret=interpret,
    )(w, n, g, *dynamic_hypers(alpha))


@functools.partial(jax.jit, static_argnames=("block_tiles", "interpret"))
def ftrl_flush_tiles_kernel(
    wzn: jnp.ndarray,  # [T, 3, 128] the (w, z, n) state, one (3, 128) tile a coordinate block
    alpha: jnp.ndarray,  # scalar f32 (dynamic)
    beta: jnp.ndarray,
    lam1: jnp.ndarray,
    lam2: jnp.ndarray,
    *,
    block_tiles: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call: the same tiles with row 0 rebuilt, written over the
    input buffer (use repro.kernels.ops.ftrl_flush for the ``[d, 3]``
    wrapper)."""
    assert wzn.shape[1:] == (3, 128), wzn.shape
    bt = min(block_tiles, wzn.shape[0])
    block = pl.BlockSpec((bt, 3, 128), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _flush_kernel,
        grid=(pl.cdiv(wzn.shape[0], bt),),
        in_specs=[SCALAR_SPEC] * 4 + [block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(wzn.shape, jnp.float32),
        input_output_aliases={4: 0},
        name="ftrl_flush",
        interpret=interpret,
    )(*dynamic_hypers(alpha, beta, lam1, lam2), wzn)
