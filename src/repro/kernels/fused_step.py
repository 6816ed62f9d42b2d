"""Pallas TPU kernels: ONE whole lazy training step per tile pass.

The multi-op step (gather -> catch-up -> predict -> gradient -> prox ->
scatter) round-trips the gathered [B, p] slab through HBM between every op
and pays one dispatch per op; BENCH_solvers shows all four solvers pinned at
~18us/step by exactly that overhead.  These kernels collapse everything
between the gather and the scatters into a single double-buffered tile pass
over the slab bytes:

* ``dp_fused_step_kernel`` (sgd / fobos / trunc — they differ only in how
  the DP caches extend, which happens OUTSIDE, in O(1)):

    w_cur  = sgn(w) * max(|w| * ratio - shift, 0)        closed-form catch-up
    z      = sum_p(w_cur * val) [+ b]                    sparse predict
    loss, gz = loss_fn(z, y)                             logistic / squared
    delta  = -eta * gz * val                             the SGD step to
                                                         scatter-ADD back

* ``ftrl_fused_step_kernel`` (apply-at-read + AdaGrad deltas):

    w_cur  = ftrl read from (z, n)                       elastic-net closed form
    zlin   = sum_p(w_cur * val) [+ b]
    loss, gz = loss_fn(zlin, y)
    g      = gz * val
    dz, dn = (g - sigma * w_cur, g^2),  sigma = (sqrt(n + g^2) - sqrt(n))/alpha

The row reduction for ``z`` needs the whole feature axis resident, so the
grid is 1-D over example-row blocks with the (padded) feature axis as one
full-width tile — serving/sweep batches have p <= a few hundred, far under
a VMEM tile.  Padded feature columns carry w = val = 0 and contribute
exactly 0 to every output (sign(0) = 0 gates the catch-up; val = 0 gates
z, delta, and the FTRL deltas); padded example rows produce garbage gz/loss
and are sliced off by the ops.py wrapper.

The gather that produces the slab and the scatter-SET/scatter-ADD pair that
writes it back stay in XLA (DESIGN.md §11): duplicate-index semantics
(identical SET then accumulating ADD) are exactly what jnp scatters already
implement, and the paper's O(p) claim lives in the slab math between them.

Hypers (eta / b / alpha / beta / lam1 / lam2) are DYNAMIC (1, 1) operands
(kernels.common): traced (lam1, lam2, eta0) sweeps reuse one compiled
program.  ``loss`` and ``use_bias`` are trace-static — they change the
program, like LinearConfig structure always does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import SCALAR_SPEC, dynamic_hypers, tile_spec

LOGISTIC = "logistic"
SQUARED = "squared"


def _loss_grad(z, y, loss: str):
    """Per-example loss and dLoss/dz — the same expressions as
    core.linear_trainer.loss_and_grad_z (kept in sync by the bitwise test)."""
    if loss == LOGISTIC:
        loss_v = jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        gz = jax.nn.sigmoid(z) - y
    else:
        loss_v = 0.5 * (z - y) ** 2
        gz = z - y
    return loss_v, gz


def _dp_kernel(
    w_ref, ratio_ref, shift_ref, val_ref, y_ref, b_ref, eta_ref,
    wcur_ref, delta_ref, gz_ref, loss_ref, *, loss: str, use_bias: bool,
):
    w = w_ref[...].astype(jnp.float32)
    val = val_ref[...].astype(jnp.float32)
    # closed-form catch-up: all missed elastic-net updates at once
    mag = jnp.abs(w) * ratio_ref[...].astype(jnp.float32) - shift_ref[...].astype(jnp.float32)
    w_cur = jnp.sign(w) * jnp.maximum(mag, 0.0)
    # sparse predict over the (full-width) feature axis
    z = jnp.sum(w_cur * val, axis=-1)
    if use_bias:
        z = z + b_ref[0, 0].astype(jnp.float32)
    loss_v, gz = _loss_grad(z, y_ref[...].reshape(-1).astype(jnp.float32), loss)
    delta = -eta_ref[0, 0].astype(jnp.float32) * (gz[:, None] * val)
    wcur_ref[...] = w_cur.astype(wcur_ref.dtype)
    delta_ref[...] = delta.astype(delta_ref.dtype)
    gz_ref[...] = gz.reshape(gz_ref.shape).astype(gz_ref.dtype)
    loss_ref[...] = loss_v.reshape(loss_ref.shape).astype(loss_ref.dtype)


def _ftrl_kernel(
    z_ref, n_ref, val_ref, y_ref, b_ref, alpha_ref, beta_ref, lam1_ref, lam2_ref,
    wcur_ref, dz_ref, dn_ref, gz_ref, loss_ref, *, loss: str, use_bias: bool,
):
    zf = z_ref[...].astype(jnp.float32)
    nf = n_ref[...].astype(jnp.float32)
    val = val_ref[...].astype(jnp.float32)
    lam1 = lam1_ref[0, 0].astype(jnp.float32)
    # reciprocal-of-alpha form, matching ReferenceBackend.ftrl_read exactly
    inv_alpha = 1.0 / alpha_ref[0, 0].astype(jnp.float32)
    denom = (beta_ref[0, 0].astype(jnp.float32) + jnp.sqrt(nf)) * inv_alpha + lam2_ref[
        0, 0
    ].astype(jnp.float32)
    w_read = (jnp.sign(zf) * lam1 - zf) / denom
    w_cur = jnp.where(jnp.abs(zf) <= lam1, 0.0, w_read)
    zlin = jnp.sum(w_cur * val, axis=-1)
    if use_bias:
        zlin = zlin + b_ref[0, 0].astype(jnp.float32)
    loss_v, gz = _loss_grad(zlin, y_ref[...].reshape(-1).astype(jnp.float32), loss)
    g = gz[:, None] * val
    g2 = g * g
    sigma = (jnp.sqrt(nf + g2) - jnp.sqrt(nf)) * inv_alpha
    wcur_ref[...] = w_cur.astype(wcur_ref.dtype)
    dz_ref[...] = (g - sigma * w_cur).astype(dz_ref.dtype)
    dn_ref[...] = g2.astype(dn_ref.dtype)
    gz_ref[...] = gz.reshape(gz_ref.shape).astype(gz_ref.dtype)
    loss_ref[...] = loss_v.reshape(loss_ref.shape).astype(loss_ref.dtype)


def _row_specs(block_rows: int, P: int):
    """Specs for one example-row block: full-width [br, P] data tiles plus
    the [br, 1] per-example label/output columns, over a 1-D row grid."""
    data = pl.BlockSpec((block_rows, P), lambda i: (i, 0))
    col = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    return data, col


@functools.partial(jax.jit, static_argnames=("loss", "use_bias", "block_rows", "interpret"))
def dp_fused_step_kernel(
    w: jnp.ndarray,  # [B, P] gathered weights (padded)
    ratio: jnp.ndarray,  # [B, P] per-element catch-up ratio
    shift: jnp.ndarray,  # [B, P] per-element catch-up shift
    val: jnp.ndarray,  # [B, P] feature values
    y: jnp.ndarray,  # [B, 1] labels
    b: jnp.ndarray,  # scalar f32 bias (dynamic)
    eta: jnp.ndarray,  # scalar f32 learning rate (dynamic)
    *,
    loss: str,
    use_bias: bool,
    block_rows: int = 8,
    interpret: bool = False,
):
    """Raw pallas_call; shapes must already be padded (B to a block_rows
    multiple, P to a 128 multiple — use repro.kernels.ops.dp_fused_step).
    Returns ``(w_cur [B, P], delta [B, P], gz [B, 1], loss [B, 1])``."""
    B, P = w.shape
    assert B % block_rows == 0 and P % 128 == 0, (w.shape, block_rows)
    assert w.shape == ratio.shape == shift.shape == val.shape and y.shape == (B, 1)
    data, col = _row_specs(block_rows, P)
    grid = (B // block_rows,)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_dp_kernel, loss=loss, use_bias=use_bias),
        grid=grid,
        in_specs=[data] * 4 + [col] + [SCALAR_SPEC] * 2,
        out_specs=(data, data, col, col),
        out_shape=(
            jax.ShapeDtypeStruct((B, P), f32),
            jax.ShapeDtypeStruct((B, P), f32),
            jax.ShapeDtypeStruct((B, 1), f32),
            jax.ShapeDtypeStruct((B, 1), f32),
        ),
        name="dp_fused_step",
        interpret=interpret,
    )(w, ratio, shift, val, y, *dynamic_hypers(b, eta))


@functools.partial(jax.jit, static_argnames=("loss", "use_bias", "block_rows", "interpret"))
def ftrl_fused_step_kernel(
    z: jnp.ndarray,  # [B, P] gathered FTRL accumulators (padded)
    n: jnp.ndarray,  # [B, P] gathered AdaGrad sums
    val: jnp.ndarray,  # [B, P] feature values
    y: jnp.ndarray,  # [B, 1] labels
    b: jnp.ndarray,  # scalar f32 bias (dynamic)
    alpha: jnp.ndarray,  # scalar f32 hypers (dynamic)
    beta: jnp.ndarray,
    lam1: jnp.ndarray,
    lam2: jnp.ndarray,
    *,
    loss: str,
    use_bias: bool,
    block_rows: int = 8,
    interpret: bool = False,
):
    """Raw pallas_call (padded shapes — use repro.kernels.ops.ftrl_fused_step).
    Returns ``(w_cur [B, P], dz [B, P], dn [B, P], gz [B, 1], loss [B, 1])``."""
    B, P = z.shape
    assert B % block_rows == 0 and P % 128 == 0, (z.shape, block_rows)
    assert z.shape == n.shape == val.shape and y.shape == (B, 1)
    data, col = _row_specs(block_rows, P)
    grid = (B // block_rows,)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_ftrl_kernel, loss=loss, use_bias=use_bias),
        grid=grid,
        in_specs=[data] * 3 + [col] + [SCALAR_SPEC] * 5,
        out_specs=(data, data, data, col, col),
        out_shape=(
            jax.ShapeDtypeStruct((B, P), f32),
            jax.ShapeDtypeStruct((B, P), f32),
            jax.ShapeDtypeStruct((B, P), f32),
            jax.ShapeDtypeStruct((B, 1), f32),
            jax.ShapeDtypeStruct((B, 1), f32),
        ),
        name="ftrl_fused_step",
        interpret=interpret,
    )(z, n, val, y, *dynamic_hypers(b, alpha, beta, lam1, lam2))
