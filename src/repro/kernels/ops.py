"""jit'd public wrappers around the Pallas kernels.

These handle: deriving per-row catch-up factors from the DP caches, padding
ragged shapes to hardware-aligned block multiples, 1-D <-> 2-D reshaping,
and the interpret-mode switch (``common.default_interpret``: interpret off
the TPU, compiled on it).

Hyperparameters (``lam1``, ``eta``, the prox ``a``/``s``) are DYNAMIC f32
operands, never static: they only enter through the catch-up factors / shift
scalars computed outside the kernels, so a new value must not recompile, and
``repro.sweeps`` passes them as traced per-config scalars under vmap.  All
hyper normalization runs through :func:`repro.kernels.common.dynamic_hypers`
inside the raw kernels — one shared helper instead of per-op
``jnp.asarray(..., jnp.float32).reshape(1, 1)`` copies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.dp_caches import RegCaches
from repro.core.lazy_enet import catchup_factors

from .common import default_interpret
from .enet_prox import enet_prox_kernel
from .ftrl import ftrl_flush_tiles_kernel, ftrl_read_rows_kernel, ftrl_update_rows_kernel
from .fused_step import dp_fused_step_kernel, ftrl_fused_step_kernel
from .lazy_enet import enet_apply_rows_kernel, lazy_enet_rows_kernel
from .margin import dp_margin_rows_kernel, ftrl_margin_rows_kernel
from .screen import screen_rows_kernel


def _pad_to(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    R, D = x.shape
    pr = (-R) % rows
    pc = (-D) % cols
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


def _tile_flat(x: jnp.ndarray, block_rows: int, block_cols: int) -> jnp.ndarray:
    """[n] -> [rows, block_cols] zero-padded to block multiples."""
    n = x.shape[0]
    rows_needed = -(-n // block_cols)
    pad_rows = (-rows_needed) % block_rows
    total = (rows_needed + pad_rows) * block_cols
    return jnp.pad(x, (0, total - n)).reshape(rows_needed + pad_rows, block_cols)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def lazy_enet_update(
    w_rows: jnp.ndarray,  # [R, D] gathered parameter rows
    grad: jnp.ndarray,  # [R, D] loss gradient for those rows
    psi: jnp.ndarray,  # [R] int32 last-touch step per row (or scalar)
    k: jnp.ndarray,  # scalar int32 current step (catch up over [psi, k))
    caches: RegCaches,
    eta: jnp.ndarray,  # scalar f32 learning rate for the gradient step
    *,
    lam1,  # scalar f32 l1 strength — dynamic (may be traced per-config)
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool | None = None,
):
    """Fused: bring rows current (O(1)/row via DP caches) + SGD step.

    Padding is safe: padded w=grad=0 rows/cols produce 0 (sign(0)=0)."""
    if interpret is None:
        interpret = default_interpret()
    R, D = w_rows.shape
    ratio, shift = catchup_factors(psi, k, caches, lam1)  # [R] f32 each
    ratio = jnp.broadcast_to(ratio, (R,))
    shift = jnp.broadcast_to(shift, (R,))
    wp = _pad_to(w_rows, block_rows, block_cols)
    gp = _pad_to(grad, block_rows, block_cols)
    pr = wp.shape[0] - R
    if pr:
        ratio = jnp.pad(ratio, (0, pr))
        shift = jnp.pad(shift, (0, pr))
    out = lazy_enet_rows_kernel(
        wp, gp, ratio, shift, eta,
        block_rows=block_rows, block_cols=block_cols, interpret=interpret,
    )
    return out[:R, :D]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def enet_apply(
    w: jnp.ndarray,  # [n] flat or [R, D] row slab
    ratio: jnp.ndarray,  # broadcastable to w: per-element, per-row, or scalar
    shift: jnp.ndarray,  # same shape as ratio
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool | None = None,
):
    """Gradient-free shrink apply ``sgn(w)*max(|w|*ratio - shift, 0)`` with
    pre-computed factors, shape-preserving.  Layouts:

    * ``w`` [R, D] with factors [R] / [R, 1]: per-row tiles (flush of an
      embedding-table slab — one catch-up window per row).
    * ``w`` [n] with factors [n]: per-element — the linear trainer's flat
      weight vector; both are reshaped to lane-aligned tiles.
    * scalar factors broadcast over either layout.
    """
    if interpret is None:
        interpret = default_interpret()
    if w.ndim == 2:
        R, D = w.shape
        wp = _pad_to(w, block_rows, block_cols)
        if jnp.ndim(ratio) == 0:
            ratio = jnp.broadcast_to(ratio, (R,))
            shift = jnp.broadcast_to(shift, (R,))
        if ratio.shape in ((R,), (R, 1)):
            pr = wp.shape[0] - R
            rr, ss = ratio.reshape(R), shift.reshape(R)
            if pr:
                rr, ss = jnp.pad(rr, (0, pr)), jnp.pad(ss, (0, pr))
        else:  # per-element factors over the slab
            assert ratio.shape == (R, D), (ratio.shape, w.shape)
            rr = _pad_to(ratio, block_rows, block_cols)
            ss = _pad_to(shift, block_rows, block_cols)
        out = enet_apply_rows_kernel(
            wp, rr, ss, block_rows=block_rows, block_cols=block_cols, interpret=interpret
        )
        return out[:R, :D]
    assert w.ndim == 1, w.shape
    n = w.shape[0]
    ratio = jnp.broadcast_to(ratio, (n,))
    shift = jnp.broadcast_to(shift, (n,))
    w2 = _tile_flat(w, block_rows, block_cols)
    r2 = _tile_flat(ratio, block_rows, block_cols)
    s2 = _tile_flat(shift, block_rows, block_cols)
    out = enet_apply_rows_kernel(
        w2, r2, s2, block_rows=block_rows, block_cols=block_cols, interpret=interpret
    )
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def catchup_update(
    w: jnp.ndarray,  # [n] flat or [R, D] row slab
    psi: jnp.ndarray,  # [n] / [R] / [R, 1] int32 last-touch, or scalar
    k: jnp.ndarray,  # scalar int32 current step
    caches: RegCaches,
    lam1,  # dynamic f32 (may be traced per-config)
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool | None = None,
):
    """Pure catch-up (no gradient step): derive per-entry (ratio, shift) from
    the DP caches and apply the shrink in one pass — the kernel form of
    ``repro.core.lazy_enet.catchup``."""
    ratio, shift = catchup_factors(psi, k, caches, lam1)
    return enet_apply(
        w, ratio, shift, block_rows=block_rows, block_cols=block_cols, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def ftrl_read(
    z: jnp.ndarray,  # [n] flat FTRL accumulators
    n: jnp.ndarray,  # [n] flat AdaGrad sums
    alpha,  # dynamic f32 scalars (may be traced per-config)
    beta,
    lam1,
    lam2,
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool | None = None,
):
    """Apply-at-read FTRL-Proximal weights from flat ``(z, n)`` state —
    the solver's elastic-net closed form, shape-preserving."""
    if interpret is None:
        interpret = default_interpret()
    assert z.ndim == 1 and z.shape == n.shape, (z.shape, n.shape)
    cnt = z.shape[0]
    z2 = _tile_flat(z, block_rows, block_cols)
    n2 = _tile_flat(n, block_rows, block_cols)
    out = ftrl_read_rows_kernel(
        z2, n2, alpha, beta, lam1, lam2,
        block_rows=block_rows, block_cols=block_cols, interpret=interpret,
    )
    return out.reshape(-1)[:cnt]


@functools.partial(jax.jit, static_argnames=("block_tiles", "interpret"))
def ftrl_flush(
    wpsi: jnp.ndarray,  # [d, 3] packed (w, z, n) state
    alpha,  # dynamic f32 scalars (may be traced per-config)
    beta,
    lam1,
    lam2,
    *,
    block_tiles: int = 512,
    interpret: bool | None = None,
):
    """The FTRL round flush: ``wpsi`` with its w column rebuilt from each
    row's own ``(z, n)`` (the arithmetic of :func:`ftrl_read`), z and n
    unchanged.  ``[d, 3]`` is viewed as its own (3, 128) tiles, a bitcast of
    the chip's layout; a ragged ``d`` is zero-padded to whole tiles first
    (zero rows read 0) and cut back after."""
    if interpret is None:
        interpret = default_interpret()
    d = wpsi.shape[0]
    assert wpsi.shape == (d, 3), wpsi.shape
    tiles = _pad_to(wpsi, 128, 1).reshape(-1, 128, 3).transpose(0, 2, 1)
    out = ftrl_flush_tiles_kernel(
        tiles, alpha, beta, lam1, lam2, block_tiles=block_tiles, interpret=interpret
    )
    return out.transpose(0, 2, 1).reshape(-1, 3)[:d]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def ftrl_update(
    w: jnp.ndarray,  # [n] flat current (read) weights
    n: jnp.ndarray,  # [n] flat AdaGrad sums
    g: jnp.ndarray,  # [n] flat loss gradients
    alpha,  # dynamic f32 scalar (may be traced per-config)
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool | None = None,
):
    """Per-coordinate AdaGrad FTRL update deltas ``(dz, dn)`` — the caller
    scatter-ADDs them so duplicate indices keep additive semantics in XLA."""
    if interpret is None:
        interpret = default_interpret()
    assert w.ndim == 1 and w.shape == n.shape == g.shape, (w.shape, n.shape, g.shape)
    cnt = w.shape[0]
    w2 = _tile_flat(w, block_rows, block_cols)
    n2 = _tile_flat(n, block_rows, block_cols)
    g2 = _tile_flat(g, block_rows, block_cols)
    dz, dn = ftrl_update_rows_kernel(
        w2, n2, g2, alpha,
        block_rows=block_rows, block_cols=block_cols, interpret=interpret,
    )
    return dz.reshape(-1)[:cnt], dn.reshape(-1)[:cnt]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def enet_prox(
    w: jnp.ndarray,  # any shape; flattened internally
    a: jnp.ndarray,  # scalar multiplicative decay
    s: jnp.ndarray,  # scalar l1 shift
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool | None = None,
):
    """Dense elastic-net shrink sweep, shape-preserving."""
    if interpret is None:
        interpret = default_interpret()
    shape = w.shape
    flat = w.reshape(-1)
    n = flat.shape[0]
    w2 = _tile_flat(flat, block_rows, block_cols)
    out = enet_prox_kernel(
        w2, a, s,
        block_rows=block_rows, block_cols=block_cols, interpret=interpret,
    )
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def dp_margin(
    w: jnp.ndarray,  # [B, p] gathered weights
    ratio: jnp.ndarray,  # [B, p] per-element catch-up factors
    shift: jnp.ndarray,  # [B, p]
    val: jnp.ndarray,  # [B, p] routing-masked feature values
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool | None = None,
):
    """Shard-local pre-psum half of the fused DP step (dist.linear):
    catch-up + margin contributions in one elementwise pass.  Padding is
    safe (w = val = 0 -> 0 outputs).  Returns ``(w_cur, contrib)`` [B, p]."""
    if interpret is None:
        interpret = default_interpret()
    B, p = w.shape
    w_cur, contrib = dp_margin_rows_kernel(
        _pad_to(w, block_rows, block_cols), _pad_to(ratio, block_rows, block_cols),
        _pad_to(shift, block_rows, block_cols), _pad_to(val, block_rows, block_cols),
        block_rows=block_rows, block_cols=block_cols, interpret=interpret,
    )
    return w_cur[:B, :p], contrib[:B, :p]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def ftrl_margin(
    z: jnp.ndarray,  # [B, p] gathered FTRL accumulators
    n: jnp.ndarray,  # [B, p] gathered AdaGrad sums
    val: jnp.ndarray,  # [B, p] routing-masked feature values
    alpha,  # dynamic f32 scalars (may be traced per-config)
    beta,
    lam1,
    lam2,
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool | None = None,
):
    """Shard-local pre-psum half of the fused FTRL step: apply-at-read +
    margin contributions.  Returns ``(w_cur, contrib)`` [B, p]."""
    if interpret is None:
        interpret = default_interpret()
    B, p = z.shape
    w_cur, contrib = ftrl_margin_rows_kernel(
        _pad_to(z, block_rows, block_cols), _pad_to(n, block_rows, block_cols),
        _pad_to(val, block_rows, block_cols), alpha, beta, lam1, lam2,
        block_rows=block_rows, block_cols=block_cols, interpret=interpret,
    )
    return w_cur[:B, :p], contrib[:B, :p]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def screen_mask(
    g: jnp.ndarray,  # [n] flat unpenalized loss gradient
    w: jnp.ndarray,  # [n] flat previous-stage weights
    thr,  # dynamic f32 strong-rule bound (may be traced per-stage)
    chk,  # dynamic f32 KKT tolerance bound
    *,
    block_rows: int = 8,
    block_cols: int = 256,
    interpret: bool | None = None,
):
    """Fused strong-rule + KKT screening pass (repro.paths): returns 0/1 f32
    masks ``(active, viol)`` where ``active = (|g| >= thr) | (w != 0)`` and
    ``viol = ~active & (|g| > chk)``.  Comparisons only — exactly equal to
    the reference twin, never merely close."""
    if interpret is None:
        interpret = default_interpret()
    assert g.ndim == 1 and g.shape == w.shape, (g.shape, w.shape)
    cnt = g.shape[0]
    g2 = _tile_flat(g, block_rows, block_cols)
    w2 = _tile_flat(w, block_rows, block_cols)
    active, viol = screen_rows_kernel(
        g2, w2, thr, chk,
        block_rows=block_rows, block_cols=block_cols, interpret=interpret,
    )
    return active.reshape(-1)[:cnt], viol.reshape(-1)[:cnt]


def _pad_step_slab(x: jnp.ndarray, Bp: int, P: int) -> jnp.ndarray:
    B, p = x.shape
    if Bp != B or P != p:
        x = jnp.pad(x, ((0, Bp - B), (0, P - p)))
    return x


def _step_dims(B: int, p: int, block_rows: int):
    """Pad example rows to the sublane multiple and the feature axis to a
    full 128-lane-aligned width (the fused kernels reduce over it, so it
    must be one resident tile)."""
    return -(-B // block_rows) * block_rows, max(128, -(-p // 128) * 128)


@functools.partial(jax.jit, static_argnames=("loss", "use_bias", "block_rows", "interpret"))
def dp_fused_step(
    w: jnp.ndarray,  # [B, p] gathered weights
    ratio: jnp.ndarray,  # [B, p] per-element catch-up factors
    shift: jnp.ndarray,  # [B, p]
    val: jnp.ndarray,  # [B, p] feature values
    y: jnp.ndarray,  # [B] labels
    b,  # dynamic f32 bias (may be traced per-config)
    eta,  # dynamic f32 learning rate
    *,
    loss: str,
    use_bias: bool,
    block_rows: int = 8,
    interpret: bool | None = None,
):
    """Fused whole step for the cache-based solvers: catch-up + predict +
    loss gradient + update delta in one tile pass.  Padding is safe: padded
    feature columns (w = val = 0) contribute exactly 0 everywhere, and
    padded example rows are sliced off here.  Returns
    ``(w_cur [B, p], delta [B, p], gz [B], loss [B])``."""
    if interpret is None:
        interpret = default_interpret()
    B, p = w.shape
    Bp, P = _step_dims(B, p, block_rows)
    y2 = jnp.pad(y.reshape(B, 1).astype(jnp.float32), ((0, Bp - B), (0, 0)))
    w_cur, delta, gz, loss_v = dp_fused_step_kernel(
        _pad_step_slab(w, Bp, P), _pad_step_slab(ratio, Bp, P),
        _pad_step_slab(shift, Bp, P), _pad_step_slab(val, Bp, P), y2, b, eta,
        loss=loss, use_bias=use_bias, block_rows=block_rows, interpret=interpret,
    )
    return w_cur[:B, :p], delta[:B, :p], gz[:B, 0], loss_v[:B, 0]


@functools.partial(jax.jit, static_argnames=("loss", "use_bias", "block_rows", "interpret"))
def ftrl_fused_step(
    z: jnp.ndarray,  # [B, p] gathered FTRL accumulators
    n: jnp.ndarray,  # [B, p] gathered AdaGrad sums
    val: jnp.ndarray,  # [B, p] feature values
    y: jnp.ndarray,  # [B] labels
    b,  # dynamic f32 scalars (may be traced per-config)
    alpha,
    beta,
    lam1,
    lam2,
    *,
    loss: str,
    use_bias: bool,
    block_rows: int = 8,
    interpret: bool | None = None,
):
    """Fused whole step for FTRL-Proximal: apply-at-read + predict + loss
    gradient + AdaGrad deltas in one tile pass.  Padded columns carry
    z = n = val = 0 and produce w_cur = dz = dn = 0 exactly.  Returns
    ``(w_cur [B, p], dz [B, p], dn [B, p], gz [B], loss [B])``."""
    if interpret is None:
        interpret = default_interpret()
    B, p = z.shape
    Bp, P = _step_dims(B, p, block_rows)
    y2 = jnp.pad(y.reshape(B, 1).astype(jnp.float32), ((0, Bp - B), (0, 0)))
    w_cur, dz, dn, gz, loss_v = ftrl_fused_step_kernel(
        _pad_step_slab(z, Bp, P), _pad_step_slab(n, Bp, P),
        _pad_step_slab(val, Bp, P), y2, b, alpha, beta, lam1, lam2,
        loss=loss, use_bias=use_bias, block_rows=block_rows, interpret=interpret,
    )
    return w_cur[:B, :p], dz[:B, :p], dn[:B, :p], gz[:B, 0], loss_v[:B, 0]
