"""Closed-form lazy (delayed) regularization updates — the paper's core.

``catchup(w, psi, k, caches, lam1)`` applies, in O(1) per weight, all the
regularization-only updates for round-local steps ``tau in [psi, k)`` that a
weight missed while its feature was absent.  It covers, via the lam choices:

  * lam1>0, lam2=0 : l1 / truncated gradient        (paper Eq 4)
  * lam1=0, lam2>0 : l2^2 ridge                     (paper Lemma 1, Eq 6 /
                                                     FoBoS Eq 15)
  * lam1>0, lam2>0 : elastic net                    (paper Thm 1 Eq 14 /
                                                     FoBoS Thm 2 Eq 16)

The SGD-vs-FoBoS distinction is entirely inside the caches (see
dp_caches.py); the catch-up expression is identical for both flavors.

Everything here is shape-polymorphic: ``w`` and ``psi`` may be any matching
shape (a scalar weight, a gathered [B, p] slab of linear-model weights, or
[rows, d_embed] embedding rows — the per-row generalization used by
repro.optim.lazy_rows, where one psi covers a whole row).
"""
from __future__ import annotations

import jax.numpy as jnp

from .dp_caches import RegCaches, concrete_zero


# below this, 1 + expm1 drifts past 1 ulp (its add rounds a second time)
EXPM1_FLOOR = -1.0 / 16


def exp_nonpos(x: jnp.ndarray) -> jnp.ndarray:
    """``exp(x)`` for ``x <= 0`` (a decay): ``1 + expm1(x)`` above
    ``EXPM1_FLOOR``, plain ``exp(x)`` below.

    A short window's decay is tiny (~eta*lam2 per step), and the TPU's f32
    exp of such arguments reads low (18 ulp on average on v5e, 3.1 ulp at
    most for 1 + expm1): a bias every touch of a hot feature repeats.  A
    window that decays a weight by more than 6% is rare for any one weight,
    so exp's bias there cannot pile up.  On the CPU both branches read
    within 1 ulp (tests/core/test_dp_caches.py)."""
    return jnp.where(x > EXPM1_FLOOR, 1.0 + jnp.expm1(x), jnp.exp(x))


def catchup_factors(psi: jnp.ndarray, k: jnp.ndarray, caches: RegCaches, lam1):
    """Per-entry multiplicative ``ratio`` and subtractive ``shift`` such that
    the lazy update is ``sgn(w) * relu(|w| * ratio - shift)``.

      ratio = exp(logP[k] - logP[psi])                (window product of a's)
      shift = lam1 * exp(logP[k]) * (B[k] - B[psi])   (collapsed lam1 shifts)

    both exps through :func:`exp_nonpos`.  ``lam1`` may be a traced scalar
    (per-config, under vmap); only a concrete 0 takes the no-l1 shortcut.
    """
    logP_k = caches.logP[k]
    logP_psi = caches.logP[psi]
    ratio = exp_nonpos(logP_k - logP_psi)
    if concrete_zero(lam1):
        shift = jnp.zeros_like(ratio)
    else:
        # Computed as exp(logP[k]) * (B[k]-B[psi]): with round-rebased caches
        # |logP| stays O(1) so there is no under/overflow (DESIGN.md §2).
        shift = lam1 * exp_nonpos(logP_k) * (caches.B[k] - caches.B[psi])
    return ratio, shift


def catchup(
    w: jnp.ndarray,
    psi: jnp.ndarray,
    k: jnp.ndarray,
    caches: RegCaches,
    lam1,
) -> jnp.ndarray:
    """Bring ``w`` current from per-entry round-local step ``psi`` to ``k``.

    Exactly equal (see tests) to applying the per-step dense regularization
    update (dense_enet.reg_update) for every step in [psi, k) — including the
    sign-restoring clip at zero, which needs to be applied only once because
    (a) the unclipped affine recursion is monotone increasing in |w| and
    (b) 0 is absorbing under regularization-only updates.
    """
    ratio, shift = catchup_factors(psi, k, caches, lam1)
    mag = jnp.abs(w) * ratio - shift
    return jnp.sign(w) * jnp.maximum(mag, 0.0)
