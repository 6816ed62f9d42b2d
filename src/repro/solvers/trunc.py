"""Truncated gradient (Langford, Li & Zhang 2009) with K-step lazy
truncation.

The online truncated-gradient update leaves weights alone for K-1 steps and
then, at every K-th step, shrinks every coordinate toward zero by the
accumulated l1 gravity ``K * eta_t * lam1`` (the amortized form of the
paper's ``g*K*eta`` with gravity ``g = lam1``; we take ``theta = inf``, the
standard choice that makes the truncation a pure soft-threshold).  An
optional l2^2 term decays magnitudes multiplicatively *every* step, exactly
like the SGD flavor (``a_t = 1 - eta_t*lam2``).

Closed-form multi-step shrink (DESIGN.md §12): for a weight absent over
round-local steps ``[psi, i)``, the missed updates compose to

    |w|' = [ |w| * prod a_tau  -  lam1 * sum_{boundaries b} K*eta_b *
             prod_{tau > b} a_tau ]_+

— the same ``(ratio, shift)`` affine-then-clip form as the paper's Thm 1,
with the B cache accumulating ``K * eta_b * exp(-logP[b+1])`` **only at
boundary steps** instead of every step.  The single outer clip is exact for
the same reason as SGD/FoBoS (the unclipped recursion is monotone in |w|
and 0 is absorbing), so the entire DP-cache engine — ``catchup_rows``,
``flush_rows``, the fused kernels — is reused unchanged; only the O(1)
cache extension differs.  With ``K = 1`` this IS the SGD flavor.

Truncation boundaries are round-local (``(i+1) % K == 0``), so
``round_len % K == 0`` is required for boundaries to stay aligned across
round rebases — validated eagerly.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import dp_caches
from repro.core.dp_caches import SGD, RegCaches
from repro.core.schedules import validate_schedule

from .dp import LazyCacheSolver


class TruncSolver(LazyCacheSolver):
    name = "trunc"

    def k_period(self, cfg) -> int:
        return cfg.trunc_k

    def validate(self, cfg) -> None:
        k = cfg.trunc_k
        if k < 1:
            raise ValueError(f"trunc solver needs trunc_k >= 1, got {k}")
        if cfg.round_len % k:
            raise ValueError(
                f"trunc solver needs round_len % trunc_k == 0 (boundaries are "
                f"round-local), got round_len={cfg.round_len}, trunc_k={k}"
            )
        # the l2^2 decay is SGD-form (a = 1 - eta*lam2), so the same
        # divergence constraint applies
        validate_schedule(cfg.schedule.make(), cfg.lam2, SGD, horizon=10_000_000)

    def extend_caches(self, caches, i, eta, lam2, *, k_period: int = 0):
        assert k_period >= 1, k_period
        la = dp_caches.log_a(eta, lam2, SGD)
        logP_i = caches.logP[i]
        logP_next = logP_i + la
        # l1 gravity fires only at K-step boundaries; the shift at a boundary
        # step is multiplied by the a's of steps after it (decay-then-shrink
        # within the step), exactly the SGD-flavor weighting
        boundary = ((i + 1) % k_period) == 0
        b_inc = jnp.where(boundary, k_period * eta * jnp.exp(-logP_next), 0.0)
        return RegCaches(
            logP=caches.logP.at[i + 1].set(logP_next),
            B=caches.B.at[i + 1].set(caches.B[i] + b_inc),
            S=caches.S.at[i + 1].set(caches.S[i] + eta),
        )

    def touch_spans(self, cfg, state, idx_f: jnp.ndarray) -> jnp.ndarray:
        # debt = truncation boundaries missed over [psi, i): boundaries are
        # the steps tau with (tau+1) % K == 0, and the count of those below
        # x is x // K — so spans are i//K - psi//K (0 between boundaries)
        psi = state.wpsi[idx_f, 1].astype(jnp.int32)
        k = cfg.trunc_k
        return state.i // k - psi // k

    def dense_reg(self, cfg, wpsi, eta, t, bk) -> jnp.ndarray:
        # per-step l2^2 decay (lam1=0 makes prox_sweep a pure decay) ...
        wpsi = bk.prox_sweep(wpsi, eta, 0.0, cfg.lam2, SGD)
        # ... then the K-step truncation, gated on the global step (the dense
        # baseline never rebases, and round_len % K == 0 keeps global and
        # round-local boundaries congruent)
        boundary = ((t + 1) % cfg.trunc_k) == 0
        shift = jnp.where(boundary, cfg.trunc_k * eta * cfg.lam1, 0.0)
        return bk.trunc_shrink(wpsi, shift)
