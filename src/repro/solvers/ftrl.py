"""FTRL-Proximal with per-coordinate AdaGrad learning rates and closed-form
elastic net applied **at read** (McMahan et al., KDD 2013 — the solver
F10-SGD benchmarks elastic-net linear models against).

Per-coordinate state (packed ``wpsi[:, :3]`` = ``(w, z, n)``):

  ``z`` — the FTRL linearized-loss accumulator,
  ``n`` — the AdaGrad sum of squared gradients,
  ``w`` — a *materialized cache* of the weight, refreshed at flush; every
          read derives the weight from ``(z, n)`` directly.

Weight read (the elastic-net proximal step in closed form):

  w = 0                                          if |z| <= lam1
      (sgn(z)*lam1 - z) / ((beta + sqrt(n))/alpha + lam2)   otherwise

Touched-coordinate update with per-example gradient g:

  sigma = (sqrt(n + g^2) - sqrt(n)) / alpha      # per-coordinate rate delta
  z    += g - sigma * w
  n    += g^2

This solver is *naturally lazy*: regularization is applied at read, so an
absent coordinate owes nothing when it returns — **no shared DP catch-up
cache exists** (``caches_based = False``; the LinearState caches ride along
untouched).  There is consequently no eta*lam2 schedule constraint to
validate (the satellite fix: core.schedules' SGD divergence check must not
reject FTRL), and no meaningful dense per-step baseline (``has_dense =
False``; the eager reference lives in tests/solvers).

Hyper mapping: ``hp.eta_scale`` is FTRL's ``alpha`` (the per-coordinate
rate scale — a sweep's eta0 ladder sweeps alpha), ``cfg.ftrl_beta`` is
``beta``; ``lam1``/``lam2`` are the elastic-net strengths, all dynamic
(traced per-config under the sweeps vmap).  The *bias* has a dense
gradient (every example touches it), so it takes a plain SGD step with the
global-schedule ``eta`` — documented, and mirrored by the test reference.

Duplicate features in one batch scatter-ADD their ``(dz, dn)`` deltas, each
computed against the pre-update ``(w, n)`` — per-example AdaGrad
accumulation, the same additive-duplicate convention as the DP solvers'
gradient scatter.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import phases

from .api import Solver


def gather_touched(wpsi: jnp.ndarray, idx_f: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(z, n)`` of the touched features ``idx_f`` ([B*p] each), from one
    gather of their ``(w, z, n)`` rows."""
    g3 = wpsi[idx_f]
    return g3[:, 1], g3[:, 2]


def write_back(wpsi, idx_f, dz, dn) -> jnp.ndarray:
    """Scatter-ADD the ``(dz, dn)`` deltas (duplicates accumulate).  The w
    column stays stale: reads always derive from ``(z, n)``, and the flush
    rematerializes it."""
    wpsi = wpsi.at[idx_f, 1].add(dz)
    return wpsi.at[idx_f, 2].add(dn)


class FTRLSolver(Solver):
    name = "ftrl"
    state_cols = 3
    caches_based = False
    has_dense = False

    def validate(self, cfg) -> None:
        if cfg.ftrl_beta <= 0.0:
            raise ValueError(f"ftrl needs beta > 0, got {cfg.ftrl_beta}")
        if cfg.schedule.eta0 <= 0.0:
            raise ValueError(f"ftrl needs alpha (= schedule.eta0) > 0, got {cfg.schedule.eta0}")
        # deliberately NO eta*lam2 constraint: regularization is applied at
        # read, never as a multiplicative per-step factor

    def touch_spans(self, cfg, state, idx_f: jnp.ndarray) -> jnp.ndarray:
        # apply-at-read: an absent coordinate owes nothing when it returns,
        # so the catch-up debt is identically zero (obs histograms land in
        # bucket 0 — itself a useful signature of the solver family)
        return jnp.zeros(idx_f.shape, jnp.int32)

    def seed_cols(self, cfg, w0, hp) -> jnp.ndarray:
        """Invert the read at ``n = 0`` so a freshly-seeded state reads back
        exactly ``w0`` (warm starts / swap_weights).  Shape-polymorphic:
        ``w0`` may be ``[d]`` or ``[n_cfg, d]`` with ``hp`` fields scalars
        or per-config ``[n_cfg]`` lanes."""
        w0 = jnp.asarray(w0, jnp.float32)

        def bc(x):  # right-pad hp lanes to broadcast against w0
            x = jnp.asarray(x, jnp.float32)
            return x.reshape(x.shape + (1,) * (w0.ndim - x.ndim))

        # reciprocal-of-alpha form, matching ftrl_read's arithmetic (keeps
        # constant vs traced hypers bitwise — see ReferenceBackend.ftrl_read)
        denom = cfg.ftrl_beta * (1.0 / bc(hp.eta_scale)) + bc(hp.lam2)
        z = -w0 * denom - jnp.sign(w0) * bc(hp.lam1)
        return jnp.stack([w0, z, jnp.zeros_like(w0)], axis=-1)

    def init_cols(self, cfg, w0: Optional[jnp.ndarray]) -> jnp.ndarray:
        if w0 is None:
            return jnp.zeros((cfg.dim, 3), jnp.float32)
        return self.seed_cols(cfg, w0, cfg.hypers())

    def touched_update(self, cfg, state, batch, hp, eta, bk) -> Tuple[object, jnp.ndarray]:
        from repro.core import linear_trainer as lt

        alpha = jnp.asarray(hp.eta_scale, jnp.float32)
        with jax.named_scope(phases.GATHER):
            idx_f = batch.idx.reshape(-1)
            z_g, n_g = gather_touched(state.wpsi, idx_f)
            shape = batch.idx.shape
        with jax.named_scope(phases.KERNEL):
            # ONE whole-step tile pass: apply-at-read weights, predict,
            # loss gradient, AdaGrad deltas (backend.ftrl_fused_step)
            _, dz2, dn2, gz, loss = bk.ftrl_fused_step(
                z_g.reshape(shape),
                n_g.reshape(shape),
                batch.val,
                batch.y,
                state.b,
                alpha,
                cfg.ftrl_beta,
                hp.lam1,
                hp.lam2,
                loss=cfg.loss,
                use_bias=cfg.use_bias,
            )
            dz, dn = dz2.reshape(-1), dn2.reshape(-1)
        with jax.named_scope(phases.SCATTER):
            wpsi = write_back(state.wpsi, idx_f, dz, dn)
            b = state.b - eta * jnp.sum(gz) if cfg.use_bias else state.b
        new = lt.LinearState(wpsi=wpsi, b=b, caches=state.caches, i=state.i + 1, t=state.t + 1)
        return new, jnp.mean(loss)

    def sharded_update(self, cfg, state, batch, hp, eta, bk, axis) -> Tuple[object, jnp.ndarray]:
        """touched_update over this shard's (z, n) row slab (see
        Solver.sharded_update): apply-at-read weights from the LOCAL rows,
        one margin psum, then the same per-coordinate AdaGrad deltas —
        sentinel lanes see g = 0 (so dz = dn = 0) and scatter out of bounds
        (dropped) anyway."""
        from repro.core import linear_trainer as lt
        from repro.dist import linear as dl

        alpha = jnp.asarray(hp.eta_scale, jnp.float32)
        with jax.named_scope(phases.GATHER):
            idx_f = batch.idx.reshape(-1)
            # clip-gather; sentinel rows are masked by their zero values
            z_g, n_g = gather_touched(state.wpsi, idx_f)
            shape = batch.idx.shape
        with jax.named_scope(phases.KERNEL):
            w_cur2, contrib = bk.ftrl_margin(
                z_g.reshape(shape),
                n_g.reshape(shape),
                batch.val,
                alpha,
                cfg.ftrl_beta,
                hp.lam1,
                hp.lam2,
            )
            w_cur = w_cur2.reshape(-1)
            # --- the ONLY cross-shard traffic: the per-example margin ---
            zlin = dl.margin_psum(cfg, contrib)
            if cfg.use_bias:
                zlin = zlin + state.b
            loss, gz = lt._grad_z(cfg, zlin, batch.y)
            g_w = (gz[:, None] * batch.val).reshape(-1)  # masked: 0 off-shard
            dz, dn = bk.ftrl_update(w_cur, n_g, g_w, alpha)
        with jax.named_scope(phases.SCATTER):
            wpsi = write_back(state.wpsi, idx_f, dz, dn)
            b = state.b - eta * jnp.sum(gz) if cfg.use_bias else state.b
        new = lt.LinearState(wpsi=wpsi, b=b, caches=state.caches, i=state.i + 1, t=state.t + 1)
        return new, jnp.mean(loss)

    def read_rows(self, cfg, rows, state, hp, bk) -> jnp.ndarray:
        return bk.ftrl_read(
            rows[:, 1], rows[:, 2],
            jnp.asarray(hp.eta_scale, jnp.float32), cfg.ftrl_beta, hp.lam1, hp.lam2,
        )

    def read_weights(self, cfg, state, hp, bk) -> jnp.ndarray:
        return bk.ftrl_read(
            state.wpsi[:, 1], state.wpsi[:, 2],
            jnp.asarray(hp.eta_scale, jnp.float32), cfg.ftrl_beta, hp.lam1, hp.lam2,
        )

    def flush(self, cfg, state, hp, bk):
        """No caches to rebase — flushing just rematerializes the w column
        (so raw ``weights()`` views and warm-start seeding read current
        values) and reopens the round counter.  ``bk.ftrl_flush`` rebuilds
        the ``[d, 3]`` state (one vmapped lane, or a shard's slab) in one
        pass over its own layout."""
        from repro.core import linear_trainer as lt

        wpsi = bk.ftrl_flush(
            state.wpsi,
            jnp.asarray(hp.eta_scale, jnp.float32), cfg.ftrl_beta, hp.lam1, hp.lam2,
        )
        return lt.LinearState(
            wpsi=wpsi, b=state.b, caches=state.caches, i=jnp.zeros_like(state.i), t=state.t
        )
