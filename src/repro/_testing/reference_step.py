"""The lazy step as a chain of separate backend ops: the plain reference
that the solvers' one-pass steps are held to.

:func:`reference_update` is a per-step twin of ``Solver.touched_update``.
The cache-based solvers catch the touched weights up (``catchup_rows``),
predict, take the loss gradient and step by ``-eta * gz * val``; FTRL
reads its weights from ``(z, n)`` (``ftrl_read``), predicts, takes the
loss gradient and the AdaGrad deltas (``ftrl_update``).  The row gathers,
the write-backs and the round flush are the solvers' own, so a mismatch
points at the step's arithmetic.  On the reference backend the one-pass
step is the same jnp arithmetic regrouped, so the two agree bitwise.
"""

import jax
import jax.numpy as jnp

from repro import backend, solvers
from repro.core import linear_trainer as lt
from repro.solvers import dp, ftrl


def reference_update(cfg, state, batch, hp, eta, bk):
    """One lazy step of ``cfg``'s solver: ``(new state, mean loss)``."""
    solver = solvers.for_config(cfg)
    idx_f = batch.idx.reshape(-1)
    shape = batch.idx.shape
    if solver.caches_based:
        caches = solver.extend_caches(
            state.caches, state.i, eta, hp.lam2, k_period=solver.k_period(cfg)
        )
        w_g, psi_g = dp.gather_touched(state.wpsi, idx_f)
        # lazy catch-up of the touched weights: reg for tau in [psi, i)
        w_cur = bk.catchup_rows(w_g, psi_g, state.i, caches, hp.lam1)
        z = lt._predict_current(cfg, w_cur.reshape(shape), state.b, batch)
        loss, gz = lt._grad_z(cfg, z, batch.y)
        neg_eta_g = -eta * (gz[:, None] * batch.val).reshape(-1)  # [B*p]
        wpsi = dp.write_back(state.wpsi, idx_f, w_cur, state.i, neg_eta_g)
    else:
        caches = state.caches
        alpha = jnp.asarray(hp.eta_scale, jnp.float32)
        z_g, n_g = ftrl.gather_touched(state.wpsi, idx_f)
        # apply-at-read: current weights straight from (z, n), no catch-up
        w_cur = bk.ftrl_read(z_g, n_g, alpha, cfg.ftrl_beta, hp.lam1, hp.lam2)
        zlin = lt._predict_current(cfg, w_cur.reshape(shape), state.b, batch)
        loss, gz = lt._grad_z(cfg, zlin, batch.y)
        g_w = (gz[:, None] * batch.val).reshape(-1)  # [B*p]
        dz, dn = bk.ftrl_update(w_cur, n_g, g_w, alpha)
        wpsi = ftrl.write_back(state.wpsi, idx_f, dz, dn)
    b = state.b - eta * jnp.sum(gz) if cfg.use_bias else state.b
    new = lt.LinearState(wpsi=wpsi, b=b, caches=caches, i=state.i + 1, t=state.t + 1)
    return new, jnp.mean(loss)


def reference_round(cfg):
    """``round(state, hp, round_batches) -> (state, losses)``: a scan of
    :func:`reference_update` and then the round flush, with eta computed as
    the solver steps compute it.  Call it under ``jax.jit`` with ``hp``
    closed over for a single config (as ``core.make_round_fn`` does), or
    under ``jax.vmap`` over config lanes (as the sweeps do)."""
    unit_sched = cfg.schedule.unit().make()

    def step(state, batch, hp):
        bk = backend.resolve(cfg.backend)
        eta = jnp.asarray(hp.eta_scale, jnp.float32) * unit_sched(state.t)
        return reference_update(cfg, state, batch, hp, eta, bk)

    def round_fn(state, hp, round_batches):
        state, losses = jax.lax.scan(lambda s, rb: step(s, rb, hp), state, round_batches)
        return lt.flush(cfg, state, hp=hp), losses

    return round_fn
