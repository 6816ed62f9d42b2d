"""Cache-based delayed-regularization solvers: the paper's SGD and FoBoS
flavors, refactored out of ``core.linear_trainer`` onto the Solver
interface **bitwise-identically** (the step/flush/read bodies below are the
pre-refactor code, moved, with the touched state read and written per
column; tests/solvers pins this with an inline copy of the old closure).

The whole family shares one structure — the DP caches are the engine:

  touched step:  extend cache slot i+1, gather the touched (w, psi), replay the
                 missed regularization for tau in [psi, i) in closed form,
                 predict, scatter back (caught-up w, psi=i) + gradient.
  flush:         one (ratio, shift) pair per coordinate from the caches,
                 applied buffer-wide; caches rebase.

Subclasses only choose how slot ``i+1`` is filled (``extend_caches``):
SGD/FoBoS via :func:`repro.core.dp_caches.extend` (per-step elastic net,
Thm 1/2), truncated gradient via a boundary-gated B increment (trunc.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dp_caches, lazy_enet, phases
from repro.core.dp_caches import FOBOS, SGD
from repro.core.schedules import validate_schedule

from .api import Solver


# The touched (w, psi) state is read and written one column at a time: the
# chip keeps the packed [.., d, 2] state column-major, so a row access costs
# a relayout of the whole state into a layout padded to 128 lanes.
def gather_touched(wpsi: jnp.ndarray, idx_f: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(w, psi)`` of the touched features ``idx_f`` ([B*p] each)."""
    return wpsi[idx_f, 0], wpsi[idx_f, 1].astype(jnp.int32)


def write_back(wpsi, idx_f, w_cur, i, neg_eta_g) -> jnp.ndarray:
    """Set the caught-up ``w`` and ``psi = i`` (duplicates identical), then
    scatter-ADD the loss-gradient step (duplicates accumulate)."""
    psi_new = jnp.broadcast_to(i.astype(jnp.float32), w_cur.shape)
    wpsi = wpsi.at[idx_f, 0].set(w_cur)
    wpsi = wpsi.at[idx_f, 1].set(psi_new)
    return wpsi.at[idx_f, 0].add(neg_eta_g)


class LazyCacheSolver(Solver):
    """Shared machinery for solvers whose delayed updates replay against the
    round-local DP caches.  ``state_cols = 2``: packed ``(w, psi)``."""

    state_cols = 2
    caches_based = True
    has_dense = True

    # subclass hook: the truncation period (0 = regularize every step)
    def k_period(self, cfg) -> int:
        return 0

    def init_cols(self, cfg, w0: Optional[jnp.ndarray]) -> jnp.ndarray:
        wpsi = jnp.zeros((cfg.dim, 2), jnp.float32)
        if w0 is not None:
            wpsi = wpsi.at[:, 0].set(jnp.asarray(w0, jnp.float32))
        return wpsi

    def seed_cols(self, cfg, w0, hp) -> jnp.ndarray:
        w0 = jnp.asarray(w0, jnp.float32)
        return jnp.stack([w0, jnp.zeros_like(w0)], axis=-1)  # psi = 0: current

    def adopt_state(self, cfg, packed: jnp.ndarray) -> jnp.ndarray:
        # psi is round-local; a state adopted into a fresh round (empty
        # caches, i=0) must read its weights as current, so psi rebases to 0
        packed = jnp.asarray(packed, jnp.float32)
        return packed.at[..., 1].set(0.0)

    def touched_update(self, cfg, state, batch, hp, eta, bk) -> Tuple[object, jnp.ndarray]:
        from repro.core import linear_trainer as lt

        with jax.named_scope(phases.GATHER):
            # O(1): fill DP cache slot i+1 with this step's eta (Lemma 1 / Thm 1-2)
            caches = self.extend_caches(
                state.caches, state.i, eta, hp.lam2, k_period=self.k_period(cfg)
            )
            idx_f = batch.idx.reshape(-1)
            w_g, psi_g = gather_touched(state.wpsi, idx_f)
            shape = batch.idx.shape
            # (ratio, shift) from the caches in XLA — tiny O(B*p) gathers
            # + exps, and where a traced per-config lam1 enters
            ratio, shift = lazy_enet.catchup_factors(psi_g, state.i, caches, hp.lam1)
        with jax.named_scope(phases.KERNEL):
            # ONE whole-step tile pass: catch-up, predict, gradient,
            # update delta
            w_cur2, delta, gz, loss = bk.fused_step(
                w_g.reshape(shape),
                ratio.reshape(shape),
                jnp.broadcast_to(shift, ratio.shape).reshape(shape),
                batch.val,
                batch.y,
                state.b,
                eta,
                loss=cfg.loss,
                use_bias=cfg.use_bias,
            )
            w_cur = w_cur2.reshape(-1)
            neg_eta_g = delta.reshape(-1)  # [B*p]
        with jax.named_scope(phases.SCATTER):
            wpsi = write_back(state.wpsi, idx_f, w_cur, state.i, neg_eta_g)
            b = state.b - eta * jnp.sum(gz) if cfg.use_bias else state.b
        # reg for step i itself stays pending (applied at next touch / flush)
        new = lt.LinearState(wpsi=wpsi, b=b, caches=caches, i=state.i + 1, t=state.t + 1)
        return new, jnp.mean(loss)

    def sharded_update(self, cfg, state, batch, hp, eta, bk, axis) -> Tuple[object, jnp.ndarray]:
        """touched_update over this shard's row slab (see Solver.sharded_update
        for the routing contract).  Identical op sequence around the margin:
        extend the replicated caches, gather + catch up the LOCAL rows, one
        margin psum, then the same gradient scatter — sentinel lanes carry
        value 0 (contribute nothing) and scatter out of bounds (dropped)."""
        from repro.core import linear_trainer as lt
        from repro.dist import linear as dl

        with jax.named_scope(phases.GATHER):
            caches = self.extend_caches(
                state.caches, state.i, eta, hp.lam2, k_period=self.k_period(cfg)
            )
            idx_f = batch.idx.reshape(-1)
            # clip-gather; sentinel lanes are masked by their zero values
            w_g, psi_g = gather_touched(state.wpsi, idx_f)
            shape = batch.idx.shape
            ratio, shift = lazy_enet.catchup_factors(psi_g, state.i, caches, hp.lam1)
        with jax.named_scope(phases.KERNEL):
            # shard-local fused pass: catch-up + masked margin contributions
            w_cur2, contrib = bk.fused_margin(
                w_g.reshape(shape),
                ratio.reshape(shape),
                jnp.broadcast_to(shift, ratio.shape).reshape(shape),
                batch.val,
            )
            w_cur = w_cur2.reshape(-1)
            # --- the ONLY cross-shard traffic: the per-example margin ---
            z = dl.margin_psum(cfg, contrib)
            if cfg.use_bias:
                z = z + state.b
            loss, gz = lt._grad_z(cfg, z, batch.y)
            neg_eta_g = (-eta * (gz[:, None] * batch.val)).reshape(-1)  # [B*p]
        with jax.named_scope(phases.SCATTER):
            wpsi = write_back(state.wpsi, idx_f, w_cur, state.i, neg_eta_g)
            b = state.b - eta * jnp.sum(gz) if cfg.use_bias else state.b
        new = lt.LinearState(wpsi=wpsi, b=b, caches=caches, i=state.i + 1, t=state.t + 1)
        return new, jnp.mean(loss)

    def touch_spans(self, cfg, state, idx_f: jnp.ndarray) -> jnp.ndarray:
        # the debt touched_update replays: reg for tau in [psi, i)
        psi = state.wpsi[idx_f, 1].astype(jnp.int32)
        return state.i - psi

    def read_rows(self, cfg, rows, state, hp, bk) -> jnp.ndarray:
        return bk.catchup_rows(
            rows[:, 0], rows[:, 1].astype(jnp.int32), state.i, state.caches, hp.lam1
        )

    def read_weights(self, cfg, state, hp, bk) -> jnp.ndarray:
        from repro.core import linear_trainer as lt

        ratio, shift = lazy_enet.catchup_factors(lt.psi(state), state.i, state.caches, hp.lam1)
        return bk.flush_rows(lt.weights(state), ratio, shift)

    def flush(self, cfg, state, hp, bk):
        from repro.core import linear_trainer as lt

        w = self.read_weights(cfg, state, hp, bk)
        wpsi = jnp.stack([w, jnp.zeros_like(w)], axis=1)
        return lt.LinearState(
            wpsi=wpsi,
            b=state.b,
            caches=dp_caches.init_caches(cfg.round_len),
            i=jnp.zeros_like(state.i),
            t=state.t,
        )


class DPSolver(LazyCacheSolver):
    """The paper's two flavors (Eq 9 / §6.2) as registry entries: per-step
    elastic net, delayed via :func:`repro.core.dp_caches.extend`."""

    def __init__(self, flavor: str):
        assert flavor in (SGD, FOBOS), flavor
        self.name = flavor

    def validate(self, cfg) -> None:
        # the eta*lam2 < 1 divergence check is SGD-specific; FoBoS is
        # unconditionally valid (validate_schedule returns early for it)
        validate_schedule(cfg.schedule.make(), cfg.lam2, self.name, horizon=10_000_000)

    def extend_caches(self, caches, i, eta, lam2, *, k_period: int = 0):
        return dp_caches.extend(caches, i, eta, lam2, self.name)

    def dense_reg(self, cfg, wpsi, eta, t, bk) -> jnp.ndarray:
        # O(d): dense regularization sweep over EVERY coordinate (Eq 9 / §6.2)
        return bk.prox_sweep(wpsi, eta, cfg.lam1, cfg.lam2, self.name)
