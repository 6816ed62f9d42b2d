"""Metrics-instrumented trainer factories: the lazy step / round scan with
a :class:`~repro.obs.metrics_state.MetricsState` riding the carry.

The instrumented step is a *wrapper*, not a fork: it calls the exact step
``core.make_lazy_step`` builds and accumulates its observations beside it
from values the program already carries (the pre-step solver state, the
batch, the returned loss).  Nothing feeds back into the update arithmetic,
so a metrics-on fit is bitwise-identical to metrics-off on the reference
backend and adds zero recompiles — both pinned by tests/obs.

Span observation dispatches through the solver
(:meth:`repro.solvers.api.Solver.touch_spans`): cache-based solvers report
how many round-local steps each touched row was behind (trunc: how many
truncation boundaries it missed); apply-at-read solvers owe nothing and
report zeros.

Layering note: this module imports core/solvers, never the reverse —
``core.make_round_fn(metrics=True)`` reaches here through a deferred
import, the same pattern core uses for backends and solvers.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import metrics_state
from .metrics_state import MetricsState, init_metrics


def _solver(cfg):
    from repro import solvers

    return solvers.for_config(cfg)


def make_obs_step_hp(cfg):
    """``step((state, mstate), batch, hp) -> ((state, mstate), loss)`` —
    the hyper-parameterized instrumented step (the form the batched sweep
    runner vmaps, mirroring ``core.make_lazy_step_hp``)."""
    from repro.core import linear_trainer as lt

    step_hp = lt.make_lazy_step_hp(cfg)
    solver = _solver(cfg)

    def ostep(carry, batch, hp):
        state, m = carry
        # observe BEFORE the step writes psi forward: the debt this step's
        # catch-up is about to pay
        spans = solver.touch_spans(cfg, state, batch.idx.reshape(-1))
        new_state, loss = step_hp(state, batch, hp)
        m = metrics_state.record_step(m, spans, batch, loss)
        return (new_state, m), loss

    return ostep


def make_obs_step(cfg):
    """Single-config instrumented step, hypers closed over as constants."""
    from repro.core import linear_trainer as lt

    lt._solver(cfg).validate(cfg)
    ostep_hp = make_obs_step_hp(cfg)
    hp = cfg.hypers()

    def ostep(carry, batch):
        return ostep_hp(carry, batch, hp)

    return ostep


def init_obs(cfg, w0=None) -> Tuple[object, MetricsState]:
    """(LinearState, MetricsState) pair the instrumented round fn carries."""
    from repro.core import linear_trainer as lt

    return lt.init_state(cfg, w0), init_metrics()


def make_obs_round_fn(cfg):
    """Instrumented twin of ``core.make_round_fn(cfg, "lazy")``: scans a
    round over the ``(LinearState, MetricsState)`` carry, flushes at the
    boundary, and records the flush + post-flush weight nnz."""
    from repro.core import linear_trainer as lt

    step = make_obs_step(cfg)

    @functools.partial(jax.jit, donate_argnums=0)
    def round_fn(carry, round_batches):
        carry, losses = jax.lax.scan(step, carry, round_batches)
        state, m = carry
        state = lt.flush(cfg, state)
        # post-flush, column 0 is current for every solver (cache-based
        # solvers rebase; apply-at-read solvers rematerialize w)
        m = metrics_state.record_flush(m, state.wpsi[:, 0])
        return (state, m), losses

    return round_fn


def metrics_axes() -> MetricsState:
    """vmap in/out axes for a config-batched MetricsState: every field
    grows a leading config lane (losses differ per config; touch counters
    are shared-data duplicates, kept per-lane for uniformity)."""
    return MetricsState(*([0] * len(MetricsState._fields)))


def init_batched_metrics(n_cfg: int) -> MetricsState:
    """Config-batched zero MetricsState ([n_cfg] leading axis per field)."""
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n_cfg,) + a.shape), init_metrics())


def pull_metrics(m: MetricsState, cfg, registry=None, logger=None, step: Optional[int] = None):
    """Device -> host: summarize a pulled MetricsState and fan it out to a
    registry (counters/gauges) and/or RunLogger (metrics event).  Returns
    the summary dict."""
    m = jax.tree.map(jax.device_get, m)
    summary = metrics_state.summarize(m, cfg.dim, solver=cfg.solver or cfg.flavor)
    if registry is not None:
        registry.pull(summary)
    if logger is not None:
        logger.metrics(summary, step=step)
    return summary
