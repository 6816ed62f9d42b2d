"""Fused whole-step solver kernels vs the plain reference step.

The acceptance property for the solvers' one-pass step: an end-to-end fit
+ flush + predict through ``core.make_round_fn`` matches a round scan of
``repro._testing.reference_step`` (the step as a chain of separate backend
ops) for every solver x backend x schedule x loss — BITWISE on the
reference backend (the fused reference op is the same jnp arithmetic, only
regrouped into shapes XLA computes identically) and to <= 1e-5 on the
pallas backend (interpret mode on CPU; tile-local f32 accumulation may
differ in the last ulps).

The vmapped sweep runner goes through the same ``make_lazy_step_hp`` body,
so a grid fit must also agree bitwise on reference with the reference step
under the same vmap, lane by lane.
"""
import jax
import numpy as np
import pytest

import jax.numpy as jnp
from repro._testing.reference_step import reference_round
from repro.core import (
    LinearConfig,
    ScheduleConfig,
    SparseBatch,
    init_state,
    make_round_fn,
    predict_proba_sparse,
)
from repro.sweeps import log_ladder, make_grid, run_grid
from repro.sweeps.batched_trainer import HYPER_AXES, STATE_AXES, init_batched_state

DIM = 64
ROUND_LEN = 8
B, P = 2, 3

SCHEDULES = {
    "constant": ScheduleConfig(kind="constant", eta0=0.1),
    "inv_sqrt": ScheduleConfig(kind="inv_sqrt", eta0=0.3, t0=100.0),
}


def _cfg(solver, backend, sched, loss="logistic"):
    return LinearConfig(
        dim=DIM,
        solver=solver,
        loss=loss,
        lam1=1e-3,
        lam2=1e-4,
        round_len=ROUND_LEN,
        trunc_k=4,
        schedule=SCHEDULES[sched],
        backend=backend,
    )


def _mk_rounds(rng, n_rounds):
    out = []
    for _ in range(n_rounds):
        idx = rng.randint(0, DIM, size=(ROUND_LEN, B, P)).astype(np.int32)
        val = rng.uniform(-2.0, 2.0, size=(ROUND_LEN, B, P)).astype(np.float32)
        y = (rng.uniform(size=(ROUND_LEN, B)) > 0.5).astype(np.float32)
        out.append(SparseBatch(idx=jnp.asarray(idx), val=jnp.asarray(val), y=jnp.asarray(y)))
    return out


def _fit(cfg, round_fn, rounds, test_batch):
    state = init_state(cfg)
    losses = []
    for rb in rounds:
        state, step_losses = round_fn(state, rb)
        losses.append(np.asarray(step_losses))
    proba = np.asarray(predict_proba_sparse(cfg, state, test_batch))
    return (
        np.concatenate(losses),
        np.asarray(state.wpsi),
        np.asarray(state.b),
        proba,
    )


@pytest.mark.parametrize("loss", ["logistic", "squared"])
@pytest.mark.parametrize("sched", sorted(SCHEDULES))
@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("solver", ["sgd", "fobos", "trunc", "ftrl"])
def test_round_matches_reference_step_end_to_end(solver, backend, sched, loss, rng):
    rounds = _mk_rounds(rng, 2)
    test_batch = SparseBatch(
        idx=jnp.asarray(rng.randint(0, DIM, size=(4, P)).astype(np.int32)),
        val=jnp.asarray(rng.uniform(-2.0, 2.0, size=(4, P)).astype(np.float32)),
        y=jnp.asarray((rng.uniform(size=4) > 0.5).astype(np.float32)),
    )
    cfg = _cfg(solver, backend, sched, loss)
    got = _fit(cfg, make_round_fn(cfg, "lazy"), rounds, test_batch)
    ref_round, hp = reference_round(cfg), cfg.hypers()  # hypers as trace constants
    want = _fit(cfg, jax.jit(lambda state, rb: ref_round(state, hp, rb)), rounds, test_batch)
    for g, w, name in zip(got, want, ("losses", "wpsi", "b", "proba")):
        if backend == "reference":
            np.testing.assert_array_equal(g, w, err_msg=name)
        else:
            np.testing.assert_allclose(g, w, rtol=0, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("solver", ["fobos", "ftrl"])
def test_vmapped_grid_matches_reference_step(solver, rng):
    """The batched sweep runner threads the same solver.touched_update body
    under vmap; each lane must agree bitwise on the reference backend with
    the reference step under the same vmap."""
    rounds = _mk_rounds(rng, 2)
    grid = make_grid(
        _cfg(solver, "reference", "inv_sqrt"),
        log_ladder(1e-3, 1e-5, 2),
        log_ladder(1e-4, 1e-6, 2),
    )
    st, losses = run_grid(grid, rounds)

    hp = grid.hypers()
    ref = jax.jit(
        jax.vmap(
            reference_round(grid.base),
            in_axes=(STATE_AXES, HYPER_AXES, None),
            out_axes=(STATE_AXES, 0),
        )
    )
    st_ref = init_batched_state(grid.base, grid.n_cfg, hp=hp)
    losses_ref = []
    for rb in rounds:
        st_ref, ls = ref(st_ref, hp, rb)
        losses_ref.append(np.asarray(ls))
    losses_ref = np.concatenate(losses_ref, axis=1)
    for lane in range(grid.n_cfg):
        np.testing.assert_array_equal(np.asarray(losses[lane]), losses_ref[lane])
        np.testing.assert_array_equal(np.asarray(st.wpsi[lane]), np.asarray(st_ref.wpsi[lane]))
        np.testing.assert_array_equal(np.asarray(st.b[lane]), np.asarray(st_ref.b[lane]))


@pytest.mark.parametrize("solver", ["fobos", "ftrl"])
def test_round_zero_recompiles(solver, rng):
    """The fused round program compiles once; steady-state rounds must hold
    the compile budget (obs.CompileTracker — the same invariant serving and
    the warm-started sweep path assert)."""
    from repro.obs import CompileTracker, cache_size

    cfg = _cfg(solver, "reference", "inv_sqrt")
    round_fn = make_round_fn(cfg, "lazy")
    rounds = _mk_rounds(rng, 4)
    state = init_state(cfg)
    state, _ = round_fn(state, rounds[0])  # warmup: the one compile
    assert cache_size(round_fn) == 1
    tracker = CompileTracker({"round": round_fn})
    with tracker.assert_no_new_compiles(f"fused {solver} steady state"):
        for rb in rounds[1:]:
            state, _ = round_fn(state, rb)
