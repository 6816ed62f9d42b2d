"""The cache-based solvers read and write the touched ``(w, psi)`` state one
column at a time.

The chip keeps the packed ``[.., d, 2]`` state column-major; a row gather or
a row write-back makes the compiler relayout the whole state, padded to 128
lanes, twice a step.  These cases trace the lazy step of each cache-based
solver, alone and vmapped over a config axis as the sweeps run it, and
check every gather from and scatter into the state.  The feature-sharded
step is checked in tests/dist/test_linear_sharded.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro._testing import assert_column_access, state_accesses
from repro.core import LinearConfig, SparseBatch, init_state
from repro.core import linear_trainer as lt
from repro.sweeps.batched_trainer import HYPER_AXES, STATE_AXES, init_batched_state

DIM, B, P, N_CFG = 61, 2, 3, 3


def _batch():
    rng = np.random.RandomState(0)
    return SparseBatch(
        idx=jnp.asarray(rng.randint(0, DIM, size=(B, P)), jnp.int32),
        val=jnp.asarray(rng.uniform(-1, 1, size=(B, P)), jnp.float32),
        y=jnp.asarray(rng.uniform(size=(B,)) > 0.5, jnp.float32),
    )


def _single(cfg):
    return lt.make_lazy_step_hp(cfg), (init_state(cfg), _batch(), cfg.hypers())


def _sweep(cfg):
    step = jax.vmap(lt.make_lazy_step_hp(cfg), in_axes=(STATE_AXES, None, HYPER_AXES))
    bhp = jax.tree.map(lambda x: jnp.full((N_CFG,), x, jnp.float32), cfg.hypers())
    return step, (init_batched_state(cfg, N_CFG), _batch(), bhp)


STEPS = {"single": _single, "sweep": _sweep}


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("solver", ["sgd", "fobos", "trunc"])
@pytest.mark.parametrize("step", sorted(STEPS))
def test_lazy_step_touches_the_state_by_column(step, solver, backend):
    cfg = LinearConfig(
        dim=DIM, solver=solver, backend=backend, round_len=4, lam1=1e-3, lam2=1e-4, trunc_k=2
    )
    fn, args = STEPS[step](cfg)
    accesses = state_accesses(jax.make_jaxpr(fn)(*args).jaxpr, DIM, 2)
    assert_column_access(accesses, B * P, args[0].wpsi.ndim)
