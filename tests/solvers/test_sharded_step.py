"""The feature-sharded step (``Solver.sharded_update``) on a one-device
mesh matches the single-device step.

``LinearConfig(mesh=1)`` runs the same ``shard_map`` program as a
multi-device mesh — routing, shard-local margin op, margin psum, gradient
and write-back — in this process, on one device.  Two rounds of each
solver x backend x schedule fit with and without the mesh: bitwise on the
pallas backend, and within 1e-6 on the reference backend, where XLA may
fuse the unsharded one-pass step and the sharded margin chain differently
(the multi-device pins in tests/dist differ from the unsharded fit by one
f32 ulp for the same reason).
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp
from repro.core import LinearConfig, ScheduleConfig, SparseBatch, init_state, make_round_fn

DIM = 61  # odd: the mesh pads the state's rows
R, B, P = 8, 2, 3

SCHEDULES = {
    "constant": ScheduleConfig(kind="constant", eta0=0.1),
    "inv_sqrt": ScheduleConfig(kind="inv_sqrt", eta0=0.3, t0=100.0),
}


def _rounds(n_rounds=2):
    rng = np.random.RandomState(0)
    return [
        SparseBatch(
            idx=jnp.asarray(rng.randint(0, DIM, size=(R, B, P)), jnp.int32),
            val=jnp.asarray(rng.uniform(-2.0, 2.0, size=(R, B, P)), jnp.float32),
            y=jnp.asarray(rng.uniform(size=(R, B)) > 0.5, jnp.float32),
        )
        for _ in range(n_rounds)
    ]


def _fit(cfg, rounds):
    round_fn = make_round_fn(cfg, "lazy")
    state = init_state(cfg)
    losses = []
    for rb in rounds:
        state, step_losses = round_fn(state, rb)
        losses.append(np.asarray(step_losses))
    return np.concatenate(losses), np.asarray(state.wpsi)[:DIM], np.asarray(state.b)


@pytest.mark.parametrize("sched", sorted(SCHEDULES))
@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("solver", ["sgd", "fobos", "trunc", "ftrl"])
def test_one_device_mesh_matches_unsharded(solver, backend, sched):
    cfg = LinearConfig(
        dim=DIM,
        solver=solver,
        backend=backend,
        lam1=1e-3,
        lam2=1e-4,
        round_len=R,
        trunc_k=4,
        schedule=SCHEDULES[sched],
    )
    rounds = _rounds()
    want = _fit(cfg, rounds)
    got = _fit(dataclasses.replace(cfg, mesh=1), rounds)
    for g, w, name in zip(got, want, ("losses", "wpsi", "b")):
        if backend == "pallas":
            np.testing.assert_array_equal(g, w, err_msg=name)
        else:
            np.testing.assert_allclose(g, w, rtol=0, atol=1e-6, err_msg=name)
