"""Correctness of the vmap-batched sweep against the single-config trainer.

The load-bearing property: a batch-of-1 sweep is BITWISE equal to a plain
`core.make_round_fn` lazy fit — same weights, same bias, same per-step
losses — across regularizer flavors (l1 / l2^2 / elastic net), SGD and
FoBoS, schedules, and losses.  This holds because both paths run the same
`make_lazy_step_hp` arithmetic (the single-config step closes over concrete
hypers, the batched step maps over traced ones) and vmap only adds a batch
dimension to the same gather/scatter chain.
"""

import jax
import numpy as np
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp
from repro._testing import assert_f32_close
from repro.core import (
    FOBOS,
    SGD,
    LinearConfig,
    ScheduleConfig,
    SparseBatch,
    init_state,
    make_round_fn,
    mean_loss,
)
from repro.sweeps import (
    batched_current_weights,
    make_batched_eval,
    make_grid,
    run_grid,
    run_sequential,
)

DIM = 41


def _mk_rounds(rng, n_rounds, R, B, p, dim=DIM, unique=False):
    """``unique=True`` draws collision-free indices within each step: the
    scatter-add over duplicate indices is the one place XLA may reassociate
    float adds differently under vmap, and the bitwise property is about the
    trainer's arithmetic, not scatter ordering (duplicates are covered by
    the allclose grid-vs-sequential test)."""
    out = []
    for _ in range(n_rounds):
        if unique:
            idx = np.stack(
                [rng.choice(dim, size=B * p, replace=False).reshape(B, p) for _ in range(R)]
            ).astype(np.int32)
        else:
            idx = rng.randint(0, dim, size=(R, B, p)).astype(np.int32)
        val = rng.uniform(-2.0, 2.0, size=(R, B, p)).astype(np.float32)
        val = (val * (rng.uniform(size=val.shape) > 0.3)).astype(np.float32)
        y = (rng.uniform(size=(R, B)) > 0.5).astype(np.float32)
        out.append(SparseBatch(idx=jnp.asarray(idx), val=jnp.asarray(val), y=jnp.asarray(y)))
    return out


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    flavor=st.sampled_from([SGD, FOBOS]),
    lam1=st.floats(0.0, 0.3),
    lam2=st.floats(0.0, 0.3),
    eta0=st.floats(0.05, 0.8),
    kind=st.sampled_from(["constant", "inv_t", "inv_sqrt"]),
    loss=st.sampled_from(["logistic", "squared"]),
)
def test_batch_of_one_bitwise_equals_plain_fit(seed, flavor, lam1, lam2, eta0, kind, loss):
    rng = np.random.RandomState(seed)
    base = LinearConfig(
        dim=DIM,
        loss=loss,
        flavor=flavor,
        lam1=lam1,
        lam2=lam2,
        round_len=6,
        schedule=ScheduleConfig(kind=kind, eta0=eta0),
    )
    rounds = _mk_rounds(rng, 2, base.round_len, 2, 3, unique=True)
    grid = make_grid(base, (lam1,), (lam2,), (eta0,))  # explicit ladders may hold 0.0
    bstate, blosses = run_grid(grid, rounds)

    round_fn = make_round_fn(grid.config_at(0), "lazy")
    state = init_state(grid.config_at(0))
    losses = []
    for rb in rounds:
        state, ls = round_fn(state, rb)
        losses.append(np.asarray(ls))
    losses = np.concatenate(losses)

    # the batched and plain programs fuse differently under this XLA
    assert_f32_close(np.asarray(bstate.wpsi[0]), np.asarray(state.wpsi))
    np.testing.assert_array_max_ulp(np.asarray(bstate.b)[0], np.asarray(state.b), maxulp=2)
    np.testing.assert_array_max_ulp(blosses[0], losses, maxulp=2)


def test_grid_matches_sequential_fits():
    """Every lane of a 12-point batched grid tracks its own sequential fit
    (tight tolerance: identical math, different fusion/batching order)."""
    rng = np.random.RandomState(7)
    base = LinearConfig(
        dim=DIM,
        flavor=FOBOS,
        round_len=8,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=0.4),
    )
    grid = make_grid(base, (0.1, 0.01, 0.001), (0.05, 0.0), (0.2, 0.5))
    rounds = _mk_rounds(rng, 3, base.round_len, 2, 4)
    bstate, blosses = run_grid(grid, rounds)
    w_seq, l_seq = run_sequential(grid, rounds)
    np.testing.assert_allclose(np.asarray(bstate.wpsi[:, :, 0]), w_seq, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(blosses, l_seq, rtol=1e-5, atol=1e-7)


def test_lanes_are_independent():
    """Adding a config lane must not change another lane's trajectory (no
    cross-lane leakage through the shared scan/flush)."""
    rng = np.random.RandomState(11)
    base = LinearConfig(
        dim=DIM,
        flavor=SGD,
        round_len=8,
        schedule=ScheduleConfig(kind="constant", eta0=0.3),
    )
    rounds = _mk_rounds(rng, 2, base.round_len, 2, 3)
    small = make_grid(base, (0.1,), (0.01,))
    big = make_grid(base, (0.1, 0.007), (0.01,))
    bs_small, _ = run_grid(small, rounds)
    bs_big, _ = run_grid(big, rounds)
    np.testing.assert_array_equal(np.asarray(bs_small.wpsi[0]), np.asarray(bs_big.wpsi[0]))


def test_batched_eval_matches_mean_loss():
    rng = np.random.RandomState(13)
    base = LinearConfig(
        dim=DIM,
        flavor=FOBOS,
        round_len=8,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=0.4),
    )
    grid = make_grid(base, (0.1, 0.001), (0.01,), (0.3, 0.6))
    rounds = _mk_rounds(rng, 2, base.round_len, 2, 4)
    bstate, _ = run_grid(grid, rounds)
    held_out = jax.tree.map(lambda a: a[0], _mk_rounds(rng, 1, 1, 16, 4)[0])
    hp = grid.hypers()
    batched = np.asarray(make_batched_eval(base)(bstate, hp, held_out))
    w_all = np.asarray(batched_current_weights(base, bstate, hp))
    for c in range(grid.n_cfg):
        cfg = grid.config_at(c)
        state = init_state(cfg, w0=w_all[c])._replace(b=bstate.b[c])
        ref = float(mean_loss(cfg, state, held_out))
        np.testing.assert_allclose(batched[c], ref, rtol=1e-6, atol=1e-7)
