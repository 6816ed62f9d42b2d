"""Warm-started lam1-path continuation: the cold path is exactly an
independent grid fit, and warm starts must not lose to cold starts on the
training objective at equal step budget (continuation seeds each relaxation
inside the previous optimum's basin)."""

import numpy as np

from repro._testing import assert_f32_close
from repro.core import LinearConfig, ScheduleConfig
from repro.data import BowConfig, SyntheticBow
from repro.sweeps import log_ladder, make_grid, run_grid, run_path

DIM = 400


def _base(**kw):
    defaults = dict(
        dim=DIM,
        flavor="fobos",
        round_len=16,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=0.3, t0=50.0),
    )
    defaults.update(kw)
    return LinearConfig(**defaults)


def _bow_rounds(n_rounds, R, B, seed=5):
    bow = SyntheticBow(
        BowConfig(
            dim=DIM,
            p_max=16,
            p_mean=8.0,
            informative_pool=100,
            n_informative=32,
            seed=seed,
        )
    )
    return [bow.sample_round(r, R, B) for r in range(n_rounds)]


def test_cold_path_equals_grid_fit():
    """warm_start=False is stage-sliced independent fits — bitwise the same
    as one full-grid vmapped run (the stages just partition the config
    axis)."""
    base = _base()
    grid = make_grid(base, log_ladder(1e-2, 1e-4, 3), (1e-3, 1e-5), (0.2, 0.4))
    rounds = _bow_rounds(2, base.round_len, 2)
    cold = run_path(grid, rounds, warm_start=False)
    bstate, losses = run_grid(grid, rounds)
    # the batched and plain programs fuse differently under this XLA
    # (here bias and losses too: 3 and 4 ulps)
    assert_f32_close(cold.weights, np.asarray(bstate.wpsi[:, :, 0]))
    assert_f32_close(cold.b, np.asarray(bstate.b))
    assert_f32_close(cold.losses, losses)


def test_warm_start_beats_cold_start_on_lam1_path():
    """Equal per-stage step budget: warm-started stages must reach final
    training loss no worse than cold-started ones (averaged over the final
    round, beyond the first stage — stage 0 has no neighbor and is
    identical in both modes)."""
    base = _base()
    grid = make_grid(base, log_ladder(3e-2, 1e-5, 4), (1e-4,))
    rounds = _bow_rounds(2, base.round_len, 4)
    warm = run_path(grid, rounds, warm_start=True)
    cold = run_path(grid, rounds, warm_start=False)

    # stage 0 identical: no neighbor to chain from
    np.testing.assert_array_equal(warm.weights[:1], cold.weights[:1])

    r = base.round_len
    warm_tail = warm.losses[1:, -r:].mean(axis=1)
    cold_tail = cold.losses[1:, -r:].mean(axis=1)
    assert np.all(warm_tail <= cold_tail + 1e-3), (warm_tail, cold_tail)
    # and the chain must help somewhere, not merely tie everywhere
    assert np.any(warm_tail < cold_tail - 1e-3), (warm_tail, cold_tail)


def test_warm_start_first_step_loss_drops():
    """The warm-started stage opens near the neighbor's optimum: its FIRST
    step's loss beats the cold start's first step for every post-initial
    stage."""
    base = _base()
    grid = make_grid(base, log_ladder(3e-2, 1e-5, 4), (1e-4,))
    rounds = _bow_rounds(1, base.round_len, 4)
    warm = run_path(grid, rounds, warm_start=True)
    cold = run_path(grid, rounds, warm_start=False)
    assert np.all(warm.losses[1:, 0] < cold.losses[1:, 0]), (
        warm.losses[:, 0],
        cold.losses[:, 0],
    )


def test_single_stage_ladder_is_the_plain_grid_fit():
    """A one-point lam1 "ladder" has no continuation: run_path must run it
    as the plain batched grid fit (bitwise, warm or cold — the flags are
    vacuous) without building the continuation machinery."""
    base = _base()
    grid = make_grid(base, (1e-3,), (1e-3, 1e-5), (0.2, 0.4))
    rounds = _bow_rounds(2, base.round_len, 2)
    bstate, losses = run_grid(grid, rounds)
    want_w = np.asarray(bstate.wpsi[:, :, 0])
    for warm in (True, False):
        res = run_path(grid, rounds, warm_start=warm)
        np.testing.assert_array_equal(res.weights, want_w)
        np.testing.assert_array_equal(res.b, np.asarray(bstate.b))
        np.testing.assert_array_equal(res.losses, np.asarray(losses))


def test_single_stage_ladder_honors_caller_round_fn():
    """kfold_cv shares one jitted round program across folds; a single-stage
    grid must still route through it (and match the default path)."""
    from repro.sweeps import make_batched_round_fn

    base = _base()
    grid = make_grid(base, (1e-3,), (1e-3, 1e-5))
    rounds = _bow_rounds(2, base.round_len, 2)
    round_fn = make_batched_round_fn(base)
    res = run_path(grid, rounds, round_fn=round_fn)
    plain = run_path(grid, rounds)
    np.testing.assert_array_equal(res.weights, plain.weights)
    np.testing.assert_array_equal(res.b, plain.b)
    np.testing.assert_array_equal(res.losses, plain.losses)


def test_path_result_shapes():
    base = _base()
    grid = make_grid(base, log_ladder(1e-2, 1e-4, 3), (1e-3, 1e-5))
    rounds = _bow_rounds(2, base.round_len, 2)
    res = run_path(grid, rounds)
    assert res.weights.shape == (grid.n_cfg, DIM)
    assert res.b.shape == (grid.n_cfg,)
    assert res.losses.shape == (grid.n_cfg, 2 * base.round_len)
    assert np.all(np.isfinite(res.losses))
