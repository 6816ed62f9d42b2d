"""End-to-end backend parity: a full lazy fit — round flush included — plus
sparse serving predictions must agree between ``backend="pallas"``
(interpret mode on this CPU container) and ``backend="reference"`` across
flavors, losses, and schedule kinds; and the reference backend must keep the
pre-backend arithmetic BITWISE (the sweeps batch-of-1 property)."""
import dataclasses

import numpy as np
import pytest
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
    make_lazy_step,
    make_round_fn,
    predict_proba_sparse,
)
from repro.core import linear_trainer as lt
from repro.serving import LinearService, ServiceConfig
from repro.sweeps import make_grid, run_grid

DIM = 96


def _mk_round(rng, R, B, p, dim=DIM):
    idx = rng.randint(0, dim, size=(R, B, p)).astype(np.int32)
    val = rng.uniform(-2.0, 2.0, size=(R, B, p)).astype(np.float32)
    y = (rng.uniform(size=(R, B)) > 0.5).astype(np.float32)
    return SparseBatch(idx=jnp.asarray(idx), val=jnp.asarray(val), y=jnp.asarray(y))


def _fit(cfg: LinearConfig, rounds, tail: SparseBatch):
    """One full round (scan + boundary flush) per entry of ``rounds``, then a
    half-round of single steps so the final state holds a *pending* catch-up
    window — predict_proba_sparse must bring it current on the fly."""
    round_fn = make_round_fn(cfg, "lazy")
    state = init_state(cfg)
    losses = []
    for rb in rounds:
        state, ls = round_fn(state, rb)
        losses.append(np.asarray(ls))
    step = make_lazy_step(cfg)
    for r in range(tail.idx.shape[0]):
        state, loss = step(state, SparseBatch(tail.idx[r], tail.val[r], tail.y[r]))
        losses.append(np.asarray(loss)[None])
    return state, np.concatenate(losses)


@pytest.mark.parametrize("flavor", [SGD, FOBOS])
@pytest.mark.parametrize("loss", ["logistic", "squared"])
@pytest.mark.parametrize("kind", ["constant", "inv_sqrt"])
def test_full_fit_flush_predict_parity(flavor, loss, kind, rng):
    base = dict(
        dim=DIM,
        loss=loss,
        flavor=flavor,
        lam1=3e-3,
        lam2=1e-3,
        round_len=12,
        schedule=ScheduleConfig(kind=kind, eta0=0.3),
    )
    rounds = [_mk_round(rng, 12, 3, 5) for _ in range(2)]
    tail = _mk_round(rng, 6, 3, 5)
    eval_batch = SparseBatch(
        idx=jnp.asarray(rng.randint(0, DIM, size=(8, 5)).astype(np.int32)),
        val=jnp.asarray(rng.uniform(-2, 2, size=(8, 5)).astype(np.float32)),
        y=jnp.asarray(np.zeros(8, np.float32)),
    )

    cfg_ref = LinearConfig(backend="reference", **base)
    cfg_pal = LinearConfig(backend="pallas", **base)
    s_ref, l_ref = _fit(cfg_ref, rounds, tail)
    s_pal, l_pal = _fit(cfg_pal, rounds, tail)

    np.testing.assert_allclose(
        np.asarray(lt.current_weights(cfg_pal, s_pal)),
        np.asarray(lt.current_weights(cfg_ref, s_ref)),
        rtol=1e-5,
        atol=1e-6,
    )
    np.testing.assert_allclose(np.asarray(s_pal.b), np.asarray(s_ref.b), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(l_pal, l_ref, rtol=1e-5, atol=1e-6)
    # O(p) serving predictions against the mid-round (stale-psi) state
    p_ref = np.asarray(predict_proba_sparse(cfg_ref, s_ref, eval_batch))
    p_pal = np.asarray(predict_proba_sparse(cfg_pal, s_pal, eval_batch))
    np.testing.assert_allclose(p_pal, p_ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("flavor", [SGD, FOBOS])
def test_dense_baseline_parity(flavor, rng):
    base = dict(dim=DIM, flavor=flavor, lam1=2e-3, lam2=1e-3, round_len=16)
    rb = _mk_round(rng, 16, 3, 5)
    out = {}
    for name in ("reference", "pallas"):
        cfg = LinearConfig(backend=name, **base)
        round_fn = make_round_fn(cfg, "dense")
        state, losses = round_fn(init_state(cfg, mode="dense"), rb)
        out[name] = (np.asarray(state.wpsi[:, 0]), np.asarray(losses))
    np.testing.assert_allclose(out["pallas"][0], out["reference"][0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out["pallas"][1], out["reference"][1], rtol=1e-5, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    flavor=st.sampled_from([SGD, FOBOS]),
    lam1=st.floats(0.0, 0.3),
    lam2=st.floats(0.0, 0.3),
    kind=st.sampled_from(["constant", "inv_sqrt"]),
)
def test_reference_backend_keeps_sweep_bitwise(seed, flavor, lam1, lam2, kind):
    """The guarantee the refactor must not break: under the explicit
    reference backend, a batch-of-1 vmapped sweep stays BITWISE equal to the
    plain single-config fit (collision-free indices, as in tests/sweeps)."""
    rng = np.random.RandomState(seed)
    base = LinearConfig(
        dim=DIM,
        flavor=flavor,
        lam1=lam1,
        lam2=lam2,
        round_len=5,
        schedule=ScheduleConfig(kind=kind, eta0=0.4),
        backend="reference",
    )
    R, B, p = base.round_len, 2, 3
    idx = np.stack(
        [rng.choice(DIM, size=B * p, replace=False).reshape(B, p) for _ in range(R)]
    ).astype(np.int32)
    val = rng.uniform(-2, 2, size=(R, B, p)).astype(np.float32)
    y = (rng.uniform(size=(R, B)) > 0.5).astype(np.float32)
    rounds = [SparseBatch(idx=jnp.asarray(idx), val=jnp.asarray(val), y=jnp.asarray(y))]

    grid = make_grid(base, (lam1,), (lam2,), (base.schedule.eta0,))
    bstate, blosses = run_grid(grid, rounds)

    round_fn = make_round_fn(grid.config_at(0), "lazy")
    state, losses = round_fn(init_state(grid.config_at(0)), rounds[0])

    # the batched and plain programs fuse differently under this XLA
    assert_f32_close(np.asarray(bstate.wpsi[0]), np.asarray(state.wpsi))
    np.testing.assert_array_max_ulp(np.asarray(bstate.b)[0], np.asarray(state.b), maxulp=2)
    np.testing.assert_array_max_ulp(blosses[0], np.asarray(losses), maxulp=2)


def test_vmapped_sweep_runs_on_pallas(rng):
    """Traced per-config lam1/lam2 must flow through the Pallas kernels under
    vmap (dynamic hyper operands — satellite: no static lam1): a 2-point grid
    trains and stays close to the same grid on the reference backend."""
    base = dict(
        dim=DIM,
        flavor=FOBOS,
        lam1=1e-3,
        lam2=1e-4,
        round_len=8,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=0.3),
    )
    rounds = [_mk_round(rng, 8, 2, 4)]
    out = {}
    for name in ("reference", "pallas"):
        grid = make_grid(LinearConfig(backend=name, **base), (1e-2, 1e-4), (1e-3,), (0.3,))
        bstate, _ = run_grid(grid, rounds)
        out[name] = np.asarray(bstate.wpsi[:, :, 0])
    np.testing.assert_allclose(out["pallas"], out["reference"], rtol=1e-5, atol=1e-6)


def test_linear_service_compile_counts_backend_independent(rng):
    """Zero new recompiles under the non-default backend: the jit cache
    profile after identical traffic must be identical — backend choice is
    trace-static, never a jit argument."""
    counts = {}
    for name in ("reference", "pallas"):
        cfg = LinearConfig(dim=DIM, round_len=8, lam1=1e-3, lam2=1e-4)
        svc = LinearService(cfg, ServiceConfig(p_max=8, micro_batch=4, backend=name))
        assert svc.cfg.backend == name  # pinned via dataclasses.replace
        r = np.random.RandomState(0)
        for t in range(12):
            svc.submit_learn(r.randint(0, DIM, 5), r.uniform(-1, 1, 5), float(t % 2), arrival=0.0)
            svc.poll(now=1.0, force=True)
        svc.predict(
            SparseBatch(
                idx=r.randint(0, DIM, size=(3, 6)).astype(np.int32),
                val=r.uniform(-1, 1, size=(3, 6)).astype(np.float32),
                y=np.zeros(3, np.float32),
            )
        )
        counts[name] = svc.compile_counts()
    assert counts["pallas"] == counts["reference"], counts


def test_swap_weights_preserves_backend(rng):
    cfg = LinearConfig(dim=DIM, round_len=8, backend="pallas")
    svc = LinearService(cfg, ServiceConfig(p_max=8, micro_batch=4))
    svc.swap_weights(np.zeros(DIM, np.float32), cfg=dataclasses.replace(cfg, lam1=5e-4))
    assert svc.cfg.backend == "pallas"


@pytest.mark.parametrize("dim", [1030, 9 * 128])
def test_ftrl_flush_kernel_matches_reference(dim, rng):
    """The Pallas flush over the state's (3, 128) tiles, with a ragged dim
    padded to whole tiles and a last block of tiles cut short, against the
    reference backend's elementwise flush."""
    from repro import backend as kb
    from repro.kernels import ops

    wpsi = np.stack(
        [rng.normal(size=dim), rng.normal(scale=2.0, size=dim), rng.uniform(0.0, 4.0, size=dim)],
        axis=1,
    ).astype(np.float32)
    hypers = (jnp.float32(0.1), 1.0, 0.5, 0.25)  # alpha, beta, lam1, lam2
    want = np.asarray(kb.get_backend("reference").ftrl_flush(jnp.asarray(wpsi), *hypers))
    got = np.asarray(ops.ftrl_flush(jnp.asarray(wpsi), *hypers, block_tiles=4))
    np.testing.assert_array_equal(got[:, 1:], wpsi[:, 1:])
    np.testing.assert_allclose(got[:, 0], want[:, 0], rtol=1e-6, atol=0)
    assert np.sum(got[:, 0] == 0.0) > 0 and np.sum(got[:, 0] != 0.0) > 0
