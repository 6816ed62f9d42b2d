"""Feature-sharded lazy linear training (repro.dist.linear): shard-count
invariance is the whole contract — a mesh=N fit must match the unsharded
fit bitwise on the reference backend (exact column-aligned margin mode) and
to float tolerance on pallas, for every solver and both schedules.

Multi-device cases run in subprocesses (tests/dist/conftest.py); the
host-side router and the device-count guard run in the parent.
"""
import numpy as np
import pytest

PARITY = r"""
import numpy as np
import jax.numpy as jnp

from repro.core import linear_trainer as lt
from repro.dist import linear as dl

DIM = 97  # odd: every mesh size pads rows, so padding inertness is exercised
R, B, p = 8, 4, 6
rng = np.random.default_rng(0)


def make_batches(rounds=3):
    out = []
    for _ in range(rounds):
        idx = rng.integers(0, DIM, size=(R, B, p)).astype(np.int32)
        val = rng.normal(size=(R, B, p)).astype(np.float32)
        y = (rng.random(size=(R, B)) < 0.5).astype(np.float32)
        out.append(lt.SparseBatch(jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y)))
    return out


BATCHES = make_batches()


def fit(cfg):
    state = lt.init_state(cfg)
    rf = lt.make_round_fn(cfg, "lazy")
    losses = []
    for b in BATCHES:
        state, ls = rf(state, b)
        losses.append(np.asarray(ls))
    return state, np.concatenate(losses)


def run(solver, backend="reference"):
    base = dict(dim=DIM, round_len=R, solver=solver, backend=backend,
                lam1=0.01, lam2=0.005, trunc_k=4)
    cfg0 = lt.LinearConfig(**base)
    s0, l0 = fit(cfg0)
    w0 = np.asarray(lt.current_weights(cfg0, s0))
    for mesh in (1, 2, 4):
        cfgM = lt.LinearConfig(**base, mesh=mesh)
        sM, lM = fit(cfgM)
        wM = np.asarray(lt.current_weights(cfgM, sM))
        if backend == "reference":
            assert np.array_equal(w0, wM), (solver, mesh, np.abs(w0 - wM).max())
            assert np.array_equal(l0, lM), (solver, mesh)
            assert np.array_equal(np.asarray(s0.b), np.asarray(sM.b)), (solver, mesh)
        else:
            err = max(np.abs(w0 - wM).max(), np.abs(l0 - lM).max())
            assert err <= 1e-5, (solver, mesh, err)
        pb = lt.SparseBatch(BATCHES[0].idx[0], BATCHES[0].val[0], BATCHES[0].y[0])
        p0 = np.asarray(lt.predict_proba_sparse(cfg0, s0, pb))
        pM = np.asarray(lt.predict_proba_sparse(cfgM, sM, pb))
        tol = 0.0 if backend == "reference" else 1e-6
        assert np.abs(p0 - pM).max() <= tol, (solver, mesh, np.abs(p0 - pM).max())
    print(f"OK {solver} {backend}")


# every solver, bitwise on the reference backend
for solver in ("sgd", "fobos", "trunc", "ftrl"):
    run(solver)
# pallas kernels: one cache-based + the apply-at-read solver, float tolerance
run("fobos", backend="pallas")
run("ftrl", backend="pallas")

# margin modes: partial (order change only) and quantized (lossy compress)
for margin, tol in (("partial", 1e-5), ("quantized", 5e-2)):
    cfg0 = lt.LinearConfig(dim=DIM, round_len=R, solver="fobos", lam1=0.01, lam2=0.005)
    s0, _ = fit(cfg0)
    cfgM = lt.LinearConfig(dim=DIM, round_len=R, solver="fobos", lam1=0.01,
                           lam2=0.005, mesh=4, shard_margin=margin)
    sM, _ = fit(cfgM)
    err = np.abs(np.asarray(lt.current_weights(cfg0, s0))
                 - np.asarray(lt.current_weights(cfgM, sM))).max()
    assert err <= tol, (margin, err)
    print(f"OK margin={margin} err={err:.2e}")

# routed rounds (host-compacted per-shard blocks) == in-graph routing exactly
cfgP = lt.LinearConfig(dim=DIM, round_len=R, solver="fobos", lam1=0.01, lam2=0.005,
                       mesh=4, shard_margin="partial")
sP, lP = fit(cfgP)
rrf = dl.make_routed_round_fn(cfgP)
sR = lt.init_state(cfgP)
lR = []
for b in BATCHES:
    oi, ov, y = dl.route_round(cfgP, b, q=p)
    oi, ov, y = dl.place_routed(cfgP, oi, ov, y)
    sR, ls = rrf(sR, oi, ov, y)
    lR.append(np.asarray(ls))
wP = np.asarray(lt.current_weights(cfgP, sP))
wR = np.asarray(lt.current_weights(cfgP, sR))
assert np.array_equal(wP, wR) and np.array_equal(lP, np.concatenate(lR).reshape(lP.shape))
print("OK routed")
"""


def test_sharded_fit_matches_unsharded(subproc):
    """mesh={1,2,4} fits are bitwise-identical to the single-device fit on
    the reference backend (all four solvers), <=1e-5 on pallas; margin
    modes and host-routed rounds ride in the same process."""
    out = subproc(PARITY, n_devices=4)
    assert out.count("OK ") >= 9


COLUMNS = r"""
import jax
import numpy as np
import jax.numpy as jnp

from repro._testing import assert_column_access, state_accesses
from repro.core import linear_trainer as lt
from repro.dist import linear as dl

R, B, p = 4, 2, 3
rng = np.random.default_rng(0)
batches = lt.SparseBatch(
    jnp.asarray(rng.integers(0, 97, size=(R, B, p)).astype(np.int32)),
    jnp.asarray(rng.normal(size=(R, B, p)).astype(np.float32)),
    jnp.asarray((rng.random(size=(R, B)) < 0.5).astype(np.float32)),
)
for solver in ("sgd", "fobos", "trunc"):
    cfg = lt.LinearConfig(dim=97, round_len=R, solver=solver,
                          lam1=0.01, lam2=0.005, trunc_k=2, mesh=4)
    _, ds, _ = dl.shard_info(cfg)
    jaxpr = jax.make_jaxpr(lt.make_round_fn(cfg, "lazy"))(lt.init_state(cfg), batches)
    assert_column_access(state_accesses(jaxpr.jaxpr, ds, 2), B * p, 2)
    print(f"OK {solver}")
"""


def test_sharded_step_touches_the_state_by_column(subproc):
    """sharded_update gathers and writes back the shard's (w, psi) rows one
    column at a time, as touched_update does (tests/solvers)."""
    out = subproc(COLUMNS, n_devices=4)
    assert out.count("OK ") == 3


def _cfg4(**kw):
    from repro.core import linear_trainer as lt

    kw.setdefault("dim", 97)
    kw.setdefault("round_len", 8)
    kw.setdefault("lam1", 0.01)
    kw.setdefault("lam2", 0.005)
    kw.setdefault("mesh", 4)
    return lt.LinearConfig(**kw)


def test_route_round_host_compaction():
    """route_round is pure host numpy: every owned (example, feature) lands
    on its owning shard at the local index, sentinel-padded to q, values
    zeroed elsewhere — so re-expanding the blocks recovers the batch."""
    from repro.core.linear_trainer import SparseBatch
    from repro.dist import linear as dl

    cfg = _cfg4()
    n, ds, _ = dl.shard_info(cfg)
    rng = np.random.default_rng(3)
    idx = rng.integers(0, cfg.dim, size=(2, 3, 5)).astype(np.int32)
    val = rng.normal(size=(2, 3, 5)).astype(np.float32)
    y = np.zeros((2, 3), np.float32)
    oi, ov, oy = dl.route_round(cfg, SparseBatch(idx, val, y), q=5)
    assert oi.shape == (n, 2, 3, 5) and ov.shape == (n, 2, 3, 5)
    assert np.array_equal(oy, y)
    # sentinel slots carry zero value; owned slots are in-range local rows
    assert np.all(ov[oi == ds] == 0.0)
    assert np.all((oi >= 0) & (oi <= ds))
    # scatter-expand back to the global space and compare per-example sums
    dense = np.zeros((2, 3, cfg.dim), np.float32)
    for r in range(2):
        for b in range(3):
            np.add.at(dense[r, b], idx[r, b], val[r, b])
    re = np.zeros_like(dense)
    for k in range(n):
        for r in range(2):
            for b in range(3):
                owned = oi[k, r, b] < ds
                gl = oi[k, r, b][owned] + k * ds
                np.add.at(re[r, b], gl, ov[k, r, b][owned])
    np.testing.assert_allclose(re, dense, rtol=0, atol=0)


def test_route_round_overflow_raises():
    """An example concentrating more than q features on one shard is a
    routing error, not silent truncation."""
    from repro.core.linear_trainer import SparseBatch
    from repro.dist import linear as dl

    cfg = _cfg4()
    idx = np.zeros((1, 1, 6), np.int32)  # six features, all on shard 0
    val = np.ones((1, 1, 6), np.float32)
    y = np.zeros((1, 1), np.float32)
    with pytest.raises(ValueError, match="overflow"):
        dl.route_round(cfg, SparseBatch(idx, val, y), q=4)


def test_feature_mesh_needs_devices():
    """mesh=N on a single-device host fails loudly with the XLA_FLAGS
    incantation in the message (the parent pytest process has one device)."""
    from repro.dist import linear as dl

    with pytest.raises(RuntimeError, match="xla_force_host_platform_device_count"):
        dl.feature_mesh(_cfg4(mesh=4))


def test_shard_info_padding():
    from repro.dist import linear as dl

    n, ds, d_pad = dl.shard_info(_cfg4(dim=97, mesh=4))
    assert (n, ds, d_pad) == (4, 25, 100)
    n, ds, d_pad = dl.shard_info(_cfg4(dim=96, mesh=4))
    assert (n, ds, d_pad) == (4, 24, 96)


def test_mesh_rejects_dense_mode():
    """The dense round fn has no sharded path — only the lazy O(p) trainer
    shards; asking for dense on a mesh is an immediate ValueError."""
    from repro.core import linear_trainer as lt

    with pytest.raises(ValueError, match="lazy"):
        lt.make_round_fn(_cfg4(), "dense")
