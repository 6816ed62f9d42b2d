"""FTRL-Proximal solver correctness: the jitted scan/scatter implementation
(and both kernel backends) against a straightforward eager NumPy reference
of McMahan et al.'s per-coordinate update, across losses x schedules; plus
the apply-at-read algebra (seed inversion, sparsity thresholding)."""
import numpy as np
import pytest

import jax.numpy as jnp
from repro.core import (
    LinearConfig,
    ScheduleConfig,
    SparseBatch,
    init_state,
    make_round_fn,
    predict_proba_sparse,
)
from repro.core import linear_trainer as lt

DIM = 47


def _mk_steps(rng, T, B, p, dim=DIM):
    idx = rng.randint(0, dim, size=(T, B, p)).astype(np.int32)
    val = rng.uniform(-2.0, 2.0, size=(T, B, p)).astype(np.float32)
    val = (val * (rng.uniform(size=val.shape) > 0.3)).astype(np.float32)
    y = (rng.uniform(size=(T, B)) > 0.5).astype(np.float32)
    return idx, val, y


def _ftrl_read_np(z, n, alpha, beta, lam1, lam2):
    denom = (beta + np.sqrt(n)) / alpha + lam2
    w = (np.sign(z) * lam1 - z) / denom
    return np.where(np.abs(z) <= lam1, 0.0, w).astype(np.float32)


def _eager_ftrl(cfg: LinearConfig, idx, val, y, eta_fn):
    """Dense eager reference: plain NumPy loop, no laziness, no jit."""
    alpha, beta = cfg.schedule.eta0, cfg.ftrl_beta
    lam1, lam2 = cfg.lam1, cfg.lam2
    z = np.zeros(cfg.dim, np.float64)
    n = np.zeros(cfg.dim, np.float64)
    b = 0.0
    losses = []
    for t in range(idx.shape[0]):
        B, p = idx[t].shape
        f = idx[t].reshape(-1)
        w_cur = _ftrl_read_np(z[f], n[f], alpha, beta, lam1, lam2)
        zlin = np.sum(w_cur.reshape(B, p) * val[t], axis=-1) + b
        if cfg.loss == "logistic":
            loss = np.maximum(zlin, 0.0) - zlin * y[t] + np.log1p(np.exp(-np.abs(zlin)))
            gz = 1.0 / (1.0 + np.exp(-zlin)) - y[t]
        else:
            loss = 0.5 * (zlin - y[t]) ** 2
            gz = zlin - y[t]
        g = (gz[:, None] * val[t]).reshape(-1)
        sigma = (np.sqrt(n[f] + g * g) - np.sqrt(n[f])) / alpha
        np.add.at(z, f, g - sigma * w_cur)
        np.add.at(n, f, g * g)
        b -= float(eta_fn(t)) * float(np.sum(gz))
        losses.append(np.mean(loss))
    w = _ftrl_read_np(z, n, alpha, beta, lam1, lam2)
    return w, b, np.asarray(losses)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("loss", ["logistic", "squared"])
@pytest.mark.parametrize("kind", ["constant", "inv_t", "inv_sqrt"])
def test_ftrl_matches_eager_reference(backend, loss, kind, rng):
    cfg = LinearConfig(
        dim=DIM,
        loss=loss,
        solver="ftrl",
        lam1=3e-3,
        lam2=1e-3,
        round_len=8,
        schedule=ScheduleConfig(kind=kind, eta0=0.4),
        backend=backend,
    )
    T = 2 * cfg.round_len + 5  # two flushed rounds + a partial tail
    idx, val, y = _mk_steps(rng, T, 3, 5)
    sched = cfg.schedule.make()

    round_fn = make_round_fn(cfg, "lazy")
    state = init_state(cfg)
    losses = []
    for start in range(0, 2 * cfg.round_len, cfg.round_len):
        rb = SparseBatch(
            idx=jnp.asarray(idx[start : start + cfg.round_len]),
            val=jnp.asarray(val[start : start + cfg.round_len]),
            y=jnp.asarray(y[start : start + cfg.round_len]),
        )
        state, ls = round_fn(state, rb)
        losses.append(np.asarray(ls))
    from repro.core import make_lazy_step

    step = make_lazy_step(cfg)
    for t in range(2 * cfg.round_len, T):
        state, ls = step(
            state, SparseBatch(jnp.asarray(idx[t]), jnp.asarray(val[t]), jnp.asarray(y[t]))
        )
        losses.append(np.asarray(ls)[None])
    losses = np.concatenate(losses)

    w_ref, b_ref, l_ref = _eager_ftrl(cfg, idx, val, y, sched)
    np.testing.assert_allclose(
        np.asarray(lt.current_weights(cfg, state)), w_ref, rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(float(state.b), b_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(losses, l_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_ftrl_sparse_predictions_match_full_read(backend, rng):
    cfg = LinearConfig(
        dim=DIM, solver="ftrl", lam1=5e-3, lam2=1e-3, round_len=16, backend=backend,
        schedule=ScheduleConfig(kind="constant", eta0=0.5),
    )
    idx, val, y = _mk_steps(rng, 10, 3, 5)
    # drive a partial round so state is mid-stream (i > 0)
    from repro.core import make_lazy_step

    step = make_lazy_step(cfg)
    state = init_state(cfg)
    for t in range(10):
        state, _ = step(
            state, SparseBatch(jnp.asarray(idx[t]), jnp.asarray(val[t]), jnp.asarray(y[t]))
        )
    ev_idx = rng.randint(0, DIM, size=(6, 5)).astype(np.int32)
    ev = SparseBatch(
        idx=jnp.asarray(ev_idx),
        val=jnp.asarray(rng.uniform(-2, 2, size=(6, 5)).astype(np.float32)),
        y=jnp.asarray(np.zeros(6, np.float32)),
    )
    # O(p) gathered read == O(d) full read at the gathered positions
    w_full = np.asarray(lt.current_weights(cfg, state))
    z = np.sum(w_full[ev_idx] * np.asarray(ev.val), axis=-1) + float(state.b)
    want = 1.0 / (1.0 + np.exp(-z))
    got = np.asarray(predict_proba_sparse(cfg, state, ev))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_ftrl_seed_inversion_roundtrip(rng):
    cfg = LinearConfig(dim=DIM, solver="ftrl", lam1=0.02, lam2=0.01,
                       schedule=ScheduleConfig(kind="constant", eta0=0.3))
    w0 = (rng.randn(DIM) * (rng.uniform(size=DIM) > 0.5)).astype(np.float32)
    state = init_state(cfg, w0)
    np.testing.assert_allclose(
        np.asarray(lt.current_weights(cfg, state)), w0, rtol=1e-5, atol=1e-6
    )


def test_ftrl_thresholds_to_exact_zeros(rng):
    """|z| <= lam1 coordinates read exactly 0 — the sparsity elastic net is
    prized for, via the proximal threshold rather than a shrink chain."""
    cfg = LinearConfig(
        dim=DIM, solver="ftrl", lam1=0.5, lam2=1e-3, round_len=32,
        schedule=ScheduleConfig(kind="constant", eta0=0.3),
    )
    from repro.core import make_lazy_step

    step = make_lazy_step(cfg)
    state = init_state(cfg)
    idx, val, y = _mk_steps(rng, 20, 3, 5)
    for t in range(20):
        state, _ = step(
            state, SparseBatch(jnp.asarray(idx[t]), jnp.asarray(val[t]), jnp.asarray(y[t]))
        )
    w = np.asarray(lt.current_weights(cfg, state))
    assert np.sum(w == 0.0) > 0  # the heavy lam1 must zero some touched coords
    z = np.asarray(state.wpsi[:, 1])
    np.testing.assert_array_equal(w[np.abs(z) <= cfg.lam1], 0.0)


# -- the round flush: w rebuilt in place from each row's own (z, n) ----------

FLUSH_DIM = 1030  # not a multiple of 4: a four-way mesh pads two rows
FLUSH_HYPERS = [(0.5, 0.01, 0.3), (1.0, 0.5, 0.1), (0.05, 0.2, 2.0)]  # (lam1, lam2, alpha)


def _stale_wpsi(rng, dim):
    """(w, z, n) rows with a stale w column and z on both sides of lam1."""
    w = rng.normal(size=dim)
    z = rng.normal(scale=2.0, size=dim) * (rng.uniform(size=dim) > 0.2)
    n = rng.uniform(0.0, 4.0, size=dim) * (rng.uniform(size=dim) > 0.1)
    return np.stack([w, z, n], axis=1).astype(np.float32)


def _flush_cfg(backend):
    lam1, lam2, alpha = FLUSH_HYPERS[1]
    return LinearConfig(
        dim=FLUSH_DIM, solver="ftrl", lam1=lam1, lam2=lam2, round_len=16, backend=backend,
        schedule=ScheduleConfig(kind="constant", eta0=alpha),
    )


def _lane_hypers(k):
    from repro.core import Hypers

    lam1, lam2, alpha = FLUSH_HYPERS[k]
    return Hypers(lam1=jnp.float32(lam1), lam2=jnp.float32(lam2), eta_scale=jnp.float32(alpha))


def _read(cfg, wpsi, hp):
    """The solver's own full read of a [d, 3] state, as its own program."""
    import jax

    sv = lt._solver(cfg)
    state = init_state(cfg)._replace(wpsi=jnp.asarray(wpsi))
    fn = jax.jit(lambda s, h: sv.read_weights(cfg, s, h, lt._backend(cfg.backend)))
    return np.asarray(fn(state, hp))


def _check_flushed(backend, got, wpsi, want):
    np.testing.assert_array_equal(got[:, 1:], wpsi[:, 1:])  # z, n untouched
    if backend == "reference":
        np.testing.assert_array_equal(got[:, 0], want)
    else:
        np.testing.assert_allclose(got[:, 0], want, rtol=1e-6, atol=0)


def _flush_single(backend, rng):
    import jax

    cfg = _flush_cfg(backend)
    wpsi = _stale_wpsi(rng, FLUSH_DIM)
    state = init_state(cfg)._replace(wpsi=jnp.asarray(wpsi), i=jnp.int32(5))
    out = jax.jit(lambda s: lt.flush(cfg, s), donate_argnums=0)(state)
    assert int(out.i) == 0
    _check_flushed(backend, np.asarray(out.wpsi), wpsi, _read(cfg, wpsi, cfg.hypers()))


def _flush_sweep(backend, rng):
    """The sweeps' vmapped flush, each config with its own hypers."""
    import jax

    from repro.core import Hypers
    from repro.sweeps.batched_trainer import HYPER_AXES, STATE_AXES

    cfg = _flush_cfg(backend)
    n_cfg = len(FLUSH_HYPERS)
    wpsi = np.stack([_stale_wpsi(rng, FLUSH_DIM) for _ in range(n_cfg)])
    one = init_state(cfg)
    bstate = one._replace(
        wpsi=jnp.asarray(wpsi),
        b=jnp.zeros((n_cfg,), jnp.float32),
        caches=jax.tree.map(lambda c: jnp.broadcast_to(c, (n_cfg,) + c.shape), one.caches),
    )
    lam1, lam2, alpha = (jnp.asarray(np.float32(h)) for h in zip(*FLUSH_HYPERS))
    hp = Hypers(lam1=lam1, lam2=lam2, eta_scale=alpha)
    vflush = jax.vmap(
        lambda s, h: lt.flush(cfg, s, hp=h), in_axes=(STATE_AXES, HYPER_AXES), out_axes=STATE_AXES
    )
    out = np.asarray(jax.jit(vflush)(bstate, hp).wpsi)
    for k in range(n_cfg):
        _check_flushed(backend, out[k], wpsi[k], _read(cfg, wpsi[k], _lane_hypers(k)))


def _flush_tenants(backend, rng):
    """MultiLinearService's masked per-slot flush: flushed lanes rebuild w,
    the lane left out of the mask stays bitwise as it was."""
    from repro.serving.multi_service import MultiLinearService

    svc = MultiLinearService(_flush_cfg(backend), n_slots=len(FLUSH_HYPERS))
    for k, (lam1, lam2, alpha) in enumerate(FLUSH_HYPERS):
        svc.add_tenant(f"t{k}", lam1=lam1, lam2=lam2, eta0=alpha)
    g = svc.groups["ftrl"]
    wpsi = np.stack([_stale_wpsi(rng, FLUSH_DIM) for _ in FLUSH_HYPERS])
    g.bstate = g.bstate._replace(wpsi=jnp.asarray(wpsi))
    mask = np.array([True, False, True])
    out = np.asarray(g.flush_fn(g.bstate, g.hp(), jnp.asarray(mask)).wpsi)
    for k in range(len(FLUSH_HYPERS)):
        if mask[k]:
            _check_flushed(backend, out[k], wpsi[k], _read(g.cfg, wpsi[k], g.hp_at(k)))
        else:
            np.testing.assert_array_equal(out[k], wpsi[k])


_MESH_FLUSH = """
import sys
import jax, numpy as np
from repro.core import LinearConfig, ScheduleConfig, init_state
from repro.core import linear_trainer as lt
from repro.dist import linear as dl

backend, dim, lam1, lam2, alpha = sys.argv[1], int(sys.argv[2]), *map(float, sys.argv[3:6])
cfg = LinearConfig(dim=dim, solver="ftrl", lam1=lam1, lam2=lam2, round_len=16, backend=backend,
                   schedule=ScheduleConfig(kind="constant", eta0=alpha), mesh=4)
wpsi = np.load(sys.argv[6])
host = dl.host_template(cfg)._replace(wpsi=wpsi)
out = jax.jit(lambda s: lt.flush(cfg, s), donate_argnums=0)(dl.place_state(cfg, host))
np.save(sys.argv[7], np.asarray(out.wpsi))
"""


def _flush_mesh(backend, rng, tmp_path):
    """Each shard flushes its own slab; the ceil-div padding rows stay zero."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    cfg = _flush_cfg(backend)
    wpsi = _stale_wpsi(rng, FLUSH_DIM)
    np.save(tmp_path / "in.npy", wpsi)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    src = str(Path(__file__).resolve().parents[2] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    args = [backend, str(FLUSH_DIM), *map(str, FLUSH_HYPERS[1])]
    args += [str(tmp_path / "in.npy"), str(tmp_path / "out.npy")]
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_FLUSH, *args],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = np.load(tmp_path / "out.npy")
    assert out.shape == (4 * -(-FLUSH_DIM // 4), 3)
    np.testing.assert_array_equal(out[FLUSH_DIM:], 0.0)
    _check_flushed(backend, out[:FLUSH_DIM], wpsi, _read(cfg, wpsi, cfg.hypers()))


_FLUSH_CASES = {"single": _flush_single, "sweep": _flush_sweep, "tenants": _flush_tenants}


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("case", [*_FLUSH_CASES, "mesh4"])
def test_ftrl_flush_rebuilds_w_from_z_and_n(case, backend, rng, tmp_path):
    """The round flush leaves z and n bitwise as they were and rebuilds the
    w column as the solver's full read gives it: bitwise on the reference
    backend, within 1e-6 relative against the flat Pallas read kernel."""
    if case == "mesh4":
        _flush_mesh(backend, rng, tmp_path)
    else:
        _FLUSH_CASES[case](backend, rng)
