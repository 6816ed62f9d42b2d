"""ServiceConfig surface: LinearService takes service=ServiceConfig(...)
only (the pre-ServiceConfig loose kwargs finished their deprecation cycle
and are gone), pin_config resolves every deferred LinearConfig field
exactly once, and swap_weights' packed state= form round-trips solver
state losslessly."""
import numpy as np
import pytest

from repro.core import LinearConfig, ScheduleConfig, SparseBatch
from repro.serving import LinearService, ServiceConfig, binary_buckets, pin_config

DIM = 61


def _cfg(**kw):
    kw.setdefault("dim", DIM)
    kw.setdefault("round_len", 8)
    kw.setdefault("lam1", 0.01)
    kw.setdefault("lam2", 0.005)
    kw.setdefault("schedule", ScheduleConfig(kind="inv_sqrt", eta0=0.3))
    return LinearConfig(**kw)


def _mk(rng, B, p):
    import jax.numpy as jnp

    idx = rng.randint(0, DIM, size=(B, p)).astype(np.int32)
    val = rng.uniform(-1, 1, size=(B, p)).astype(np.float32)
    y = (rng.uniform(size=B) > 0.5).astype(np.float32)
    return SparseBatch(jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y))


def test_loose_kwargs_removed():
    """The deprecated per-kwarg aliases (PR 8's DeprecationWarning cycle)
    are gone: a pre-ServiceConfig call site now fails loudly with TypeError
    instead of silently constructing a differently-configured service."""
    for kwargs in (
        {"p_max": 8},
        {"micro_batch": 4},
        {"max_delay": 0.5},
        {"metrics": None},
        {"backend": "reference"},
        {"solver": "fobos"},
        {"p_max": 8, "micro_batch": 4, "solver": "fobos"},
    ):
        with pytest.raises(TypeError):
            LinearService(_cfg(), **kwargs)
    # aliases alongside service= are equally gone
    with pytest.raises(TypeError):
        LinearService(_cfg(), ServiceConfig(p_max=16), p_max=4)


def test_service_config_path_is_the_only_ctor():
    """service= is taken verbatim (no warning, no copy) and None defaults
    to ServiceConfig()."""
    base = ServiceConfig(p_max=16, micro_batch=8, max_delay=2.0)
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        svc = LinearService(_cfg(), base)
        svc_default = LinearService(_cfg())
    assert svc.service is base
    assert svc.service.p_max == 16
    assert svc_default.service == ServiceConfig()


def test_pin_config_resolves_and_rejects_conflicts():
    pinned = pin_config(_cfg(), ServiceConfig())
    assert pinned.backend is not None
    assert pinned.solver is not None
    # explicit-vs-explicit disagreements are errors, not silent overrides
    with pytest.raises(ValueError, match="conflicting explicit solvers"):
        pin_config(_cfg(solver="sgd"), ServiceConfig(solver="ftrl"))
    with pytest.raises(ValueError, match="conflicting explicit backends"):
        pin_config(_cfg(backend="reference"), ServiceConfig(backend="pallas"))
    # agreeing explicit choices pass through
    ok = pin_config(_cfg(solver="ftrl"), ServiceConfig(solver="ftrl"))
    assert ok.solver == "ftrl"


def test_binary_buckets():
    assert binary_buckets(1) == (1,)
    assert binary_buckets(8) == (1, 2, 4, 8)
    with pytest.raises(AssertionError):
        binary_buckets(6)


def test_swap_state_is_lossless_for_ftrl():
    """swap_weights(state=) installs the packed [d, 3] ftrl state verbatim
    (z, n survive), where the (w, b) form must re-seed through seed_cols and
    forget the per-coordinate accumulators — so only the state= service
    tracks the donor exactly through continued training."""
    cfg = _cfg(solver="ftrl")
    rng = np.random.RandomState(1)
    donor = LinearService(cfg, ServiceConfig(p_max=8, micro_batch=4))
    for _ in range(8):  # exactly one round: flushed, w column current
        donor.learn(_mk(rng, 1, 4))
    packed = np.asarray(donor.state.wpsi)
    b = float(donor.state.b)
    assert packed.shape == (DIM, 3)

    via_state = LinearService(cfg, ServiceConfig(p_max=8, micro_batch=4))
    via_state.swap_weights(state=packed, b=b)
    via_w = LinearService(cfg, ServiceConfig(p_max=8, micro_batch=4))
    via_w.swap_weights(w=donor.current_weights(), b=b)

    np.testing.assert_array_equal(np.asarray(via_state.state.wpsi), packed)
    np.testing.assert_array_equal(via_state.current_weights(), donor.current_weights())
    np.testing.assert_allclose(
        via_w.current_weights(), donor.current_weights(), rtol=1e-6, atol=1e-7
    )
    assert not np.array_equal(np.asarray(via_w.state.wpsi), packed)  # z/n lost

    probe = _mk(rng, 2, 4)
    # the probe loss reads the pre-step weights: identical packed state ->
    # identical loss (the w= path already matches here too; the z/n
    # difference shows up in subsequent update magnitudes)
    assert via_state.learn(probe) == donor.learn(probe)


def test_swap_state_rebases_cache_solver_psi():
    """Cache solvers adopt a packed state by rebasing psi to 0 (the swapped
    weights are already current — stale catch-up debt must not replay)."""
    cfg = _cfg(solver="fobos")
    svc = LinearService(cfg, ServiceConfig(p_max=8, micro_batch=4))
    packed = np.stack(
        [np.linspace(-1, 1, DIM, dtype=np.float32),
         np.full((DIM,), 7.0, np.float32)],  # garbage psi: must be dropped
        axis=1,
    )
    svc.swap_weights(state=packed, b=0.25)
    out = np.asarray(svc.state.wpsi)
    np.testing.assert_array_equal(out[:, 0], packed[:, 0])
    np.testing.assert_array_equal(out[:, 1], 0.0)
    np.testing.assert_array_equal(svc.current_weights(), packed[:, 0])


def test_swap_rejects_both_or_neither():
    svc = LinearService(_cfg(), ServiceConfig(p_max=8, micro_batch=4))
    with pytest.raises(ValueError, match="exactly one"):
        svc.swap_weights()
    with pytest.raises(ValueError, match="exactly one"):
        svc.swap_weights(w=np.zeros(DIM), state=np.zeros((DIM, 2)))
    with pytest.raises(ValueError, match="shape"):
        svc.swap_weights(state=np.zeros((DIM, 3), np.float32))  # fobos is [d, 2]
