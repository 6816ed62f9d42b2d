"""The DP caches must reproduce the direct (non-DP) window products / sums."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FOBOS, SGD, extend, init_caches, log_a
from repro.core.lazy_enet import EXPM1_FLOOR, catchup_factors


def _build(etas, lam2, flavor):
    caches = init_caches(len(etas))
    for i, eta in enumerate(etas):
        caches = extend(caches, jnp.asarray(i, jnp.int32), jnp.asarray(eta, jnp.float32), lam2, flavor)
    return caches


def _a(eta, lam2, flavor):
    return 1.0 - eta * lam2 if flavor == SGD else 1.0 / (1.0 + eta * lam2)


@pytest.mark.parametrize("flavor", [SGD, FOBOS])
@pytest.mark.parametrize("lam2", [0.0, 0.05, 0.3])
def test_logP_matches_direct_product(flavor, lam2, rng):
    etas = rng.uniform(0.01, 0.9, size=23)
    caches = _build(etas, lam2, flavor)
    logP = np.asarray(caches.logP)
    for i in range(len(etas) + 1):
        direct = float(np.sum([np.log(_a(e, lam2, flavor)) for e in etas[:i]])) if i else 0.0
        np.testing.assert_allclose(logP[i], direct, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("flavor", [SGD, FOBOS])
@pytest.mark.parametrize("lam2", [0.0, 0.05, 0.3])
def test_B_matches_direct_sum(flavor, lam2, rng):
    """B[i] = sum_{tau<i} eta_tau / prod-of-a's, with the flavor-specific
    off-by-one in which a's divide (see dp_caches module doc)."""
    etas = rng.uniform(0.01, 0.9, size=23)
    caches = _build(etas, lam2, flavor)
    B = np.asarray(caches.B)
    a = np.array([_a(e, lam2, flavor) for e in etas], dtype=np.float64)
    logs = np.concatenate([[0.0], np.cumsum(np.log(a))])  # logs[i] = logP slot i
    for i in range(len(etas) + 1):
        terms = []
        for tau in range(i):
            if flavor == SGD:
                terms.append(etas[tau] * np.exp(-logs[tau + 1]))
            else:
                terms.append(etas[tau] * np.exp(-logs[tau]))
        np.testing.assert_allclose(B[i], np.sum(terms) if terms else 0.0, rtol=1e-5, atol=1e-6)


def test_S_is_eta_prefix_sum(rng):
    etas = rng.uniform(0.0, 1.0, size=17)
    caches = _build(etas, 0.1, SGD)
    np.testing.assert_allclose(
        np.asarray(caches.S), np.concatenate([[0.0], np.cumsum(etas)]).astype(np.float32), rtol=1e-5
    )


def test_log_a_flavors():
    eta = jnp.asarray(0.5, jnp.float32)
    np.testing.assert_allclose(float(log_a(eta, 0.2, SGD)), np.log(0.9), rtol=1e-6)
    np.testing.assert_allclose(float(log_a(eta, 0.2, FOBOS)), -np.log(1.1), rtol=1e-6)
    assert float(log_a(eta, 0.0, SGD)) == 0.0


@pytest.mark.parametrize("flavor", [SGD, FOBOS])
@pytest.mark.parametrize("lam2", [1e-4, 1e-2])  # windows that decay < 6%; and to 5%
def test_catchup_factors_match_float64(flavor, lam2):
    """ratio within 1 ulp and shift (three rounded factors) within 2 ulps of
    float64 on the same f32 caches.  Measured on the CPU: 0.85 and 1.97."""
    R, lam1 = 1024, np.float32(2e-4)
    etas = jnp.asarray(0.5 / np.sqrt(1.0 + np.arange(R) / 200.0), jnp.float32)
    caches = jax.jit(
        lambda: jax.lax.fori_loop(
            0, R, lambda i, c: extend(c, i, etas[i], lam2, flavor), init_caches(R)
        )
    )()
    rng = np.random.default_rng(0)
    ends = rng.integers(0, R + 1, size=(2, 4000))
    psi, k = ends.min(axis=0), ends.max(axis=0)
    ratio, shift = catchup_factors(jnp.asarray(psi), jnp.asarray(k), caches, float(lam1))

    logP, B = np.asarray(caches.logP), np.asarray(caches.B)
    x = (logP[k] - logP[psi]).astype(np.float64)  # the f32 difference the code takes
    assert (x < EXPM1_FLOOR).any() == (lam2 > 1e-3)  # both branches of exp_nonpos
    ratio64 = np.exp(x)
    shift64 = np.float64(lam1) * np.exp(logP[k].astype(np.float64)) * (B[k] - B[psi])
    np.testing.assert_array_max_ulp(np.asarray(ratio), ratio64.astype(np.float32), maxulp=1)
    np.testing.assert_array_max_ulp(np.asarray(shift), shift64.astype(np.float32), maxulp=2)


def test_catchup_ratio_reads_short_windows_through_expm1():
    """The TPU's f32 exp of small arguments reads ~18 ulp low; the CPU's
    does not, so only the program's structure can pin the choice here."""
    caches = init_caches(4)
    jaxpr = str(jax.make_jaxpr(catchup_factors, static_argnums=3)(
        jnp.zeros(3, jnp.int32), jnp.ones(3, jnp.int32), caches, 1e-3
    ))
    assert jaxpr.count("expm1") == 2  # ratio and shift
