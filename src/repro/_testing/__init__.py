"""Helpers shared by the test suites."""

import numpy as np

F32_EPS = float(np.finfo(np.float32).eps)


def assert_f32_close(actual, desired, k: float = 2.0) -> None:
    """Element-wise ``|actual - desired| <= 2 eps |desired| + k eps max|desired|``
    (float32 eps).

    For two programs that compute the same f32 arithmetic but fuse it
    differently.  Each rounding differs by an ulp of its operands; after
    cancellation (a weight shrunk toward zero) that is many ulps of a small
    result, so the absolute term is scaled by the array's largest value.
    Measured worst case on the CPU, 300 hypothesis examples per pin: k =
    1.71 (reference-backend sweep), 1.52 (batch of one), 0.60 (cold path),
    at up to 384 element-wise ulps of near-zero weights."""
    actual = np.asarray(actual, np.float32)
    desired = np.asarray(desired, np.float32)
    scale = float(np.max(np.abs(desired))) if desired.size else 0.0
    np.testing.assert_allclose(actual, desired, rtol=2 * F32_EPS, atol=k * F32_EPS * scale)


def state_accesses(jaxpr, rows: int, cols: int) -> list:
    """Every gather from and scatter into an array whose last two dims are
    ``[rows, cols]`` in ``jaxpr`` and the jaxprs nested in it (scan bodies,
    jit calls, shard_map regions), in program order.  Each entry is
    ``(primitive name, gather slice sizes or scatter updates shape)``."""
    from jax.extend import core as jcore

    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if eqn.invars and getattr(eqn.invars[0].aval, "shape", ())[-2:] == (rows, cols):
            if name == "gather":
                out.append((name, tuple(eqn.params["slice_sizes"])))
            elif name.startswith("scatter"):
                out.append((name, eqn.invars[2].aval.shape))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    out += state_accesses(sub, rows, cols)
    return out


def assert_column_access(accesses: list, slots: int, state_ndim: int) -> None:
    """The lazy step's two gathers and three write-backs (SET w, SET psi,
    ADD the gradient step) each touch ONE column of the packed state: a
    gather slices one element per index, a scatter's updates are
    ``[.., slots]``, one dim fewer than the state.  A row access would slice
    ``cols`` elements, or write ``[.., slots, cols]``."""
    kinds = sorted(name for name, _ in accesses)
    assert kinds == ["gather", "gather", "scatter", "scatter", "scatter-add"], accesses
    for name, what in accesses:
        if name == "gather":
            assert what[-2:] == (1, 1), accesses
        else:
            assert len(what) == state_ndim - 1 and what[-1] == slots, accesses
