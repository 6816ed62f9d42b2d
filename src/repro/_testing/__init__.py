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
