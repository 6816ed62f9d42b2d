"""Device time per lazy step of the round program's ``lazy.kernel`` ops
(the fused whole-step kernel and what feeds it), in microseconds: their
time in the window over the window's steps (``chipbench/phases.py``)."""

from chipbench import phases


def read(r):
    return phases.per_step_us(r, phases.KERNEL)
