"""Device time per lazy step of the round program's ``lazy.scatter`` ops
(the write-back into the state, with the layout copies it forces), in
microseconds: their time in the window over the window's steps
(``chipbench/phases.py``)."""

from chipbench import phases


def read(r):
    return phases.per_step_us(r, phases.SCATTER)
