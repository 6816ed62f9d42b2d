"""Device time per lazy step of the round program's ``lazy.gather`` ops
(the touched rows' gather, the DP-cache extend and catch-up factors), in
microseconds: their time in the window over the window's steps
(``chipbench/phases.py`` maps each op to its phase)."""

from chipbench import phases


def read(r):
    return phases.per_step_us(r, phases.GATHER)
