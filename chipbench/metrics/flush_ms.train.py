"""Device time per round of the round program's ``lazy.flush`` ops (every
weight brought current, the caches rebased), in milliseconds: their time
in the window over the window's rounds (``chipbench/phases.py``)."""

from chipbench import phases


def read(r):
    ns = phases.phase_ns(r, phases.FLUSH)
    return None if ns is None or not r.rounds else ns / 1e6 / r.rounds
