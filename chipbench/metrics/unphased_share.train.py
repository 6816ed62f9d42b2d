"""Share of the round program's device time in the window spent in ops of
no phase: how much of the program the ``lazy.*`` scopes fail to cover
(``chipbench/phases.py``)."""

from chipbench import phases


def read(r):
    s = phases.split(r)
    total = sum(s.values()) if s else 0
    return 100.0 * s[None] / total if total else None
