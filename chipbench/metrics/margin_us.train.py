"""Device time per lazy step of the margin's cross-chip reduction, in
microseconds: the round program's ops named ``lazy.margin`` (the psum and
what the compiler puts around it; ``chipbench/shards.py``), their time in
the window on each chip over the window's steps, averaged over the chips.
It reads the compiled per-device program that ``train_mesh`` hands over
(``reading.hlo``); a program without the scope gives nothing."""

from chipbench import shards


def read(r):
    names = shards.margin_ops(getattr(r, "hlo", ""))
    per = shards.device_ns(r, lambda o, c: o.name in names)
    if not names or not per or not r.steps:
        return None
    return sum(per.values()) / len(per) / 1e3 / r.steps
