"""Share of the HBM roofline that one lazy step reaches.

The least time for the bytes one step must move (``work.step_bytes``: the
gathered state rows read and written at their logical width, plus ids,
values and labels) at the chip's peak HBM bandwidth, over the device time
per step: the round program's ops inside its loop, over the window's steps.
It reads the same work whatever implements the step.
"""

from chipbench import work


def read(r):
    body = sum(o.end - o.start for o, c in r.round_ops() if c and c["loop"])
    if not body or not r.steps:
        return None
    cfg = r.config
    least = work.step_bytes(cfg["train"]["batch"], cfg["p_max"], work.state_cols(cfg))
    least_s = least / work.peaks(r.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (body / 1e9 / r.steps)
