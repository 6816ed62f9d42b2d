"""Share of the mesh's HBM roofline that one sharded lazy step reaches.

The least time for the bytes one step must move (``work.step_bytes``: the
touched state rows read and written at their logical width, plus ids,
values and labels) at the peak HBM bandwidth of all the mesh's chips,
over the slowest chip's device time per step: its round-program ops inside
the loop, over the window's steps.  The same work whatever implements the
step (on the mesh, ``ftrl_margin``, the psum and ``ftrl_update``).
"""

from chipbench import shards, work


def read(r):
    body = shards.device_ns(r, lambda o, c: c["loop"])
    if not body or not r.steps:
        return None
    cfg = r.config
    least = work.step_bytes(cfg["train"]["batch"], cfg["p_max"], work.state_cols(cfg))
    least_s = least / (cfg["mesh"] * work.peaks(r.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / (max(body.values()) / 1e9 / r.steps)
