"""Share of the mesh's HBM roofline that the round flush reaches.

The least time for one pass over the whole logical ``[dim, cols]`` state,
read plus write (``work.flush_bytes``), at the peak HBM bandwidth of all
the mesh's chips, over the slowest chip's device time per round of the
round program's ops outside its loop (each chip's flush of its own slab,
and whatever else the program does once a round).
"""

from chipbench import shards, work


def read(r):
    outside = shards.device_ns(r, lambda o, c: not c["loop"])
    if not outside or not r.rounds:
        return None
    cfg = r.config
    least = work.flush_bytes(cfg["data"]["dim"], work.state_cols(cfg))
    least_s = least / (cfg["mesh"] * work.peaks(r.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / (max(outside.values()) / 1e9 / r.rounds)
