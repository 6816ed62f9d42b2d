"""Share of the HBM roofline that the round flush reaches.

The least time for one pass over the whole logical ``[dim, cols]`` state,
read plus write (``work.flush_bytes``), at the chip's peak HBM bandwidth,
over the device time per round of the round program's ops outside its
loop (the flush, and whatever else the program does once a round).
"""

from chipbench import work


def read(r):
    outside = sum(o.end - o.start for o, c in r.round_ops() if c is not None and not c["loop"])
    if not outside or not r.rounds:
        return None
    least = work.flush_bytes(r.config["data"]["dim"], work.state_cols(r.config))
    least_s = least / work.peaks(r.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (outside / 1e9 / r.rounds)
