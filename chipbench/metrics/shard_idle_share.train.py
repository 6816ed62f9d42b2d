"""The largest of the mesh's chips' idle shares in the traced window:
1 - (union of that chip's op intervals) / (window).  A straggler, or a
chip left waiting on the others, shows here."""

from chipbench import shards


def read(r):
    idle = shards.idle_shares(r)
    return max(idle.values()) if idle else None
