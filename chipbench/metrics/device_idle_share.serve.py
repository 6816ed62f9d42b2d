"""Share of the traced window in which no op ran on the device:
1 - (union of the device ops' intervals) / (window)."""


def read(r):
    return r.idle_share()
