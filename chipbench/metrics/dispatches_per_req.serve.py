"""Jitted calls per request, from the service's own counters over the
window: predict chunks (the predict latency histogram's count), learn
steps and round flushes."""


def read(r):
    if not r.requests:
        return None
    c = r.counters
    return (c["predict_calls"] + c["learn_steps"] + c["round_flushes"]) / r.requests
