"""Share of the round program's loop-body device time spent in ops that
gather or scatter: gather and scatter instructions, and fusions whose
fused computation holds one, as the compiled HLO says."""


def read(r):
    loop = [(o.end - o.start, c["gs"]) for o, c in r.round_ops() if c and c["loop"]]
    total = sum(d for d, _ in loop)
    if not total:
        return None
    return 100.0 * sum(d for d, gs in loop if gs) / total
