"""Per-chip readings of a round program that runs on a feature mesh.

Every chip runs the same per-device program, so the trace holds each of
its ops once a chip, and ``trace.Reading.round_ops`` sums over them all.
The readers of a mesh cell group by ``Op.device`` here and then take the
mean or the slowest chip, as each one says.

The margin's cross-chip reduction is named by the program with a
``jax.named_scope`` of its own, ``lazy.margin``, inside ``lazy.kernel``.
``chipbench/phases.py`` knows only the four phases and places those ops in
``lazy.kernel``; :func:`margin_ops` finds them by their own ``op_name``.
"""

from __future__ import annotations

import functools
import re

from chipbench import phases, trace

_MARGIN = re.compile(r"(?<![\w.])lazy\.margin(?![\w.])")


@functools.lru_cache(maxsize=4)
def margin_ops(text: str) -> frozenset:
    """The ops that run as units on the device (``trace.classify``) and
    belong to the margin's reduction: their own ``op_name`` holds
    ``lazy.margin`` (a fusion carries its root's).  Instruction names are
    unique in an HLO module."""
    named = set()
    for line in text.splitlines():
        m, op_name = trace._INSTR.match(line), phases._OP_NAME.search(line)
        if m and op_name and _MARGIN.search(op_name.group(1)):
            named.add(m.group(1))
    return frozenset(named.intersection(trace.classify(text)))


def device_ns(r, keep) -> dict:
    """``{device: ns}`` of the window's round-program ops whose class
    (``trace.classify``) passes ``keep``."""
    out: dict = {}
    for o, c in r.round_ops():
        if c is not None and keep(o, c):
            out[o.device] = out.get(o.device, 0) + (o.end - o.start)
    return out


def idle_shares(r) -> dict:
    """``{device: percent}``: the share of the window in which no op ran
    on that chip."""
    lo, hi = r.trace.window
    spans: dict = {}
    for o in r.trace.ops:
        spans.setdefault(o.device, []).append((o.start, o.end))
    return {d: 100.0 * (1.0 - trace.union_ns(s, lo, hi) / r.window_ns) for d, s in spans.items()}
