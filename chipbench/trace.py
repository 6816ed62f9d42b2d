"""The profiler trace of a window, and its reduction to per-layer metrics.

``capture`` runs the window under ``jax.profiler`` with a host span
``chipbench.window`` around it; ``load`` reads the ``.xplane.pb`` back into
device op intervals (per TPU, from the "XLA Ops" line, with their HLO
module) and host spans.  The reduction helpers below are plain functions of
those lists, so that tests can check them on a small synthetic trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import re
import shutil
from pathlib import Path

WINDOW_SPAN = "chipbench.window"


@dataclasses.dataclass
class Op:
    name: str  # HLO instruction name, e.g. "fusion.12"
    start: int  # ns
    end: int  # ns
    module: str  # HLO module, e.g. "jit_round_fn"
    device: int


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    ops: list  # [Op]
    spans: list  # [Span] on the host
    window: tuple  # (start, end) ns of the window span


def capture(run, fn) -> Path:
    """Trace ``fn()``; returns the ``.xplane.pb`` path."""
    import jax

    out = Path(run.out_dir) / f"trace-{run.workload}-{run.seed}"
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans come from TraceAnnotation alone
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(out), profiler_options=opts):
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            fn()
    files = sorted(glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {out}")
    return Path(files[-1])


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        out[k] = v
    return out


def module_at(modules: list, t: int) -> str:
    """The name of the module run ``(start, end, name)``, sorted by start,
    that holds the time ``t``; "" where none does."""
    k = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if k >= 0 and modules[k][0] <= t < modules[k][1]:
        return modules[k][2]
    return ""


def op_name(text: str) -> str:
    """The instruction's name from a trace event's name, which on a TPU is
    the whole HLO line: ``"fusion.19 = f32[1024,2]{...} fusion(...)"``."""
    m = re.match(r"\s*%?([\w.\-]+)", text)
    return m.group(1) if m else text


def module_base(text: str) -> str:
    """An HLO module's name without the fingerprint that the TPU trace adds:
    ``"jit_round_fn(18136172526847648574)"`` is ``"jit_round_fn"``."""
    return re.sub(r"\(\d+\)$", "", text.strip())


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, spans = [], []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted(
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name)
                for ev in lines.get("XLA Modules", [])
            )
            for ev in lines.get("XLA Ops", []):
                st = _stats(ev)
                start = int(ev.start_ns)
                ops.append(
                    Op(
                        name=op_name(str(st.get("hlo_op") or ev.name)),
                        start=start,
                        end=start + int(ev.duration_ns),
                        module=module_base(str(st.get("hlo_module") or module_at(modules, start))),
                        device=int(m.group(1)),
                    )
                )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        spans.append(
                            Span(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        )
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if not windows:
        raise RuntimeError(f"no {WINDOW_SPAN} span in {path}")
    w = max(windows, key=lambda s: s.end - s.start)
    return Trace(ops=ops, spans=spans, window=(w.start, w.end))


def summary(path: Path, top: int = 40) -> str:
    """A readable dump of a trace's planes, lines and busiest events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    lines = []
    for plane in pd.planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:4]:
                lines.append(f"    {ev.name!r} start={ev.start_ns} dur={ev.duration_ns} {_stats(ev)}")
            tot: dict = {}
            for ev in evs:
                tot[ev.name] = tot.get(ev.name, 0) + ev.duration_ns
            for name, d in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                lines.append(f"    TOTAL {d / 1e6:.3f} ms {name!r}")
    return "\n".join(lines)


# -- compiled HLO: which ops run in a loop body, which hold a gather/scatter --

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")  # at column 0
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_CALLS = re.compile(r"\b(calls|to_apply|body|condition|branch_computations|called_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_CONTROL = ("body", "condition", "branch_computations")
GATHER_SCATTER = ("gather", "scatter")
CONTAINERS = ("while", "conditional")  # their trace events span the ops they run


def parse_hlo(text: str) -> tuple:
    """``(entry, comps)`` of an HLO module's text: ``comps[name]`` lists the
    computation's instructions as ``(name, opcode, {key: [callees]})``."""
    comps: dict = {}
    entry, cur = None, None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h and not line.startswith("HloModule"):
            cur = h.group(2)
            comps[cur] = []
            if h.group(1):
                entry = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rest = m.group(2)
        op = _OPCODE.search(" " + rest)
        calls: dict = {}
        for key, val in _CALLS.findall(rest):
            calls.setdefault(key, []).extend(re.findall(r"[\w.\-]+", val.replace("%", " ")))
        comps[cur].append((m.group(1), op.group(1) if op else "", calls))
    return entry, comps


def classify(text: str) -> dict:
    """``{op: {"loop": bool, "gs": bool}}`` for every op that runs as a
    unit on the device (the instructions of the entry computation and of
    the computations that control flow runs: loop bodies and conditions,
    branches).  ``loop``: the op runs inside a while loop's body or
    condition.  ``gs``: the op is a gather or scatter, or a fusion whose
    fused computation holds one.  A ``while`` or ``conditional`` is left
    out: its event in a trace spans the ops it runs, which are counted
    themselves."""
    entry, comps = parse_hlo(text)
    memo: dict = {}

    def holds_gs(comp: str) -> bool:
        if comp not in memo:
            memo[comp] = False
            memo[comp] = any(
                op in GATHER_SCATTER or any(holds_gs(c) for cs in calls.values() for c in cs)
                for _, op, calls in comps.get(comp, [])
            )
        return memo[comp]

    out: dict = {}
    todo = [(entry, False)]
    seen = set()
    while todo:
        comp, in_loop = todo.pop()
        if (comp, in_loop) in seen or comp not in comps:
            continue
        seen.add((comp, in_loop))
        for name, op, calls in comps[comp]:
            gs = op in GATHER_SCATTER or (
                op == "fusion" and any(holds_gs(c) for c in calls.get("calls", []))
            )
            if op not in CONTAINERS:
                prev = out.get(name, {"loop": False, "gs": False})
                out[name] = {"loop": prev["loop"] or in_loop, "gs": prev["gs"] or gs}
            for key in _CONTROL:
                for c in calls.get(key, []):
                    todo.append((c, in_loop or op == "while"))
    return out


def module_name(text: str) -> str:
    m = re.search(r"^HloModule\s+([\w.\-]+)", text, re.M)
    return m.group(1) if m else ""


# -- reductions --


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, end)`` intervals, clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: int, hi: int) -> list:
    """The idle ``(start, end)`` stretches of ``[lo, hi]`` between intervals."""
    out, t = [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans, t: int) -> str:
    """The innermost host span open at ``t`` (the shortest that covers it)."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and s.name != WINDOW_SPAN:
            if best is None or s.end - s.start < best.end - best.start:
                best = s
    return best.name if best else "none"


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader gets: the trace and its window, and
    what the cell's driver module knows of the work inside it."""

    trace: Trace
    device_kind: str
    config: dict
    steps: int = 0  # lazy steps in the window (training)
    rounds: int = 0  # rounds in the window (training)
    module: str = ""  # HLO module of the round program (training)
    ops: dict = dataclasses.field(default_factory=dict)  # classify() of that module
    counters: dict = dataclasses.field(default_factory=dict)  # service counters (serving)
    requests: int = 0  # requests in the window (serving)

    @property
    def window_ns(self) -> int:
        return self.trace.window[1] - self.trace.window[0]

    def in_window(self, module_prefix: str = ""):
        lo, hi = self.trace.window
        return [
            o
            for o in self.trace.ops
            if o.end > lo and o.start < hi and o.module.startswith(module_prefix)
        ]

    def busy_ns(self) -> int:
        """Device busy time, averaged over the devices in the trace."""
        lo, hi = self.trace.window
        devs = sorted({o.device for o in self.trace.ops}) or [0]
        per = [union_ns([(o.start, o.end) for o in self.trace.ops if o.device == d], lo, hi) for d in devs]
        return sum(per) // len(per)

    def idle_share(self):
        """Percent of the window in which no op ran on the device: 1 - (union
        of the device ops' intervals) / (window); None without device ops."""
        if not self.trace.ops:
            return None
        return 100.0 * (1.0 - self.busy_ns() / self.window_ns)

    def round_ops(self):
        """The window's ops of the round program, each with its class."""
        return [(o, self.ops.get(o.name)) for o in self.in_window(self.module)]


def per_layer(run, reading: Reading) -> dict:
    """The cell's per-layer metrics by their readers; a reader that finds
    nothing to read returns None, and its metric is left out of the line
    and named on standard error."""
    from chipbench import spec

    out = {}
    for m in spec.per_layer(run.bench, run.workload):
        value = spec.reader(m["name"], run.base)(reading)
        if value is None:
            run.log(f"per-layer metric {m['name']} found nothing to read in the trace")
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(reading: Reading, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps by the
    host span open in each."""
    lo, hi = reading.trace.window
    ops = reading.in_window()
    total: dict = {}
    for o in ops:
        key = f"{o.module}/{o.name}"
        total[key] = total.get(key, 0) + (min(o.end, hi) - max(o.start, lo))
    device_ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps([(o.start, o.end) for o in ops], lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[k, v / 1e9] for k, v in device_ops],
        "idle_gaps": [[span_at(reading.trace.spans, (s + e) // 2), (e - s) / 1e9] for s, e in idle],
    }


def reduce(run, reading: Reading) -> dict:
    """What a traced run adds to its result."""
    return {
        "metrics": per_layer(run, reading),
        "busy_s": reading.busy_ns() / 1e9,
        "window_s": reading.window_ns / 1e9,
        "breakdown": breakdown(reading),
    }
