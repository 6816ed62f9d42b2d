"""The lazy step's phases in a traced window: each round-program op mapped
to the phase the program named it with, and the device time of each.

The program wraps each phase of its lazy step in a ``jax.named_scope``
(``lazy.gather``, ``lazy.kernel``, ``lazy.scatter``, ``lazy.flush``), and
the compiled HLO carries the scope in each op's
``metadata={op_name=".../while/body/lazy.scatter/scatter"}``.  The
benchmark keeps its own copy of the four names: it reads what the program
says and imports nothing of it to do so.

An op's phase:

* the innermost ``lazy.*`` name in its own ``op_name``;
* a fusion takes its root's phase, found by these same rules inside the
  fused computation.  A fusion that merges ops of two phases counts whole
  in its root's phase: that is the limit of this split;
* an op without one (a layout ``copy`` that the compiler inserts, a
  kernel's custom call) takes the phase of the nearest scoped op that reads
  its result, breadth-first through its users and on through unscoped
  copies, bitcasts, reshapes, transposes and tuples; of two users equally
  near, the later in the scheduled program (the last reader: a copy that a
  gather reads and a scatter then writes into serves the scatter).  If no
  user is scoped, the nearest scoped op it reads, by the same rules;
  otherwise the op stays unphased.

The round program's compiled HLO text is ``reading.hlo`` where the reading
carries it.  Otherwise the round program is built again from the reading's
configuration and compiled (from the compile cache, after the run's own
compile), and used only if it holds the same ops as the module that was
traced.  A program without the scopes gives no phases, and the readers
that use this module then find nothing to read.
"""

from __future__ import annotations

import functools
import json
import re
import sys
import traceback
from typing import NamedTuple, Optional

from chipbench import trace

GATHER, KERNEL, SCATTER, FLUSH = "lazy.gather", "lazy.kernel", "lazy.scatter", "lazy.flush"
PHASES = (GATHER, KERNEL, SCATTER, FLUSH)
PASS_THROUGH = (
    "copy", "copy-start", "copy-done", "bitcast", "reshape", "transpose", "tuple",
    "get-tuple-element",
)
IN_PLACE = ("scatter", "dynamic-update-slice")

_PHASE = re.compile(r"(?<![\w.])(lazy\.(?:gather|kernel|scatter|flush))(?![\w.])")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_PARAM = re.compile(r"\sparameter\((\d+)\)")


class Instr(NamedTuple):
    name: str
    opcode: str
    operands: tuple  # names of the instructions of the same computation it reads
    scope: Optional[str]  # the innermost lazy.* name in its own op_name
    calls: dict  # {"calls": [...], "body": [...], ...} as trace.parse_hlo gives
    root: bool
    param: int  # a parameter's number, else -1


def scope_of(op_name: str) -> Optional[str]:
    found = _PHASE.findall(op_name)
    return found[-1] if found else None


def _operands(rest: str, opcode_end: int) -> tuple:
    """The ``%names`` inside the opcode's balanced parentheses."""
    depth = 0
    for j in range(opcode_end - 1, len(rest)):
        if rest[j] == "(":
            depth += 1
        elif rest[j] == ")":
            depth -= 1
            if depth == 0:
                return tuple(_OPERAND.findall(rest[opcode_end:j]))
    return ()


def parse(text: str) -> tuple:
    """``(entry, comps)``: ``comps[name]`` lists the computation's
    instructions as :class:`Instr`, in the order of the text (for a
    scheduled module, the order they run in)."""
    comps: dict = {}
    entry, cur = None, None
    for line in text.splitlines():
        h = trace._HEADER.match(line)
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
        m = trace._INSTR.match(line)
        if not m:
            continue
        rest = " " + m.group(2)
        op = trace._OPCODE.search(rest)
        calls: dict = {}
        for key, val in trace._CALLS.findall(rest):
            calls.setdefault(key, []).extend(re.findall(r"[\w.\-]+", val.replace("%", " ")))
        op_name = _OP_NAME.search(rest)
        comps[cur].append(
            Instr(
                name=m.group(1),
                opcode=op.group(1) if op else "",
                operands=_operands(rest, op.end()) if op else (),
                scope=scope_of(op_name.group(1)) if op_name else None,
                calls=calls,
                root=line.lstrip().startswith("ROOT"),
                param=int(p.group(1)) if (p := _PARAM.search(rest)) else -1,
            )
        )
    return entry, comps


class _Resolver:
    """Phases of one module's instructions, by the rules of the module's
    docstring.  ``caller``: the ``(computation, index)`` of the fusion whose
    fused computation is being read."""

    def __init__(self, comps: dict):
        self.comps = comps
        self.graphs: dict = {}
        self.memo: dict = {}

    def _graph(self, comp: str) -> tuple:
        """``(index of each name, users of each instruction)``."""
        if comp not in self.graphs:
            instrs = self.comps[comp]
            index = {ins.name: k for k, ins in enumerate(instrs)}
            users = [[] for _ in instrs]
            for k, ins in enumerate(instrs):
                for o in ins.operands:
                    if o in index:
                        users[index[o]].append(k)
            self.graphs[comp] = (index, users)
        return self.graphs[comp]

    def own(self, comp: str, k: int) -> Optional[str]:
        """The phase an op names itself: its scope, or a fusion's root's."""
        ins = self.comps[comp][k]
        if ins.opcode == "fusion":
            for callee in ins.calls.get("calls", []):
                instrs = self.comps.get(callee)
                if instrs:
                    roots = [j for j, x in enumerate(instrs) if x.root] or [len(instrs) - 1]
                    phase = self.phase(callee, roots[-1], caller=(comp, k))
                    if phase:
                        return phase
        return ins.scope

    def written(self, comp: str, k: int, caller=None) -> Optional[str]:
        """For a scatter or dynamic-update-slice, the phase of the buffer it
        writes into: the op that made its first operand, back through
        unscoped copies and bitcasts and out of its fusion."""
        instrs = self.comps[comp]
        if instrs[k].opcode not in IN_PLACE or not instrs[k].operands:
            return None
        return self._made(comp, instrs[k].operands[0], caller)

    def _made(self, comp: str, name: str, caller) -> Optional[str]:
        index, _ = self._graph(comp)
        j = index.get(name)
        while j is not None:
            ins = self.comps[comp][j]
            phase = self.own(comp, j)
            if phase:
                return phase
            if ins.opcode == "parameter" and caller is not None:
                outer, at = caller
                args = self.comps[outer][at].operands
                return self._made(outer, args[ins.param], None) if ins.param < len(args) else None
            if ins.opcode not in PASS_THROUGH or not ins.operands:
                return None
            j = index.get(ins.operands[0])
        return None

    def _nearest(self, comp: str, k: int, step) -> Optional[str]:
        instrs = self.comps[comp]
        seen, frontier = {k}, [k]
        while frontier:
            near = []
            for j in frontier:
                for u in step(j):
                    if u not in seen:
                        seen.add(u)
                        near.append(u)
            scoped = [u for u in near if self.own(comp, u)]
            if scoped:
                return self.own(comp, max(scoped))
            frontier = [u for u in near if instrs[u].opcode in PASS_THROUGH]
        return None

    def phase(self, comp: str, k: int, caller=None) -> Optional[str]:
        key = (comp, k)
        if key not in self.memo:
            index, users = self._graph(comp)
            instrs = self.comps[comp]
            self.memo[key] = (
                self.own(comp, k)
                or self.written(comp, k, caller)
                or self._nearest(comp, k, lambda j: users[j])
                or self._nearest(
                    comp, k, lambda j: [index[o] for o in instrs[j].operands if o in index]
                )
            )
        return self.memo[key]


@functools.lru_cache(maxsize=4)
def op_table(text: str) -> dict:
    """``{op: (opcode, phase or None)}`` for every op that runs as a unit on
    the device: the instructions of the entry computation and of the
    computations that its control flow runs (as ``trace.classify``)."""
    entry, comps = parse(text)
    res = _Resolver(comps)
    out: dict = {}
    todo, seen = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for k, ins in enumerate(comps[comp]):
            out[ins.name] = (ins.opcode, res.phase(comp, k))
            for key in trace._CONTROL:
                todo.extend(ins.calls.get(key, []))
    return out


@functools.lru_cache(maxsize=2)
def _compile_round_program(config_json: str) -> str:
    """The compiled HLO text of ``make_round_fn(cfg, "lazy")`` for the
    configuration, at its round and batch shape, on the first device."""
    import jax
    import jax.numpy as jnp
    from repro.core import SparseBatch, init_state, make_round_fn

    from chipbench.drivers.train import linear_config

    config = json.loads(config_json)
    cfg = linear_config(config)
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    state = jax.tree.map(
        lambda a: placed(a.shape, a.dtype), jax.eval_shape(lambda: init_state(cfg))
    )
    R, B, P = config["train"]["round_len"], config["train"]["batch"], config["p_max"]
    batches = SparseBatch(
        idx=placed((R, B, P), jnp.int32), val=placed((R, B, P), jnp.float32),
        y=placed((R, B), jnp.float32),
    )
    return make_round_fn(cfg, "lazy").lower(state, batches).compile().as_text()


def round_hlo(r) -> str:
    """The round program's compiled HLO text for the reading ``r``; "" where
    there is none to be had that matches the traced module."""
    text = getattr(r, "hlo", "")
    if text or not (r.module and r.config):
        return text
    try:
        text = _compile_round_program(json.dumps(r.config, sort_keys=True))
    except Exception:  # a reader finds nothing rather than fail the run
        print("phases: the round program did not compile again", file=sys.stderr)
        traceback.print_exc()
        return ""
    if trace.module_name(text) != r.module or (r.ops and set(trace.classify(text)) != set(r.ops)):
        print("phases: the round program compiled again is not the traced one", file=sys.stderr)
        return ""
    return text


def split(r) -> Optional[dict]:
    """``{phase: ns}`` (``None`` for unphased) of the window's round-program
    ops; each op's whole event counts, as the other readers count it.  A
    ``while`` or ``conditional`` is left out: its event spans the ops it
    runs.  None where the program names no phase."""
    table = op_table(round_hlo(r))
    if not any(phase for _, phase in table.values()):
        return None
    out = dict.fromkeys(PHASES + (None,), 0)
    for o in r.in_window(r.module):
        opcode, phase = table.get(o.name, ("", None))
        if opcode not in trace.CONTAINERS:
            out[phase] += o.end - o.start
    return out


def phase_ns(r, phase: str) -> Optional[int]:
    """Device ns of ``phase`` in the window; None where the program has no
    op in that phase."""
    s = split(r)
    if s is None or phase not in {p for _, p in op_table(round_hlo(r)).values()}:
        return None
    return s[phase]


def per_step_us(r, phase: str) -> Optional[float]:
    ns = phase_ns(r, phase)
    return None if ns is None or not r.steps else ns / 1e3 / r.steps
