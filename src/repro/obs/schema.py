"""The JSONL event schema: what a run log may contain, and the validator
``python -m repro.obs.report --check`` (and CI's obs-smoke step) runs
against emitted files.

Every line is one JSON object with a ``kind`` and the stamps RunLogger
adds; per-kind required fields:

  run_meta  {kind, ts, t, program, meta}        + optional d (int)
  metrics   {kind, ts, t, data}                 + optional step (int)
  span      {kind, ts, t, name, dur_s, attrs}    + optional start_ns (int)
  event     {kind, ts, t, name, data}

``data``/``meta``/``attrs`` are open objects (forward-compatible: readers
must ignore unknown fields), but the stamps and discriminators are typed
strictly — the report tool and any downstream collector key on them.
"""
from __future__ import annotations

import json
from typing import Dict, List, Tuple

#: kind -> {field: required type(s)}; every kind also requires ts/t floats.
KINDS: Dict[str, Dict[str, tuple]] = {
    "run_meta": {"program": (str,), "meta": (dict,)},
    "metrics": {"data": (dict,)},
    "span": {"name": (str,), "dur_s": (int, float), "attrs": (dict,)},
    "event": {"name": (str,), "data": (dict,)},
}

_STAMPS = {"ts": (int, float), "t": (int, float)}


def validate_event(obj: object, lineno: int = 0) -> List[str]:
    """Schema errors for one parsed event (empty list = valid)."""
    where = f"line {lineno}: " if lineno else ""
    if not isinstance(obj, dict):
        return [f"{where}event is not an object"]
    kind = obj.get("kind")
    if kind not in KINDS:
        return [f"{where}unknown kind {kind!r} (expected one of {sorted(KINDS)})"]
    errors = []
    for field, types in {**_STAMPS, **KINDS[kind]}.items():
        v = obj.get(field)
        if v is None:
            errors.append(f"{where}{kind} event missing required field {field!r}")
        elif not isinstance(v, types) or isinstance(v, bool):
            errors.append(
                f"{where}{kind}.{field} has type {type(v).__name__}, "
                f"expected {'/'.join(t.__name__ for t in types)}"
            )
    if kind == "run_meta" and "d" in obj:
        if not isinstance(obj["d"], int) or isinstance(obj["d"], bool):
            errors.append(f"{where}run_meta.d must be an int")
    if kind == "metrics" and "step" in obj:
        if not isinstance(obj["step"], int) or isinstance(obj["step"], bool):
            errors.append(f"{where}metrics.step must be an int")
    if kind == "span" and "start_ns" in obj:
        if not isinstance(obj["start_ns"], int) or isinstance(obj["start_ns"], bool):
            errors.append(f"{where}span.start_ns must be an int")
    return errors


def load(path: str) -> Tuple[List[dict], List[str]]:
    """Parse a run log: (events, errors).  Unparseable lines become errors
    and are skipped; events are returned in file order regardless of
    validity (the report degrades gracefully, --check does not)."""
    events: List[dict] = []
    errors: List[str] = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"line {lineno}: not valid JSON ({e})")
                continue
            errors.extend(validate_event(obj, lineno))
            if isinstance(obj, dict):
                events.append(obj)
    if not events:
        errors.append("empty run log (no events)")
    elif events[0].get("kind") != "run_meta":
        errors.append("first event must be run_meta")
    return events, errors
