"""Export sinks: structured JSONL event logs and Prometheus text.

One run = one ``RunLogger`` = one JSONL file; every line is a single event
object stamped with wall time (``ts``, epoch seconds) and monotonic offset
(``t``, seconds since the logger opened).  The event vocabulary — run_meta
/ metrics / span / event — is defined and validated by
:mod:`repro.obs.schema`; ``python -m repro.obs.report`` consumes the files.

Library code never takes a logger parameter: it emits through the active
logger installed by :func:`run_logger` (a context manager the launch CLIs
enter when ``--metrics-out`` is given).  With no active logger every emit
is a no-op, so instrumented code paths cost nothing in ordinary runs.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import Dict, List, Optional

from .registry import MetricsRegistry

_ACTIVE: List["RunLogger"] = []
_LOCK = threading.Lock()


def active_logger() -> Optional["RunLogger"]:
    """The innermost live RunLogger, or None (emits become no-ops)."""
    return _ACTIVE[-1] if _ACTIVE else None


class JsonlSink:
    """Append-only JSONL file; one json object per line, flushed per event
    (a killed run keeps every event it reported)."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "w")

    def emit(self, event: Dict[str, object]) -> None:
        self._f.write(json.dumps(event, default=_jsonable) + "\n")
        self._f.flush()

    def close(self) -> None:
        self._f.close()


def _jsonable(x):
    """Last-resort coercion for numpy scalars/arrays riding in payloads."""
    if hasattr(x, "item") and getattr(x, "ndim", 1) == 0:
        return x.item()
    if hasattr(x, "tolist"):
        return x.tolist()
    return str(x)


class RunLogger:
    """Stamps and writes schema-shaped events for one run."""

    def __init__(self, sink: JsonlSink, clock=time.monotonic):
        self.sink = sink
        self._clock = clock
        self._t0 = clock()

    def _emit(self, kind: str, payload: Dict[str, object]) -> None:
        event = {"kind": kind, "ts": time.time(), "t": self._clock() - self._t0}
        event.update(payload)
        self.sink.emit(event)

    # -- the event vocabulary (repro.obs.schema) ----------------------------

    def run_meta(self, program: str, d: Optional[int] = None, **meta) -> None:
        """First line of a run: what program produced it and the dense
        coordinate count ``d`` the work ratio divides by."""
        payload: Dict[str, object] = {"program": program, "meta": meta}
        if d is not None:
            payload["d"] = int(d)
        self._emit("run_meta", payload)

    def metrics(self, data: Dict[str, object], step: Optional[int] = None) -> None:
        """Periodic counters/gauges snapshot (flat-ish dict of numbers)."""
        payload: Dict[str, object] = {"data": data}
        if step is not None:
            payload["step"] = int(step)
        self._emit("metrics", payload)

    def span(self, name: str, dur_s: float, start_ns: Optional[int] = None, **attrs) -> None:
        """A completed tracing span (obs.trace.span emits these);
        ``start_ns`` on the profiler's clock (see obs.trace.span)."""
        payload: Dict[str, object] = {"name": name, "dur_s": float(dur_s), "attrs": attrs}
        if start_ns is not None:
            payload["start_ns"] = int(start_ns)
        self._emit("span", payload)

    def event(self, name: str, **data) -> None:
        """A rare point event (flush, round boundary, weight swap, ...)."""
        self._emit("event", {"name": name, "data": data})

    def registry_snapshot(self, registry: MetricsRegistry, step: Optional[int] = None) -> None:
        self.metrics(registry.snapshot(), step=step)

    def close(self) -> None:
        self.sink.close()


@contextlib.contextmanager
def run_logger(path: Optional[str], program: str, d: Optional[int] = None, **meta):
    """Open a RunLogger on ``path``, install it as the active logger (so
    library spans/events reach it), emit run_meta, and tear down on exit.
    ``path=None`` yields None and installs nothing — callers can wrap the
    run unconditionally."""
    if path is None:
        yield None
        return
    logger = RunLogger(JsonlSink(path))
    logger.run_meta(program, d=d, **meta)
    with _LOCK:
        _ACTIVE.append(logger)
    try:
        yield logger
    finally:
        with _LOCK:
            _ACTIVE.remove(logger)
        logger.close()


def prometheus_text(registry: MetricsRegistry, prefix: str = "repro") -> str:
    """Prometheus text exposition of a registry (counters as ``*_total``,
    gauges plain, histograms as quantile summaries)."""
    return registry.to_prometheus(prefix=prefix)
