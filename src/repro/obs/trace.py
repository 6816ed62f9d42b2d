"""Tracing spans: structured wall-time accounting around phase boundaries
(prefill / decode / learn / flush / sweep stage), with optional compile-
cache deltas and ``jax.profiler`` annotation.

    with obs.span("sweep.stage", stage=3, lam1=1e-4):
        ... one warm-started stage ...

A span measures wall time between enter and exit, wraps the body in a
``jax.profiler.TraceAnnotation`` (so the region is visible in a collected
profile), and — when a RunLogger is active (:mod:`repro.obs.sinks`) —
emits a ``span`` JSONL event carrying the duration, the caller's
attributes, and, if a tracker was given, the jit-cache delta across the
span (compiles attributable to this phase).  With no active logger the
cost is three clock reads.

The event's ``start_ns`` is read from the clock the profiler stamps its
host events with (``time.time_ns()``, the wall clock), just before the
annotation opens.  A collected profile stores its events relative to the
``profile_start_time`` of its "Task Environment" plane, on that same
clock, so a span in a ``--log`` file lines up with its annotation in a
``--profile`` trace of the same run: annotation start = start_ns -
profile_start_time.

``profile_to(dir)`` wraps ``jax.profiler.start_trace/stop_trace`` for the
launch CLIs' ``--profile DIR`` flag; ``step_annotation(i)`` is the
``StepTraceAnnotation`` passthrough for per-step profiler markup in
training loops.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax

from . import sinks
from .compile_tracker import CompileTracker


@contextlib.contextmanager
def span(name: str, tracker: Optional[CompileTracker] = None, **attrs):
    """Time a phase; emit a ``span`` event to the active RunLogger (no-op
    without one).  ``tracker`` adds the compile-cache delta across the
    span to the event (which functions compiled, and how many entries)."""
    before = tracker.counts() if tracker is not None else None
    start_ns = time.time_ns()  # the profiler's clock
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(name):
        yield
    dur = time.monotonic() - t0
    logger = sinks.active_logger()
    if logger is not None:
        if before is not None:
            after = tracker.counts()
            delta = {k: after[k] - before.get(k, 0) for k in after}
            attrs = {**attrs, "compiles": delta}
        logger.span(name, dur, start_ns=start_ns, **attrs)


def step_annotation(step: int):
    """``jax.profiler.StepTraceAnnotation`` for training-loop step markup
    (groups device activity per step in the collected profile)."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


@contextlib.contextmanager
def profile_to(profile_dir: Optional[str]):
    """Collect a jax profiler trace into ``profile_dir`` for the duration
    of the block (None: no-op) — the ``--profile DIR`` flag body."""
    if not profile_dir:
        yield
        return
    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
