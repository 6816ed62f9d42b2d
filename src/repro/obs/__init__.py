"""repro.obs — observability for the lazy elastic-net stack.

Three pillars:

* **Jit-safe metrics** — :class:`MetricsState` rides the compiled scan
  carry (touched coords, catch-up span histogram, loss EMA, flush nnz)
  with zero recompiles and bitwise-unchanged fits;
  :class:`MetricsRegistry` is the host-side accumulator (counters /
  gauges / p50-p99 histograms) everything reports into.
* **Tracing** — :func:`span` wraps phase boundaries in wall-time +
  ``jax.profiler`` annotation and emits structured events;
  :class:`CompileTracker` / :func:`assert_no_new_compiles` generalize the
  serving engine's jit-cache introspection into a reusable invariant.
* **Export** — :func:`run_logger` JSONL sinks, Prometheus text, and
  ``python -m repro.obs.report`` (the paper-style lazy-work table).
"""
from .compile_tracker import (
    CompileTracker,
    RecompileError,
    assert_no_new_compiles,
    cache_size,
    compile_counts,
)
from .instrument import (
    init_batched_metrics,
    init_obs,
    make_obs_round_fn,
    make_obs_step,
    make_obs_step_hp,
    metrics_axes,
    pull_metrics,
)
from .metrics_state import (
    SPAN_BUCKETS,
    MetricsState,
    init_metrics,
    record_flush,
    record_step,
    span_bucket,
    summarize,
)
from .registry import MetricsRegistry
from .sinks import (
    JsonlSink,
    RunLogger,
    active_logger,
    prometheus_text,
    run_logger,
)
from .trace import profile_to, span, step_annotation

__all__ = [
    "CompileTracker",
    "RecompileError",
    "assert_no_new_compiles",
    "cache_size",
    "compile_counts",
    "init_batched_metrics",
    "init_obs",
    "make_obs_round_fn",
    "make_obs_step",
    "make_obs_step_hp",
    "metrics_axes",
    "pull_metrics",
    "SPAN_BUCKETS",
    "MetricsState",
    "init_metrics",
    "record_flush",
    "record_step",
    "span_bucket",
    "summarize",
    "MetricsRegistry",
    "JsonlSink",
    "RunLogger",
    "active_logger",
    "prometheus_text",
    "run_logger",
    "profile_to",
    "span",
    "step_annotation",
]
