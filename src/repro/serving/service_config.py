"""The shared serving-surface configuration (`ServiceConfig`) and the
config-pinning rule every linear serving frontend applies at construction.

`LinearService` grew its knobs one kwarg at a time (p_max, micro_batch,
max_delay, backend, solver, metrics); `MultiLinearService` needs the same
set per service, and a kwarg pile does not generalize to slots.  The knobs
now live in one frozen dataclass shared by both services:

    LinearService(cfg, service=ServiceConfig(p_max=64, micro_batch=8))
    MultiLinearService(cfg, n_slots=64, service=ServiceConfig(...))

The old `LinearService(cfg, p_max=..., micro_batch=...)` kwargs finished
their deprecation cycle and are gone — a pre-ServiceConfig call site fails
with TypeError; tests/serving/test_service_config.py pins that
`service=ServiceConfig(...)` is the only construction path.

`pin_config` is the other construction-time rule both services share: a
live service must never change its kernel backend or solver because
trace-time context ($REPRO_BACKEND / $REPRO_SOLVER or a `use_backend()`
scope) changed under it — so every
deferred LinearConfig field is resolved to a concrete value exactly once,
before the first jit is built.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.linear_trainer import LinearConfig


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Serving-frontend knobs shared by LinearService and MultiLinearService.

    * ``p_max`` — features per request pad to this (the trainer's padding
      convention makes it exact).
    * ``micro_batch`` — largest power-of-two example bucket; the admission
      queue flushes in binary decompositions of the waiting count.
    * ``max_delay`` — admission-queue deadline (seconds in the caller's
      clock) before a sub-``micro_batch`` group flushes anyway.
    * ``backend`` / ``solver`` — explicit kernel backend / update rule;
      None defers to the config (then env / platform default), pinned
      concrete at construction by :func:`pin_config`.
    * ``metrics`` — a ServingMetrics/MetricsRegistry to report into
      (None: the service makes its own).
    * ``per_tenant_cap`` — QoS: max queued learn examples per tenant tag
      before the admission queue rejects (MultiLinearService; None = no
      cap).
    """

    p_max: int = 128
    micro_batch: int = 8
    max_delay: float = 0.0
    backend: Optional[str] = None
    solver: Optional[str] = None
    metrics: Optional[object] = None
    per_tenant_cap: Optional[int] = None

    def __post_init__(self):
        assert self.p_max >= 1
        assert self.micro_batch >= 1 and self.micro_batch & (self.micro_batch - 1) == 0, (
            f"micro_batch must be a power of two, got {self.micro_batch}"
        )
        if self.per_tenant_cap is not None:
            assert self.per_tenant_cap >= 1


def pin_config(cfg: LinearConfig, service: ServiceConfig) -> LinearConfig:
    """Resolve every deferred LinearConfig field to a concrete value for a
    live service (backend, solver), checking the service's explicit
    choices against the config's.  Every jit the service builds —
    now or in a later swap rebuild — closes over the same resolved choices,
    whatever use_backend()/$REPRO_* context happens to be live when it
    first traces."""
    from repro import backend as kernel_backend
    from repro import solvers as solver_registry

    if service.backend is not None and cfg.backend is not None and service.backend != cfg.backend:
        raise ValueError(
            f"conflicting explicit backends: cfg.backend={cfg.backend!r} "
            f"vs backend={service.backend!r}"
        )
    if service.solver is not None and cfg.solver is not None and service.solver != cfg.solver:
        raise ValueError(
            f"conflicting explicit solvers: cfg.solver={cfg.solver!r} "
            f"vs solver={service.solver!r}"
        )
    if cfg.backend is None:
        cfg = dataclasses.replace(
            cfg, backend=service.backend or kernel_backend.resolve(None).name
        )
    if cfg.solver is None:
        cfg = dataclasses.replace(
            cfg, solver=(service.solver or solver_registry.for_config(cfg).name)
        )
    return cfg


def binary_buckets(micro_batch: int) -> tuple:
    """(1, 2, 4, ..., micro_batch) — the complete example-count compile set
    of a binary-decomposition micro-batching frontend."""
    assert micro_batch >= 1 and micro_batch & (micro_batch - 1) == 0, (
        f"micro_batch must be a power of two, got {micro_batch}"
    )
    out, b = [], 1
    while b <= micro_batch:
        out.append(b)
        b *= 2
    return tuple(out)
