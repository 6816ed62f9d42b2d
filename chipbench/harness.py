"""One run of one cell: device check, the cell's driver, the result line.

The driver named by the traffic mix (``chipbench/drivers/<driver>.py``)
builds the system under test, warms it up, measures for ``seconds`` and
checks what the timed path produced against the plain reference; this
module frames that with what every run shares.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import math
import sys
import time
from pathlib import Path
from typing import Callable, Optional

from chipbench import spec

CACHE_DIR = spec.ROOT / ".jax_cache"
OUT_DIR = spec.ROOT / "chipbench_out"


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    workload: str
    seed: int
    seconds: float
    trace: bool
    t_start: float
    bench: dict
    cell: dict
    config: dict
    traffic: dict
    limits: dict
    fault: Optional[str] = None  # a planted fault (tests only)
    out_dir: Path = OUT_DIR
    base: Path = spec.HERE  # where the cell's files were found

    def log(self, msg: str) -> None:
        print(f"[{self.workload}] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> None:
    """JAX's persistent cache at one fixed directory inside the checkout
    (the path is part of the key, so it never moves), for every program
    however quick to compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if require_chip and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileWatch:
    """Counts the programs JAX compiles or loads from its cache, from
    anywhere in the process, while it is open."""

    EVENTS = ("backend_compile", "cache_retrieval")

    def __init__(self):
        import jax

        self.events: list = []
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **kw) -> None:
        if self.open and any(e in name for e in self.EVENTS):
            self.events.append(name)

    @contextlib.contextmanager
    def window(self, tracker, tag: str):
        """No compile inside: the tracked jits keep their cache sizes, and
        no program is compiled or loaded at all."""
        self.events.clear()
        self.open = True
        try:
            with tracker.assert_no_new_compiles(tag):
                yield
        finally:
            self.open = False
        if self.events:
            raise RuntimeError(f"{len(self.events)} compiles inside the window ({tag})")


def verdict(values: dict, limits: dict) -> tuple:
    """``(correct, checks)``: every number at or under its limit; a number
    without a limit, or not finite, is not correct."""
    checks = {}
    ok = True
    for name, value in values.items():
        limit = limits.get(name, {}).get("limit")
        checks[name] = {"value": value, "limit": limit}
        if limit is None or not math.isfinite(value) or value > limit:
            ok = False
    return ok, checks


def make_run(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: Optional[float] = None,
    root: Path = spec.ROOT,
    config: Optional[dict] = None,
    traffic: Optional[dict] = None,
    limits: Optional[dict] = None,
    fault: Optional[str] = None,
    out_dir: Path = OUT_DIR,
) -> Run:
    """The run's cell and files, found by name under ``root``.  The keyword
    arguments let tests and calibration drive a changed copy."""
    base = Path(root) / "chipbench"
    bench = spec.benchmark(root)
    cell = spec.cell(bench, workload)
    return Run(
        workload=workload,
        seed=seed,
        seconds=seconds,
        trace=trace,
        t_start=time.monotonic() if t_start is None else t_start,
        bench=bench,
        cell=cell,
        config=config or spec.config(cell["config"], base),
        traffic=traffic or spec.traffic(cell["traffic"], base),
        limits=spec.limits(workload, base) if limits is None else limits,
        fault=fault,
        out_dir=out_dir,
        base=base,
    )


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    require_chip: bool = True,
    emit: Callable[[str], None] = print,
    **kw,
) -> dict:
    """Run the cell, print its result line and return it (a dict).
    ``require_chip=False`` skips the look for a TPU (tests on the CPU)."""
    run = make_run(workload, seed, seconds, trace, **kw)
    device = device_info(run.cell["chips"], require_chip)
    driver = importlib.import_module(f"chipbench.drivers.{run.traffic['driver']}")
    out = driver.run(run)
    correct, checks = verdict(out["checks"], run.limits)
    device = {**device, "memory_peak_bytes": out["memory_peak_bytes"]}
    if trace:
        device.update(busy_s=out["busy_s"], window_s=out["window_s"])
    result = {
        "correct": correct and out["failed"] == 0,
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": out["metrics"],
        "device": device,
    }
    if trace and out.get("breakdown"):
        result["breakdown"] = out["breakdown"]
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    emit(json.dumps(result))
    return result
