"""What a run reads by name: the cell from ``BENCHMARK.json``, and the files
of its configuration, its traffic mix, its limits and its per-layer metrics.

    chipbench/configs/<config>.json     sizes, hypers, generator, reference
    chipbench/traffic/<traffic>.json    the mix, with the driver that runs it
    chipbench/limits/<workload>.json    each compared number's limit
    chipbench/metrics/<metric>.py       ``read(run) -> float | None``

Adding a cell adds files; no file here or in the drivers names a cell.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class SpecError(ValueError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SpecError(f"no workload {workload!r} in BENCHMARK.json")


def config(name: str, base: Path = HERE) -> dict:
    return load_json(base / "configs" / f"{name}.json")


def traffic(name: str, base: Path = HERE) -> dict:
    return load_json(base / "traffic" / f"{name}.json")


def limits(workload: str, base: Path = HERE) -> dict:
    path = base / "limits" / f"{workload}.json"
    return load_json(path) if path.exists() else {}


def end_to_end(bench: dict, workload: str) -> list:
    """The end-to-end metrics that this cell reports."""
    return [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]


def per_layer(bench: dict, workload: str) -> list:
    """The per-layer metrics that this cell reports: those that list it,
    and those without a list whose end-to-end metric the cell reports."""
    mine = {m["name"] for m in end_to_end(bench, workload)}
    out = []
    for m in bench["per_layer"]:
        listed = m.get("workloads")
        if (workload in listed) if listed is not None else (m["moves"] in mine):
            out.append(m)
    return out


def reader(metric: str, base: Path = HERE):
    """The ``read`` function of ``chipbench/metrics/<metric>.py``."""
    path = base / "metrics" / f"{metric}.py"
    if not path.exists():
        raise SpecError(f"no reader {path} for per-layer metric {metric!r}")
    mod_spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
