"""benchmarks/run.py SUITES is the single source of truth for the bench
harness — a bench module that isn't registered silently drops out of
``--only``, CI smoke, and ``--help``.  This pins registry completeness:
every ``benchmarks/bench_*.py`` stem is reachable through a suite runner,
and every registered suite lazily imports a module that exists.

Source-level checks only (no jax, no bench execution): the registry's
runners reference their modules via ``_m("bench_<stem>")`` literals.
"""
import importlib.util
import re
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1] / "benchmarks"


def _registered_modules():
    src = (BENCH_DIR / "run.py").read_text()
    return set(re.findall(r'_m\(["\'](bench_\w+)["\']\)', src))


def test_every_bench_module_is_registered():
    on_disk = {p.stem for p in BENCH_DIR.glob("bench_*.py")}
    registered = _registered_modules()
    missing = on_disk - registered
    assert not missing, (
        f"bench modules not reachable from run.py SUITES: {sorted(missing)}"
    )


def test_every_registered_module_exists():
    on_disk = {p.stem for p in BENCH_DIR.glob("bench_*.py")}
    stale = _registered_modules() - on_disk
    assert not stale, f"run.py SUITES references missing modules: {sorted(stale)}"


def test_suite_names_cover_json_baselines():
    """Every committed BENCH_*.json baseline has a producer: some bench
    module mentions it by name (a baseline whose producer was deleted would
    gate nothing and rot silently)."""
    baselines = (BENCH_DIR / "baselines").glob("BENCH_*.json")
    sources = "".join(p.read_text() for p in BENCH_DIR.glob("bench_*.py"))
    orphans = [b.name for b in baselines if b.name not in sources]
    assert not orphans, f"baselines with no producing bench module: {orphans}"


def _check_regression():
    spec = importlib.util.spec_from_file_location(
        "check_regression", BENCH_DIR / "check_regression.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize(
    "key, sense",
    [
        ("us_per_step", "lower"),  # a time, though it holds "_per_s"
        ("p99_ms", "lower"),
        ("touched_rows_per_s", "higher"),
        ("screen_speedup", "higher"),
        ("scaling_4", None),  # emulated-mesh scaling is recorded, not gated
        ("all_reduces", None),
        ("max_ms", None),
    ],
)
def test_gate_direction(key, sense):
    assert _check_regression().direction(key) == sense
