"""Benchmark regression gate: compare a fresh BENCH_*.json against its
committed baseline in benchmarks/baselines/ and fail on regression.

Only performance leaves are gated, direction-aware:

  * lower-is-better  (``us_per``, ``_ms``, ``elapsed_s``, ``p50``/``p99``):
    fail when fresh > baseline * (1 + tol)
  * higher-is-better (``tok_s``, ``speedup``, ``examples_s``, ``cfg_steps_s``,
    or a key ending in ``_per_s``): fail when fresh < baseline * (1 - tol)

Everything else (counters, workload echo, compile counts) is ignored — those
are asserted by tests, not tolerance-gated.  A gated key present in the
baseline but missing from the fresh run is a failure (a silently dropped
metric must not pass the gate).  Default tolerance is +-30%: wide enough for
shared-CI jitter, tight enough to catch a lost vmap or an accidental O(d)
hot path.

Usage:
  python benchmarks/check_regression.py BENCH_sweeps.json \
      --baseline benchmarks/baselines/BENCH_sweeps.json [--tol 0.3]
  python benchmarks/check_regression.py BENCH_serving.json --update
      # refresh the committed baseline from a trusted run
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

LOWER_IS_BETTER = ("us_per", "_ms", "elapsed_s", "p50", "p99")
HIGHER_IS_BETTER = ("tok_s", "speedup", "examples_s", "cfg_steps_s")
# a suffix only: ``us_per_step`` is a time, ``touched_rows_per_s`` a rate
RATE_SUFFIX = "_per_s"
# single-sample extremes: one scheduler stall on a shared runner moves the
# max of a run arbitrarily far — informative in the artifact, never gated
UNGATED = ("max_ms",)


def direction(key: str):
    """'higher' | 'lower' | None for a leaf key (higher wins ties: a rate
    named like a time, e.g. tokens_per_elapsed_s, is still a rate)."""
    if any(p in key for p in UNGATED):
        return None
    if any(p in key for p in HIGHER_IS_BETTER) or key.endswith(RATE_SUFFIX):
        return "higher"
    if any(p in key for p in LOWER_IS_BETTER):
        return "lower"
    return None


def walk(base, fresh, tol, prefix=""):
    """Yield (path, baseline, fresh, verdict) for every gated leaf."""
    if isinstance(base, dict):
        for key, bval in base.items():
            path = f"{prefix}.{key}" if prefix else key
            if isinstance(bval, dict):
                yield from walk(bval, (fresh or {}).get(key), tol, path)
                continue
            sense = direction(key)
            if sense is None or not isinstance(bval, (int, float)) or isinstance(bval, bool):
                continue
            fval = None if not isinstance(fresh, dict) else fresh.get(key)
            if not isinstance(fval, (int, float)) or isinstance(fval, bool):
                yield (path, bval, fval, "missing")
            elif sense == "lower" and fval > bval * (1.0 + tol):
                yield (path, bval, fval, "regressed")
            elif sense == "higher" and fval < bval * (1.0 - tol):
                yield (path, bval, fval, "regressed")
            else:
                yield (path, bval, fval, "ok")


def main() -> int:
    ap = argparse.ArgumentParser(description="benchmark regression gate")
    ap.add_argument("fresh", help="freshly produced BENCH_*.json")
    ap.add_argument(
        "--baseline",
        default=None,
        help="committed baseline (default: benchmarks/baselines/<name of fresh>)",
    )
    ap.add_argument("--tol", type=float, default=0.3, help="relative tolerance (default 0.30)")
    ap.add_argument("--update", action="store_true", help="copy fresh over the baseline and exit")
    args = ap.parse_args()

    fresh_path = Path(args.fresh)
    base_path = Path(args.baseline or Path(__file__).parent / "baselines" / fresh_path.name)
    if args.update:
        base_path.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(fresh_path, base_path)
        print(f"baseline updated: {base_path}")
        return 0
    if not base_path.exists():
        print(f"FAIL: no committed baseline at {base_path} (run with --update to create)")
        return 1

    with open(base_path) as f:
        base = json.load(f)
    with open(fresh_path) as f:
        fresh = json.load(f)

    failures = 0
    print(f"gate: {fresh_path.name} vs {base_path} (tol +-{args.tol:.0%})")
    for path, bval, fval, verdict in walk(base, fresh, args.tol):
        if verdict == "ok":
            print(f"  ok        {path}: {bval:.4g} -> {fval:.4g}")
            continue
        failures += 1
        shown = "absent" if fval is None else f"{fval:.4g}"
        print(f"  {verdict.upper():9s} {path}: baseline {bval:.4g}, fresh {shown}")
    if failures:
        print(
            f"FAIL: {failures} gated metric(s) regressed beyond +-{args.tol:.0%} "
            f"vs baseline {base_path} (re-baseline a trusted run with --update)"
        )
        return 1
    print("PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
