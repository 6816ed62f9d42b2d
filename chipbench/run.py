"""Run one cell of the on-chip benchmark, once, in this process.

    python3 chipbench/run.py --workload medline.train --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic mix, limits and per-layer metrics are
found by name (``chipbench/spec.py``).  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with the reference, beside its limit.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import repro  # noqa: F401  (the system under test: without it there is no run)

    harness.enable_compile_cache()
    try:
        harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
