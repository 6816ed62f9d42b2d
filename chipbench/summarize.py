"""Summarize one cell's measurement files (``chipbench/measure.sh``): per
set, each metric's median and quartile spread as a share of the median, the
compared numbers' largest readings, the calibration readings, and the
traced runs' device lines and breakdowns.

    python3 chipbench/summarize.py chipbench_out/measure medline.train
"""

import json
import statistics
import sys
from pathlib import Path


def results(path: Path) -> list:
    if not path.exists():
        return []
    return [json.loads(x) for x in open(path) if x.startswith("{") and '"correct"' in x]


def spread(values: list) -> tuple:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return q2, (q3 - q1) / q2


def main(out: str, cell: str) -> None:
    out = Path(out)
    for name in ("warm", "A", "B", "T"):
        rs = results(out / f"{name}.{cell}.out")
        print(f"{cell} {name}: {len(rs)} runs, correct {[r['correct'] for r in rs]}")
        for k in sorted({k for r in rs for k in r["metrics"]}):
            v = [r["metrics"][k]["value"] for r in rs if k in r["metrics"]]
            if len(v) >= 3:
                med, sp = spread(v)
                print(f"  {k}: median {med!r} spread {sp!r} values {v}")
            else:
                print(f"  {k}: {v}")
        for k in sorted({k for r in rs for k in r.get("checks", {})}):
            v = [r["checks"][k]["value"] for r in rs]
            print(f"  check {k}: max {max(v)!r}")
        for r in rs:
            print(f"  device {json.dumps(r['device'])}")
            if "breakdown" in r:
                print(f"  breakdown {json.dumps(r['breakdown'])}")
    cal = out / f"cal.{cell}.out"
    if cal.exists():
        lines = [json.loads(x) for x in open(cal) if x.startswith("{")]
        kinds = sorted({k for x in lines for k in x if isinstance(x[k], dict)})
        for kind in kinds:
            rows = [x[kind] for x in lines if kind in x]
            for k in sorted(rows[0]):
                v = [r[k] for r in rows]
                print(f"  cal {kind} {k}: n {len(v)} max {max(v)!r} min {min(v)!r}")


if __name__ == "__main__":
    main(*sys.argv[1:])
