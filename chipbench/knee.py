"""Pick a serving cell's offered rate from a sweep (``chipbench/sweep.py``):
0.8 of the knee, the highest rate below the first whose backlog grew.  It
prints the rate; committing it to the cell's traffic file is left to hand.

    python3 chipbench/knee.py <sweep output>

A rate's backlog grew where the loop picked up the last tenth of its
requests more than 5 ms late and three times later than the first tenth.
"""

import json
import sys


def grew(line: dict) -> bool:
    first, last = line["late_first_tenth_ms"], line["late_last_tenth_ms"]
    return last > 5.0 and last > 3 * first


def knee(lines: list) -> float:
    best = None
    for line in sorted(lines, key=lambda x: x["rate_per_s"]):
        if grew(line):
            break
        best = line["rate_per_s"]
    if best is None:
        raise SystemExit("every rate's backlog grew: no knee in the sweep")
    return best


def main(sweep: str) -> None:
    lines = [json.loads(x) for x in open(sweep) if x.startswith("{")]
    k = knee(lines)
    print(f"knee {k} /s: offered rate {round(0.8 * k)} /s")


if __name__ == "__main__":
    main(*sys.argv[1:])
