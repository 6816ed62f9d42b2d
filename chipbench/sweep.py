"""Find a serving cell's knee on the chip: its window at each of a list of
offered rates, in one process.  The knee is the highest rate whose backlog
does not grow over the window; a serving cell offers a fixed share of it.

    python3 chipbench/sweep.py --workload medline.serve --rates 500,1000,2000 --seconds 5

One JSON line per rate: the latency tails, and how late the loop picked up
the first and the last tenth of the requests (a growing backlog shows as a
last tenth far later than the first).
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, type=lambda s: [float(r) for r in s.split(",")])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    from chipbench.drivers import serve

    for rate in args.rates:
        run = harness.make_run(args.workload, args.seed, args.seconds, False)
        harness.device_info(run.cell["chips"])
        run.traffic = {**run.traffic, "rate_per_s": rate}
        out = serve.run(run)
        late = out["lateness"]
        tenth = max(1, len(late) // 10)
        line = {
            "rate_per_s": rate,
            "requests": out["attempted"],
            **{k: v["value"] for k, v in out["metrics"].items()},
            "late_first_tenth_ms": 1e3 * float(sorted(late[:tenth])[tenth // 2]),
            "late_last_tenth_ms": 1e3 * float(sorted(late[-tenth:])[tenth // 2]),
            "checks": out["checks"],
        }
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
