"""Readings that the limits of a feature-mesh training cell (its traffic
names ``train_mesh``) are set from; run on the chips, never by the benchmark's
own runs.  As ``chipbench/calibrate.py`` for ``train``:

    python3 chipbench/calibrate_mesh.py --workload ctr_sharded.train --seeds 101-103 \
        --control-seeds 101-102 --faults half_batch,unchanged

For each seed it prints one JSON line (and appends it to
``chipbench_out/calibrate/<workload>.jsonl``): the compared numbers of a
sound run (``sound``), and on the control seeds those of the plain
reference computed in bfloat16 (``control``) and of each planted fault
(``fault.<name>``).  The readings come from the first rounds; no window.
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
from chipbench.calibrate import seeds  # noqa: E402


def readings(run, seed: int, control: bool, faults: list) -> dict:
    import jax.numpy as jnp

    from chipbench.drivers import train_mesh

    config = run.config
    R, B = config["train"]["round_len"], config["train"]["batch"]
    n = run.traffic["check_rounds"]
    data = train_mesh.blocks(config, seed, n, (R, B))
    params = config["params"]

    def first(fault=None):
        prog = train_mesh.Program(config, data, fault)
        got, kept = prog.first_rounds(n)
        prog.delete()
        return got, kept

    sound, kept = first()
    ref = train_mesh.reference_rounds(config, kept)
    out = {"sound": train_mesh.compare(sound, ref, params)}
    if control:
        low = train_mesh.reference_rounds(config, kept, jnp.bfloat16)
        out["control"] = train_mesh.compare(low, ref, params)
        for f in faults:
            out[f"fault.{f}"] = train_mesh.compare(first(f)[0], ref, params)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=seeds)
    ap.add_argument("--control-seeds", default="", type=lambda s: seeds(s) if s else [])
    ap.add_argument("--faults", default="", type=lambda s: [f for f in s.split(",") if f])
    ap.add_argument("--out", default=str(ROOT / "chipbench_out" / "calibrate"))
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    run = harness.make_run(args.workload, args.seeds[0], 0.0, False, t_start=T_START)
    if run.traffic["driver"] != "train_mesh":
        raise SystemExit(f"{args.workload} is no feature-mesh training cell")
    harness.device_info(run.cell["chips"])
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"{args.workload}.jsonl", "a") as f:
        for seed in args.seeds:
            t = time.monotonic()
            res = readings(run, seed, seed in args.control_seeds, args.faults)
            line = json.dumps(
                {"workload": args.workload, "seed": seed, **res, "seconds": time.monotonic() - t}
            )
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
