"""Readings that the limits in ``chipbench/limits`` are set from; run on the
chip, never by the benchmark's own runs.

    python3 chipbench/calibrate.py --workload medline.train --seeds 101-112 \
        --control-seeds 101-103 [--faults half_batch] [--seconds 3]

For each seed it prints one JSON line: the compared numbers of a sound run
of the program (``sound``), and on the control seeds those of the control,
the plain reference computed in bfloat16 in the program's place
(``control``), and of each planted fault (``fault.<name>``).  A training
cell needs no window: its readings come from the first rounds.  A serving
cell runs its window for ``--seconds`` at the cell's own rate.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import corpus, harness  # noqa: E402


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def train_readings(run, seed: int, control: bool, faults: list) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench.drivers import train

    config = run.config
    R, B = config["train"]["round_len"], config["train"]["batch"]
    n = run.traffic["check_rounds"]
    data = corpus.blocks(config, seed, n, (R, B))
    prog = train.Program(config, data)
    readings, kept = prog.first_rounds(n)
    for x in jax.tree.leaves(prog.state):
        x.delete()
    del prog
    ref = train.reference_rounds(config, kept)
    out = {"sound": train.compare(readings, ref, config["params"])}
    if control:
        low = train.reference_rounds(config, kept, jnp.bfloat16)
        out["control"] = train.compare(low, ref, config["params"])
        for f in faults:
            prog = train.Program(config, data, f)
            bad, _ = prog.first_rounds(n)
            out[f"fault.{f}"] = train.compare(bad, ref, config["params"])
            del prog
    return out


def serve_readings(run, seed: int, control: bool, faults: list) -> dict:
    import jax.numpy as jnp

    from chipbench.drivers import serve

    run.seed = seed
    out = serve.run(run)
    res = {"sound": out["checks"], "requests": out["attempted"]}
    if control and "record" in out:
        rec = out["record"]
        ref = serve.reference(run.config)
        hp = serve.hypers(run.config)
        p16, w16, _ = ref.online(
            hp, run.config["data"]["dim"], rec["feed"], t=rec["t"], dtype=jnp.bfloat16
        )
        res["control"] = serve.compare(run.config, {**rec, "preds": p16, "w": w16})
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=seeds)
    ap.add_argument("--control-seeds", default="", type=lambda s: seeds(s) if s else [])
    ap.add_argument("--faults", default="", type=lambda s: [f for f in s.split(",") if f])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=str(ROOT / "chipbench_out" / "calibrate"))
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    run = harness.make_run(args.workload, args.seeds[0], args.seconds, False, t_start=T_START)
    harness.device_info(run.cell["chips"])
    readings = {"train": train_readings, "serve": serve_readings}[run.traffic["driver"]]
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"{args.workload}.jsonl", "a") as f:
        for seed in args.seeds:
            t = time.monotonic()
            res = readings(run, seed, seed in args.control_seeds, args.faults)
            line = json.dumps({"workload": args.workload, "seed": seed, **res,
                               "seconds": time.monotonic() - t})
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
