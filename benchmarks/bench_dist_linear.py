"""Feature-sharded lazy linear training scaling benchmark (repro.dist.linear).

Weak and strong scaling of the routed-round training path over meshes
{1, 2, 4}, every mesh in the calling process over ``jax.devices()[:n]``: a
chip belongs to one process, so no mesh size runs in a child.  The workload
feeds ``route_round``-compacted per-shard blocks to
``make_routed_round_fn`` (partial margin — the production ingestion path),
with routing and placement excluded from the clock.

* weak scaling: per-shard slab (ds rows) and per-shard features (q per
  example) fixed; total dim and total touched rows grow with the mesh.
* strong scaling: total dim and features per example fixed; each shard's
  block shrinks as 1/N.

On the CPU the four devices are host-emulated: the caller sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before the process
starts (as the CI dist-linear job does).  Emulated shards share the host's
cores, so raw wall time cannot show the speedup a real mesh gives; the
reported throughput is then the CRITICAL-PATH rate — touched rows per
second at wall/N, each shard's own timeline if the shards serialize — with
``emulated: true`` and ``physical_cores`` recorded so a real reading is
distinguishable in the artifact.

The 4-shard scaling ratio (``scaling_4``) is recorded but not gated: on
emulated devices it reads the host's core count more than the code.  On
one core the shards serialize and wall/N is their own timeline (3.18x
weak, 2.70x strong); on eight cores they run side by side and each step's
emulated all-reduce costs a thread rendezvous (1.41x/1.28x with one child
process per mesh size, 1.67x/1.48x with every mesh in one process, same
machine).  What the gate watches instead is the one-psum-per-step
contract itself: the compiled round program must hold exactly one
all-reduce (its scan body runs it once per step), recorded as
``all_reduces`` per mesh; the run fails if cross-shard traffic grows.

Writes BENCH_dist_linear.json (gated by check_regression.py against
benchmarks/baselines/); the mesh-size keys are identical in --fast and
full runs.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import time

import jax
import numpy as np

from repro.core import linear_trainer as lt
from repro.dist import linear as dl

MESHES = (1, 2, 4)
R = 64       # steps per round (one scan per round call)
B = 8        # examples per step
Q = 16       # weak: features per example PER SHARD (fixed per-shard work)
DS = 50_000  # weak: rows per shard (fixed per-shard slab)
P_TOTAL = 64          # strong: features per example, total (divisible by 4)
STRONG_DIM = 4 * DS   # strong: fixed logical dim
# collective ops in compiled HLO text (async forms lower to *-start/-done pairs)
COLLECTIVE = re.compile(
    r"= \S+ (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(-start)?\("
)


def collectives(compiled) -> list[str]:
    """Names of the cross-device collectives in a compiled program."""
    return [m.group(1) for m in COLLECTIVE.finditer(compiled.as_text())]


def _measure(mode: str, mesh: int, rounds: int, emulated: bool) -> dict:
    if mode == "weak":
        dim, q = mesh * DS, Q
    else:
        dim, q = STRONG_DIM, P_TOTAL // mesh
    cfg = lt.LinearConfig(
        dim=dim, round_len=R, solver="fobos", lam1=1e-4, lam2=1e-5,
        mesh=mesh, shard_margin="partial",
    )
    n, ds, _ = dl.shard_info(cfg)
    rng = np.random.default_rng(7)

    def make_round():
        # indices balanced over the 4-shard grain by construction: every
        # mesh size in MESHES owns exactly q features of every example, so
        # route_round never overflows and shards stay perfectly load-even
        grain = 4 if mode == "strong" else n
        per = (P_TOTAL if mode == "strong" else n * Q) // grain
        gs = dim // grain
        idx = np.concatenate(
            [rng.integers(k * gs, (k + 1) * gs, size=(R, B, per)).astype(np.int32)
             for k in range(grain)], axis=-1,
        )
        val = rng.normal(size=idx.shape).astype(np.float32)
        y = (rng.random(size=(R, B)) < 0.5).astype(np.float32)
        return lt.SparseBatch(idx, val, y)

    # route + place OUTSIDE the clock (the ingestion pipeline's job)
    placed = [
        dl.place_routed(cfg, *dl.route_round(cfg, make_round(), q=q))
        for _ in range(rounds + 1)
    ]
    rrf = dl.make_routed_round_fn(cfg)
    state = lt.init_state(cfg)
    ops = collectives(rrf.lower(state, *placed[0]).compile())
    if ops != ["all-reduce"]:
        raise RuntimeError(
            f"dist_linear {mode}/n{mesh}: the routed round compiles to collectives "
            f"{ops}, not the one margin all-reduce per step"
        )
    state, _ = rrf(state, *placed[0])  # compile + first-touch, untimed
    jax.block_until_ready(state.wpsi)
    t0 = time.perf_counter()
    for oi, ov, y in placed[1:]:
        state, losses = rrf(state, oi, ov, y)
    jax.block_until_ready(state.wpsi)
    elapsed = time.perf_counter() - t0

    steps = rounds * R
    p_tot = P_TOTAL if mode == "strong" else n * Q
    touched = steps * B * p_tot
    critical = elapsed / n if emulated else elapsed
    return {
        "dim": dim, "q": q, "steps": steps, "touched_rows": touched, "all_reduces": len(ops),
        "wall_s": elapsed, "critical_path_s": critical,
        "touched_rows_per_s": touched / max(critical, 1e-9),
        "us_per_step": 1e6 * critical / steps,
    }


def run(fast: bool = False, json_path: str = "BENCH_dist_linear.json"):
    # enough timed steps that the per-step clock window is O(seconds):
    # sub-50ms windows put scheduler noise inside the ±30% gate tolerance
    rounds = 8 if fast else 48
    devices = jax.devices()
    if len(devices) < max(MESHES):
        raise RuntimeError(
            f"dist_linear runs meshes {MESHES} in this process but only "
            f"{len(devices)} devices are visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={max(MESHES)}"
        )
    emulated = devices[0].platform == "cpu"
    payload = {
        "emulated": emulated,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "physical_cores": os.cpu_count(),
        "workload": {
            "solver": "fobos", "margin": "partial", "round_len": R, "batch": B,
            "weak_ds": DS, "weak_q": Q, "strong_dim": STRONG_DIM,
            "strong_p": P_TOTAL, "rounds": rounds,
        },
        "weak": {}, "strong": {},
    }
    rows = []
    for mode in ("weak", "strong"):
        for mesh in MESHES:
            res = _measure(mode, mesh, rounds, emulated)
            payload[mode][str(mesh)] = res
            rows.append((
                f"dist_linear/{mode}_n{mesh}", res["us_per_step"],
                f"touched_rows_per_s={res['touched_rows_per_s']:.0f}",
            ))
        r1 = payload[mode]["1"]["touched_rows_per_s"]
        r4 = payload[mode]["4"]["touched_rows_per_s"]
        payload[mode]["scaling_4"] = r4 / max(r1, 1e-9)
        rows.append((
            f"dist_linear/{mode}_scaling", 0.0,
            f"scaling={payload[mode]['scaling_4']:.2f}x (not gated)",
        ))
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", default="BENCH_dist_linear.json")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, us, derived in run(fast=args.fast, json_path=args.json):
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
