"""Hyperparameter sweep driver: vmap-batched (lam1, lam2) regularization
paths over the lazy elastic-net trainer, with warm-started continuation,
k-fold CV, and a hot swap of the winner into the online LinearService.

Usage (CPU-scale):
  python -m repro.launch.sweep --grid 8x4 --folds 5 --warm-start
  python -m repro.launch.sweep --grid 4x4 --dim 20000 --folds 2 --no-warm-start
  python -m repro.launch.sweep --grid 4x2 --folds 3 --swap-demo

``--grid N1xN2`` sweeps an N1-point log-spaced lam1 ladder (descending —
the order the warm-started path walks) against an N2-point lam2 ladder.
Every (lam2, eta0) stage of the path trains as ONE vmapped compiled
program; the winner is the argmin of fold-averaged held-out loss.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro import obs
from repro import solvers as solver_registry
from repro.core import LinearConfig, ScheduleConfig, SparseBatch
from repro.data import BowConfig, SyntheticBow
from repro.launch import compile_cache, flags
from repro.serving import LinearService, ServiceConfig
from repro.sweeps import kfold_cv, log_ladder, make_grid


def parse_grid(spec: str) -> tuple:
    try:
        n1, n2 = (int(v) for v in spec.lower().split("x"))
    except ValueError as e:
        raise SystemExit(f"--grid wants N1xN2 (e.g. 8x4), got {spec!r}") from e
    if n1 < 1 or n2 < 1:
        raise SystemExit(f"--grid dims must be >= 1, got {spec!r}")
    return n1, n2


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--grid", default="8x4", metavar="N1xN2", help="lam1 x lam2 grid shape")
    ap.add_argument("--folds", type=int, default=5, help="k-fold CV folds (>= 2)")
    ap.add_argument(
        "--warm-start",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="chain each lam1 stage from its neighbor's flushed weights",
    )
    flags.add_dim(ap)
    flags.add_mesh(
        ap,
        help="shard every config's packed state across N feature shards "
        "(repro.dist.linear; the vmapped config axis rides inside the "
        "mesh program; CPU emulation: "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N)",
    )
    ap.add_argument("--round-len", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=1, help="rounds per fold")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--p-max", type=int, default=64)
    ap.add_argument("--lam1-hi", type=float, default=1e-3)
    ap.add_argument("--lam1-lo", type=float, default=1e-6)
    ap.add_argument("--lam2-hi", type=float, default=1e-4)
    ap.add_argument("--lam2-lo", type=float, default=1e-7)
    ap.add_argument("--eta0", type=float, default=0.3)
    ap.add_argument("--flavor", default="fobos", choices=("sgd", "fobos"))
    flags.add_solver(
        ap,
        metavar="NAME[,NAME...]",
        help="solver(s) to sweep (repro.solvers: sgd | fobos | ftrl | trunc); "
        "a comma-separated list adds a solver axis to the grid — every "
        "solver trains on the same data, one vmapped program each "
        "(default: --flavor)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--swap-demo",
        action="store_true",
        help="hot-swap the winner into a LinearService and serve a sample batch",
    )
    flags.add_backend(
        ap,
        help="kernel backend for the vmapped lazy/flush hot "
        "paths (default: $REPRO_BACKEND or platform default)",
    )
    flags.add_metrics_out(
        ap,
        help="write a structured JSONL run log (per-stage spans + compile "
        "deltas; summarize with `python -m repro.obs.report`)",
    )
    flags.add_profile(ap, help="collect a jax profiler trace of the sweep into DIR")
    args = ap.parse_args()
    compile_cache.enable()

    n1, n2 = parse_grid(args.grid)
    solvers = None
    if args.solver:
        solvers = tuple(s.strip() for s in args.solver.split(",") if s.strip())
        for s in solvers:
            solver_registry.get_solver(s)  # fail fast on unknown names
    base = LinearConfig(
        dim=args.dim,
        flavor=args.flavor,
        lam1=args.lam1_hi,
        lam2=args.lam2_hi,
        round_len=args.round_len,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=args.eta0, t0=100.0),
        backend=args.backend,
        mesh=args.mesh,
    )
    grid = make_grid(
        base,
        log_ladder(args.lam1_hi, args.lam1_lo, n1),
        log_ladder(args.lam2_hi, args.lam2_lo, n2),
        solvers=solvers,
    )
    pool = min(8192, args.dim // 2)
    bow = SyntheticBow(
        BowConfig(
            dim=args.dim,
            p_max=args.p_max,
            p_mean=args.p_max / 2.0,
            informative_pool=pool,
            n_informative=min(512, pool // 4),
            seed=args.seed,
        )
    )
    print(
        f"sweep: {grid.n_cfg} configs ({n1} lam1 x {n2} lam2), {args.folds} folds, "
        f"{args.rounds}x{args.round_len} steps/fold, warm_start={args.warm_start}"
    )
    t0 = time.monotonic()
    # run_path's per-stage spans (compile deltas included) land in the run
    # log through the active logger run_logger() installs
    with (
        obs.run_logger(
            args.metrics_out,
            "sweep",
            d=args.dim,
            grid=args.grid,
            folds=args.folds,
            warm_start=args.warm_start,
            solvers=",".join(solvers) if solvers else args.flavor,
            mesh=args.mesh,
        ),
        obs.profile_to(args.profile),
        obs.span("sweep.kfold_cv"),
    ):
        res = kfold_cv(
            grid,
            bow,
            folds=args.folds,
            rounds_per_fold=args.rounds,
            batch=args.batch,
            warm_start=args.warm_start,
        )
    elapsed = time.monotonic() - t0
    # k fits on (k-1) chunks each + the final whole-stream refit on k chunks
    steps = args.folds**2 * args.rounds * args.round_len * grid.n_cfg
    print(f"done in {elapsed:.1f}s ({steps / elapsed:.0f} config-steps/s)\n")

    print("solver  lam1        lam2        cv_loss   nnz")
    # winner's weights come from the final fold fit; nnz is reported for the
    # winner only (per-config weights of other points are not retained)
    for c in range(grid.n_cfg):
        cfg = grid.config_at(c)
        star = " <- winner" if c == res.best_index else ""
        nnz = (
            f"{int(np.sum(np.abs(res.best_weights) > 0)):>6d}" if c == res.best_index else "     -"
        )
        print(
            f"{cfg.solver:<6s}  {cfg.lam1:.3e}  {cfg.lam2:.3e}  "
            f"{res.cv_loss[c]:.4f}  {nnz}{star}"
        )

    if args.swap_demo:
        print("\nswap demo: installing the winner into a live LinearService")
        svc = LinearService(res.best_config, ServiceConfig(p_max=args.p_max, micro_batch=8))
        svc.swap_weights(res.best_weights, res.best_b, cfg=res.best_config)
        chunk = bow.sample_round(10_007, 1, 8)
        batch = SparseBatch(idx=chunk.idx[0], val=chunk.val[0], y=chunk.y[0])
        proba = svc.predict(batch)
        loss = svc.learn(batch)
        print(f"served probs {np.round(proba, 3)}; online learn loss {loss:.4f}")
        print(f"service counters: {svc.metrics.snapshot()['counters']}")


if __name__ == "__main__":
    main()
