"""Regularization-path driver: full descending-lam1 elastic-net paths with
safe/strong screening (repro.paths, DESIGN.md §17).

Usage (CPU-scale):
  python -m repro.launch.path --grid 8x4
  python -m repro.launch.path --grid 8x4 --no-screen        # ladder baseline
  python -m repro.launch.path --grid 6x2 --strategy elastic_gd
  python -m repro.launch.path --grid 4x2 --swap-demo --smoke

``--grid N1xN2`` walks an N1-stage log-spaced lam1 ladder (descending)
crossed with an N2-point lam2 ladder.  Each stage screens with the
sequential strong rule, trains only the survivors through the vmapped lazy
solvers, KKT-checks the screened-out set, and prints the per-stage
screening story.  ``--smoke`` runs the path twice and asserts the second
pass compiles nothing new (the recompile guard CI pins).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro import obs, paths
from repro import solvers as solver_registry
from repro.core import LinearConfig, ScheduleConfig, SparseBatch
from repro.data import BowConfig, SyntheticBow
from repro.launch import compile_cache, flags
from repro.launch.sweep import parse_grid
from repro.serving import LinearService, ServiceConfig
from repro.sweeps import log_ladder, make_grid


def stage_table(result: paths.PathResult) -> str:
    lines = ["solver  stage  lam1        active/dim      width  readm  refits  nnz"]
    for d in result.stages:
        lines.append(
            f"{d.solver:<6s}  {d.stage:>5d}  {d.lam1:.3e}  "
            f"{d.active:>6d}/{d.dim:<6d}  {d.width:>5d}  {d.readmitted:>5d}  "
            f"{d.refits:>6d}  {d.nnz:>5d}"
        )
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--grid", default="8x4", metavar="N1xN2", help="lam1 x lam2 grid shape")
    ap.add_argument(
        "--screen",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="sequential strong-rule screening per stage (--no-screen: the "
        "plain warm-started ladder baseline)",
    )
    ap.add_argument(
        "--strategy",
        default="lazy",
        choices=("lazy", "elastic_gd"),
        help="path engine: lazy solvers with screening, or the Allerbo & "
        "Jonasson elastic gradient-flow approximation",
    )
    ap.add_argument(
        "--warm-start",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="chain each lam1 stage from its neighbor's flushed weights",
    )
    ap.add_argument(
        "--kkt",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="KKT safety check on the screened-out set (re-admit violators)",
    )
    ap.add_argument("--kkt-tol", type=float, default=0.1)
    ap.add_argument("--max-refits", type=int, default=2)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="run the path twice; the second pass must compile nothing new",
    )
    flags.add_dim(ap)
    flags.add_mesh(ap)
    ap.add_argument("--round-len", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=2, help="training rounds")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--p-max", type=int, default=64)
    ap.add_argument("--lam1-hi", type=float, default=1e-2)
    ap.add_argument("--lam1-lo", type=float, default=1e-5)
    ap.add_argument("--lam2-hi", type=float, default=1e-4)
    ap.add_argument("--lam2-lo", type=float, default=1e-7)
    ap.add_argument("--eta0", type=float, default=0.3)
    ap.add_argument("--flavor", default="fobos", choices=("sgd", "fobos"))
    flags.add_solver(
        ap,
        metavar="NAME[,NAME...]",
        help="solver(s) to path (repro.solvers); a comma-separated list adds "
        "a solver axis — one path per solver (default: --flavor)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--swap-demo",
        action="store_true",
        help="hot-swap the best-by-loss path point into a LinearService",
    )
    flags.add_backend(ap)
    flags.add_metrics_out(
        ap,
        help="write a structured JSONL run log (per-stage path.stage spans + "
        "events; summarize with `python -m repro.obs.report`)",
    )
    flags.add_profile(ap, help="collect a jax profiler trace of the path into DIR")
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
    rounds = [bow.sample_round(r, args.round_len, args.batch) for r in range(args.rounds)]
    path = paths.PathConfig(
        screen=args.screen,
        kkt=args.kkt,
        kkt_tol=args.kkt_tol,
        max_refits=args.max_refits,
        strategy=args.strategy,
    )
    programs = paths.PathPrograms()
    print(
        f"path: {grid.n_cfg} configs ({n1} lam1 x {n2} lam2), "
        f"{args.rounds}x{args.round_len} steps, strategy={args.strategy}, "
        f"screen={args.screen}"
    )
    t0 = time.monotonic()
    with (
        obs.run_logger(
            args.metrics_out,
            "path",
            d=args.dim,
            grid=args.grid,
            screen=args.screen,
            strategy=args.strategy,
            solvers=",".join(solvers) if solvers else args.flavor,
            mesh=args.mesh,
        ),
        obs.profile_to(args.profile),
        obs.span("path.run"),
    ):
        res = paths.run_path(
            grid, rounds, path=path, warm_start=args.warm_start, programs=programs
        )
    elapsed = time.monotonic() - t0
    steps = args.rounds * args.round_len * grid.n_cfg
    print(f"done in {elapsed:.1f}s ({steps / elapsed:.0f} config-steps/s)\n")
    print(stage_table(res))
    print(
        f"\nmean active fraction {res.mean_active_fraction():.3f}, "
        f"re-admitted {res.total_readmitted()} coords total"
    )

    if args.smoke:
        # every stage program is warm now; a second identical path must not
        # compile anything (the zero-recompile guarantee CI pins)
        with programs.tracker.assert_no_new_compiles("path smoke repeat"):
            res2 = paths.run_path(
                grid, rounds, path=path, warm_start=args.warm_start, programs=programs
            )
        np.testing.assert_allclose(res2.weights, res.weights, rtol=0, atol=0)
        print("smoke: second pass reused every compiled program (bitwise equal)")

    if args.swap_demo:
        best = paths.best_by_loss(res, window=args.round_len)
        cfg, w, b = paths.select(grid, res, best)
        print(
            f"\nswap demo: path point {best} (solver={cfg.solver}, "
            f"lam1={cfg.lam1:.3e}, lam2={cfg.lam2:.3e}) -> LinearService"
        )
        svc = LinearService(cfg, ServiceConfig(p_max=args.p_max, micro_batch=8))
        svc.swap_weights(w, b, cfg=cfg)
        chunk = bow.sample_round(10_007, 1, 8)
        batch = SparseBatch(idx=chunk.idx[0], val=chunk.val[0], y=chunk.y[0])
        proba = svc.predict(batch)
        loss = svc.learn(batch)
        print(f"served probs {np.round(proba, 3)}; online learn loss {loss:.4f}")


if __name__ == "__main__":
    main()
