"""Benchmark harness — one module per paper table/figure (+ the kernel and
minibatch extensions).  Prints ``name,us_per_call,derived`` CSV.

The suite registry below is the single source of truth: ``--only`` choices,
``--help`` text, and dispatch all read it (a suite added to SUITES shows up
everywhere at once; an unknown ``--only`` name fails fast with the list).

Roofline tables (per arch x shape x mesh) come from the dry-run artifacts:
``python -m repro.analysis.roofline`` (results/dryrun must exist).
"""
import argparse
import sys

# name -> (runner factory, one-line description).  Runners import lazily so
# ``--help`` and an unknown ``--only`` never pay jax startup.
SUITES = {
    "table1": (
        lambda a, steps: _m("bench_lazy_vs_dense").run(steps=steps),
        "paper §7 Table 1 (lazy vs dense FoBoS elastic net, Medline stats)",
    ),
    "scaling": (
        lambda a, steps: _m("bench_scaling").run(),
        "O(p) vs O(d): per-step cost against nominal dimensionality",
    ),
    "dp_overhead": (
        lambda a, steps: _m("bench_dp_overhead").run(steps=steps),
        "the elastic-net DP caches' constant factor vs l1-only/ridge/none",
    ),
    "kernels": (
        lambda a, steps: _m("bench_kernels").run(fast=a.fast),
        "fused whole-step solver kernels vs the unfused multi-op step "
        "(sgd/fobos/trunc/ftrl + the lazy row slab); writes BENCH_fused.json",
    ),
    "minibatch": (
        lambda a, steps: _m("bench_minibatch").run(steps=min(steps, 256)),
        "lazy minibatch extension throughput",
    ),
    "serving": (
        lambda a, steps: _m("bench_serving").run(fast=a.fast),
        "continuous-batching engine vs lock-step loop (Poisson traffic) + "
        "online linear predict/learn service; writes BENCH_serving.json",
    ),
    "sweeps": (
        lambda a, steps: _m("bench_sweeps").run(fast=a.fast),
        "vmap-batched 16-point (lam1, lam2) grid vs sequential fits; "
        "writes BENCH_sweeps.json",
    ),
    "paths": (
        lambda a, steps: _m("bench_paths").run(fast=a.fast),
        "screened regularization path vs the plain warm-started ladder "
        "(strong rule + compaction + KKT loop); writes BENCH_paths.json",
    ),
    "solvers": (
        lambda a, steps: _m("bench_solvers").run(fast=a.fast),
        "per-solver steady-state step time + sparsity at convergence; "
        "writes BENCH_solvers.json",
    ),
    "multitenant": (
        lambda a, steps: _m("bench_multitenant").run(fast=a.fast),
        "N stacked tenant models per vmapped dispatch vs N sequential "
        "LinearServices; writes BENCH_multitenant.json",
    ),
    "dist_linear": (
        lambda a, steps: _m("bench_dist_linear").run(fast=a.fast),
        "feature-sharded weak/strong scaling over meshes {1,2,4} of the "
        "visible devices (routed rounds, one process); writes BENCH_dist_linear.json",
    ),
}


def _m(name):
    import importlib

    return importlib.import_module(f"benchmarks.{name}")


def main() -> None:
    suite_lines = "\n".join(f"  {n:<12s}{desc}" for n, (_, desc) in SUITES.items())
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog=f"suites:\n{suite_lines}",
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "--only",
        default=None,
        metavar="SUITE[,SUITE...]",
        help=f"comma-separated subset of: {', '.join(SUITES)}",
    )
    ap.add_argument("--fast", action="store_true", help="smaller step counts")
    args = ap.parse_args()

    only = None
    if args.only:
        only = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [s for s in only if s not in SUITES]
        if unknown:
            ap.error(
                f"unknown suite(s) {', '.join(unknown)}; choose from: {', '.join(SUITES)}"
            )

    steps = 128 if args.fast else 512
    failed = []
    print("name,us_per_call,derived")
    for name, (fn, _) in SUITES.items():
        if only is not None and name not in only:
            continue
        try:
            for row_name, us, derived in fn(args, steps):
                print(f"{row_name},{us:.2f},{derived}")
                sys.stdout.flush()
        except Exception as e:  # report and continue: one table failing
            # must not hide the rest — but the run as a whole still fails
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            failed.append(name)
    if failed:
        print(f"# FAILED suites: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
