"""Shared CLI flag definitions for the launch drivers.

``--backend``, ``--solver``, ``--dim``, ``--metrics-out`` and ``--profile``
mean the same thing in train.py, sweep.py, path.py and serve.py, so each
flag has ONE definition here; drivers customize only what genuinely
differs (help-text focus, solver choices).  ``--fused`` is train.py's
alone (the LM embedding's one-pass catch-up + step), with ``--reg-fused``
as a documented alias.
"""
from __future__ import annotations

import argparse
from typing import Optional, Sequence, Tuple


def add_backend(ap: argparse.ArgumentParser, help: Optional[str] = None) -> None:
    from repro import backend as kernel_backend

    if help is None:
        help = "kernel backend for the hot paths (default: $REPRO_BACKEND or platform default)"
    ap.add_argument(
        "--backend",
        default=None,
        choices=kernel_backend.available_backends(),
        help=help,
    )


def add_solver(
    ap: argparse.ArgumentParser,
    *,
    choices: Optional[Tuple[str, ...]] = None,
    metavar: Optional[str] = None,
    help: Optional[str] = None,
) -> None:
    """``choices=None`` admits any registered solver name (validated by the
    registry downstream); train passes the cache-based subset, sweep a
    comma-list metavar."""
    if help is None:
        help = (
            "update rule (repro.solvers: sgd | fobos | ftrl | trunc; "
            "default: $REPRO_SOLVER or the config's flavor)"
        )
    ap.add_argument("--solver", default=None, choices=choices, metavar=metavar, help=help)


def add_fused(
    ap: argparse.ArgumentParser,
    *,
    aliases: Sequence[str] = (),
    help: Optional[str] = None,
) -> None:
    """BooleanOptionalAction under dest ``fused``; every alias also gets its
    ``--no-`` form (train's ``--reg-fused`` / ``--no-reg-fused``)."""
    ap.add_argument(
        "--fused",
        *aliases,
        dest="fused",
        action=argparse.BooleanOptionalAction,
        default=None,
        help=help,
    )


def add_dim(ap: argparse.ArgumentParser, default: int = 20_000, help: Optional[str] = None) -> None:
    ap.add_argument("--dim", type=int, default=default, help=help or "feature-space size")


def add_mesh(ap: argparse.ArgumentParser, help: Optional[str] = None) -> None:
    """Feature-mesh size for the linear paths (repro.dist.linear): shard the
    packed [d, cols] solver state across N devices on a named "features"
    axis.  Distinct from the LM drivers' ``--mesh DxM`` data x model spec —
    linear training shards one axis (features), so the flag is a plain int.
    Emulate on CPU with XLA_FLAGS=--xla_force_host_platform_device_count=N."""
    if help is None:
        help = (
            "shard the packed linear state across N feature shards "
            "(default: unsharded; CPU emulation: "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    ap.add_argument("--mesh", type=int, default=None, metavar="N", help=help)


def add_metrics_out(ap: argparse.ArgumentParser, help: Optional[str] = None) -> None:
    if help is None:
        help = (
            "write a structured JSONL run log (summarize with "
            "`python -m repro.obs.report`)"
        )
    ap.add_argument("--metrics-out", default=None, metavar="RUN.jsonl", help=help)


def add_profile(ap: argparse.ArgumentParser, help: Optional[str] = None) -> None:
    if help is None:
        help = "collect a jax profiler trace of the run into DIR"
    ap.add_argument("--profile", default=None, metavar="DIR", help=help)
