"""End-to-end training driver.

Runs reduced configs on this CPU container end-to-end and full configs on a
real mesh unchanged (the step function and shardings are the dry-run's).

Fault tolerance model (documented here; exercised in tests/checkpoint):
  * checkpoint every --ckpt-every steps: atomic dir rename, retention of
    the last 3; the manifest carries the data cursor (seed, step) and the
    lazy-regularizer round state, so a killed job resumes bit-identically;
  * node failure -> restart the job; --resume picks up the newest intact
    checkpoint (a torn write is impossible by construction);
  * elastic restart: the checkpoint stores full logical arrays;
    checkpointer.restore_distributed() re-shards onto any new mesh size
    (straggler mitigation at the cluster level is re-scheduling + elastic
    re-mesh: same global batch, different chip count);
  * the embedding's lazy elastic-net round is flushed before every save so
    restores never owe cross-round catch-ups.

Usage (CPU-scale):
  python -m repro.launch.train --arch stablelm_3b --reduced --steps 200
  python -m repro.launch.train --arch stablelm_3b --reduced --mesh 2x2 \
      # data x model sharding via repro.dist (multi-device processes)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import backend as kernel_backend
from repro import obs
from repro import solvers
from repro.checkpoint import checkpointer
from repro.launch import compile_cache, flags
from repro.configs import get_arch
from repro.data import LMDataConfig, SyntheticLMData
from repro.dist import sharding as dist_sharding
from repro.launch.mesh import host_mesh_from_spec
from repro.models import build, init_params, make_train_batch_specs
from repro.train import make_flush_fn, make_init_state, make_train_step


def make_batch_fn(cfg, batch_size: int, seq_len: int, seed: int):
    data = SyntheticLMData(
        LMDataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len, batch_size=batch_size, seed=seed)
    )

    def batch_fn(step: int):
        toks = data.batch(step)
        out = {"tokens": jnp.asarray(toks[:, :-1]), "labels": jnp.asarray(toks[:, 1:])}
        if cfg.encdec:
            rng = np.random.RandomState(step + 7)
            out["frames"] = jnp.asarray(
                rng.randn(batch_size, cfg.enc_seq, cfg.d_model).astype(np.float32) * 0.1
            )
        if cfg.n_patches:
            rng = np.random.RandomState(step + 13)
            out["patches"] = jnp.asarray(
                rng.randn(batch_size, cfg.n_patches, cfg.d_model).astype(np.float32) * 0.02
            )
        return out

    return batch_fn


def train(
    arch: str,
    *,
    reduced: bool = True,
    steps: int = 100,
    batch_size: int = 4,
    seq_len: int = 64,
    ckpt_dir: str | None = None,
    ckpt_every: int = 0,
    resume: bool = False,
    seed: int = 0,
    log_every: int = 10,
    mesh_shape: str | None = None,
    solver: str | None = None,
    reg_fused: bool | None = None,
    metrics_interval: int = 50,
    profile: str | None = None,
):
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    if solver is not None:
        # update rule for the embedding's lazy elastic-net regularizer
        # (repro.solvers; cache-based solvers only — validated eagerly when
        # the step function is built)
        import dataclasses as _dc

        cfg = _dc.replace(cfg, reg_solver=solver)
    if reg_fused is not None:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, reg_fused=reg_fused)
    model = build(cfg)

    # Optional data x model mesh over the visible devices ("2x2", "4x1", …).
    # All shardings come from the dist.sharding rule table — the same specs
    # the dry-run compiles at production scale.
    mesh = rules = state_sh = None
    if mesh_shape:
        mesh = host_mesh_from_spec(mesh_shape)
        rules = dist_sharding.make_rules(cfg, mesh, batch_size)
        state_sh = dist_sharding.shardings_for_axes(
            dist_sharding.train_state_axes(cfg, model), mesh, rules
        )
        batch_sh = dist_sharding.shardings_for_axes(
            dist_sharding.batch_axes(cfg, make_train_batch_specs(cfg, batch_size, seq_len)),
            mesh, rules,
        )
        step_fn = jax.jit(
            make_train_step(cfg, model, mesh=mesh, rules=rules),
            in_shardings=(state_sh, batch_sh), out_shardings=(state_sh, None),
            donate_argnums=0,
        )
    else:
        step_fn = jax.jit(make_train_step(cfg, model), donate_argnums=0)
    flush_fn = make_flush_fn(cfg)
    if state_sh is not None:
        # the round flush rebuilds psi/caches as fresh (replicated) arrays;
        # re-place them so the donated step_fn sees its declared shardings
        raw_flush, flush_fn = flush_fn, lambda s: jax.device_put(raw_flush(s), state_sh)
    init_fn = make_init_state(cfg, model)
    batch_fn = make_batch_fn(cfg, batch_size, seq_len, seed)

    start = 0
    state = None
    if resume and ckpt_dir:
        last = checkpointer.latest_step(ckpt_dir)
        if last is not None:
            template = jax.eval_shape(init_fn, jax.eval_shape(lambda: init_params(model, seed)))
            if mesh is not None:
                # elastic restore: leaves land directly in the shardings
                # the step function was compiled with
                state, manifest = checkpointer.restore_distributed(
                    ckpt_dir, last, template, state_sh
                )
            else:
                state, manifest = checkpointer.restore(ckpt_dir, last, template)
                state = jax.tree.map(jnp.asarray, state)
            start = int(manifest["extra"]["next_step"])
            print(f"resumed from step {last} (next data step {start})")
    if state is None:
        state = init_fn(init_params(model, seed))
        if state_sh is not None:
            state = jax.device_put(state, state_sh)

    losses = []
    t0 = time.time()
    # host-side lazy-work accounting for the embedding regularizer: each
    # token slot touches one embedding row per step vs. the dense baseline's
    # vocab_size rows — the LM-trainer analogue of the linear trainer's
    # in-graph MetricsState (tokens are host-visible, so no device pull)
    touched = examples = flushes = 0

    def lazy_summary(steps_done: int, nnz: int) -> dict:
        return {
            "steps": steps_done,
            "examples": examples,
            "touched_coords": touched,
            "flushes": flushes,
            "nnz": nnz,
            "d": int(cfg.vocab_size),
            "work_ratio": touched / (cfg.vocab_size * max(steps_done, 1)),
            "loss_ema": float(np.mean(losses[-20:])) if losses else 0.0,
            "solver": cfg.reg_solver or cfg.reg_flavor,
        }

    def emb_nnz(st) -> int:
        if st.lazy is None:
            return 0
        from repro.optim import lazy_rows

        return int(lazy_rows.row_nnz(st.params["embedding"], st.lazy, lam1=cfg.lam1))

    logger = obs.active_logger()
    with obs.profile_to(profile):
        for t in range(start, steps):
            with obs.step_annotation(t):
                state, metrics = step_fn(state, batch_fn(t))
            losses.append(float(metrics["loss"]))
            if state.lazy is not None:
                touched += batch_size * seq_len
                examples += batch_size
            if state.lazy is not None and int(state.lazy.i) >= cfg.reg_round_len:
                state = flush_fn(state)
                flushes += 1
                if logger is not None:
                    logger.event("flush", step=t + 1, flushes=flushes, nnz=emb_nnz(state))
            if log_every and (t + 1) % log_every == 0:
                rate = (t + 1 - start) / (time.time() - t0)
                print(f"step {t+1}/{steps} loss={losses[-1]:.4f} "
                      f"lr={float(metrics['lr']):.2e} gnorm={float(metrics['grad_norm']):.2f} "
                      f"({rate:.1f} steps/s)", flush=True)
            if logger is not None and metrics_interval and (t + 1) % metrics_interval == 0:
                logger.metrics(lazy_summary(t + 1 - start, emb_nnz(state)), step=t + 1)
            if ckpt_dir and ckpt_every and (t + 1) % ckpt_every == 0:
                state = flush_fn(state)  # no cross-round debt inside checkpoints
                checkpointer.save(ckpt_dir, t + 1, state, extra_meta={"next_step": t + 1, "seed": seed})
                checkpointer.keep_last(ckpt_dir, 3)
    if logger is not None and steps > start and (
        not metrics_interval or (steps - start) % metrics_interval
    ):  # final cumulative line, unless the periodic one just covered it
        logger.metrics(lazy_summary(steps - start, emb_nnz(state)), step=steps)
    return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--mesh", default=None, metavar="DxM",
        help='data x model mesh over visible devices (e.g. "2x2"); '
             "default: single-device, no sharding",
    )
    flags.add_backend(ap, help="kernel backend for attention + lazy-reg hot "
                               "paths (default: $REPRO_BACKEND or platform default)")
    # only cache-based solvers can host the embedding row slab (one psi per
    # row; apply-at-read solvers keep per-coordinate state) — reject the
    # rest at argparse time, not after the model is built
    row_solvers = tuple(
        n for n in solvers.available_solvers() if solvers.get_solver(n).caches_based
    )
    flags.add_solver(
        ap, choices=row_solvers,
        help="update rule for the embedding's lazy regularizer "
             "(cache-based solvers only; default: $REPRO_SOLVER or the "
             "arch's reg_flavor)",
    )
    # --reg-fused / --no-reg-fused stay as documented aliases of --fused
    flags.add_fused(
        ap, aliases=("--reg-fused",),
        help="one-pass fused catchup+SGD on the embedding row slab "
             "(--no-fused / --no-reg-fused: split catchup-then-step; "
             "default: the arch's reg_fused)",
    )
    flags.add_metrics_out(ap)
    ap.add_argument(
        "--metrics-interval", type=int, default=50, metavar="N",
        help="steps between periodic metrics lines in the run log",
    )
    flags.add_profile(ap)
    args = ap.parse_args()
    compile_cache.enable()
    d = get_arch(args.arch)
    if args.reduced:
        d = d.reduced()
    with obs.run_logger(
        args.metrics_out, "train", d=d.vocab_size,
        arch=args.arch, reduced=args.reduced, steps=args.steps,
    ), kernel_backend.use_backend(args.backend), obs.span("train.run"):
        _, losses = train(
            args.arch,
            reduced=args.reduced,
            steps=args.steps,
            batch_size=args.batch,
            seq_len=args.seq,
            ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            resume=args.resume,
            seed=args.seed,
            mesh_shape=args.mesh,
            solver=args.solver,
            reg_fused=args.fused,
            metrics_interval=args.metrics_interval,
            profile=args.profile,
        )
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")


if __name__ == "__main__":
    main()
