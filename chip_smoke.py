"""Smoke run of the system's main paths on a TPU, through its user entry points.

    python chip_smoke.py            # phases a-f on one chip
    python chip_smoke.py --chips 4  # the feature-sharded phase only, on four chips

Phases (one process; each prints one line of what it checked):

  a. device    the default device is a TPU and the kernel backend is pallas
  b. trainer   lazy FoBoS rounds at the paper's Medline shape (d = 260,941,
               mean 88.54 features, p_max 128) vs the plain O(d) dense rounds
  c. service   LinearService learn/predict at d = 260,941 (fobos, ftrl) with
               zero recompiles after warm-up, vs the same stream on reference
  d. path      a screened lam1 path at d = 260,941, pallas vs reference
  e. hbm       the online service at d = 2^24 (ftrl state: 192 MiB) with
               round flushes over the whole buffer, vs reference
  f. lm        ServeEngine serving stablelm_3b at its published widths
               (8 requests of 32 + 16 tokens) vs serve_step.generate on each
               prompt: equal tokens, or a first difference at a near-tie

Every compared run uses compiled kernels: each phase asserts that its lowered
program holds a ``tpu_custom_call``.  Any failure raises, and the script exits
non-zero before its last line, which is exactly one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The readings printed along the way are one-off smoke numbers, not benchmarks.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import backend, paths  # noqa: E402
from repro.core import (  # noqa: E402
    LinearConfig,
    ScheduleConfig,
    SparseBatch,
    current_weights,
    init_state,
    make_round_fn,
    nnz,
)
from repro.data import BowConfig, SyntheticBow  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.serve import serve_linear  # noqa: E402
from repro.models import build, init_params  # noqa: E402
from repro.serving import EngineConfig, ServeEngine  # noqa: E402
from repro.sweeps import log_ladder, make_grid  # noqa: E402
from repro.train import serve_step  # noqa: E402

MEDLINE_DIM = BowConfig().dim  # 260,941
HASHED_DIM = 2**24
KERNEL = "tpu_custom_call"
# phase f: how far below the reference's best logit the engine's token may
# be where the two first differ (bf16 ulps of that logit: the logits are
# bf16, so one ulp is one rounding)
TIE_ULPS = 1


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_kernel(lowered, what: str) -> None:
    """The lowered program runs a compiled Pallas kernel: interpret mode and
    the reference backend lower to plain XLA ops, never to this call."""
    check(KERNEL in lowered.as_text(), f"{what}: no {KERNEL} in the lowered program")


def bf16_ulp(x):
    """Spacing of bf16 numbers at ``x`` (8 significant bits)."""
    return np.exp2(np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - 7)


def report(phase: str, t0: float, msg: str) -> None:
    print(f"[{phase}] {msg} ({time.monotonic() - t0:.1f}s)", flush=True)


def max_abs(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


def batch_specs(b: int, p: int) -> SparseBatch:
    return SparseBatch(
        idx=jax.ShapeDtypeStruct((b, p), jnp.int32),
        val=jax.ShapeDtypeStruct((b, p), jnp.float32),
        y=jax.ShapeDtypeStruct((b,), jnp.float32),
    )


def phase_device(chips: int) -> dict:
    t0 = time.monotonic()
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu", f"default device is {d0.platform!r}, not a TPU")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} visible")
    name = backend.resolve().name
    check(name == "pallas", f"default kernel backend is {name!r}, not pallas")
    report("a", t0, f"device: tpu kind={d0.device_kind!r} count={len(devs)} backend={name}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def phase_trainer(seed: int, dim: int = MEDLINE_DIM, rounds: int = 4, steps: int = 2048,
                  batch: int = 8) -> None:
    """Lazy (pallas) vs dense (reference) rounds on one stream; the
    tolerance is tests/test_system.py's.  eta0 is small enough that ~88
    features per example do not make plain SGD chaotic (where it is, the two
    paths' rounding differences grow without bound)."""
    t0 = time.monotonic()
    bow = SyntheticBow(BowConfig(dim=dim, seed=seed))
    cfg = LinearConfig(
        dim=dim, solver="fobos", lam1=2e-4, lam2=1e-4, round_len=steps,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=0.02, t0=200.0), backend="pallas",
    )
    dense_cfg = dataclasses.replace(cfg, backend="reference")
    lazy_fn, dense_fn = make_round_fn(cfg, "lazy"), make_round_fn(dense_cfg, "dense")
    lazy, dense = init_state(cfg), init_state(dense_cfg, mode="dense")
    for r in range(rounds):
        batches = bow.sample_round(r, steps, batch)
        if r == 0:
            require_kernel(lazy_fn.lower(lazy, batches), "lazy round")
        lazy, lazy_loss = lazy_fn(lazy, batches)
        dense, _ = dense_fn(dense, batches)
    w_lazy = np.asarray(current_weights(cfg, lazy))
    w_dense = np.asarray(dense.wpsi[:, 0])
    np.testing.assert_allclose(w_lazy, w_dense, rtol=5e-4, atol=1e-4)
    report(
        "b", t0,
        f"trainer: d={dim} {rounds}x{steps} steps x B={batch}: lazy(pallas) vs dense(reference) "
        f"max|dw|={max_abs(w_lazy, w_dense):.3e} (rtol 5e-4, atol 1e-4) "
        f"nnz={int(nnz(cfg, lazy))} last-round loss={float(np.mean(np.asarray(lazy_loss))):.4f}",
    )


def _service_pair(what: str, solver: str, stream: dict, base: dict, twin: dict) -> tuple:
    """One request stream through serve_linear twice: on pallas with
    ``base`` (kernels asserted), then with ``twin``; returns both services."""
    svc = serve_linear(solver=solver, backend="pallas", **stream, **base)
    p_max = stream["p_max"]
    require_kernel(svc._step.lower(svc.state, batch_specs(svc.micro_batch, p_max)),
                   f"{what} {solver} learn step")
    require_kernel(svc._flush.lower(svc.state), f"{what} {solver} round flush")
    return svc, serve_linear(solver=solver, **stream, **twin)


def phase_service(seed: int, dim: int = MEDLINE_DIM, requests: int = 384,
                  round_len: int = 64, p_max: int = 128, phase: str = "c") -> None:
    """serve_linear (warm-up, then traffic under assert_no_new_compiles) on
    pallas vs the same stream on the reference backend."""
    stream = dict(dim=dim, p_max=p_max, requests=requests, round_len=round_len, seed=seed)
    for solver in ("fobos", "ftrl"):
        t0 = time.monotonic()
        svc, ref = _service_pair(phase, solver, stream, {}, {"backend": "reference"})
        w, w_ref = svc.current_weights(), ref.current_weights()
        err = max_abs(w, w_ref)
        check(err <= 1e-5, f"{phase} {solver}: pallas vs reference max|dw|={err:.3e} > 1e-5")
        counters = svc.metrics.snapshot()["counters"]
        check(counters.get("round_flushes", 0) >= 1, f"{phase} {solver}: no round flush ran")
        report(
            phase, t0,
            f"service {solver}: d={dim} state={svc.state.wpsi.nbytes / 2**20:.1f} MiB "
            f"{counters['learn_examples']} learn + {counters['predict_examples']} predict, "
            f"{counters['round_flushes']} round flushes, zero recompiles after warm-up; "
            f"pallas vs reference max|dw|={err:.3e} (<= 1e-5) "
            f"at max|w|={np.max(np.abs(w_ref)):.3f}",
        )


def phase_path(seed: int, dim: int = MEDLINE_DIM, n_rounds: int = 2, round_len: int = 256,
               batch: int = 8) -> None:
    """A screened 3-stage lam1 path (screen_mask + host compaction) on
    pallas vs the same path on the reference backend.  The ladder's ratio
    stays below 2, so the sequential strong rule (|g| >= 2*lam1_k -
    lam1_{k-1}) screens from stage 1 on."""
    t0 = time.monotonic()
    base = LinearConfig(
        dim=dim, solver="fobos", lam1=1e-1, lam2=1e-6, round_len=round_len,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=0.02, t0=100.0), backend="pallas",
    )
    grid = make_grid(base, log_ladder(1e-1, 5e-2, 3), (1e-6,))
    ref_grid = make_grid(
        dataclasses.replace(base, backend="reference"), log_ladder(1e-1, 5e-2, 3), (1e-6,)
    )
    bow = SyntheticBow(BowConfig(dim=dim, seed=seed))
    rounds = [bow.sample_round(r, round_len, batch) for r in range(n_rounds)]
    screened = paths.run_path(grid, rounds)
    ref = paths.run_path(ref_grid, rounds)
    spec = jax.ShapeDtypeStruct((grid.n_cfg, dim), jnp.float32)
    require_kernel(paths.make_screen_fn(base).lower(spec, spec, 1.0, 1.0), "screen_mask")
    active = [d.active for d in screened.stages]
    check(active == [d.active for d in ref.stages],
          f"path: active sets {active} vs reference {[d.active for d in ref.stages]}")
    check(min(active) < dim, "path: no stage screened anything")
    err = max_abs(screened.weights, ref.weights)
    check(err <= 1e-5, f"path: pallas vs reference max|dw|={err:.3e} > 1e-5")
    stages = ", ".join(f"{d.active}/{d.dim} width {d.width}" for d in screened.stages)
    report(
        "d", t0,
        f"path: d={dim} {grid.n_cfg} stages, active {stages}; re-admitted "
        f"{screened.total_readmitted()}; same active sets as reference; "
        f"pallas vs reference max|dw|={err:.3e} (<= 1e-5)",
    )


def engine_tokens(model, params, prompts: np.ndarray, new_tokens: int):
    """The prompts through a ServeEngine with one slot each, after its
    warm-up, under its zero-recompile assertion.  Returns the tokens
    [n, new_tokens] and the engine's own decode step, lowered."""
    n, prompt_len = prompts.shape
    engine = ServeEngine(model, params, EngineConfig(
        n_slots=n, max_len=prompt_len + new_tokens, prompt_buckets=(prompt_len,),
    ))
    engine.warmup()
    with engine.compiles.assert_no_new_compiles("lm steady state"):
        got = np.stack(engine.generate(list(prompts), new_tokens))
    step = engine._step.lower(
        params, engine.cache, jnp.asarray(engine._last_tok), jnp.asarray(engine._pos)
    )
    return got, step


def reference_tokens(cfg, model, params, prompt: np.ndarray, new_tokens: int):
    """``serve_step.generate`` on one prompt: tokens [new_tokens] and the
    logits [new_tokens, V] each was picked from."""
    logits: list = []
    out = serve_step.generate(
        cfg, model, params, {"tokens": jnp.asarray(prompt[None], jnp.int32)}, new_tokens,
        logits=logits,
    )
    return np.asarray(out)[0], np.stack([np.asarray(x[0], np.float32) for x in logits])


def phase_lm(seed: int, arch: str = "stablelm_3b", reduced: bool = False, n_req: int = 8,
             prompt_len: int = 32, new_tokens: int = 16) -> None:
    """ServeEngine (n_req slots) vs ``serve_step.generate`` on each prompt
    alone, both on pallas, at the published widths with random weights.

    Both prefill one prompt at a time, but the engine decodes a batch of
    n_req and the reference a batch of 1: the matmuls have other shapes,
    their sums run in other orders, and a greedy pick between logits tied
    in bf16 may flip (on a v5e, 4 of 8 requests at seed 0; the engine's
    larger KV cache changes nothing).  Where a request's tokens first
    differ, the engine's token must be a tie in the reference's logits:
    below its best by at most TIE_ULPS bf16 ulps.  The continuations
    differ from there on and are not compared."""
    t0 = time.monotonic()
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    params = init_params(model, seed)
    gb = sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9
    prompts = np.random.RandomState(seed).randint(0, cfg.vocab_size, size=(n_req, prompt_len))
    with backend.use_backend("pallas"):
        got, step = engine_tokens(model, params, prompts.astype(np.int32), new_tokens)
        refs = [reference_tokens(cfg, model, params, p, new_tokens) for p in prompts]
    require_kernel(step, "engine decode step (flash attention)")
    equal, ties, margins = 0, [], []
    for r, (ref, logits) in enumerate(refs):
        top2 = np.sort(logits, axis=-1)[:, -2:]
        margins.append((top2[:, 1] - top2[:, 0]) / bf16_ulp(top2[:, 1]))
        diff = np.flatnonzero(got[r] != ref)
        if diff.size == 0:
            equal += 1
            continue
        j = int(diff[0])
        gap = float(logits[j, ref[j]] - logits[j, got[r, j]]) / float(bf16_ulp(logits[j, ref[j]]))
        check(gap <= TIE_ULPS, f"lm: request {r} step {j}: engine token {got[r, j]} is "
              f"{gap:.1f} bf16 ulps below the reference's best (> {TIE_ULPS})")
        ties.append(f"r{r}@{j}:{gap:.2f}")
    report(
        "f", t0,
        f"lm: {cfg.name} L={cfg.n_layers} d_model={cfg.d_model} vocab={cfg.vocab_size} "
        f"params={gb:.2f} GB {jnp.dtype(jax.tree.leaves(params)[0].dtype).name}; "
        f"{n_req} reqs x ({prompt_len}+{new_tokens}) over {n_req} slots vs generate per prompt: "
        f"{equal} of {n_req} equal token for token; first differences at near-ties "
        f"[{', '.join(ties)}] bf16 ulps (<= {TIE_ULPS}); median top-2 margin "
        f"{float(np.median(np.concatenate(margins))):.1f} ulps; flash kernel in the engine's "
        f"decode step; zero recompiles after warm-up",
    )


def phase_sharded(seed: int, dim: int = HASHED_DIM, requests: int = 256,
                  round_len: int = 32, p_max: int = 128, chips: int = 4) -> None:
    """LinearService feature-sharded over ``chips`` devices vs mesh=1 on the
    same stream (pallas both); the state must sit on every device."""
    stream = dict(dim=dim, p_max=p_max, requests=requests, round_len=round_len, seed=seed)
    for solver in ("fobos", "ftrl"):
        t0 = time.monotonic()
        svc, one = _service_pair(
            "sharded", solver, stream, {"mesh": chips}, {"backend": "pallas", "mesh": 1}
        )
        wpsi = svc.state.wpsi
        shards = wpsi.addressable_shards
        want = set(jax.devices()[:chips])
        check({s.device for s in shards} == want and len(shards) == chips,
              f"sharded {solver}: state on {[s.device for s in shards]}, not {want}")
        ds = -(-dim // chips)
        cols = wpsi.shape[1]
        for s in shards:
            check(s.data.shape == (ds, cols), f"shard on {s.device} holds {s.data.shape}")
            used = s.device.memory_stats()["bytes_in_use"]
            check(used >= s.data.nbytes, f"{s.device} reports {used} B < its shard")
        w, w_one = svc.current_weights(), one.current_weights()
        err = max_abs(w, w_one)
        check(err <= 1e-5, f"sharded {solver}: mesh={chips} vs mesh=1 max|dw|={err:.3e} > 1e-5")
        mib = [s.data.nbytes / 2**20 for s in shards]
        report(
            "sharded", t0,
            f"{solver}: d={dim} mesh={chips} shard_margin=exact, {chips} shards of "
            f"[{ds}, {cols}] ({mib[0]:.1f} MiB each) on {sorted(d.id for d in want)}; "
            f"mesh={chips} vs mesh=1 max|dw|={err:.3e} (<= 1e-5)",
        )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the feature-sharded phase, over four chips")
    ap.add_argument("--seed", type=int, default=0, help="data and weight seed")
    args = ap.parse_args()
    compile_cache.enable()
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_sharded(args.seed)
    else:
        phase_trainer(args.seed)
        phase_service(args.seed)
        phase_path(args.seed)
        phase_service(args.seed, dim=HASHED_DIM, requests=256, round_len=32, phase="e")
        phase_lm(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
