"""Serving driver.  The default path routes through the repro.serving
continuous-batching engine (slot-based decode, admission queue, metrics);
``--static`` keeps the original fixed-batch lock-step loop as the parity
baseline.  Families the engine can't serve exactly (recurrent state consumes
prompt padding: rwkv6/recurrentgemma; enc-dec; VLM) fall back to the static
loop automatically.

``--linear`` serves the *online elastic-net* LinearService instead of an
LM: synthetic bag-of-words traffic streams through the admission queue
(learn) and the O(p) sparse predictor, under any ``--solver``
(repro.solvers) and ``--backend``.  After warmup the jit compile set is
asserted frozen — fixed shapes, no per-solver recompiles at steady state —
which is the line CI's serving-smoke job runs per solver.  ``--linear
--tenants N`` serves N tenant models through one MultiLinearService
instead: cross-tenant vmapped learn/predict with mid-traffic tenant
add/evict/swap, under the same frozen-compile-set assertion.  ``--linear
--mesh N`` feature-shards the packed solver state across N devices
(repro.dist.linear; on CPU, emulate with
XLA_FLAGS=--xla_force_host_platform_device_count=N).

Reduced configs run on CPU; full configs lower onto the production mesh via
the same decode fns the dry-run compiles.  With --mesh the params and KV
cache are placed via the repro.dist rule table (weights tensor-parallel over
"model", batch/slots over "data"); the engine's jits trace inside the same
activation-sharding context as the static path's."""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import backend as kernel_backend
from repro import obs
from repro.configs import get_arch
from repro.dist import api as dist_api
from repro.dist import sharding as dist_sharding
from repro.launch.mesh import host_mesh_from_spec
from repro.launch import compile_cache
from repro.models import build, init_params
from repro.models import params as pp
from repro.serving import EngineConfig, ServeEngine, ServingMetrics
from repro.train import generate


def _make_prompts(cfg, rng, batch, prompt_len):
    batch_in = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, size=(batch, prompt_len)).astype(np.int32))}
    if cfg.encdec:
        batch_in["frames"] = jnp.asarray(rng.randn(batch, cfg.enc_seq, cfg.d_model).astype(np.float32) * 0.1)
    if cfg.n_patches:
        batch_in["patches"] = jnp.asarray(rng.randn(batch, cfg.n_patches, cfg.d_model).astype(np.float32) * 0.02)
    return batch_in


def serve_static(cfg, model, params, *, batch, prompt_len, new_tokens, seed=0,
                 temperature=0.0):
    """The original lock-step loop: one prefill, then every sequence decodes
    one token per step in unison — the engine's parity/throughput baseline.
    The loop itself lives in serve_step.generate (one copy of the
    cache-growth + split-per-step sampling logic); this driver adds the
    synthetic prompts and the timing report."""
    rng = np.random.RandomState(seed)
    batch_in = _make_prompts(cfg, rng, batch, prompt_len)
    timings: dict = {}
    out = generate(cfg, model, params, batch_in, new_tokens,
                   temperature=temperature, seed=seed, timings=timings)
    toks_per_s = batch * (new_tokens - 1) / max(timings["decode_s"], 1e-9)
    print(f"{cfg.name} [static]: prefill({batch}x{prompt_len}) {timings['prefill_s']*1e3:.1f}ms; "
          f"decode {new_tokens-1} steps -> {toks_per_s:.1f} tok/s")
    return np.asarray(out)


def serve_engine(cfg, model, params, *, batch, prompt_len, new_tokens, seed=0,
                 temperature=0.0, n_slots=None, requests=None):
    """Continuous-batching path: requests flow through the admission queue
    into slots; mixed-length traffic sustains full slot occupancy."""
    rng = np.random.RandomState(seed)
    n_slots = n_slots or batch
    requests = requests or batch
    metrics = ServingMetrics()
    engine = ServeEngine(
        model, params,
        EngineConfig(
            n_slots=n_slots,
            max_len=prompt_len + new_tokens,
            prompt_buckets=(prompt_len,),
            temperature=temperature,
            seed=seed,
        ),
        metrics=metrics,
    )
    with obs.span("serve.warmup", tracker=engine.compiles):
        engine.warmup()
    prompts = rng.randint(0, cfg.vocab_size, size=(requests, prompt_len)).astype(np.int32)
    t0 = time.monotonic()
    # the engine's core invariant, backend-independent: warmup is the
    # complete compile set.  Kernel-backend choice is trace-static
    # (repro.backend), so CI runs this under --backend pallas to prove the
    # non-default backend adds zero recompiles.
    with obs.span("serve.traffic", tracker=engine.compiles, requests=requests), \
            engine.compiles.assert_no_new_compiles("engine steady state"):
        futs = [engine.submit(p, max_new_tokens=new_tokens, arrival=t0) for p in prompts]
        engine.run()
    elapsed = time.monotonic() - t0
    snap = metrics.snapshot()
    lat = snap.get("latency_request", {})
    toks = snap["counters"]["tokens_out"]
    run_compiles = engine.compile_counts()
    logger = obs.active_logger()
    if logger is not None:
        logger.registry_snapshot(metrics)
    print(f"{cfg.name} [engine]: {requests} reqs x ({prompt_len}+{new_tokens}) over "
          f"{n_slots} slots -> {toks / max(elapsed, 1e-9):.1f} tok/s; "
          f"latency p50 {lat.get('p50_ms', 0):.1f}ms p99 {lat.get('p99_ms', 0):.1f}ms; "
          f"compiles {run_compiles} (unchanged since warmup)")
    return np.stack([f.result(timeout=0) for f in futs], axis=0)


def service_eta0(p_max: int) -> float:
    """The smokes' base rate: 0.3 for requests of ``p_max / 2 = 16``
    features, then inversely with the features per request.  At a fixed
    0.3, requests of ~64 features make plain SGD on the hot features
    chaotic: a 1e-6 change of the rate moves the weights by 3e-4 of their
    scale over 384 requests, against ~1e-6 at this rule's 0.075."""
    return 0.3 * min(1.0, 32 / p_max)


def serve_linear(*, solver=None, backend=None, dim=20_000, p_max=32, micro_batch=8,
                 requests=256, round_len=256, seed=0, mesh=None):
    """Online learn/predict smoke over the LinearService: warm the complete
    jit set (every power-of-two bucket x {learn, predict} + the round
    flush), then stream ``requests`` examples and assert zero recompiles.

    ``mesh=N`` feature-shards the packed solver state across N devices
    (repro.dist.linear); the same zero-recompile assertion holds — routing
    is in-graph, so bucket shapes are unchanged."""
    from repro.core import LinearConfig, ScheduleConfig, SparseBatch
    from repro.data import BowConfig, SyntheticBow
    from repro.serving import LinearService, ServiceConfig

    cfg = LinearConfig(
        dim=dim, round_len=round_len, lam1=1e-5, lam2=1e-6,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=service_eta0(p_max), t0=100.0),
        mesh=mesh,
    )
    svc = LinearService(cfg, ServiceConfig(
        p_max=p_max, micro_batch=micro_batch, backend=backend, solver=solver,
    ))
    bow = SyntheticBow(BowConfig(
        dim=dim, p_max=p_max, p_mean=p_max / 2.0,
        informative_pool=min(4096, dim // 2), n_informative=min(512, dim // 8),
        seed=seed,
    ))

    def flat_batch(chunk, n):
        return SparseBatch(idx=chunk.idx[0][:n], val=chunk.val[0][:n], y=chunk.y[0][:n])

    # --- warmup: one learn + one predict per bucket shape, plus the flush —
    # after this the compile set is COMPLETE for any traffic mix
    with obs.span("serve.warmup", tracker=svc.compiles):
        warm = bow.sample_round(10_000, 1, micro_batch)
        for b in svc.buckets:
            svc.learn(flat_batch(warm, b))
            svc.predict(flat_batch(warm, b))
        svc.state = svc._flush(svc.state)

    # --- steady state: Poisson-ish online traffic through the queue ---
    # the LinearService invariant the LM engine also holds: warmup is the
    # complete compile set — solver and backend choices are trace-static
    # (repro.solvers / repro.backend), so steady state never recompiles
    rng = np.random.RandomState(seed)
    t0 = time.monotonic()
    served = 0
    chunk_id = 0
    with obs.span("serve.traffic", tracker=svc.compiles, requests=requests), \
            svc.compiles.assert_no_new_compiles("linear steady state"):
        while served < requests:
            n = int(rng.randint(1, micro_batch + 1))
            chunk = bow.sample_round(20_000 + chunk_id, 1, micro_batch)
            chunk_id += 1
            for r in range(n):
                idx, val, y = np.asarray(chunk.idx[0][r]), np.asarray(chunk.val[0][r]), float(chunk.y[0][r])
                svc.submit_learn(idx, val, y, arrival=0.0)
            svc.poll(now=1.0, force=True)
            svc.predict(flat_batch(chunk, n))
            served += n
    elapsed = time.monotonic() - t0

    run_compiles = svc.compile_counts()
    snap = svc.metrics.snapshot()
    logger = obs.active_logger()
    if logger is not None:
        logger.registry_snapshot(svc.metrics)
    print(f"linear[{svc.cfg.solver}/{svc.cfg.backend}]: {served} learn + {served} predict "
          f"examples in {elapsed:.2f}s ({served / max(elapsed, 1e-9):.0f} ex/s each way); "
          f"counters {snap['counters']}; compiles {run_compiles} (unchanged since warmup)")
    return svc


def serve_multitenant(*, tenants=8, solver=None, backend=None, dim=20_000,
                      p_max=32, micro_batch=8, requests=512, round_len=64,
                      seed=0, mesh=None):
    """Multi-tenant smoke over MultiLinearService: warm the complete vmapped
    program set, provision ``tenants`` tenants (a lam1 ladder — every lane
    carries its own hypers), stream tenant-tagged traffic through the
    admission queue, exercise the full lifecycle (evict / re-add / swap /
    snapshot+restore) mid-traffic, and assert zero recompiles throughout."""
    import tempfile

    from repro.core import LinearConfig, ScheduleConfig
    from repro.data import BowConfig, SyntheticBow
    from repro.serving import MultiLinearService, ServiceConfig

    cfg = LinearConfig(
        dim=dim, round_len=round_len, lam1=1e-5, lam2=1e-6,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=service_eta0(p_max), t0=100.0),
        mesh=mesh,
    )
    svc = MultiLinearService(cfg, n_slots=tenants, service=ServiceConfig(
        p_max=p_max, micro_batch=micro_batch, backend=backend, solver=solver,
        per_tenant_cap=4 * micro_batch,
    ))
    with obs.span("serve.warmup", tracker=svc.compiles):
        svc.warmup()
    lam1s = np.logspace(-6, -4, tenants)
    names = [f"t{i}" for i in range(tenants)]
    bow = SyntheticBow(BowConfig(
        dim=dim, p_max=p_max, p_mean=p_max / 2.0,
        informative_pool=min(4096, dim // 2), n_informative=min(512, dim // 8),
        seed=seed,
    ))
    rng = np.random.RandomState(seed)
    t0 = time.monotonic()
    served = 0
    chunk_id = 0
    with obs.span("serve.traffic", tracker=svc.compiles, requests=requests,
                  tenants=tenants), \
            svc.compiles.assert_no_new_compiles("multi-tenant steady state"):
        for name, lam1 in zip(names, lam1s):
            svc.add_tenant(name, lam1=float(lam1))
        while served < requests:
            # a Poisson-ish cross-tenant mix: each tenant contributes a
            # random number of examples, then one poll trains them all
            chunk = bow.sample_round(20_000 + chunk_id, 1, micro_batch)
            chunk_id += 1
            preds = {}
            for name in svc.tenants():
                n = int(rng.randint(0, micro_batch // 2 + 1))
                for r in range(n):
                    svc.submit_learn(
                        name, np.asarray(chunk.idx[0][r]),
                        np.asarray(chunk.val[0][r]), float(chunk.y[0][r]),
                    )
                served += n
                if n:
                    preds[name] = (np.asarray(chunk.idx[0][:n]),
                                   np.asarray(chunk.val[0][:n]))
            svc.poll(now=1.0, force=True)
            if preds:
                svc.predict_many(preds)
            if chunk_id == 3:  # mid-traffic lifecycle churn, same compile set
                svc.evict_tenant(names[0])
                svc.add_tenant(names[0], lam1=float(lam1s[0]), eta0=0.2)
                svc.swap_tenant(names[1], w=svc.current_weights(names[2]))
                with tempfile.TemporaryDirectory() as td:
                    svc.snapshot_tenant(names[2], td)
                    svc.evict_tenant(names[2])
                    svc.restore_tenant(names[2], td)
    elapsed = time.monotonic() - t0

    run_compiles = svc.compile_counts()
    snap = svc.metrics.snapshot()
    logger = obs.active_logger()
    if logger is not None:
        logger.registry_snapshot(svc.metrics)
    agg = {k: v for k, v in snap["counters"].items() if "{" not in k}
    print(f"multitenant[{svc.cfg.solver}/{svc.cfg.backend}] x{tenants}: "
          f"{served} learn examples in {elapsed:.2f}s "
          f"({served / max(elapsed, 1e-9):.0f} ex/s); counters {agg}; "
          f"compiles {run_compiles} (unchanged since warmup, incl. "
          f"add/evict/swap/snapshot/restore)")
    return svc


def serve(arch: str, *, reduced=True, batch=4, prompt_len=32, new_tokens=32, seed=0,
          mesh_shape: str | None = None, temperature: float = 0.0,
          static: bool = False, n_slots: int | None = None,
          requests: int | None = None, backend: str | None = None):
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    params = init_params(model, seed)

    if not static and model.decode_multi_fn is None:
        print(f"{cfg.name}: no slot-decode path for this family; using the static loop")
        static = True

    ctx = contextlib.nullcontext()
    if mesh_shape:
        mesh = host_mesh_from_spec(mesh_shape)
        rules = dist_sharding.make_rules(cfg, mesh, batch)
        params = jax.device_put(
            params,
            dist_sharding.shardings_for_axes(pp.axes_tree(model.defs), mesh, rules),
        )
        # activation constraints bake in at trace time (dist/api.py), so
        # every jit below — engine or static — must trace inside the context
        ctx = dist_api.activate(mesh, rules)

    with ctx, kernel_backend.use_backend(backend):
        # every jit below traces inside the backend context: the attention
        # path is backend-selected exactly once, at warmup/trace time
        if static:
            return serve_static(cfg, model, params, batch=batch, prompt_len=prompt_len,
                                new_tokens=new_tokens, seed=seed, temperature=temperature)
        return serve_engine(cfg, model, params, batch=batch, prompt_len=prompt_len,
                            new_tokens=new_tokens, seed=seed, temperature=temperature,
                            n_slots=n_slots, requests=requests)


def main():
    from repro.launch import flags

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="LM architecture (required unless --linear)")
    ap.add_argument("--linear", action="store_true",
                    help="serve the online elastic-net LinearService instead of an LM")
    ap.add_argument("--tenants", type=int, default=None, metavar="N",
                    help="--linear: serve N tenant models through one "
                         "MultiLinearService (cross-tenant vmapped dispatch)")
    flags.add_solver(ap)
    flags.add_dim(ap, help="--linear feature-space size")
    # BooleanOptionalAction: --no-reduced reaches the full-size config (the
    # old action="store_true" + default=True made it unreachable)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction, default=True,
                    help="reduced smoke-test config (--no-reduced for full size)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--seed", type=int, default=0, help="params + sampling PRNG seed")
    ap.add_argument("--static", action="store_true",
                    help="fixed-batch lock-step loop (parity baseline)")
    ap.add_argument("--slots", type=int, default=None,
                    help="engine decode slots (default: --batch)")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests to serve through the engine (default: --batch)")
    ap.add_argument(
        "--mesh", default=None, metavar="DxM|N",
        help='data x model mesh over visible devices (e.g. "1x2"); with '
             "--linear: a plain int N of feature shards (repro.dist.linear)",
    )
    flags.add_backend(ap, help="kernel backend for the attention / solver hot "
                               "paths (default: $REPRO_BACKEND or platform default)")
    flags.add_metrics_out(ap)
    flags.add_profile(ap)
    args = ap.parse_args()
    compile_cache.enable()
    if args.linear:
        mesh = None
        if args.mesh is not None:
            try:
                mesh = int(args.mesh)
            except ValueError:
                ap.error(f"--linear takes --mesh N (feature shards), got {args.mesh!r}")
        with obs.run_logger(
            args.metrics_out, "serve", d=args.dim,
            linear=True, solver=args.solver, backend=args.backend,
            tenants=args.tenants, mesh=mesh,
        ), obs.profile_to(args.profile):
            if args.tenants:
                serve_multitenant(tenants=args.tenants, solver=args.solver,
                                  backend=args.backend, dim=args.dim,
                                  requests=args.requests or 512, seed=args.seed,
                                  mesh=mesh)
            else:
                serve_linear(solver=args.solver, backend=args.backend, dim=args.dim,
                             requests=args.requests or 256, seed=args.seed,
                             mesh=mesh)
        return
    if not args.arch:
        ap.error("--arch is required unless --linear")
    with obs.run_logger(
        args.metrics_out, "serve",
        arch=args.arch, static=args.static, backend=args.backend,
    ), obs.profile_to(args.profile):
        serve(args.arch, reduced=args.reduced, batch=args.batch,
              prompt_len=args.prompt_len, new_tokens=args.new_tokens, seed=args.seed,
              mesh_shape=args.mesh, temperature=args.temperature, static=args.static,
              n_slots=args.slots, requests=args.requests, backend=args.backend)


if __name__ == "__main__":
    main()
