"""Multi-tenant online serving: N independent linear models in ONE stacked
state, learned and served through a single vmapped program per solver.

``MultiLinearService`` is the cross-tenant generalization of
``LinearService``: where the sweeps subsystem batches many *hyperparameter
configs* over one data stream (``sweeps.batched_trainer``), this batches
many *tenants* — each with its own weights, bias, DP caches, hypers, and
round clock — over per-tenant data.  The stacked state reuses the sweeps
layout (``STATE_AXES``) with one change: the round-local step ``i`` and
global step ``t`` gain a slot axis too (``TENANT_AXES``), because tenants
receive different traffic and hit their round boundaries at different
times.

**Slot masking without O(n_slots*d) selects.**  A learn dispatch is a
``[n_slots, b, p_max]`` batch; lanes with no examples this dispatch must
come out bitwise-untouched.  Masking the packed state with ``jnp.where``
would cost O(n_slots*d) per dispatch and destroy the paper's O(p) story.
Instead, inactive lanes receive the *out-of-bounds sentinel batch*
(``idx = dim``, ``val = 0``): under jax's clamp/drop semantics a scatter at
an OOB index is DROPPED (the lane's ``wpsi`` buffer is bitwise unchanged,
at O(p) cost) and a gather CLIPS to row ``dim-1`` (a harmless read of one
real row, multiplied by ``val = 0``).  Only the small per-lane leaves —
bias, DP caches, ``i``, ``t``, loss — go through a cheap ``jnp.where``
select.  Active lanes use the ordinary ``idx=0 / val=0`` feature-padding
convention, so a 1-slot service replays ``LinearService`` bitwise on the
reference backend.

**Solver-major grouping.**  A solver is a *program* change and a *state
shape* change (ftrl packs ``[d, 3]``, the cache solvers ``[d, 2]``), so
tenants group by solver exactly like ``sweeps.run_grid``: one slot pool +
one compiled program set per solver, dispatched independently.

**Zero recompiles.**  Every program is traced at ``warmup()`` — per-bucket
learn/predict, the masked flush, and the two seed programs (slot index,
weights, hypers, and round clock are all *dynamic* operands) — so tenant
add / evict / swap / snapshot / restore and all steady-state traffic stay
inside the frozen compile set (``CompileTracker``; the bench and the
serving smoke wrap traffic in ``assert_no_new_compiles``).

Admission is tenant-tagged through ``AdmissionQueue`` (``per_tenant_cap``
QoS rejections are counted per tenant via ``obs.registry.label``); the
queue drains through a generalized binary decomposition — per bucket size
``b`` descending, one dispatch trains every tenant with ``>= b`` pending
examples at once.
"""
from __future__ import annotations

import dataclasses
import json
import time
from functools import partial
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import solvers as solver_registry
from repro.checkpoint import checkpointer
from repro.core import linear_trainer as lt
from repro.core.dp_caches import init_caches
from repro.core.linear_trainer import Hypers, LinearConfig, LinearState, SparseBatch
from repro.obs.compile_tracker import CompileTracker
from repro.obs.registry import label as metric_label
from repro.serving.metrics import ServingMetrics
from repro.serving.queue import AdmissionQueue
from repro.serving.service_config import ServiceConfig, binary_buckets, pin_config
from repro.sweeps.batched_trainer import HYPER_AXES, STATE_AXES

# Per-tenant state axes: the sweeps layout plus a slot axis on the round
# clocks — tenants flush when *their* round fills, not in lock-step.
TENANT_AXES = STATE_AXES._replace(i=0, t=0)


class _SolverGroup:
    """One solver's slot pool: stacked state, host-side bookkeeping, and the
    compiled program set (learn/predict per bucket + flush + two seeders)."""

    def __init__(self, cfg: LinearConfig, n_slots: int, tracker: CompileTracker):
        self.key = cfg.solver
        self.cfg = cfg
        self.sv = solver_registry.for_config(cfg)
        self.sv.validate(cfg)  # the group's default hypers must be sane
        self.n_slots = n_slots
        d, cols = cfg.dim, self.sv.state_cols
        caches = init_caches(cfg.round_len)
        self.bstate = LinearState(
            wpsi=jnp.zeros((n_slots, d, cols), jnp.float32),
            b=jnp.zeros((n_slots,), jnp.float32),
            caches=jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_slots,) + a.shape), caches
            ),
            i=jnp.zeros((n_slots,), jnp.int32),
            t=jnp.zeros((n_slots,), jnp.int32),
        )
        if cfg.mesh is not None:
            # every lane's rows pad to the shard grain and shard over the
            # feature mesh; the per-lane clocks/bias/caches replicate
            from repro.dist import linear as dl

            self.bstate = dl.place_batched(cfg, self.bstate)
        # host mirrors: per-slot hypers (uploaded per dispatch — tiny) and
        # the round counter (flush decisions without a device sync per step)
        self.hp_lam1 = np.full((n_slots,), cfg.lam1, np.float32)
        self.hp_lam2 = np.full((n_slots,), cfg.lam2, np.float32)
        self.hp_eta = np.full((n_slots,), cfg.schedule.eta0, np.float32)
        self.i_host = np.zeros((n_slots,), np.int64)
        # descending free list: adds fill slot 0 upward; evicted slots are
        # appended and reused LIFO (the ServeEngine slot-reuse discipline)
        self.free: List[int] = list(range(n_slots - 1, -1, -1))
        self.names: Dict[int, str] = {}  # slot -> tenant
        self._build_jits(tracker)

    def hp(self) -> Hypers:
        return Hypers(
            lam1=jnp.asarray(self.hp_lam1),
            lam2=jnp.asarray(self.hp_lam2),
            eta_scale=jnp.asarray(self.hp_eta),
        )

    def hp_at(self, k: int) -> Hypers:
        return Hypers(
            lam1=jnp.float32(self.hp_lam1[k]),
            lam2=jnp.float32(self.hp_lam2[k]),
            eta_scale=jnp.float32(self.hp_eta[k]),
        )

    def _build_jits(self, tracker: CompileTracker) -> None:
        cfg, sv = self.cfg, self.sv
        sharded = cfg.mesh is not None
        if sharded:
            # feature-sharded lanes: the vmap over tenants moves INSIDE one
            # manual shard_map region (dist.linear wraps it) and every lane
            # fn becomes its shard-local twin — the OOB sentinel batch
            # (idx = dim) is unowned by every shard, so inactive-lane
            # masking works unchanged
            from repro.dist import linear as dl

            step_hp = dl.make_tenant_step_hp(cfg)
        else:
            step_hp = lt.make_lazy_step_hp(cfg)

        def lane_learn(state, hp, active, batch):
            new, loss = step_hp(state, batch, hp)
            keep = partial(jnp.where, active)
            # wpsi needs no select: inactive lanes carry the OOB sentinel
            # batch, whose scatters DROP — the buffer is bitwise untouched
            new = LinearState(
                wpsi=new.wpsi,
                b=keep(new.b, state.b),
                caches=jax.tree.map(keep, new.caches, state.caches),
                i=keep(new.i, state.i),
                t=keep(new.t, state.t),
            )
            return new, keep(loss, jnp.float32(0.0))

        if sharded:
            def lane_predict(state, hp, batch):
                return dl._local_predict(cfg, sv, state, batch, hp)
        else:
            def lane_predict(state, hp, batch):
                return lt.predict_proba_sparse(cfg, state, batch, hp=hp)

        def lane_flush(state, hp, mask):
            flushed = dl.local_flush(cfg, state, hp) if sharded else lt.flush(
                cfg, state, hp=hp
            )
            return jax.tree.map(partial(jnp.where, mask), flushed, state)

        def _seed_rows(rows):
            # seeds arrive at the logical dim; sharded buffers carry the
            # padded shard grain
            return dl.pad_rows(cfg, rows) if sharded else rows

        def seed_w(bstate, k, w, b, t, hp):
            # dynamic slot index k: one trace serves every add/swap
            return LinearState(
                wpsi=bstate.wpsi.at[k].set(_seed_rows(sv.seed_cols(cfg, w, hp))),
                b=bstate.b.at[k].set(b),
                caches=jax.tree.map(
                    lambda c, f: c.at[k].set(f), bstate.caches, init_caches(cfg.round_len)
                ),
                i=bstate.i.at[k].set(0),
                t=bstate.t.at[k].set(t),
            )

        def seed_state(bstate, k, packed, b, t):
            return LinearState(
                wpsi=bstate.wpsi.at[k].set(_seed_rows(sv.adopt_state(cfg, packed))),
                b=bstate.b.at[k].set(b),
                caches=jax.tree.map(
                    lambda c, f: c.at[k].set(f), bstate.caches, init_caches(cfg.round_len)
                ),
                i=bstate.i.at[k].set(0),
                t=bstate.t.at[k].set(t),
            )

        def reg(name, fn):
            return tracker.register(f"{self.key}/{name}", fn)

        if sharded:
            learn_sh = dl.wrap_tenant(cfg, lane_learn, 2)
            self.learn_fn = reg("learn", jax.jit(learn_sh, donate_argnums=0))
            self.predict_fn = reg(
                "predict", jax.jit(dl.wrap_tenant_predict(cfg, lane_predict))
            )

            def lane_flush_loss(state, hp, mask):
                # wrap_tenant's lane contract is (state, per-lane value)
                return lane_flush(state, hp, mask), jnp.float32(0.0)

            flush_sh = dl.wrap_tenant(cfg, lane_flush_loss, 1)
            self.flush_fn = reg("flush", jax.jit(
                lambda bs, hp, mask: flush_sh(bs, hp, mask)[0], donate_argnums=0
            ))
        else:
            self.learn_fn = reg("learn", jax.jit(
                jax.vmap(lane_learn, in_axes=(TENANT_AXES, HYPER_AXES, 0, 0),
                         out_axes=(TENANT_AXES, 0)),
                donate_argnums=0,
            ))
            self.predict_fn = reg("predict", jax.jit(
                jax.vmap(lane_predict, in_axes=(TENANT_AXES, HYPER_AXES, 0))
            ))
            self.flush_fn = reg("flush", jax.jit(
                jax.vmap(lane_flush, in_axes=(TENANT_AXES, HYPER_AXES, 0),
                         out_axes=TENANT_AXES),
                donate_argnums=0,
            ))
        self.seed_w_fn = reg("seed_w", jax.jit(seed_w, donate_argnums=0))
        self.seed_state_fn = reg("seed_state", jax.jit(seed_state, donate_argnums=0))


class MultiLinearService:
    """N tenant linear models served through one vmapped program per solver.

    ``cfg`` is the *shared structure* (dim, loss, schedule kind, round_len,
    backend — everything that changes the program); per-
    tenant hypers (lam1, lam2, eta0) and the solver vary per tenant.
    ``n_slots`` is the capacity of each solver group; ``solvers`` names the
    groups to provision (default: the config's resolved solver only — every
    group costs its own compiled program set at warmup)."""

    def __init__(self, cfg: LinearConfig, n_slots: int = 8,
                 service: Optional[ServiceConfig] = None, *,
                 solvers: Optional[Tuple[str, ...]] = None):
        assert n_slots >= 1
        service = service or ServiceConfig()
        cfg = pin_config(cfg, service)
        self.cfg = cfg
        self.service = service
        self.n_slots = n_slots
        self.p_max = service.p_max
        self.micro_batch = service.micro_batch
        self.buckets = binary_buckets(service.micro_batch)
        self.metrics = service.metrics or ServingMetrics()
        self.queue = AdmissionQueue(max_batch=service.micro_batch,
                                    max_delay=service.max_delay,
                                    per_tag_cap=service.per_tenant_cap)
        self.compiles = CompileTracker()
        solvers = tuple(solvers) if solvers else (cfg.solver,)
        if cfg.solver not in solvers:
            raise ValueError(
                f"resolved default solver {cfg.solver!r} not in solvers={solvers}"
            )
        self.groups: Dict[str, _SolverGroup] = {}
        for name in solvers:
            gcfg = dataclasses.replace(cfg, solver=name)
            self.groups[name] = _SolverGroup(gcfg, n_slots, self.compiles)
        self._tenants: Dict[str, Tuple[str, int]] = {}  # name -> (group, slot)
        self._pending: Dict[str, Dict[int, List]] = {g: {} for g in self.groups}

    # -- introspection -------------------------------------------------------

    def compile_counts(self) -> dict:
        return self.compiles.counts()

    def tenants(self) -> Tuple[str, ...]:
        return tuple(self._tenants)

    def slot_of(self, name: str) -> Tuple[str, int]:
        """(solver-group key, slot index) of a tenant."""
        return self._tenants[name]

    def n_free(self, solver: Optional[str] = None) -> int:
        g = self.groups[solver or self.cfg.solver]
        return len(g.free)

    def tenant_state(self, name: str) -> LinearState:
        """One tenant's lane as a single-model LinearState (host view)."""
        gk, k = self._tenants[name]
        g = self.groups[gk]
        return LinearState(
            wpsi=g.bstate.wpsi[k], b=g.bstate.b[k],
            caches=jax.tree.map(lambda c: c[k], g.bstate.caches),
            i=g.bstate.i[k], t=g.bstate.t[k],
        )

    def current_weights(self, name: str) -> np.ndarray:
        gk, k = self._tenants[name]
        g = self.groups[gk]
        return np.asarray(
            lt.current_weights(g.cfg, self.tenant_state(name), hp=g.hp_at(k))
        )

    # -- tenant lifecycle ----------------------------------------------------

    def _tenant_cfg(self, g: _SolverGroup, lam1, lam2, eta0) -> LinearConfig:
        return dataclasses.replace(
            g.cfg, lam1=lam1, lam2=lam2,
            schedule=dataclasses.replace(g.cfg.schedule, eta0=eta0),
        )

    def add_tenant(self, name: str, *, solver: Optional[str] = None,
                   lam1: Optional[float] = None, lam2: Optional[float] = None,
                   eta0: Optional[float] = None, w0=None, b0: float = 0.0) -> int:
        """Provision a tenant on a free slot of its solver group; returns the
        slot.  Per-tenant hypers default to the shared config's; they are
        validated eagerly (concrete) against the tenant's solver."""
        if name in self._tenants:
            raise ValueError(f"tenant {name!r} already exists")
        g = self.groups[solver or self.cfg.solver]
        if not g.free:
            raise RuntimeError(f"no free slots in solver group {g.key!r} "
                               f"({self.n_slots} in use)")
        lam1 = g.cfg.lam1 if lam1 is None else float(lam1)
        lam2 = g.cfg.lam2 if lam2 is None else float(lam2)
        eta0 = g.cfg.schedule.eta0 if eta0 is None else float(eta0)
        g.sv.validate(self._tenant_cfg(g, lam1, lam2, eta0))
        k = g.free.pop()
        g.hp_lam1[k], g.hp_lam2[k], g.hp_eta[k] = lam1, lam2, eta0
        g.i_host[k] = 0
        w0 = np.zeros((g.cfg.dim,), np.float32) if w0 is None else np.asarray(w0, np.float32)
        g.bstate = g.seed_w_fn(
            g.bstate, jnp.int32(k), jnp.asarray(w0), jnp.float32(b0),
            jnp.int32(0), g.hp_at(k),
        )
        self._tenants[name] = (g.key, k)
        g.names[k] = name
        self._pending[g.key][k] = []
        self.metrics.count("tenant_adds")
        return k

    def evict_tenant(self, name: str) -> None:
        """Host-only: free the slot (no device work — the next add reseeds
        the lane completely).  Unflushed pending examples are shed."""
        gk, k = self._tenants.pop(name)
        g = self.groups[gk]
        shed = len(self._pending[gk].pop(k, []) or [])
        if shed:
            self.metrics.count("shed_examples", shed)
        del g.names[k]
        g.free.append(k)
        self.metrics.count("tenant_evicts")

    # -- learn ---------------------------------------------------------------

    def submit_learn(self, tenant: str, idx, val, y, arrival: float = 0.0) -> bool:
        """Enqueue one tenant-tagged example; False = QoS-rejected (the
        tenant already has ``per_tenant_cap`` examples waiting)."""
        if tenant not in self._tenants:
            raise KeyError(f"unknown tenant {tenant!r}")
        item = (tenant, np.asarray(idx, np.int32).reshape(-1),
                np.asarray(val, np.float32).reshape(-1), np.float32(y))
        ok = self.queue.put(item, arrival=arrival, tag=tenant)
        if not ok:
            self.metrics.count(metric_label("qos_rejected", tenant=tenant))
            self.metrics.count("qos_rejected")
        return ok

    def poll(self, now: float, force: bool = False) -> int:
        """Drain the admission queue cross-tenant: pop arrived examples,
        bucket them per (group, slot), then dispatch — per bucket size ``b``
        descending, one vmapped step trains every tenant holding ``>= b``
        pending examples.  Returns examples trained."""
        items = self.queue.pop_ready(now, force=force)
        for tenant, fi, fv, fy in items:
            rec = self._tenants.get(tenant)
            if rec is None:  # evicted while queued
                self.metrics.count("shed_examples")
                continue
            gk, k = rec
            self._pending[gk][k].append((fi, fv, fy))
        total = 0
        t0 = time.monotonic()
        for g in self.groups.values():
            total += self._drain_group(g)
        if total:
            self.metrics.record_latency("learn", time.monotonic() - t0)
            self.metrics.sample_queue_depth(self.queue.depth(now))
        return total

    def _drain_group(self, g: _SolverGroup) -> int:
        pend = self._pending[g.key]
        total = 0
        while True:
            counts = {s: len(v) for s, v in pend.items() if v}
            if not counts:
                return total
            b = max(bb for bb in self.buckets if bb <= max(counts.values()))
            per_slot = {
                s: [pend[s].pop(0) for _ in range(b)]
                for s, c in counts.items() if c >= b
            }
            self._dispatch_learn(g, per_slot, b)
            for s in per_slot:
                self.metrics.count(
                    metric_label("learn_examples", tenant=g.names[s]), b
                )
            total += b * len(per_slot)

    def learn(self, tenant: str, batch: SparseBatch) -> float:
        """Direct single-tenant step (bucket-sized batch), mirroring
        ``LinearService.learn``; returns the mean loss (device pull)."""
        gk, k = self._tenants[tenant]
        g = self.groups[gk]
        idx = np.asarray(batch.idx)
        val = np.asarray(batch.val)
        y = np.asarray(batch.y, np.float32)
        B = idx.shape[0]
        assert B in self.buckets, f"batch size {B} not in buckets {self.buckets}"
        per_slot = {k: [(idx[j], val[j], y[j]) for j in range(B)]}
        t0 = time.monotonic()
        losses = self._dispatch_learn(g, per_slot, B)
        self.metrics.record_latency("learn", time.monotonic() - t0)
        self.metrics.count(metric_label("learn_examples", tenant=tenant), B)
        return float(losses[k])

    def _dispatch_learn(self, g: _SolverGroup, per_slot: Dict[int, List], b: int):
        """One vmapped step: active lanes get their ``b`` examples (features
        idx=0/val=0-padded to p_max, the trainer's exact convention);
        inactive lanes get the OOB sentinel batch (idx=dim: scatters drop,
        gathers clip harmlessly) and a where-select on the small leaves."""
        n, d = g.n_slots, g.cfg.dim
        idx = np.full((n, b, self.p_max), d, np.int32)
        val = np.zeros((n, b, self.p_max), np.float32)
        y = np.zeros((n, b), np.float32)
        active = np.zeros((n,), bool)
        for s, exs in per_slot.items():
            active[s] = True
            idx[s] = 0
            for j, (fi, fv, fy) in enumerate(exs):
                p = fi.size
                assert p <= self.p_max, f"{p} features > p_max {self.p_max}"
                idx[s, j, :p] = fi
                val[s, j, :p] = fv
                y[s, j] = fy
        batch = SparseBatch(idx=jnp.asarray(idx), val=jnp.asarray(val), y=jnp.asarray(y))
        g.bstate, losses = g.learn_fn(g.bstate, g.hp(), jnp.asarray(active), batch)
        g.i_host[active] += 1
        self.metrics.count("learn_steps")
        self.metrics.count("learn_examples", b * len(per_slot))
        self._maybe_flush(g)
        return losses

    def _maybe_flush(self, g: _SolverGroup) -> None:
        mask = g.i_host >= g.cfg.round_len
        if mask.any():
            g.bstate = g.flush_fn(g.bstate, g.hp(), jnp.asarray(mask))
            g.i_host[mask] = 0
            self.metrics.count("round_flushes", int(mask.sum()))

    # -- predict -------------------------------------------------------------

    def predict(self, tenant: str, idx, val) -> np.ndarray:
        """Probabilities/values for one tenant's ``[B, p]`` request batch."""
        return self.predict_many({tenant: (idx, val)})[tenant]

    def predict_many(self, reqs: Dict[str, Tuple]) -> Dict[str, np.ndarray]:
        """Cross-tenant batched prediction: ``{tenant: (idx [B,p], val)}``
        -> ``{tenant: probs [B]}``.  Pure, so example-count padding to the
        bucket is safe (padded rows are sliced off); one vmapped call serves
        every requesting tenant of a group at once."""
        t0 = time.monotonic()
        by_group: Dict[str, Dict[int, Tuple[np.ndarray, np.ndarray]]] = {}
        out: Dict[str, np.ndarray] = {}
        for tenant, (idx, val) in reqs.items():
            gk, k = self._tenants[tenant]
            idx = np.asarray(idx, np.int32)
            val = np.asarray(val, np.float32)
            assert idx.ndim == 2 and idx.shape[1] <= self.p_max
            by_group.setdefault(gk, {})[k] = (idx, val)
            out[tenant] = np.empty((idx.shape[0],), np.float32)
        for gk, slots in by_group.items():
            g = self.groups[gk]
            done = {s: 0 for s in slots}
            while True:
                rem = {s: slots[s][0].shape[0] - done[s] for s in slots}
                rem = {s: r for s, r in rem.items() if r > 0}
                if not rem:
                    break
                b = max(bb for bb in self.buckets if bb <= max(rem.values()))
                n = g.n_slots
                idx = np.full((n, b, self.p_max), g.cfg.dim, np.int32)
                val = np.zeros((n, b, self.p_max), np.float32)
                take = {}
                for s, r in rem.items():
                    si, sv_ = slots[s]
                    nb = min(r, b)
                    lo = done[s]
                    p = si.shape[1]
                    idx[s] = 0
                    idx[s, :nb, :p] = si[lo:lo + nb]
                    val[s, :nb, :p] = sv_[lo:lo + nb]
                    take[s] = nb
                batch = SparseBatch(idx=jnp.asarray(idx), val=jnp.asarray(val),
                                    y=jnp.zeros((n, b), jnp.float32))  # y unused
                probs = np.asarray(g.predict_fn(g.bstate, g.hp(), batch))
                for s, nb in take.items():
                    out[g.names[s]][done[s]:done[s] + nb] = probs[s, :nb]
                    done[s] += nb
        for tenant, (idx, _) in reqs.items():
            self.metrics.count(
                metric_label("predict_examples", tenant=tenant), int(np.asarray(idx).shape[0])
            )
            self.metrics.count("predict_examples", int(np.asarray(idx).shape[0]))
        self.metrics.record_latency("predict", time.monotonic() - t0)
        return out

    # -- swap / snapshot / restore ------------------------------------------

    def swap_tenant(self, tenant: str, w=None, b: float = 0.0, state=None,
                    lam1: Optional[float] = None, lam2: Optional[float] = None,
                    eta0: Optional[float] = None) -> None:
        """Hot-swap one tenant's model — a weight vector (``w=``, re-seeded
        through the solver's read inversion) or a full packed solver state
        (``state=``, sanitized by ``adopt_state`` — the lossless form for
        ftrl's (z, n) and tenant migrations).  New hypers take effect
        immediately (they are dynamic operands, not trace constants); the
        tenant's global step ``t`` is preserved so attenuating schedules do
        not restart hot."""
        if (w is None) == (state is None):
            raise ValueError("swap_tenant takes exactly one of w= or state=")
        gk, k = self._tenants[tenant]
        g = self.groups[gk]
        new_lam1 = g.hp_lam1[k] if lam1 is None else float(lam1)
        new_lam2 = g.hp_lam2[k] if lam2 is None else float(lam2)
        new_eta0 = g.hp_eta[k] if eta0 is None else float(eta0)
        g.sv.validate(self._tenant_cfg(g, float(new_lam1), float(new_lam2), float(new_eta0)))
        g.hp_lam1[k], g.hp_lam2[k], g.hp_eta[k] = new_lam1, new_lam2, new_eta0
        t_cur = jnp.int32(int(g.bstate.t[k]))  # rare op: one device pull
        if state is not None:
            packed = jnp.asarray(state, jnp.float32)
            if packed.shape != (g.cfg.dim, g.sv.state_cols):
                raise ValueError(
                    f"state= shape {packed.shape} != "
                    f"[{g.cfg.dim}, {g.sv.state_cols}] for solver {g.key!r}"
                )
            g.bstate = g.seed_state_fn(
                g.bstate, jnp.int32(k), packed, jnp.float32(b), t_cur
            )
        else:
            g.bstate = g.seed_w_fn(
                g.bstate, jnp.int32(k), jnp.asarray(np.asarray(w, np.float32)),
                jnp.float32(b), t_cur, g.hp_at(k),
            )
        g.i_host[k] = 0
        self.metrics.count("weight_swaps")
        self.metrics.count(metric_label("weight_swaps", tenant=tenant))

    def snapshot_tenant(self, tenant: str, ckpt_dir) -> Path:
        """Flush one tenant's lane (masked — other lanes untouched) and
        checkpoint its packed state + bias, with solver/hypers/step in the
        manifest, via the atomic checkpointer."""
        gk, k = self._tenants[tenant]
        g = self.groups[gk]
        mask = np.zeros((g.n_slots,), bool)
        mask[k] = True
        g.bstate = g.flush_fn(g.bstate, g.hp(), jnp.asarray(mask))
        g.i_host[k] = 0
        t_k = int(g.bstate.t[k])
        # slice to the logical dim: snapshots are mesh-size independent
        state = {"wpsi": np.asarray(g.bstate.wpsi[k])[: g.cfg.dim],
                 "b": np.asarray(g.bstate.b[k])}
        extra = {
            "tenant": tenant, "solver": g.key, "t": t_k,
            "lam1": float(g.hp_lam1[k]), "lam2": float(g.hp_lam2[k]),
            "eta0": float(g.hp_eta[k]),
        }
        self.metrics.count("tenant_snapshots")
        return checkpointer.save(ckpt_dir, t_k, state, extra_meta=extra)

    def restore_tenant(self, name: str, ckpt_dir, step: Optional[int] = None) -> int:
        """Re-provision a tenant from a snapshot (new slot unless ``name``
        is already live on the snapshot's solver group, which restores in
        place).  Returns the slot."""
        if step is None:
            step = checkpointer.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
        manifest = json.loads(
            (Path(ckpt_dir) / f"step_{step:08d}" / "manifest.json").read_text()
        )
        meta = manifest["extra"]
        g = self.groups[meta["solver"]]
        template = {
            "wpsi": np.zeros((g.cfg.dim, g.sv.state_cols), np.float32),
            "b": np.zeros((), np.float32),
        }
        tree, _ = checkpointer.restore(ckpt_dir, step, template)
        if name in self._tenants:
            gk, k = self._tenants[name]
            if gk != g.key:
                raise ValueError(
                    f"tenant {name!r} lives on solver {gk!r}, snapshot is {g.key!r}"
                )
        else:
            k = self.add_tenant(name, solver=g.key, lam1=meta["lam1"],
                                lam2=meta["lam2"], eta0=meta["eta0"])
        g.hp_lam1[k], g.hp_lam2[k], g.hp_eta[k] = meta["lam1"], meta["lam2"], meta["eta0"]
        g.bstate = g.seed_state_fn(
            g.bstate, jnp.int32(k), jnp.asarray(tree["wpsi"], jnp.float32),
            jnp.float32(tree["b"]), jnp.int32(meta["t"]),
        )
        g.i_host[k] = 0
        self.metrics.count("tenant_restores")
        return k

    # -- warmup --------------------------------------------------------------

    def warmup(self) -> dict:
        """Trace every program in the steady-state compile set: per-bucket
        learn (all-inactive — state-preserving: OOB scatters drop, selects
        keep) and predict, the masked flush (all-False), and both seed
        programs (on a free slot, whose content a later add fully reseeds).
        After this, add/evict/swap/snapshot/restore and all traffic run with
        zero new compiles.  Returns the compile counts."""
        for g in self.groups.values():
            hp = g.hp()
            none = jnp.zeros((g.n_slots,), bool)
            for b in self.buckets:
                idx = jnp.full((g.n_slots, b, self.p_max), g.cfg.dim, jnp.int32)
                val = jnp.zeros((g.n_slots, b, self.p_max), jnp.float32)
                yb = jnp.zeros((g.n_slots, b), jnp.float32)
                batch = SparseBatch(idx=idx, val=val, y=yb)
                g.bstate, _ = g.learn_fn(g.bstate, hp, none, batch)
                g.predict_fn(g.bstate, hp, batch)
            g.bstate = g.flush_fn(g.bstate, hp, none)
            if g.free:
                k = jnp.int32(g.free[-1])  # peek — the slot stays free
                g.bstate = g.seed_w_fn(
                    g.bstate, k, jnp.zeros((g.cfg.dim,), jnp.float32),
                    jnp.float32(0.0), jnp.int32(0), g.hp_at(int(k)),
                )
                g.bstate = g.seed_state_fn(
                    g.bstate, k,
                    jnp.zeros((g.cfg.dim, g.sv.state_cols), jnp.float32),
                    jnp.float32(0.0), jnp.int32(0),
                )
        jax.block_until_ready([g.bstate.wpsi for g in self.groups.values()])
        self.metrics.reset_clock()
        return self.compile_counts()
