"""Feature-sharded lazy linear training (DESIGN.md §16).

Production-scale sparse linear models (CTR / hashed text, PAPERS.md's
F10-SGD) carry 10^8-10^9 features — no single host holds the packed
``[d, state_cols]`` solver state.  This module partitions that state across
a named ``features`` mesh axis and keeps the paper's O(p) lazy step
SHARD-LOCAL: each shard owns a contiguous ``[k*ds, (k+1)*ds)`` slab of
feature ids and runs catch-up -> margin -> gradient -> scatter entirely on
its own rows.  The only cross-shard traffic per step is the per-example
margin partial sum — one small ``psum`` of ``[B]`` (or ``[B, p]`` in the
bitwise-exact mode), optionally int8-quantized through
:func:`repro.dist.compress.quantized_psum`.

Index routing (the multi-tenant masking trick, inverted): inside the
manual shard_map body every shard sees the full replicated minibatch and
remaps each feature id to a local row::

    owned = (idx >= lo) & (idx < lo + ds) & (idx < dim)
    lidx  = where(owned, idx - lo, ds)      # ds = out-of-bounds sentinel
    val   = where(owned, val, 0.0)

Gathers at the sentinel CLIP (row ds-1 — garbage, but multiplied by the
zeroed value) and scatters at the sentinel DROP, so off-shard updates
vanish without any branching.  The ``idx < dim`` clause also swallows the
multi-tenant inactive-lane sentinel (idx = dim) for free.

What is replicated: the bias, the round clock ``(i, t)`` and the DP caches
— all O(round_len), not O(d) — so flush, ``current_weights`` and
``predict_proba_sparse`` stay shard-local (every shard replays the same
closed-form catch-up against its own rows; nothing but the margin ever
crosses the mesh).

Margin modes (``LinearConfig.shard_margin``):

* ``exact``     — psum the ``[B, p]`` per-slot contributions, then reduce
  columns in the unsharded order.  Disjoint ownership means each column is
  ``x + 0.0 + 0.0 + ...`` — exact in fp — so the sharded fit is BITWISE
  identical to the single-device fit on the reference backend (the parity
  suite pins mesh={1,2,4} for all four solvers).
* ``partial``   — reduce columns locally, psum the ``[B]`` partials: p/B x
  less wire traffic, fp-equivalent but not bitwise (summation order).
* ``quantized`` — ``partial`` through the int8 shared-scale psum.

Validated on CPU host meshes: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import dp_caches, phases
from repro.core import linear_trainer as lt
from repro.core.dp_caches import RegCaches
from repro.core.linear_trainer import Hypers, LinearState, SparseBatch

from .api import manual_shard_map

MARGIN_MODES = ("exact", "partial", "quantized")

# vmap axes for the per-config (sweeps) / per-tenant (serving) leading dim:
# wpsi/b/caches carry a lane each; the round clock is shared across a sweep
# (STACKED_AXES) but per-lane for tenants (TENANT_AXES) — the same split
# sweeps.batched_trainer/serving.multi_service use for the unsharded path.
STACKED_AXES = LinearState(
    wpsi=0, b=0, caches=RegCaches(logP=0, B=0, S=0), i=None, t=None
)
TENANT_AXES = STACKED_AXES._replace(i=0, t=0)
HYPER_AXES = Hypers(0, 0, 0)

BATCH_SPECS = SparseBatch(idx=P(), val=P(), y=P())
HYPER_SPECS = Hypers(P(), P(), P())


# --------------------------------------------------------------------------
# mesh / sharding plumbing
# --------------------------------------------------------------------------


def shard_info(cfg) -> Tuple[int, int, int]:
    """``(n_shards, ds, d_pad)``: rows are padded to ``n * ds`` so every
    shard owns an identical ``[ds, state_cols]`` slab.  Padding rows are
    inert by construction — zero state reads as weight 0 under every solver
    (w=psi=0 catch-up -> 0; ftrl z=n=0 -> |z| <= lam1 -> 0)."""
    n = int(cfg.mesh)
    ds = -(-cfg.dim // n)
    return n, ds, n * ds


def feature_mesh(cfg) -> Mesh:
    """A 1-D mesh over the first ``cfg.mesh`` visible devices."""
    n = int(cfg.mesh)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh={n} needs {n} devices but only {len(devs)} are visible; "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count={n}"
        )
    return Mesh(np.array(devs[:n]), (cfg.feature_axis,))


def state_specs(cfg, *, stacked: bool = False) -> LinearState:
    """PartitionSpec tree for a (possibly lane-stacked) LinearState: the
    packed rows shard over the feature axis, everything else replicates."""
    ax = cfg.feature_axis
    wp = P(None, ax, None) if stacked else P(ax, None)
    return LinearState(
        wpsi=wp, b=P(), caches=RegCaches(logP=P(), B=P(), S=P()), i=P(), t=P()
    )


def state_shardings(cfg, mesh: Optional[Mesh] = None, *, stacked: bool = False):
    """NamedSharding tree matching :func:`state_specs`."""
    mesh = feature_mesh(cfg) if mesh is None else mesh
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        state_specs(cfg, stacked=stacked),
        is_leaf=lambda x: isinstance(x, P),
    )


def _hp(hp: Hypers) -> Hypers:
    """Hypers as arrays — shard_map body arguments, never closure constants
    (a closed-over tracer would escape the manual region)."""
    return Hypers(*(jnp.asarray(h, jnp.float32) for h in hp))


# --------------------------------------------------------------------------
# shard-local building blocks (call these INSIDE a manual shard_map body)
# --------------------------------------------------------------------------


def route_batch(cfg, batch: SparseBatch) -> SparseBatch:
    """In-graph index routing: global feature ids -> local rows with the
    OOB-sentinel convention documented in the module docstring."""
    n, ds, _ = shard_info(cfg)
    lo = jax.lax.axis_index(cfg.feature_axis) * ds
    owned = (batch.idx >= lo) & (batch.idx < lo + ds) & (batch.idx < cfg.dim)
    lidx = jnp.where(owned, batch.idx - lo, ds).astype(jnp.int32)
    val = jnp.where(owned, batch.val, jnp.zeros_like(batch.val))
    return SparseBatch(idx=lidx, val=val, y=batch.y)


def margin_psum(cfg, contrib: jnp.ndarray) -> jnp.ndarray:
    """Reduce the masked per-slot margin contributions ``[B, p]`` to the
    per-example margin ``[B]`` — the ONLY cross-shard traffic of a step.
    Its ops carry the ``lazy.margin`` scope (``core.phases.MARGIN``)."""
    with jax.named_scope(phases.MARGIN):
        if cfg.shard_margin == "exact":
            # column-aligned: each slot is owned by exactly one shard, so the
            # psum adds zeros — exact — and the column reduction then runs in
            # the unsharded order (bitwise parity on the reference backend)
            return jnp.sum(jax.lax.psum(contrib, cfg.feature_axis), axis=-1)
        part = jnp.sum(contrib, axis=-1)
        if cfg.shard_margin == "quantized":
            from . import compress

            return compress.quantized_psum(part, cfg.feature_axis)
        return jax.lax.psum(part, cfg.feature_axis)


def make_local_step_hp(cfg):
    """``step(state_local, batch, hp)`` for use inside a manual shard_map
    body: route the replicated batch, then the solver's shard-local fused
    pass (:meth:`repro.solvers.api.Solver.sharded_update`)."""
    solver = lt._solver(cfg)
    unit_sched = cfg.schedule.unit().make()

    def step(state: LinearState, batch: SparseBatch, hp: Hypers):
        bk = lt._backend(cfg.backend)
        eta = jnp.asarray(hp.eta_scale, jnp.float32) * unit_sched(state.t)
        local = route_batch(cfg, batch)
        return solver.sharded_update(cfg, state, local, hp, eta, bk, cfg.feature_axis)

    return step


def local_flush(cfg, state: LinearState, hp: Hypers) -> LinearState:
    """Shard-local flush: the caches/clock are replicated, so every shard
    rebases identically while bringing only its own rows current."""
    with jax.named_scope(phases.FLUSH):
        return lt._solver(cfg).flush(cfg, state, hp, lt._backend(cfg.backend))


def _local_predict(cfg, solver, state: LinearState, batch: SparseBatch, hp: Hypers):
    bk = lt._backend(cfg.backend)
    local = route_batch(cfg, batch)
    rows = state.wpsi[local.idx.reshape(-1)]  # clip-gather; sentinel masked
    w_cur = solver.read_rows(cfg, rows, state, hp, bk)
    z = margin_psum(cfg, w_cur.reshape(local.idx.shape) * local.val)
    if cfg.use_bias:
        z = z + state.b
    return jax.nn.sigmoid(z) if cfg.loss == lt.LOGISTIC else z


# --------------------------------------------------------------------------
# single-config training surface (lt.* delegates here when cfg.mesh is set)
# --------------------------------------------------------------------------


def init_state(cfg, w0=None) -> LinearState:
    """Packed state padded to ``n * ds`` rows, row-sharded over the feature
    mesh; bias/caches/clock replicated.  One program partitioned by its
    output shardings builds it, so each device makes (and, given ``w0``,
    seeds) its own ``[ds, cols]`` slab: no device ever holds the whole
    ``[d_pad, cols]`` state, which at production ``d`` outgrows one chip."""
    d_pad = shard_info(cfg)[2]
    mesh = feature_mesh(cfg)
    padded = dataclasses.replace(cfg, dim=d_pad)

    def build(w0):
        if w0 is not None:
            # the seed padded with zeros (a zero seed reads back weight 0
            # from zero state: inert), one [ds] slice a device
            w0 = jax.lax.with_sharding_constraint(
                jnp.pad(jnp.asarray(w0, jnp.float32), (0, d_pad - cfg.dim)),
                NamedSharding(mesh, P(cfg.feature_axis)),
            )
        return LinearState(
            wpsi=lt._solver(cfg).init_cols(padded, w0),
            b=jnp.zeros((), jnp.float32),
            caches=dp_caches.init_caches(cfg.round_len),
            i=jnp.zeros((), jnp.int32),
            t=jnp.zeros((), jnp.int32),
        )

    return jax.jit(
        build,
        in_shardings=NamedSharding(mesh, P()),  # a seed may sit on any one device
        out_shardings=state_shardings(cfg, mesh),
    )(w0)


def make_lazy_step(cfg):
    """``step(state, batch) -> (state, mean_loss)`` over the feature mesh —
    the sharded twin of :func:`repro.core.linear_trainer.make_lazy_step`."""
    lt._solver(cfg).validate(cfg)
    mesh = feature_mesh(cfg)
    step_hp = make_local_step_hp(cfg)
    hp = _hp(cfg.hypers())

    def body(state, batch, hp):
        return step_hp(state, batch, hp)

    sh = manual_shard_map(
        body,
        mesh,
        in_specs=(state_specs(cfg), BATCH_SPECS, HYPER_SPECS),
        out_specs=(state_specs(cfg), P()),
        manual_axes=(cfg.feature_axis,),
    )

    def step(state: LinearState, batch: SparseBatch):
        return sh(state, batch, hp)

    return step


def make_round_fn(cfg):
    """jit'd whole-round scan + boundary flush, one shard_map region: the
    entire round lowers to ONE per-shard executable (the scan and the flush
    never leave the manual region, so no per-step resharding)."""
    solver = lt._solver(cfg)
    solver.validate(cfg)
    mesh = feature_mesh(cfg)
    step_hp = make_local_step_hp(cfg)
    hp = _hp(cfg.hypers())

    def body(state, round_batches, hp):
        state, losses = jax.lax.scan(
            lambda s, b: step_hp(s, b, hp), state, round_batches
        )
        return local_flush(cfg, state, hp), losses

    sh = manual_shard_map(
        body,
        mesh,
        in_specs=(state_specs(cfg), BATCH_SPECS, HYPER_SPECS),
        out_specs=(state_specs(cfg), P()),
        manual_axes=(cfg.feature_axis,),
    )
    return jax.jit(lambda state, batches: sh(state, batches, hp), donate_argnums=0)


def flush(cfg, state: LinearState, hp: Optional[Hypers] = None) -> LinearState:
    if hp is None:
        hp = cfg.hypers()
    mesh = feature_mesh(cfg)
    sh = manual_shard_map(
        lambda s, h: local_flush(cfg, s, h),
        mesh,
        in_specs=(state_specs(cfg), HYPER_SPECS),
        out_specs=state_specs(cfg),
        manual_axes=(cfg.feature_axis,),
    )
    return sh(state, _hp(hp))


def current_weights(cfg, state: LinearState, hp: Optional[Hypers] = None) -> jnp.ndarray:
    """All ``[dim]`` weights brought current: every shard replays the
    replicated caches against its own slab; the padding rows are sliced off."""
    if hp is None:
        hp = cfg.hypers()
    solver = lt._solver(cfg)
    mesh = feature_mesh(cfg)
    sh = manual_shard_map(
        lambda s, h: solver.read_weights(cfg, s, h, lt._backend(cfg.backend)),
        mesh,
        in_specs=(state_specs(cfg), HYPER_SPECS),
        out_specs=P(cfg.feature_axis),
        manual_axes=(cfg.feature_axis,),
    )
    return sh(state, _hp(hp))[: cfg.dim]


def predict_proba_sparse(cfg, state: LinearState, batch: SparseBatch, hp=None):
    """O(p)-per-shard serving predictions: route, gather + bring current
    only the touched LOCAL rows, one exact margin psum."""
    if hp is None:
        hp = cfg.hypers()
    solver = lt._solver(cfg)
    mesh = feature_mesh(cfg)
    sh = manual_shard_map(
        lambda s, b, h: _local_predict(cfg, solver, s, b, h),
        mesh,
        in_specs=(state_specs(cfg), BATCH_SPECS, HYPER_SPECS),
        out_specs=P(),
        manual_axes=(cfg.feature_axis,),
    )
    return sh(state, batch, _hp(hp))


# --------------------------------------------------------------------------
# batched-config surface (sweeps.batched_trainer delegates here)
# --------------------------------------------------------------------------


def place_batched(cfg, bstate: LinearState) -> LinearState:
    """Pad a host-built ``[n_cfg, dim, cols]`` batched state to ``d_pad``
    rows and place it config-replicated, feature-sharded."""
    n, ds, d_pad = shard_info(cfg)
    wpsi = bstate.wpsi
    if d_pad > cfg.dim:
        pad = jnp.zeros(wpsi.shape[:-2] + (d_pad - cfg.dim, wpsi.shape[-1]), jnp.float32)
        wpsi = jnp.concatenate([wpsi, pad], axis=-2)
    return jax.device_put(
        bstate._replace(wpsi=wpsi), state_shardings(cfg, stacked=True)
    )


def make_batched_round_fn(cfg):
    """vmap-over-configs INSIDE the shard_map region: one program trains the
    whole hyper grid, each shard holding every config's slab of rows.  The
    round clock is shared across the grid (STACKED_AXES), exactly like the
    unsharded batched trainer."""
    solver = lt._solver(cfg)
    mesh = feature_mesh(cfg)
    step_hp = make_local_step_hp(cfg)

    def body(bstate, hp, round_batches):
        def cfg_round(state, hp):
            state, losses = jax.lax.scan(
                lambda s, b: step_hp(s, b, hp), state, round_batches
            )
            return local_flush(cfg, state, hp), losses

        return jax.vmap(
            cfg_round, in_axes=(STACKED_AXES, HYPER_AXES),
            out_axes=(STACKED_AXES, 0),
        )(bstate, hp)

    sh = manual_shard_map(
        body,
        mesh,
        in_specs=(state_specs(cfg, stacked=True), HYPER_SPECS, BATCH_SPECS),
        out_specs=(state_specs(cfg, stacked=True), P()),
        manual_axes=(cfg.feature_axis,),
    )
    return jax.jit(sh, donate_argnums=0)


def make_batched_eval(cfg):
    """jit'd per-config held-out mean loss, arithmetic-identical to the
    unsharded eval: buffer-wide catch-up, then gather — so CV losses (and
    the winner) match the single-device sweep bitwise in exact-margin mode."""
    solver = lt._solver(cfg)
    mesh = feature_mesh(cfg)

    def body(bstate, hp, batch):
        def one(state, hp):
            bk = lt._backend(cfg.backend)
            w = solver.read_weights(cfg, state, hp, bk)  # local [ds]
            local = route_batch(cfg, batch)
            w_g = w[local.idx.reshape(-1)].reshape(local.idx.shape)
            z = margin_psum(cfg, w_g * local.val)
            if cfg.use_bias:
                z = z + state.b
            loss, _ = lt._grad_z(cfg, z, batch.y)
            return jnp.mean(loss)

        return jax.vmap(one, in_axes=(STACKED_AXES, HYPER_AXES))(bstate, hp)

    sh = manual_shard_map(
        body,
        mesh,
        in_specs=(state_specs(cfg, stacked=True), HYPER_SPECS, BATCH_SPECS),
        out_specs=P(),
        manual_axes=(cfg.feature_axis,),
    )
    return jax.jit(sh)


def batched_current_weights(cfg, bstate: LinearState, hp: Hypers) -> jnp.ndarray:
    """``[n_cfg, dim]`` current weights across the grid."""
    solver = lt._solver(cfg)
    mesh = feature_mesh(cfg)

    def body(bstate, hp):
        def one(state, hp):
            return solver.read_weights(cfg, state, hp, lt._backend(cfg.backend))

        return jax.vmap(one, in_axes=(STACKED_AXES, HYPER_AXES))(bstate, hp)

    sh = manual_shard_map(
        body,
        mesh,
        in_specs=(state_specs(cfg, stacked=True), HYPER_SPECS),
        out_specs=P(None, cfg.feature_axis),
        manual_axes=(cfg.feature_axis,),
    )
    return sh(bstate, _hp(hp))[:, : cfg.dim]


# --------------------------------------------------------------------------
# multi-tenant surface (serving.multi_service delegates here)
# --------------------------------------------------------------------------


def tenant_specs(cfg):
    """(state, hyper, lane) specs for the per-tenant lane-stacked programs:
    every lane's rows shard over features; hypers/active masks replicate."""
    return state_specs(cfg, stacked=True), HYPER_SPECS, P()


def make_tenant_step_hp(cfg):
    """The per-lane local step the multi-tenant learn program vmaps: same
    as :func:`make_local_step_hp` (routing already swallows the inactive-
    lane sentinel idx=dim — it is unowned by every shard)."""
    return make_local_step_hp(cfg)


def wrap_tenant(cfg, lane_fn, n_lane_args: int):
    """vmap ``lane_fn(state, hp, *lane_args)`` over the tenant axis inside
    one manual shard_map region; returns the unjitted mesh program.  Lane
    args beyond (state, hp) are per-lane batches/masks (replicated across
    shards, split across lanes)."""
    mesh = feature_mesh(cfg)
    st_specs, hp_specs, lane_spec = tenant_specs(cfg)

    def body(bstate, hp, *lane_args):
        return jax.vmap(
            lane_fn,
            in_axes=(TENANT_AXES, HYPER_AXES) + (0,) * n_lane_args,
            out_axes=(TENANT_AXES, 0),
        )(bstate, hp, *lane_args)

    return manual_shard_map(
        body,
        mesh,
        in_specs=(st_specs, hp_specs) + (lane_spec,) * n_lane_args,
        out_specs=(st_specs, P()),
        manual_axes=(cfg.feature_axis,),
    )


def wrap_tenant_predict(cfg, lane_fn):
    """Like :func:`wrap_tenant` for the pure per-lane predict program
    (no state output)."""
    mesh = feature_mesh(cfg)
    st_specs, hp_specs, lane_spec = tenant_specs(cfg)

    def body(bstate, hp, batch):
        return jax.vmap(lane_fn, in_axes=(TENANT_AXES, HYPER_AXES, 0))(
            bstate, hp, batch
        )

    return manual_shard_map(
        body,
        mesh,
        in_specs=(st_specs, hp_specs, SparseBatch(P(), P(), P())),
        out_specs=P(),
        manual_axes=(cfg.feature_axis,),
    )


def pad_rows(cfg, packed: jnp.ndarray) -> jnp.ndarray:
    """Pad ``[..., dim, cols]`` packed state to ``[..., d_pad, cols]`` —
    seeding/swap helpers build at the logical dim and pad before placement."""
    n, ds, d_pad = shard_info(cfg)
    if d_pad == cfg.dim:
        return packed
    pad = jnp.zeros(packed.shape[:-2] + (d_pad - cfg.dim, packed.shape[-1]), jnp.float32)
    return jnp.concatenate([packed, pad], axis=-2)


# --------------------------------------------------------------------------
# routed rounds (pre-compacted per-shard batches — the scaling bench path)
# --------------------------------------------------------------------------


def route_round(cfg, batches: SparseBatch, q: int):
    """Host-side bucketed compaction: route a ``[R, B, p]`` round of sparse
    batches into per-shard ``[n, R, B, q]`` local-index blocks (sentinel-
    padded), so each shard's in-graph work is O(q) instead of O(p_total).
    This is how a real ingestion pipeline feeds the mesh — the router knows
    the shard map, so the per-device batch shrinks with the shard count.
    Raises if any (example, shard) owns more than ``q`` features."""
    n, ds, _ = shard_info(cfg)
    idx = np.asarray(batches.idx)
    val = np.asarray(batches.val)
    out_i = np.full((n,) + idx.shape[:-1] + (q,), ds, np.int32)
    out_v = np.zeros((n,) + idx.shape[:-1] + (q,), np.float32)
    for k in range(n):
        lo = k * ds
        owned = (idx >= lo) & (idx < min(lo + ds, cfg.dim))
        counts = owned.sum(-1)
        if counts.max(initial=0) > q:
            raise ValueError(
                f"shard {k} overflow: an example owns {int(counts.max())} "
                f"features > q={q}; raise q or rebalance the hash"
            )
        order = np.argsort(~owned, axis=-1, kind="stable")  # owned first
        oi = np.take_along_axis(idx, order, -1)[..., :q]
        ov = np.take_along_axis(val, order, -1)[..., :q]
        om = np.take_along_axis(owned, order, -1)[..., :q]
        out_i[k] = np.where(om, oi - lo, ds)
        out_v[k] = np.where(om, ov, 0.0)
    return out_i, out_v, np.asarray(batches.y)


def place_routed(cfg, out_i, out_v, y):
    """Device placement for :func:`route_round` output: shard k's block to
    shard k, labels replicated."""
    mesh = feature_mesh(cfg)
    ax = cfg.feature_axis
    return (
        jax.device_put(out_i, NamedSharding(mesh, P(ax))),
        jax.device_put(out_v, NamedSharding(mesh, P(ax))),
        jax.device_put(jnp.asarray(y), NamedSharding(mesh, P())),
    )


def make_routed_round_fn(cfg):
    """jit'd round over pre-routed per-shard blocks.  Compacted columns are
    not slot-aligned across shards, so the exact (column-aligned) margin
    mode cannot apply — use ``shard_margin='partial'`` or ``'quantized'``."""
    if cfg.shard_margin == "exact":
        raise ValueError(
            "routed rounds need shard_margin='partial' or 'quantized' "
            "(compacted columns are not slot-aligned across shards)"
        )
    solver = lt._solver(cfg)
    solver.validate(cfg)
    mesh = feature_mesh(cfg)
    unit_sched = cfg.schedule.unit().make()
    hp = _hp(cfg.hypers())

    def body(state, lidx, lval, y, hp):
        lidx, lval = lidx[0], lval[0]  # shed the size-1 local shard dim

        def step(state, xs):
            li, lv, yy = xs
            bk = lt._backend(cfg.backend)
            eta = jnp.asarray(hp.eta_scale, jnp.float32) * unit_sched(state.t)
            return solver.sharded_update(
                cfg, state, SparseBatch(li, lv, yy), hp, eta, bk, cfg.feature_axis
            )

        state, losses = jax.lax.scan(step, state, (lidx, lval, y))
        return local_flush(cfg, state, hp), losses

    ax = cfg.feature_axis
    sh = manual_shard_map(
        body,
        mesh,
        in_specs=(state_specs(cfg), P(ax), P(ax), P(), HYPER_SPECS),
        out_specs=(state_specs(cfg), P()),
        manual_axes=(ax,),
    )
    return jax.jit(
        lambda state, lidx, lval, y: sh(state, lidx, lval, y, hp), donate_argnums=0
    )


# --------------------------------------------------------------------------
# checkpoint bridge (mesh-size-independent packed state on disk)
# --------------------------------------------------------------------------


def host_template(cfg) -> LinearState:
    """Host-side zero LinearState at the LOGICAL dim (no padding) — the
    checkpoint template; checkpoints are mesh-size independent."""
    cols = lt._solver(cfg).state_cols
    caches = jax.device_get(dp_caches.init_caches(cfg.round_len))
    return LinearState(
        wpsi=np.zeros((cfg.dim, cols), np.float32),
        b=np.zeros((), np.float32),
        caches=RegCaches(*(np.asarray(c) for c in caches)),
        i=np.zeros((), np.int32),
        t=np.zeros((), np.int32),
    )


def gather_state(cfg, state: LinearState) -> LinearState:
    """Device -> host with the padding rows stripped (the save form)."""
    host = jax.device_get(state)
    return host._replace(wpsi=np.asarray(host.wpsi)[: cfg.dim])


def place_state(cfg, state: LinearState) -> LinearState:
    """Host ``[dim, cols]`` state -> padded, feature-sharded placement."""
    wpsi = jnp.asarray(np.asarray(state.wpsi), jnp.float32)
    if wpsi.shape[0] != cfg.dim:
        raise ValueError(f"packed state rows {wpsi.shape[0]} != dim {cfg.dim}")
    return jax.device_put(
        state._replace(wpsi=pad_rows(cfg, wpsi)), state_shardings(cfg)
    )


def restore_sharded(cfg, ckpt_dir, step: int):
    """Restore a packed linear checkpoint straight onto the feature mesh;
    returns ``(state, manifest)`` like the checkpointer.  When the dim
    divides evenly, each shard is placed straight from the logical arrays
    via ``checkpoint.restore_distributed``; otherwise restore to host, pad
    to the shard grain, and place."""
    from repro.checkpoint import checkpointer

    n, ds, d_pad = shard_info(cfg)
    if d_pad == cfg.dim:
        return checkpointer.restore_distributed(
            ckpt_dir, step, host_template(cfg), shardings=state_shardings(cfg)
        )
    state, manifest = checkpointer.restore(ckpt_dir, step, host_template(cfg))
    return place_state(cfg, state), manifest


# --------------------------------------------------------------------------
# observability (per-shard touch accounting — host-side, obs.registry gauges)
# --------------------------------------------------------------------------


def shard_touch_counts(cfg, idx) -> np.ndarray:
    """``[n]`` touched-feature counts per shard for a batch of feature ids
    (host-side np; sentinel/ignored ids ``>= dim`` excluded)."""
    n, ds, _ = shard_info(cfg)
    flat = np.asarray(idx).reshape(-1)
    flat = flat[flat < cfg.dim]
    return np.bincount(np.minimum(flat // ds, n - 1), minlength=n)


def record_shard_metrics(metrics, cfg, idx) -> np.ndarray:
    """Gauge per-shard touched counts + the max/mean imbalance ratio into a
    :class:`repro.obs.MetricsRegistry`; returns the counts."""
    from repro.obs.registry import label

    counts = shard_touch_counts(cfg, idx)
    for k, c in enumerate(counts):
        metrics.gauge(label("shard_touched", shard=str(k)), float(c))
    mean = float(counts.mean())
    metrics.gauge("shard_imbalance", float(counts.max()) / mean if mean else 0.0)
    return counts
