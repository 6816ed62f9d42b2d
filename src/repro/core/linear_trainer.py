"""The paper's training algorithm (Algorithm 1): sparse linear models with
lazy elastic-net regularization, plus the dense-update baseline it is
benchmarked against (§7).

Time complexity per step: O(p) lazy vs O(d) dense, where p = nonzeros per
example.  Training runs in *rounds* of ``round_len`` steps; at every round
boundary all weights are brought current and the DP caches rebase — the
paper's own space-budget amortization (fn.1), doubling as the fp32 overflow
guard (DESIGN.md §2).

The per-coordinate update rule is pluggable (:mod:`repro.solvers`,
DESIGN.md §12): the paper's SGD/FoBoS DP-cache flavors, FTRL-Proximal with
per-coordinate AdaGrad rates (apply-at-read, no catch-up cache), and
K-step truncated gradient all run through the same step/flush/predict
machinery here.  ``LinearConfig.solver`` picks one; unset, it falls back
to ``$REPRO_SOLVER`` and then to ``flavor`` — so the default path is the
pre-subsystem SGD/FoBoS trainer, bitwise (pinned by tests/solvers).

State layout (DESIGN.md §8): the per-coordinate solver state is PACKED
into one [d, state_cols] f32 array — ``(w, psi)`` for the DP-cache solvers
(psi is exact in f32 for round_len < 2^24), ``(w, z, n)`` for FTRL.
With separate arrays, XLA-CPU fuses the psi/w gathers into downstream
consumers, keeps both buffers live across the scatters, and inserts two full
O(d) copies per step — 245us/step at d=260,941.  The packed layout makes the
step a gather -> scatter read-modify-write chain on one buffer that buffer-
assigns in place: 18us/step (13.6x), restoring the paper's O(p) behaviour.

Both trainers share prediction code and exploit sparsity when predicting
(the paper's "fair comparison" condition, §7); they differ only in how the
regularization sweep is applied.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import dp_caches, phases
from .dp_caches import FLAVORS, RegCaches
from .schedules import ScheduleConfig


def _backend(name):
    """Resolve the kernel backend at call (trace) time.  Deferred import:
    this module sits inside repro.backend's own import chain (backend ->
    pallas -> kernels -> core -> linear_trainer), so a module-level import
    here would make `import repro.kernels` order-dependent."""
    from repro import backend as kb

    return kb.resolve(name)


def _solver(cfg):
    """Resolve the solver at call (trace/construction) time.  Deferred
    import for the same reason as :func:`_backend`: repro.solvers imports
    this module at load time."""
    from repro import solvers

    return solvers.for_config(cfg)


def _dist():
    """The feature-sharding subsystem (repro.dist.linear), deferred:
    dist imports core at load time, so the mesh branches below resolve it
    lazily — and single-device users never pay for the mesh machinery."""
    from repro.dist import linear as dl

    return dl

LOGISTIC = "logistic"
SQUARED = "squared"


class Hypers(NamedTuple):
    """The per-config hyperparameters a sweep varies.  Each field is a
    scalar: a Python float in the single-config path (baked into the trace
    as a constant) or a traced f32 (one lane of a vmapped config axis) in
    the batched-sweep path.  Structure that changes the *program* — loss,
    flavor, schedule kind, round_len — stays in LinearConfig."""

    lam1: "float | jnp.ndarray"
    lam2: "float | jnp.ndarray"
    eta_scale: "float | jnp.ndarray"  # eta_t = eta_scale * unit_schedule(t)


class SparseBatch(NamedTuple):
    """Padded sparse minibatch.  Padding convention: idx=0, val=0.0 — a
    zero-valued feature contributes nothing to predictions or gradients, and
    spuriously 'touching' weight 0 is write-consistent (the catch-up written
    back is its correct current value)."""

    idx: jnp.ndarray  # [B, p] int32 feature indices
    val: jnp.ndarray  # [B, p] f32 feature values
    y: jnp.ndarray  # [B] f32 labels ({0,1} logistic / reals squared)


@dataclasses.dataclass(frozen=True)
class LinearConfig:
    dim: int
    loss: str = LOGISTIC  # logistic | squared
    flavor: str = dp_caches.FOBOS  # sgd | fobos
    lam1: float = 1e-5
    lam2: float = 1e-6
    schedule: ScheduleConfig = dataclasses.field(default_factory=ScheduleConfig)
    use_bias: bool = True
    round_len: int = 4096  # flush/rebase period (paper's space budget)
    # update rule (repro.solvers): sgd | fobos | ftrl | trunc; None defers
    # to $REPRO_SOLVER and then to ``flavor`` (the pre-subsystem default)
    solver: Optional[str] = None
    trunc_k: int = 16  # truncation period of the `trunc` solver
    ftrl_beta: float = 1.0  # AdaGrad smoothing of the `ftrl` solver
    # kernel backend for the regularization hot paths (repro.backend):
    # None defers to use_backend()/$REPRO_BACKEND/platform default
    backend: Optional[str] = None
    # feature sharding (repro.dist.linear, DESIGN.md §16): mesh = number of
    # devices to partition the [d, state_cols] state over along
    # ``feature_axis``; None keeps every path single-device.  shard_margin
    # picks how per-example margin partial sums cross the mesh: "exact"
    # (slot-aligned psum, bitwise vs unsharded on the reference backend),
    # "partial" (local reduce first — one f32 [B] psum), or "quantized"
    # (partial through dist.compress.quantized_psum)
    mesh: Optional[int] = None
    feature_axis: str = "features"
    shard_margin: str = "exact"

    def __post_init__(self):
        assert self.flavor in FLAVORS, self.flavor
        assert self.loss in (LOGISTIC, SQUARED), self.loss
        assert self.lam1 >= 0.0 and self.lam2 >= 0.0
        assert self.round_len < 2**24  # psi lives exactly in f32
        if self.mesh is not None:
            assert isinstance(self.mesh, int) and self.mesh >= 1, self.mesh
            assert self.feature_axis, "feature_axis must be a non-empty name"
        # literal twin of repro.dist.linear.MARGIN_MODES (core cannot import
        # dist at validation time — dist imports core)
        assert self.shard_margin in ("exact", "partial", "quantized"), self.shard_margin
        if self.solver is not None:
            _solver(self)  # fail fast on unknown names
        if self.backend is not None:
            _backend(self.backend)  # fail fast on unknown names

    def hypers(self, lam1=None) -> "Hypers":
        """This config's concrete hyper triple (``lam1`` optionally
        overridden — possibly by a traced per-config scalar)."""
        return Hypers(
            lam1=self.lam1 if lam1 is None else lam1,
            lam2=self.lam2,
            eta_scale=self.schedule.eta0,
        )


class LinearState(NamedTuple):
    # [d, state_cols] f32 packed per-coordinate solver state; col 0 is
    # always the weight (cols: (w, psi) DP solvers / (w, z, n) ftrl /
    # (w,) dense baseline)
    wpsi: jnp.ndarray
    b: jnp.ndarray  # scalar f32
    caches: RegCaches  # round-local DP caches, arrays [round_len+1]
    i: jnp.ndarray  # scalar int32, round-local step
    t: jnp.ndarray  # scalar int32, global step


def weights(state: LinearState) -> jnp.ndarray:
    """Raw (possibly stale) weights — use current_weights for caught-up."""
    return state.wpsi[:, 0]


def psi(state: LinearState) -> jnp.ndarray:
    """Round-local last-touch steps — cache-based (w, psi) layouts only."""
    if state.wpsi.shape[1] == 1:  # dense layout: always current
        return jnp.zeros((state.wpsi.shape[0],), jnp.int32)
    assert state.wpsi.shape[1] == 2, state.wpsi.shape  # ftrl carries no psi
    return state.wpsi[:, 1].astype(jnp.int32)


def init_state(cfg: LinearConfig, w0: Optional[jnp.ndarray] = None, mode: str = "lazy") -> LinearState:
    """mode="lazy": the solver's packed [d, state_cols] layout.  mode=
    "dense": flat [d, 1] — the dense baseline carries no per-coordinate
    bookkeeping and must not pay strided writes for any."""
    if cfg.mesh is not None:
        if mode != "lazy":
            raise ValueError("feature sharding (cfg.mesh) supports the lazy trainer only")
        return _dist().init_state(cfg, w0)
    if mode == "lazy":
        wpsi = _solver(cfg).init_cols(cfg, w0)
    else:
        if not _solver(cfg).has_dense:
            raise ValueError(f"solver {_solver(cfg).name!r} has no dense baseline")
        wpsi = jnp.zeros((cfg.dim, 1), jnp.float32)
        if w0 is not None:
            wpsi = wpsi.at[:, 0].set(jnp.asarray(w0, jnp.float32))
    return LinearState(
        wpsi=wpsi,
        b=jnp.zeros((), jnp.float32),
        caches=dp_caches.init_caches(cfg.round_len),
        i=jnp.zeros((), jnp.int32),
        t=jnp.zeros((), jnp.int32),
    )


def loss_and_grad_z(loss: str, z: jnp.ndarray, y: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-example loss and dLoss/dz for a loss kind — the single home of
    the loss arithmetic, shared by the mesh step, the backends' fused
    whole-step ops, and the dense baseline (bitwise across all of them)."""
    if loss == LOGISTIC:
        # numerically stable BCE-with-logits
        loss_v = jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        gz = jax.nn.sigmoid(z) - y
    else:
        loss_v = 0.5 * (z - y) ** 2
        gz = z - y
    return loss_v, gz


def _grad_z(cfg: LinearConfig, z: jnp.ndarray, y: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-example loss and dLoss/dz (cfg-keyed form of loss_and_grad_z)."""
    return loss_and_grad_z(cfg.loss, z, y)


def _predict_current(cfg, w, b, batch: SparseBatch):
    """Sparse prediction from already-current gathered weights [B, p]."""
    z = jnp.sum(w * batch.val, axis=-1)
    if cfg.use_bias:
        z = z + b
    return z


def make_lazy_step_hp(cfg: LinearConfig):
    """``step(state, batch, hp)`` with the regularization strengths and the
    learning-rate scale as *call arguments* (possibly traced scalars) rather
    than trace-time constants — the form :mod:`repro.sweeps` vmaps over a
    config axis to train a whole (lam1, lam2, eta0) grid in one program.

    Static structure (loss, flavor, round_len, schedule *shape*) still comes
    from ``cfg``; ``eta_t = hp.eta_scale * unit_schedule(t)`` (exact: every
    schedule kind is linear in eta0).  No schedule validation happens here —
    callers with concrete hypers (make_lazy_step, sweeps.grid) validate
    eagerly at construction time.

    The kernel backend (repro.backend) AND the solver (repro.solvers)
    resolve when the step is TRACED — the uniform rule for every fn in this
    module, so one program never mixes backends or solvers.  Pin
    ``cfg.backend``/``cfg.solver`` (as LinearService does at construction)
    to make the choice independent of trace-time context; the gather/scatter
    chain stays in XLA either way (DESIGN.md §11)."""
    if cfg.mesh is not None:
        raise ValueError(
            "feature-sharded steps run inside a shard_map region — use "
            "repro.dist.linear (make_lazy_step / make_round_fn), not the "
            "single-device step builders"
        )
    solver = _solver(cfg)
    unit_sched = cfg.schedule.unit().make()

    def step(state: LinearState, batch: SparseBatch, hp: Hypers):
        bk = _backend(cfg.backend)
        eta = jnp.asarray(hp.eta_scale, jnp.float32) * unit_sched(state.t)
        # the O(p) touched-coordinate step (solvers/: gather, bring current,
        # gradient, scatter back; reg for step i itself stays pending for
        # cache-based solvers — applied at next touch / flush)
        return solver.touched_update(cfg, state, batch, hp, eta, bk)

    return step


def make_lazy_step(cfg: LinearConfig):
    """Single-config lazy step: the hyper-parameterized step closed over
    cfg's concrete (lam1, lam2, eta0) as trace constants.  eta is computed
    as ``eta0 * unit_schedule(t)`` — same expression in the dense step and
    in batched sweeps, so lazy/dense/swept paths share eta arithmetic
    exactly (vs the pre-sweeps single-expression schedule it can differ in
    the last ulp)."""
    if cfg.mesh is not None:
        return _dist().make_lazy_step(cfg)  # shard_map'd twin, same signature
    _solver(cfg).validate(cfg)  # per-solver hyper/schedule checks, eager
    step_hp = make_lazy_step_hp(cfg)
    hp = cfg.hypers()

    def step(state: LinearState, batch: SparseBatch):
        return step_hp(state, batch, hp)

    return step


def make_dense_step(cfg: LinearConfig):
    if cfg.mesh is not None:
        raise ValueError("feature sharding (cfg.mesh) supports the lazy trainer only")
    solver = _solver(cfg)
    if not solver.has_dense:
        raise ValueError(f"solver {solver.name!r} has no dense per-step baseline")
    solver.validate(cfg)
    # eta via the unit schedule, the same expression the lazy step uses, so
    # the lazy-vs-dense comparison stays arithmetic-identical
    unit_sched = cfg.schedule.unit().make()
    eta_scale = cfg.schedule.eta0

    def step(state: LinearState, batch: SparseBatch):
        bk = _backend(cfg.backend)  # trace-time, like every fn here
        eta = jnp.asarray(eta_scale, jnp.float32) * unit_sched(state.t)
        idx_f = batch.idx.reshape(-1)
        w_g = state.wpsi[idx_f, 0]  # already current
        z = _predict_current(cfg, w_g.reshape(batch.idx.shape), state.b, batch)
        loss, gz = _grad_z(cfg, z, batch.y)
        g_w = (gz[:, None] * batch.val).reshape(-1)
        wpsi = state.wpsi.at[idx_f, 0].add(-eta * g_w)
        # O(d): the solver's dense regularization sweep over EVERY coordinate
        wpsi = solver.dense_reg(cfg, wpsi, eta, state.t, bk)
        b = state.b - eta * jnp.sum(gz) if cfg.use_bias else state.b
        new = LinearState(wpsi=wpsi, b=b, caches=state.caches, i=state.i, t=state.t + 1)
        return new, jnp.mean(loss)

    return step


def flush(cfg: LinearConfig, state: LinearState, lam1=None, hp: Optional[Hypers] = None) -> LinearState:
    """Bring every weight current and open a fresh round (O(d), amortized;
    cache-based solvers rebase their DP caches, apply-at-read solvers
    rematerialize the weight column).

    ``lam1`` overrides cfg.lam1, or pass a full ``hp`` (either may hold
    traced per-config scalars — the batched-sweep path, where the shared
    round counter makes this flush batch-uniform: every config rebases at
    the same step)."""
    if hp is None:
        hp = cfg.hypers(lam1=lam1)
    if cfg.mesh is not None:
        return _dist().flush(cfg, state, hp=hp)  # shard-local, no collectives
    with jax.named_scope(phases.FLUSH):
        return _solver(cfg).flush(cfg, state, hp, _backend(cfg.backend))


def current_weights(
    cfg: LinearConfig, state: LinearState, lam1=None, hp: Optional[Hypers] = None
) -> jnp.ndarray:
    """All weights brought current (pure; does not advance the round)."""
    if state.wpsi.shape[1] == 1:  # dense layout: always current
        return state.wpsi[:, 0]
    if hp is None:
        hp = cfg.hypers(lam1=lam1)
    if cfg.mesh is not None:
        return _dist().current_weights(cfg, state, hp=hp)
    return _solver(cfg).read_weights(cfg, state, hp, _backend(cfg.backend))


def make_round_fn(cfg: LinearConfig, mode: str, metrics: bool = False):
    """jit'd function running a whole round of steps via lax.scan and, in
    lazy mode, flushing at the boundary.  ``round_batches`` arrays are
    [R, B, p] with R <= cfg.round_len.

    ``metrics=True`` (lazy mode only) returns the instrumented twin from
    :mod:`repro.obs.instrument` whose carry is ``(LinearState,
    obs.MetricsState)`` — same step arithmetic (bitwise on the reference
    backend), plus in-scan lazy-work accounting.  Trace-time flag, deferred
    import: core never depends on obs unless asked."""
    assert mode in ("lazy", "dense")
    if cfg.mesh is not None:
        if mode != "lazy":
            raise ValueError("feature sharding (cfg.mesh) supports the lazy trainer only")
        if metrics:
            raise ValueError(
                "in-scan metrics instrumentation is single-device; use "
                "dist.linear.record_shard_metrics for per-shard accounting"
            )
        return _dist().make_round_fn(cfg)
    if metrics:
        assert mode == "lazy", "metrics instrumentation targets the lazy trainer"
        from repro.obs import instrument

        return instrument.make_obs_round_fn(cfg)
    step = make_lazy_step(cfg) if mode == "lazy" else make_dense_step(cfg)

    @functools.partial(jax.jit, donate_argnums=0)
    def round_fn(state: LinearState, round_batches: SparseBatch):
        state, losses = jax.lax.scan(step, state, round_batches)
        if mode == "lazy":
            state = flush(cfg, state)
        return state, losses

    return round_fn


def predict_proba(cfg: LinearConfig, state: LinearState, batch: SparseBatch) -> jnp.ndarray:
    """Evaluation-time predictions with lazily-current weights."""
    w = current_weights(cfg, state)
    z = _predict_current(cfg, w[batch.idx], state.b, batch)
    return jax.nn.sigmoid(z) if cfg.loss == LOGISTIC else z


def predict_proba_sparse(
    cfg: LinearConfig, state: LinearState, batch: SparseBatch, hp: Optional[Hypers] = None
) -> jnp.ndarray:
    """Serving-path predictions in O(p) per example: gather only the touched
    (w, psi) rows and bring them current against the DP caches — the same
    catch-up the lazy step performs, minus the write-back (pure).  Agrees
    with predict_proba's O(d) full catch-up exactly; this is the form the
    paper's per-request complexity claim describes.  ``hp`` overrides the
    config's concrete hypers (possibly with traced per-tenant scalars — the
    multi-tenant serving path, which vmaps this function per slot)."""
    if hp is None:
        hp = cfg.hypers()
    if cfg.mesh is not None:
        return _dist().predict_proba_sparse(cfg, state, batch, hp=hp)
    idx_f = batch.idx.reshape(-1)
    g2 = state.wpsi[idx_f]
    if state.wpsi.shape[1] == 1:  # dense layout: weights always current
        w_cur = g2[:, 0]
    else:
        w_cur = _solver(cfg).read_rows(cfg, g2, state, hp, _backend(cfg.backend))
    z = _predict_current(cfg, w_cur.reshape(batch.idx.shape), state.b, batch)
    return jax.nn.sigmoid(z) if cfg.loss == LOGISTIC else z


def mean_loss(
    cfg: LinearConfig, state: LinearState, batch: SparseBatch, lam1=None, hp: Optional[Hypers] = None
) -> jnp.ndarray:
    """Mean held-out loss on ``batch`` with lazily-current weights (pure).
    ``lam1``/``hp`` as in :func:`current_weights` — the sweeps CV path
    evaluates a whole config axis through one vmap of this function."""
    w = current_weights(cfg, state, lam1=lam1, hp=hp)
    z = _predict_current(cfg, w[batch.idx], state.b, batch)
    loss, _ = _grad_z(cfg, z, batch.y)
    return jnp.mean(loss)


def nnz(cfg: LinearConfig, state: LinearState, threshold: float = 0.0) -> jnp.ndarray:
    """Number of (current) weights with |w| > threshold — the model-sparsity
    statistic elastic net is prized for (paper §2.1)."""
    return jnp.sum(jnp.abs(current_weights(cfg, state)) > threshold)
