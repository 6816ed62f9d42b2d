"""Online predict/learn service over the paper's lazy elastic-net trainer.

This is the paper's deployment story made concrete: examples arrive one at a
time, ``learn`` steps the O(p) lazy trainer (touching only the features the
request carries), ``predict`` serves probabilities through the O(p)
touched-rows catch-up (core.predict_proba_sparse) — no request ever pays the
O(d) dense sweep; the only O(d) work is the amortized round-boundary flush
the paper itself prescribes (fn.1).

Fixed shapes, no steady-state recompiles: features pad to ``p_max`` (the
trainer's padding convention makes that exact) and the micro-batch frontend
flushes the admission queue in power-of-two example counts, so the jitted
step sees at most log2(micro_batch)+1 distinct batch shapes.  Example-count
padding is NOT used for learn — a padded example would corrupt the bias
gradient and the loss mean — which is why the flush decomposes the waiting
count in binary instead.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import solvers as solver_registry
from repro.core import linear_trainer as lt
from repro.core.linear_trainer import LinearConfig, SparseBatch
from repro.obs.compile_tracker import CompileTracker
from repro.serving.metrics import ServingMetrics
from repro.serving.queue import AdmissionQueue
from repro.serving.service_config import ServiceConfig, binary_buckets, pin_config


class LinearService:
    def __init__(self, cfg: LinearConfig, service: Optional[ServiceConfig] = None, *,
                 w0: Optional[np.ndarray] = None):
        service = service or ServiceConfig()
        # pin every deferred LinearConfig field (backend/solver) to a
        # concrete value before the first jit: the live service must never
        # change program because $REPRO_*/use_backend() context changed
        cfg = pin_config(cfg, service)
        self.cfg = cfg
        self.service = service
        self.p_max = service.p_max
        self.micro_batch = service.micro_batch
        self.buckets = binary_buckets(service.micro_batch)
        self.state = lt.init_state(cfg, w0)
        self.metrics = service.metrics or ServingMetrics()
        self.queue = AdmissionQueue(max_batch=service.micro_batch,
                                    max_delay=service.max_delay)
        self._build_jits()

    def _build_jits(self) -> None:
        """(Re)build the jitted step/flush/predict closed over self.cfg —
        from __init__ and from a cfg-changing swap_weights.  self.cfg.backend
        is always concrete here (__init__ pins it), so all three jits route
        through the same kernel backend; it is never a jit argument, so the
        compile-count bound below is backend-independent.  A fresh tracker
        per build: a swap_weights rebuild deliberately resets the baseline
        (it costs one compile per function, by design)."""
        self.compiles = CompileTracker()
        self._step = self.compiles.register(
            "step", jax.jit(lt.make_lazy_step(self.cfg), donate_argnums=0)
        )
        self._flush = self.compiles.register(
            "flush", jax.jit(functools.partial(lt.flush, self.cfg), donate_argnums=0)
        )
        self._predict = self.compiles.register(
            "predict", jax.jit(functools.partial(lt.predict_proba_sparse, self.cfg))
        )

    # -- introspection ------------------------------------------------------

    def compile_counts(self) -> dict:
        return self.compiles.counts()

    def current_weights(self) -> np.ndarray:
        return np.asarray(lt.current_weights(self.cfg, self.state))

    # -- sweep integration ---------------------------------------------------

    def swap_weights(self, w=None, b: float = 0.0, cfg: Optional[LinearConfig] = None,
                     state=None) -> None:
        """Hot-swap a finished sweep's winning model into the live service.

        The new state opens a fresh round (psi=0, empty caches — the swapped
        weights are already current; apply-at-read solvers re-seed their
        state by inverting the read) with the global step ``t`` preserved so
        attenuating schedules do not restart hot.  Passing ``cfg`` also
        swaps the winning hyperparameters — and may swap the *solver*, as
        long as the packed state shape matches (a [d, 3] ftrl state cannot
        take over a [d, 2] cache-based service's donated buffers mid-
        flight); the jitted step/flush/predict close over the lams as
        constants, so that costs one rebuild per swap — never a per-request
        recompile.  The feature space is fixed: online requests in flight
        keep indexing the same rows.

        ``state=`` swaps a full packed ``[d, state_cols]`` solver state
        instead of a weight vector — the lossless form: an FTRL sweep winner
        keeps its accumulated (z, n) (a (w, b)-form swap would round-trip
        through seed_cols and erase the per-coordinate learning rates), and
        a migrating tenant keeps its exact optimizer state.  The solver's
        ``adopt_state`` sanitizes it against the fresh round (cache-based
        solvers rebase psi to 0)."""
        if (w is None) == (state is None):
            raise ValueError("swap_weights takes exactly one of w= or state=")
        if cfg is not None and cfg.backend is None:
            # sweep-winner configs usually carry backend=None: keep the
            # backend pinned at construction rather than reverting the live
            # service to lazy trace-time resolution (and avoid a needless
            # jit rebuild when only the backend field differs)
            cfg = dataclasses.replace(cfg, backend=self.cfg.backend)
        if cfg is not None and cfg.mesh is None and self.cfg.mesh is not None:
            # likewise: sweep winners rarely carry the mesh — the live
            # service's feature sharding survives the swap (a swap cannot
            # re-place the donated row buffers mid-flight anyway)
            cfg = dataclasses.replace(
                cfg, mesh=self.cfg.mesh, feature_axis=self.cfg.feature_axis,
                shard_margin=self.cfg.shard_margin,
            )
        if cfg is not None:
            assert cfg.mesh == self.cfg.mesh, "swap cannot change the feature mesh"
        if cfg is not None:
            if cfg.solver is None:
                cfg = dataclasses.replace(cfg, solver=self.cfg.solver)
            new_cols = solver_registry.for_config(cfg).state_cols
            old_cols = solver_registry.for_config(self.cfg).state_cols
            if new_cols != old_cols:
                raise ValueError(
                    f"swap across solvers of mismatched state shape: "
                    f"{self.cfg.solver!r} [d, {old_cols}] -> {cfg.solver!r} [d, {new_cols}]"
                )
        if cfg is not None and cfg != self.cfg:
            assert cfg.dim == self.cfg.dim, "swap cannot change the feature space"
            self.cfg = cfg
            self._build_jits()
        t = self.state.t
        if state is not None:
            sv = solver_registry.for_config(self.cfg)
            packed = jnp.asarray(state, jnp.float32)
            if packed.shape != (self.cfg.dim, sv.state_cols):
                raise ValueError(
                    f"state= shape {packed.shape} != "
                    f"[{self.cfg.dim}, {sv.state_cols}] for solver {sv.name!r}"
                )
            fresh = lt.init_state(self.cfg, None)
            wpsi = sv.adopt_state(self.cfg, packed)
            if self.cfg.mesh is not None:
                # adopted state arrives at the logical dim: pad to the shard
                # grain and place feature-sharded like the fresh buffers
                from repro.dist import linear as dl

                wpsi = jax.device_put(
                    dl.pad_rows(self.cfg, wpsi), dl.state_shardings(self.cfg).wpsi
                )
            self.state = fresh._replace(
                wpsi=wpsi, b=jnp.asarray(b, jnp.float32), t=t,
            )
        else:
            self.state = lt.init_state(self.cfg, np.asarray(w, np.float32))._replace(
                b=jnp.asarray(b, jnp.float32), t=t
            )
        self.metrics.count("weight_swaps")

    # -- padding ------------------------------------------------------------

    def _pad_features(self, idx, val) -> Tuple[np.ndarray, np.ndarray]:
        idx = np.asarray(idx, dtype=np.int32)
        val = np.asarray(val, dtype=np.float32)
        B, p = idx.shape
        assert p <= self.p_max, f"request carries {p} features > p_max {self.p_max}"
        if p < self.p_max:  # convention: idx=0/val=0 slots are inert
            idx = np.pad(idx, [(0, 0), (0, self.p_max - p)])
            val = np.pad(val, [(0, 0), (0, self.p_max - p)])
        return idx, val

    def _pad_batch(self, batch: SparseBatch) -> SparseBatch:
        idx, val = self._pad_features(np.asarray(batch.idx), np.asarray(batch.val))
        return SparseBatch(idx=jnp.asarray(idx), val=jnp.asarray(val),
                           y=jnp.asarray(np.asarray(batch.y, dtype=np.float32)))

    # -- direct API ---------------------------------------------------------

    def predict(self, batch: SparseBatch) -> np.ndarray:
        """Probabilities (logistic) / values (squared) for a request batch.
        O(p) per example: only touched rows are gathered and caught up.
        Example-count padding to the bucket is safe here — padded rows are
        sliced off, and prediction mutates nothing.  Batches larger than
        micro_batch are chunked so the bucket set stays the complete compile
        set (same bound as learn)."""
        B = int(np.asarray(batch.idx).shape[0])
        idx, val = self._pad_features(np.asarray(batch.idx), np.asarray(batch.val))
        t0 = time.monotonic()
        outs = []
        for lo in range(0, B, self.micro_batch):
            outs.append(self._predict_chunk(idx[lo : lo + self.micro_batch],
                                            val[lo : lo + self.micro_batch]))
        self.metrics.record_latency("predict", time.monotonic() - t0)
        self.metrics.count("predict_examples", B)
        return np.concatenate(outs)

    def _predict_chunk(self, idx: np.ndarray, val: np.ndarray) -> np.ndarray:
        B = idx.shape[0]
        Bb = next(b for b in self.buckets if b >= B)
        if Bb > B:
            idx = np.pad(idx, [(0, Bb - B), (0, 0)])
            val = np.pad(val, [(0, Bb - B), (0, 0)])
        padded = SparseBatch(idx=jnp.asarray(idx), val=jnp.asarray(val),
                             y=jnp.asarray(np.zeros(Bb, np.float32)))  # y unused
        return np.asarray(self._predict(self.state, padded))[:B]

    def learn(self, batch: SparseBatch) -> float:
        """One lazy step on the (feature-padded) batch; flushes + rebases at
        the round boundary exactly like core.make_round_fn."""
        t0 = time.monotonic()
        self.state, loss = self._step(self.state, self._pad_batch(batch))
        if int(self.state.i) >= self.cfg.round_len:
            self.state = self._flush(self.state)
            self.metrics.count("round_flushes")
        self.metrics.record_latency("learn", time.monotonic() - t0)
        self.metrics.count("learn_steps")
        self.metrics.count("learn_examples", int(np.asarray(batch.idx).shape[0]))
        if self.cfg.mesh is not None:
            # per-shard touched-row gauges + imbalance ratio (host-side
            # bincount over the request's feature ids — O(B*p))
            from repro.dist import linear as dl

            dl.record_shard_metrics(self.metrics, self.cfg, batch.idx)
        return float(loss)

    # -- micro-batched frontend ---------------------------------------------

    def submit_learn(self, idx: Sequence[int], val: Sequence[float], y: float,
                     arrival: float = 0.0) -> None:
        """Enqueue one online example; it trains at the next flush."""
        self.queue.put((np.asarray(idx, np.int32).reshape(-1),
                        np.asarray(val, np.float32).reshape(-1),
                        np.float32(y)), arrival=arrival)

    def poll(self, now: float, force: bool = False) -> int:
        """Flush the admission queue: pop arrived examples in power-of-two
        group sizes (binary decomposition of the waiting count — exact batch
        shapes, no padded examples) and run one lazy step per group.
        Returns the number of examples trained."""
        total = 0
        while True:
            n = self.queue.depth(now)
            if n == 0:
                break
            want = max(b for b in self.buckets if b <= n)
            items = self.queue.pop_ready(now, limit=want, force=force)
            if not items:
                break  # flush policy says keep batching
            total += len(items)
            self.learn(self._collate(items))
        if total:
            self.metrics.sample_queue_depth(self.queue.depth(now))
        return total

    def _collate(self, items: List[Tuple[np.ndarray, np.ndarray, np.float32]]) -> SparseBatch:
        p = max(it[0].size for it in items)
        B = len(items)
        idx = np.zeros((B, p), dtype=np.int32)
        val = np.zeros((B, p), dtype=np.float32)
        y = np.zeros((B,), dtype=np.float32)
        for b, (i, v, yy) in enumerate(items):
            idx[b, : i.size] = i
            val[b, : v.size] = v
            y[b] = yy
        return SparseBatch(idx=jnp.asarray(idx), val=jnp.asarray(val), y=jnp.asarray(y))
