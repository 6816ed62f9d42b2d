"""Pallas TPU kernels — the `pallas` implementation of the system's compute
substrate (`repro.backend`), not standalone scaffolding: every regularization
and attention hot path dispatches here when the pallas backend is selected
(interpret mode on CPU, compiled on TPU).

* fused_step — ONE whole lazy training step (catch-up / FTRL read ->
  predict -> loss gradient -> update deltas) per tile pass, for every
  solver; the `fused_step` backend op (DESIGN.md §13)
* lazy_enet — fused lazy catch-up + gradient update on gathered rows
  (the paper's hot spot: 2 reads + 1 write per element vs the 3 + 2 of a
  split catchup-then-update), plus the gradient-free apply used by flushes
* enet_prox — dense elastic-net shrink sweep (dense baseline / flush shrink)
* ftrl — FTRL-Proximal apply-at-read + per-coordinate AdaGrad update deltas
  (the `ftrl` solver's elementwise hot paths, repro.solvers.ftrl), and its
  round flush over the state's own tiles
* margin — the shard-local pre-psum half of the fused step (catch-up /
  apply-at-read + per-slot margin contributions) for feature-sharded
  training (repro.dist.linear, DESIGN.md §16)
* screen — fused strong-rule gradient bound + KKT violation check emitting
  packed 0/1 active/violation masks (the regularization-path engine's
  per-stage screening pass, repro.paths)
* flash_attn — flash attention (forward + custom-vjp backward), the serving
  engine's and the training loss's attention path (chunked prefill /
  per-slot continuous-batching decode via absolute q offsets)
* common — the shared dynamic-hyper operand plumbing every kernel uses

ops.py holds the padded/jit'd public wrappers (all hyperparameters are
dynamic operands — sweeping lam1 must not recompile); ref.py the pure-jnp
oracles.  Product code selects between these kernels and the bitwise
reference implementations through :mod:`repro.backend`, never by importing
this package directly.
"""
from .flash_attn import flash_attention
from .ops import (
    catchup_update,
    dp_fused_step,
    dp_margin,
    enet_apply,
    enet_prox,
    ftrl_flush,
    ftrl_fused_step,
    ftrl_margin,
    ftrl_read,
    ftrl_update,
    lazy_enet_update,
    screen_mask,
)
from . import ref

__all__ = [
    "catchup_update",
    "dp_fused_step",
    "dp_margin",
    "enet_apply",
    "enet_prox",
    "flash_attention",
    "ftrl_flush",
    "ftrl_fused_step",
    "ftrl_margin",
    "ftrl_read",
    "ftrl_update",
    "lazy_enet_update",
    "ref",
    "screen_mask",
]
