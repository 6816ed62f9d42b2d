"""The kernel-backend op surface (DESIGN.md §11).

A backend is one implementation of the paper's hot-path compute: the lazy
elastic-net catch-up / fused update / dense shrink sweep, the per-solver
update math (FTRL apply-at-read + AdaGrad deltas, truncated-gradient
boundary shrink — repro.solvers), and the serving engine's attention.  Two
ship in-tree:

* ``reference`` — the pure-jnp expressions the algorithm was validated with,
  bitwise-identical to the pre-backend code (they ARE that code, moved).
* ``pallas``    — the TPU kernels in :mod:`repro.kernels` (interpret mode on
  CPU, compiled on TPU).

Backends are plain Python objects resolved at TRACE TIME: a jitted program
closes over whichever backend was active when it traced, so switching
backends never grows a jit cache (serving's zero-recompile invariant) — and
conversely, switching after a program has traced does not retroactively
change it; rebuild the jit (e.g. ``LinearService._build_jits``) to re-route.

Shape conventions shared by every op:

* ``w`` is either a flat ``[n]`` weight vector with per-element ``psi`` /
  factors (the linear trainer's gathered slab and full weight vector) or a
  ``[R, D]`` row slab with per-row ``psi`` / factors (embedding tables —
  one catch-up window per row).  Scalars broadcast over either.
* The gather/scatter that moves rows in and out of the parameter buffer
  stays in XLA at every call site; only the row-slab *math* between them is
  backend-dispatched (DESIGN.md §11 explains why).
"""
from __future__ import annotations


class KernelBackend:
    """Abstract op surface.  Implementations override every method; the
    base class only documents semantics."""

    name: str = "abstract"

    # -- regularization ------------------------------------------------------

    def catchup_rows(self, w, psi, k, caches, lam1):
        """Bring ``w`` current from per-entry round-local step ``psi`` to
        ``k`` against the DP ``caches``: all missed elastic-net updates in
        closed form, O(1) per entry.  ``lam1`` may be a traced scalar."""
        raise NotImplementedError

    def fused_catchup_sgd(self, w, grad, psi, k, caches, lam1, eta):
        """Catch-up + SGD gradient step in one pass over the row bytes
        (``catchup_rows(w) - eta * grad``); ``w``/``grad`` are a ``[R, D]``
        row slab.  With ``psi == k`` the catch-up is the identity and this
        is a plain fused SGD step (``optim.lazy_rows.finish``)."""
        raise NotImplementedError

    def flush_rows(self, w, ratio, shift):
        """Apply pre-computed catch-up factors with no gradient term:
        ``sgn(w) * max(|w| * ratio - shift, 0)`` — the round-boundary flush,
        where the caller derived (ratio, shift) once for the whole buffer via
        :func:`repro.core.lazy_enet.catchup_factors`."""
        raise NotImplementedError

    def prox_sweep(self, w, eta, lam1, lam2, flavor):
        """One dense per-step elastic-net shrink over every coordinate of
        ``w`` (paper Eq 9 / §6.2) — the dense baseline's O(d) inner loop.
        ``eta``/``lam1``/``lam2`` may be traced scalars; ``flavor`` is
        trace-static ('sgd' | 'fobos')."""
        raise NotImplementedError

    def trunc_shrink(self, w, shift):
        """Pure subtractive soft-threshold ``sgn(w) * max(|w| - shift, 0)``
        — the truncated-gradient solver's K-step boundary truncation
        (repro.solvers.trunc).  ``shift`` may be a traced scalar (gated to 0
        off-boundary) or broadcastable to ``w``."""
        raise NotImplementedError

    def fused_step(self, w, ratio, shift, val, y, b, eta, *, loss, use_bias):
        """ONE whole lazy step for the cache-based solvers (sgd/fobos/trunc
        — they differ only in how the DP caches extend, which stays outside
        in O(1)): closed-form catch-up of the gathered ``[B, p]`` weight
        slab with pre-derived per-element ``(ratio, shift)`` factors, sparse
        predict ``z = sum_p(w_cur * val) [+ b]``, the loss gradient, and the
        SGD update delta ``-eta * gz * val`` — a single tile pass instead of
        one dispatch per op (DESIGN.md §13).  Returns ``(w_cur [B, p],
        delta [B, p], gz [B], loss [B])``; the caller keeps the gather and
        the scatter-SET/scatter-ADD pair in XLA (duplicate-index semantics).
        ``b``/``eta`` and the factors may be traced; ``loss``/``use_bias``
        are trace-static structure."""
        raise NotImplementedError

    def fused_margin(self, w, ratio, shift, val):
        """The shard-local HALF of ``fused_step`` (dist.linear, DESIGN.md
        §16): closed-form catch-up of the gathered ``[B, p]`` weight slab
        plus its per-slot margin contributions ``w_cur * val`` — everything
        of the step that precedes the cross-shard margin psum, one tile
        pass.  Returns ``(w_cur [B, p], contrib [B, p])``; the caller psums
        ``contrib``, finishes the loss gradient in jnp (identical arithmetic
        to the unsharded step) and keeps gather/scatter in XLA.  Off-shard
        slots arrive with ``val == 0`` so their contributions vanish."""
        raise NotImplementedError

    def ftrl_margin(self, z, n, val, alpha, beta, lam1, lam2):
        """FTRL twin of :meth:`fused_margin`: apply-at-read weights from the
        gathered ``[B, p]`` ``(z, n)`` slab and their margin contributions,
        one tile pass.  Returns ``(w_cur [B, p], contrib [B, p])``; hypers
        may be traced scalars."""
        raise NotImplementedError

    def ftrl_fused_step(self, z, n, val, y, b, alpha, beta, lam1, lam2, *, loss, use_bias):
        """ONE whole lazy step for FTRL-Proximal: apply-at-read weights from
        the gathered ``[B, p]`` ``(z, n)`` slab, sparse predict, loss
        gradient, and the per-coordinate AdaGrad deltas, in one tile pass.
        Returns ``(w_cur [B, p], dz [B, p], dn [B, p], gz [B], loss [B])``;
        deltas scatter-ADD outside.  All hypers may be traced scalars."""
        raise NotImplementedError

    def ftrl_read(self, z, n, alpha, beta, lam1, lam2):
        """FTRL-Proximal apply-at-read weights from flat ``(z, n)`` state:
        ``0`` where ``|z| <= lam1``, else ``(sgn(z)*lam1 - z) / ((beta +
        sqrt(n))/alpha + lam2)``.  All hypers may be traced scalars."""
        raise NotImplementedError

    def ftrl_flush(self, wpsi, alpha, beta, lam1, lam2):
        """The FTRL round flush over a packed ``[d, 3]`` ``(w, z, n)`` state:
        the state with every row's w rebuilt by :meth:`ftrl_read` from its
        own ``(z, n)``, z and n unchanged, in one pass over the state as it
        lies (the caller donates it).  All hypers may be traced scalars."""
        raise NotImplementedError

    def ftrl_update(self, w, n, g, alpha):
        """Per-coordinate AdaGrad FTRL update deltas for flat rows:
        ``sigma = (sqrt(n + g^2) - sqrt(n)) / alpha``, returns
        ``(dz, dn) = (g - sigma * w, g^2)``.  Deltas, not absolute values:
        the caller's scatter-ADD keeps duplicate-index semantics in XLA."""
        raise NotImplementedError

    def screen_mask(self, g, w, thr, chk):
        """Fused path-screening pass (repro.paths, DESIGN.md §17) over flat
        ``[n]`` arrays: the sequential strong rule's gradient bound and the
        KKT violation check on the complement, one read of the gradient
        bytes.  Returns 0/1 f32 masks ``(active, viol)`` with

        * ``active = (|g| >= thr) | (w != 0)`` — survives screening (``thr =
          2*lam1_k - lam1_{k-1}``; the ``w != 0`` term is the ever-active
          rule, and lets the KKT caller pass its current active mask as
          ``w`` with ``thr`` unreachable to test only the screened-out set);
        * ``viol = ~active & (|g| > chk)`` — a screened-out coordinate whose
          stationarity bound fails, i.e. a re-admission candidate.

        ``thr``/``chk`` may be traced scalars (a new lambda stage never
        recompiles).  Comparisons only — backends agree exactly, not merely
        to tolerance."""
        raise NotImplementedError

    # -- attention -----------------------------------------------------------

    def attention(
        self,
        q,
        k,
        v,
        *,
        causal=True,
        window=0,
        q_positions=None,
        kv_positions=None,
        kv_valid=None,
        q_offset=None,
    ):
        """GQA attention over ``q [B, Sq, H, hd]`` / ``k,v [B, Skv, KV, hd]``.

        ``q_offset`` is the offset-form position spec the flash kernel can
        stream: absolute position of q[0] — a scalar (training = 0, lock-step
        decode = pos) or a per-slot ``[B]`` vector with Sq == 1 (continuous-
        batching decode: slot b attends kv positions <= q_offset[b]).
        Explicit ``q_positions``/``kv_positions``/``kv_valid``/``window``
        express masks flash cannot; backends fall back to the reference
        einsum path for those."""
        raise NotImplementedError
