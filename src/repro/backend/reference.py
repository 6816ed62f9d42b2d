"""The ``reference`` backend: the pure-jnp hot-path expressions the paper
reproduction was validated against — bitwise-identical to the pre-backend
code (the regularization ops delegate to :mod:`repro.core`, the attention
einsum is that code moved here verbatim).  This is the CPU/GPU default and
the oracle every other backend is tested against."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import dense_enet, lazy_enet

from .api import KernelBackend

NEG_INF = -1e30


class ReferenceBackend(KernelBackend):
    name = "reference"

    # -- regularization ------------------------------------------------------

    def catchup_rows(self, w, psi, k, caches, lam1):
        return lazy_enet.catchup(w, psi, k, caches, lam1)

    def fused_catchup_sgd(self, w, grad, psi, k, caches, lam1, eta):
        ratio, shift = lazy_enet.catchup_factors(psi, k, caches, lam1)
        if jnp.ndim(ratio) == 1:  # per-row factors broadcast down the slab
            ratio, shift = ratio[:, None], shift[:, None]
        return self.flush_rows(w, ratio, shift) - eta * grad

    def flush_rows(self, w, ratio, shift):
        # the apply half of lazy_enet.catchup, with factors pre-computed
        mag = jnp.abs(w) * ratio - shift
        return jnp.sign(w) * jnp.maximum(mag, 0.0)

    def prox_sweep(self, w, eta, lam1, lam2, flavor):
        return dense_enet.reg_update(w, eta, lam1, lam2, flavor)

    def trunc_shrink(self, w, shift):
        return jnp.sign(w) * jnp.maximum(jnp.abs(w) - shift, 0.0)

    def fused_step(self, w, ratio, shift, val, y, b, eta, *, loss, use_bias):
        # the exact op sequence of the multi-op reference step
        # (repro._testing.reference_step), on the same [B, p] shapes — the
        # solver step stays BITWISE equal to it (tests/fused) and to the
        # inlined pre-refactor closure (tests/solvers)
        from repro.core import linear_trainer as lt

        mag = jnp.abs(w) * ratio - shift
        w_cur = jnp.sign(w) * jnp.maximum(mag, 0.0)
        z = jnp.sum(w_cur * val, axis=-1)
        if use_bias:
            z = z + b
        loss_v, gz = lt.loss_and_grad_z(loss, z, y)
        delta = -eta * (gz[:, None] * val)
        return w_cur, delta, gz, loss_v

    def fused_margin(self, w, ratio, shift, val):
        # the pre-psum half of fused_step, same ops in the same order — the
        # sharded step stays BITWISE equal to the unsharded one around the
        # margin reduction (tests/dist/test_linear_sharded.py)
        mag = jnp.abs(w) * ratio - shift
        w_cur = jnp.sign(w) * jnp.maximum(mag, 0.0)
        return w_cur, w_cur * val

    def ftrl_margin(self, z, n, val, alpha, beta, lam1, lam2):
        w_cur = self.ftrl_read(z, n, alpha, beta, lam1, lam2)
        return w_cur, w_cur * val

    def ftrl_fused_step(self, z, n, val, y, b, alpha, beta, lam1, lam2, *, loss, use_bias):
        from repro.core import linear_trainer as lt

        w_cur = self.ftrl_read(z, n, alpha, beta, lam1, lam2)
        zlin = jnp.sum(w_cur * val, axis=-1)
        if use_bias:
            zlin = zlin + b
        loss_v, gz = lt.loss_and_grad_z(loss, zlin, y)
        g = gz[:, None] * val
        dz, dn = self.ftrl_update(w_cur, n, g, alpha)
        return w_cur, dz, dn, gz, loss_v

    def ftrl_read(self, z, n, alpha, beta, lam1, lam2):
        # alpha enters via an explicit reciprocal so the arithmetic is the
        # same ops whether alpha is a baked constant or a traced per-config
        # scalar (XLA strength-reduces x / const to x * (1/const); writing
        # the multiply ourselves keeps batch-of-1 sweeps bitwise)
        inv_alpha = 1.0 / alpha
        denom = (beta + jnp.sqrt(n)) * inv_alpha + lam2
        w = (jnp.sign(z) * lam1 - z) / denom
        return jnp.where(jnp.abs(z) <= lam1, 0.0, w)

    def ftrl_flush(self, wpsi, alpha, beta, lam1, lam2):
        # elementwise over the state in whatever shape it has: w from the
        # row's own (z, n), selected into column 0
        w = self.ftrl_read(wpsi[..., 1:2], wpsi[..., 2:3], alpha, beta, lam1, lam2)
        col = jax.lax.broadcasted_iota(jnp.int32, wpsi.shape, wpsi.ndim - 1)
        return jnp.where(col == 0, w, wpsi)

    def ftrl_update(self, w, n, g, alpha):
        g2 = g * g
        inv_alpha = 1.0 / alpha  # see ftrl_read
        sigma = (jnp.sqrt(n + g2) - jnp.sqrt(n)) * inv_alpha
        return g - sigma * w, g2

    def screen_mask(self, g, w, thr, chk):
        ag = jnp.abs(g)
        active = jnp.where((ag >= thr) | (w != 0.0), 1.0, 0.0)
        viol = (1.0 - active) * jnp.where(ag > chk, 1.0, 0.0)
        return active, viol

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
        B, Sq, H, hd = q.shape
        KV = k.shape[2]
        G = H // KV
        Skv = k.shape[1]
        if q_offset is not None:
            assert q_positions is None and kv_positions is None
            off = jnp.asarray(q_offset, jnp.int32)
            if off.ndim == 1:
                # per-slot horizon (continuous-batching decode): slot b
                # attends kv <= off[b].  Expressed through the validity-mask
                # path, exactly as models.transformer.decode_multi always did.
                assert causal and window == 0 and Sq == 1, (causal, window, Sq)
                kvm = jnp.arange(Skv, dtype=jnp.int32)[None, :] <= off[:, None]
                kv_valid = kvm if kv_valid is None else (kvm & kv_valid)
                causal = False
            else:
                # contiguous block at an absolute offset (training: 0,
                # lock-step decode: pos) — plain position vectors.
                q_positions = off + jnp.arange(Sq, dtype=jnp.int32)
        qg = q.reshape(B, Sq, KV, G, hd)
        logits = jnp.einsum("bskgh,btkh->bkgst", qg, k, preferred_element_type=jnp.float32)
        logits = logits / math.sqrt(hd)
        if q_positions is None:
            q_positions = jnp.arange(Sq, dtype=jnp.int32)
        if kv_positions is None:
            kv_positions = jnp.arange(Skv, dtype=jnp.int32)
        mask = jnp.ones((Sq, Skv), dtype=bool)
        if causal:
            mask &= kv_positions[None, :] <= q_positions[:, None]
        if window:
            mask &= kv_positions[None, :] > q_positions[:, None] - window
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        if kv_valid is not None:
            kvm = kv_valid if kv_valid.ndim == 2 else kv_valid[None]
            logits = jnp.where(kvm[:, None, None, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v)
        return out.reshape(B, Sq, H, hd)
