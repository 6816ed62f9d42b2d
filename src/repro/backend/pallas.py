"""The ``pallas`` backend: routes the op surface onto the TPU kernels in
:mod:`repro.kernels` — interpret mode on CPU (correctness / CI), compiled on
TPU.  Catch-up factors are derived from the DP caches in XLA (tiny O(R)
gathers + exps — and the place a traced per-config ``lam1`` enters, so
sweeping hypers never recompiles a kernel); only the O(R*D) row-slab pass
runs in Pallas.  Mask forms flash attention cannot stream (local windows,
arbitrary position vectors, explicit validity masks) fall back to the
reference einsum path."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.dp_caches import FOBOS, SGD
from repro.kernels import (
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
from repro.kernels.common import default_interpret
from repro.kernels.flash_attn import flash_attention

from .api import KernelBackend
from .reference import ReferenceBackend

_REF = ReferenceBackend()


class PallasBackend(KernelBackend):
    name = "pallas"

    # -- regularization ------------------------------------------------------

    def catchup_rows(self, w, psi, k, caches, lam1):
        return catchup_update(w, psi, k, caches, lam1)

    def fused_catchup_sgd(self, w, grad, psi, k, caches, lam1, eta):
        return lazy_enet_update(w, grad, psi, k, caches, eta, lam1=lam1)

    def flush_rows(self, w, ratio, shift):
        return enet_apply(w, ratio, shift)

    def prox_sweep(self, w, eta, lam1, lam2, flavor):
        # fold the per-step update into the kernel's (a, s) shrink form:
        #   SGD   (Eq 9):  |w| <- (1 - eta*lam2)|w| - eta*lam1
        #   FoBoS (§6.2):  |w| <- (|w| - eta*lam1) / (1 + eta*lam2)
        eta = jnp.asarray(eta, jnp.float32)
        if flavor == SGD:
            a = 1.0 - eta * lam2
            s = eta * lam1
        elif flavor == FOBOS:
            inv = 1.0 / (1.0 + eta * lam2)
            a = inv
            s = eta * lam1 * inv
        else:
            raise ValueError(f"unknown flavor {flavor!r}")
        return enet_prox(w, a, s)

    def trunc_shrink(self, w, shift):
        # the (ratio=1, shift) specialization of the generic shrink tile,
        # flattened so narrow layouts (the dense path's [d, 1]) tile along
        # lanes instead of padding a 1-wide column out to a full block
        shift = jnp.asarray(shift, jnp.float32)
        if shift.ndim:
            shift = jnp.broadcast_to(shift, w.shape).reshape(-1)
        return enet_apply(w.reshape(-1), jnp.ones((), jnp.float32), shift).reshape(w.shape)

    def fused_step(self, w, ratio, shift, val, y, b, eta, *, loss, use_bias):
        return dp_fused_step(w, ratio, shift, val, y, b, eta, loss=loss, use_bias=use_bias)

    def fused_margin(self, w, ratio, shift, val):
        return dp_margin(w, ratio, shift, val)

    def ftrl_margin(self, z, n, val, alpha, beta, lam1, lam2):
        return ftrl_margin(z, n, val, alpha, beta, lam1, lam2)

    def ftrl_fused_step(self, z, n, val, y, b, alpha, beta, lam1, lam2, *, loss, use_bias):
        return ftrl_fused_step(
            z, n, val, y, b, alpha, beta, lam1, lam2, loss=loss, use_bias=use_bias
        )

    def ftrl_read(self, z, n, alpha, beta, lam1, lam2):
        return ftrl_read(z, n, alpha, beta, lam1, lam2)

    def ftrl_flush(self, wpsi, alpha, beta, lam1, lam2):
        return ftrl_flush(wpsi, alpha, beta, lam1, lam2)

    def ftrl_update(self, w, n, g, alpha):
        return ftrl_update(w, n, g, alpha)

    def screen_mask(self, g, w, thr, chk):
        return screen_mask(g, w, thr, chk)

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
        if window or kv_valid is not None or q_positions is not None or kv_positions is not None:
            # masks the flash kernel can't express stream through the
            # reference einsum (local windows / ring caches / explicit
            # validity); the engine's hot paths are all offset-form.
            return _REF.attention(
                q,
                k,
                v,
                causal=causal,
                window=window,
                q_positions=q_positions,
                kv_positions=kv_positions,
                kv_valid=kv_valid,
                q_offset=q_offset,
            )
        B, Sq, H, hd = q.shape
        off = 0 if q_offset is None else q_offset
        if jnp.ndim(off) == 1:
            # per-slot decode offsets: one absolute q position per batch row,
            # repeated across that row's heads for the (B*H,) program grid
            assert causal and Sq == 1, (causal, Sq)
            off = jnp.repeat(jnp.asarray(off, jnp.int32), H)
        Skv = k.shape[1]
        # decode tiles are tiny (Sq = 1): shrink blocks to the f32 sublane
        # multiple instead of padding every step out to 128
        block_q = 128 if Sq >= 128 else max(8, -(-Sq // 8) * 8)
        block_k = 128 if Skv >= 128 else max(8, -(-Skv // 8) * 8)
        out = flash_attention(
            q.transpose(0, 2, 1, 3),  # [B, H, Sq, hd]
            k.transpose(0, 2, 1, 3),  # [B, KV, Skv, hd]
            v.transpose(0, 2, 1, 3),
            off,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            interpret=default_interpret(),
        )
        return out.transpose(0, 2, 1, 3)
