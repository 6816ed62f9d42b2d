"""Pallas TPU kernel: flash attention (online softmax), GQA-aware, with a
custom-vjp backward pass.

Motivation (DESIGN.md §8): after sharding fixes,
the dominant roofline term on dense-attention archs is the materialized
[B,H,S,S] f32 mask+softmax chain — ~80% of per-layer bytes in the op
histogram.  Flash attention never materializes it: each program owns one
(batch*head, q-block) tile, streams k/v in BK-sized blocks, and keeps the
running max / normalizer / weighted accumulator in VMEM registers:

    m_new = max(m, rowmax(s));  alpha = exp(m - m_new)
    l     = l * alpha + rowsum(exp(s - m_new))
    acc   = acc * alpha + exp(s - m_new) @ v

HBM traffic drops from O(H*S^2) to O(S*(d_q + d_kv)) — the structural fix
for the memory term.

TPU mapping:
* grid = (B * H, Sq / BQ); q tile (BQ, hd) in VMEM; k/v arrive as the
  full (Skv, hd) slab for the program's kv-head (GQA: kv head = h // G via
  the BlockSpec index_map) and are consumed BK rows at a time with a
  fori_loop — for Skv beyond VMEM the same loop runs over an ANY-space ref
  (decode cells have Sq = 1, so the q side is trivially resident).
* causal masking via absolute positions: q_offset lets the same kernel do
  training (offset 0), chunked prefill, and single-token decode
  (Sq=1, offset=pos).

Backward (the standard flash recomputation scheme): the forward kernel
additionally emits the per-row log-sum-exp ``lse = m + log(den)``, from
which the backward kernels rebuild each probability tile as
``p = exp(s - lse)`` instead of storing the [Sq, Skv] matrix.  With
``delta = rowsum(do * out)`` (a cheap XLA reduction):

    ds = p * (do @ v^T - delta);   dq = scale * ds @ k
    dv = p^T @ do;                 dk = scale * ds^T @ q

Two kernels mirror the forward's tiling: dq over (batch*head, q-block)
programs streaming k/v blocks, dk/dv over (batch*head, kv-block) programs
streaming q/do blocks; per-q-head dk/dv partials reduce over the GQA group
in XLA.  Zero-padded ``do`` rows make padded-q contributions exactly zero;
padded/masked kv columns are re-masked before the exp.  ``q_offset`` is an
integer input, so its cotangent is the symbolic float0 zero.

Validated under interpret=True against the pure-jnp GQA oracle across
shape/dtype/causality sweeps, and the vjp against jax.grad of that oracle
(tests/kernels/test_flash_attn.py); forward and backward compile for TPU
v5e at stablelm_3b widths (tests/kernels/test_tpu_compile.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kv_index_map(KV: int, G: int):
    # grid dim 0 is bh = batch * H + head; the program's kv-head slab is
    # batch * KV + head // G
    return lambda bh, nq: ((bh // (G * KV)) * KV + (bh % (G * KV)) // G, 0, 0)


# Mosaic tiling rules (last two block dims divisible by (8, 128) or equal
# to the array's) shape the operands: the per-program q offset is a whole
# (B*H,) int32 array in SMEM read at program_id(0), and the per-row
# residuals lse / delta are [B*H, Sq_p, 1] columns, so every in-kernel
# value stays a 2-D [rows, 1] or [rows, cols] tile.
_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _mask(q_pos, kv_pos, causal: bool, skv_real: int):
    mask = kv_pos < skv_real  # padded kv rows never score
    if causal:
        mask = mask & (kv_pos <= q_pos)
    return mask


def _positions(q_start, bq: int, kv_start, bk: int):
    """Absolute [bq, bk] q / kv position grids of one score tile."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return q_pos, kv_pos


def _fwd_kernel(
    qoff_ref, q_ref, k_ref, v_ref, out_ref, lse_ref, *, bk: int, causal: bool, scale: float, skv_real: int
):
    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, hd]
    BQ = q.shape[0]
    Skv = k_ref.shape[1]
    # read outside the loop bodies: the interpreter resolves program_id only there
    q_start = qoff_ref[pl.program_id(0)] + pl.program_id(1) * BQ

    def body(i, carry):
        acc, m, den = carry
        k = k_ref[0, pl.dslice(i * bk, bk)].astype(jnp.float32)  # [BK, hd]
        v = v_ref[0, pl.dslice(i * bk, bk)].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK]
        q_pos, kv_pos = _positions(q_start, BQ, i * bk, bk)
        s = jnp.where(_mask(q_pos, kv_pos, causal, skv_real), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))  # [BQ, 1]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        den_new = den * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc_new, m_new, den_new

    acc0 = jnp.zeros((BQ, q.shape[1]), jnp.float32)
    m0 = jnp.full((BQ, 1), NEG_INF, jnp.float32)
    den0 = jnp.zeros((BQ, 1), jnp.float32)
    acc, m, den = jax.lax.fori_loop(0, Skv // bk, body, (acc0, m0, den0))
    den = jnp.maximum(den, 1e-30)
    out_ref[0] = (acc / den).astype(out_ref.dtype)
    # log-sum-exp of the (scaled, masked) scores — the backward's residual
    lse_ref[0] = m + jnp.log(den)


def _dq_kernel(
    qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, bk: int, causal: bool, scale: float, skv_real: int,
):
    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, hd] (scaled like forward)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]  # [BQ, 1]
    delta = delta_ref[0]  # [BQ, 1]
    BQ = q.shape[0]
    Skv = k_ref.shape[1]
    # read outside the loop bodies: the interpreter resolves program_id only there
    q_start = qoff_ref[pl.program_id(0)] + pl.program_id(1) * BQ

    def body(i, dq):
        k = k_ref[0, pl.dslice(i * bk, bk)].astype(jnp.float32)
        v = v_ref[0, pl.dslice(i * bk, bk)].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        q_pos, kv_pos = _positions(q_start, BQ, i * bk, bk)
        mask = _mask(q_pos, kv_pos, causal, skv_real)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [BQ, BK]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq0 = jnp.zeros((BQ, q.shape[1]), jnp.float32)
    dq = jax.lax.fori_loop(0, Skv // bk, body, dq0)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, bq: int, causal: bool, scale: float, skv_real: int,
):
    k = k_ref[0].astype(jnp.float32)  # [BK, hd] — this program's kv tile
    v = v_ref[0].astype(jnp.float32)
    BK = k.shape[0]
    Sq = q_ref.shape[1]
    qoff = qoff_ref[pl.program_id(0)]
    kv_start = pl.program_id(1) * BK

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * bq, bq)].astype(jnp.float32) * scale  # [BQ, hd]
        do = do_ref[0, pl.dslice(i * bq, bq)].astype(jnp.float32)
        lse = lse_ref[0, pl.dslice(i * bq, bq)]  # [BQ, 1]
        delta = delta_ref[0, pl.dslice(i * bq, bq)]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK]
        q_pos, kv_pos = _positions(qoff + i * bq, bq, kv_start, BK)
        mask = _mask(q_pos, kv_pos, causal, skv_real)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        # dv += p^T @ do  (padded q rows: do = 0 -> zero contribution)
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        # dk += ds^T @ (q * scale) — q is pre-scaled, so scale is included
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk_new, dv_new

    dk0 = jnp.zeros((BK, k.shape[1]), jnp.float32)
    dv0 = jnp.zeros((BK, v.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, Sq // bq, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pad_qkv(q, k, v, block_q, block_k):
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    pq = (-Sq) % block_q
    pk = (-Skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    # padded kv rows are masked off inside the kernels (kv_pos >= Skv)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    q2 = qp.reshape(B * H, Sq + pq, hd)
    k2 = kp.reshape(B * KV, Skv + pk, hd)
    v2 = vp.reshape(B * KV, Skv + pk, hd)
    return q2, k2, v2


def _fwd_impl(q, k, v, q_offset, causal, block_q, block_k, interpret):
    """Padded forward; returns (out [B,H,Sq,hd], lse [B*H, Sq_p, 1])."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    q2, k2, v2 = _pad_qkv(q, k, v, block_q, block_k)
    Sq_p, Skv_p = q2.shape[1], k2.shape[1]
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B * H,))
    grid = (B * H, Sq_p // block_q)
    kv_map = _kv_index_map(KV, G)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bk=block_k, causal=causal, scale=scale, skv_real=Skv),
        grid=grid,
        in_specs=[
            _SMEM_SPEC,  # q offsets
            pl.BlockSpec((1, block_q, hd), lambda bh, nq: (bh, nq, 0)),  # q tile
            pl.BlockSpec((1, Skv_p, hd), kv_map),
            pl.BlockSpec((1, Skv_p, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, nq: (bh, nq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, nq: (bh, nq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq_p, hd), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sq_p, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attn_fwd",
    )(offs, q2, k2, v2)

    return out.reshape(B, H, Sq_p, hd)[:, :, :Sq], lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, q_offset, causal, block_q, block_k, interpret):
    out, _ = _fwd_impl(q, k, v, q_offset, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, q_offset, causal, block_q, block_k, interpret):
    out, lse = _fwd_impl(q, k, v, q_offset, causal, block_q, block_k, interpret)
    return out, (q, k, v, q_offset, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, q_offset, out, lse = res
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    q2, k2, v2 = _pad_qkv(q, k, v, block_q, block_k)
    Sq_p, Skv_p = q2.shape[1], k2.shape[1]
    # delta = rowsum(do * out): a cheap XLA reduction over the unpadded
    # arrays; zero-padding do/delta keeps padded q rows inert in-kernel
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B, H, Sq]
    delta2 = jnp.pad(delta, ((0, 0), (0, 0), (0, Sq_p - Sq))).reshape(B * H, Sq_p, 1)
    do2 = jnp.pad(do, ((0, 0), (0, 0), (0, Sq_p - Sq), (0, 0))).reshape(B * H, Sq_p, hd)
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B * H,))
    kv_map = _kv_index_map(KV, G)
    qmap = lambda bh, nq: (bh, nq, 0)
    slabmap = lambda bh, nk: (bh, 0, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bk=block_k, causal=causal, scale=scale, skv_real=Skv),
        grid=(B * H, Sq_p // block_q),
        in_specs=[
            _SMEM_SPEC,  # q offsets
            pl.BlockSpec((1, block_q, hd), qmap),  # q tile
            pl.BlockSpec((1, Skv_p, hd), kv_map),
            pl.BlockSpec((1, Skv_p, hd), kv_map),
            pl.BlockSpec((1, block_q, hd), qmap),  # do tile
            pl.BlockSpec((1, block_q, 1), qmap),  # lse tile
            pl.BlockSpec((1, block_q, 1), qmap),  # delta tile
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), qmap),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, hd), q.dtype),
        interpret=interpret,
        name="flash_attn_dq",
    )(offs, q2, k2, v2, do2, lse, delta2)

    kv_tile = lambda bh, nk, KV=KV, G=G: ((bh // (G * KV)) * KV + (bh % (G * KV)) // G, nk, 0)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=block_q, causal=causal, scale=scale, skv_real=Skv),
        grid=(B * H, Skv_p // block_k),
        in_specs=[
            _SMEM_SPEC,  # q offsets
            pl.BlockSpec((1, Sq_p, hd), slabmap),  # q slab
            pl.BlockSpec((1, block_k, hd), kv_tile),  # k tile
            pl.BlockSpec((1, block_k, hd), kv_tile),  # v tile
            pl.BlockSpec((1, Sq_p, hd), slabmap),  # do slab
            pl.BlockSpec((1, Sq_p, 1), slabmap),  # lse slab
            pl.BlockSpec((1, Sq_p, 1), slabmap),  # delta slab
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda bh, nk: (bh, nk, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, nk: (bh, nk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Skv_p, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Skv_p, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attn_dkv",
    )(offs, q2, k2, v2, do2, lse, delta2)

    dq = dq.reshape(B, H, Sq_p, hd)[:, :, :Sq]
    # GQA: per-q-head dk/dv partials reduce over the group of G q-heads
    dk = dk_h.reshape(B, KV, G, Skv_p, hd).sum(axis=2)[:, :, :Skv].astype(k.dtype)
    dv = dv_h.reshape(B, KV, G, Skv_p, hd).sum(axis=2)[:, :, :Skv].astype(v.dtype)
    # integer positions carry no gradient: symbolic float0 zero cotangent
    doff = np.zeros(jnp.shape(jnp.asarray(q_offset)), jax.dtypes.float0)
    return dq, dk, dv, doff


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,  # [B, H, Sq, hd]
    k: jnp.ndarray,  # [B, KV, Skv, hd]
    v: jnp.ndarray,  # [B, KV, Skv, hd]
    q_offset: jnp.ndarray | int = 0,  # absolute position of q[0] (decode: pos)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns [B, H, Sq, hd].  Sq is padded to block_q and Skv to block_k
    internally (padded kv is masked off by causality or zero-prob rows).
    Differentiable w.r.t. q/k/v via the custom-vjp backward kernels."""
    return _flash(
        q, k, v, jnp.asarray(q_offset, jnp.int32), causal, block_q, block_k, interpret
    )
