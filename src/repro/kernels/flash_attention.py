"""Blockwise (flash) attention Pallas kernel for TPU.

Grid: (B * K * G, num_q_blocks, num_kv_blocks); the kv axis is minor-most so
a TPU core iterates it sequentially, accumulating the online-softmax state
(acc, row-max m, row-sum l) in VMEM scratch. Block shapes are MXU-aligned
(q_block x head_dim and kv_block x head_dim tiles, head_dim typically 128).

Causal + sliding-window masks are applied per block; fully-masked kv blocks
are skipped with pl.when (no MXU work issued). The matmuls take the inputs'
dtype (bf16 on the MXU in a bf16 model) with f32 accumulation; the softmax
statistics and the accumulator stay f32. HBM traffic is q/k/v reads + one
output write — the score matrices never leave VMEM, which is the entire
point (FlashAttention adapted to the TPU memory hierarchy: HBM->VMEM DMA via
BlockSpecs, MXU for the two matmuls, VPU for the softmax recurrence).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128        # TPU vector lanes: width of the per-row m / l / lse tiles


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *refs, scale: float,
                 causal: bool, window: Optional[int], q_block: int,
                 kv_block: int, kv_len: int):
    # refs = ([lse_ref,] acc_ref, m_ref, l_ref); m and l are kept
    # replicated over LANES lanes so every per-row vector is a legal tile
    lse_ref = refs[0] if len(refs) == 4 else None
    acc_ref, m_ref, l_ref = refs[-3:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * q_block
    k_start = ki * kv_block
    # block-level visibility: causal => k_start <= q_end; window => k block
    # not entirely below (q_start - window)
    visible = True
    if causal:
        visible = k_start <= q_start + q_block - 1
    if window is not None:
        visible = jnp.logical_and(
            visible, k_start + kv_block - 1 > q_start - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0]                                     # (Bq, D)
        k = k_ref[0]                                     # (Bk, D)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (q_block, kv_block), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (q_block, kv_block), 1)
        ok = k_pos < kv_len
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window is not None:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                              # (Bq, LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr[:, :1] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = m_ref[...] + jnp.log(l)


def flash_attention(q, k, v, *, causal=True, window=None, q_block=None,
                    kv_block=None, softmax_scale=None, interpret=None,
                    return_lse=False):
    """q: (B, S, K, G, D); k, v: (B, T, K, D) -> (B, S, K, G, D).

    return_lse additionally returns the per-row logsumexp (B, S, K, G) fp32
    used by the backward kernels. Defaults of None resolve through the
    kernel find-db (``repro.kernels.findb``): block sizes come from the
    tuned entry for this (shape, hardware) or the hand-picked fallback,
    and ``interpret`` auto-detects the platform (interpreted everywhere
    but TPU). Explicit arguments always win.
    """
    from repro.kernels import findb
    B, S, K, G, D = q.shape
    T = k.shape[1]
    if interpret is None:
        interpret = findb.default_interpret()
    if q_block is None or kv_block is None:
        tuned = findb.lookup_or_default(
            "flash_attention", findb.attention_shape_key(
                B=B, S=S, K=K, G=G, D=D, T=T, causal=causal, window=window))
        q_block = tuned["q_block"] if q_block is None else q_block
        kv_block = tuned["kv_block"] if kv_block is None else kv_block
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    nq = -(-S // q_block)
    nk = -(-T // kv_block)
    Sp, Tp = nq * q_block, nk * kv_block

    q2 = jnp.moveaxis(q, 1, 3).reshape(B * K * G, S, D)     # (BKG, S, D)
    k2 = jnp.moveaxis(k, 1, 2).reshape(B * K, T, D)
    v2 = jnp.moveaxis(v, 1, 2).reshape(B * K, T, D)
    if Sp != S:
        q2 = jnp.pad(q2, ((0, 0), (0, Sp - S), (0, 0)))
    if Tp != T:
        k2 = jnp.pad(k2, ((0, 0), (0, Tp - T), (0, 0)))
        v2 = jnp.pad(v2, ((0, 0), (0, Tp - T), (0, 0)))

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, kv_len=T)

    o_spec = pl.BlockSpec((1, q_block, D), lambda i, qi, ki: (i, qi, 0))
    o_shape = jax.ShapeDtypeStruct((B * K * G, Sp, D), q.dtype)
    if return_lse:
        out_specs = [o_spec, pl.BlockSpec((1, q_block, LANES),
                                          lambda i, qi, ki: (i, qi, 0))]
        out_shape = [o_shape, jax.ShapeDtypeStruct((B * K * G, Sp, LANES),
                                                   jnp.float32)]
    else:
        out_specs, out_shape = o_spec, o_shape
    res = pl.pallas_call(
        kernel,
        grid=(B * K * G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, D), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, kv_block, D), lambda i, qi, ki: (i // G, ki, 0)),
            pl.BlockSpec((1, kv_block, D), lambda i, qi, ki: (i // G, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((q_block, D), jnp.float32),       # acc
            pltpu.VMEM((q_block, LANES), jnp.float32),   # running max m
            pltpu.VMEM((q_block, LANES), jnp.float32),   # running sum l
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q2, k2, v2)
    out = res[0] if return_lse else res
    out = jnp.moveaxis(out[:, :S].reshape(B, K, G, S, D), 3, 1)
    if return_lse:
        lse = jnp.moveaxis(res[1][:, :S, 0].reshape(B, K, G, S), 3, 1)
        return out, lse
    return out
