"""Blockwise flash-attention BACKWARD Pallas kernels.

Standard two-pass formulation (Dao, FlashAttention-2):
  pass 0 (host-side jnp): D = rowsum(dO * O)  — cheap, O(S*d).
  dkv kernel: grid (B*K, nk, nq_inner) — one program per kv block, walking q
      blocks sequentially; accumulates dK, dV in VMEM scratch. Recomputes
      p = exp(s - m) from the saved row-max/row-sum (LSE) — score blocks
      never touch HBM, same as forward.
  dq kernel: grid (B*K*G, nq, nk_inner) — per q block, walking kv blocks,
      accumulating dQ.

The forward kernel is extended to also emit the per-row LSE so the backward
can rebuild probabilities exactly.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _mask(q_start, k_start, q_block, kv_block, kv_len, causal, window):
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (q_block, kv_block), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (q_block, kv_block), 1)
    ok = k_pos < kv_len
    if causal:
        ok = jnp.logical_and(ok, k_pos <= q_pos)
    if window is not None:
        ok = jnp.logical_and(ok, k_pos > q_pos - window)
    return ok


# --------------------------------------------------------------------- dq

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, window, q_block, kv_block, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start, k_start = qi * q_block, ki * kv_block
    visible = True
    if causal:
        visible = k_start <= q_start + q_block - 1
    if window is not None:
        visible = jnp.logical_and(
            visible, k_start + kv_block - 1 > q_start - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]                        # (Bq, 1)
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _mask(q_start, k_start, q_block, kv_block, kv_len, causal, window)
        s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse)                                # (Bq, Bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc_ref[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


# -------------------------------------------------------------------- dkv

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, window,
                q_block, kv_block, kv_len, groups):
    ki = pl.program_id(1)
    qi = pl.program_id(2)              # walks (q blocks x G groups)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = (qi // groups) * q_block
    k_start = ki * kv_block
    visible = True
    if causal:
        visible = k_start <= q_start + q_block - 1
    if window is not None:
        visible = jnp.logical_and(
            visible, k_start + kv_block - 1 > q_start - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]                        # (Bq, 1)
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _mask(q_start, k_start, q_block, kv_block, kv_len, causal, window)
        s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (Bk, D)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (Bk, D)

    @pl.when(qi == nq - 1)
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ------------------------------------------------------------------ driver

def flash_attention_bwd(q, k, v, out, lse, do, *, causal=True, window=None,
                        q_block=None, kv_block=None, softmax_scale=None,
                        interpret=None):
    """q: (B,S,K,G,D); k,v: (B,T,K,D); out/do like q; lse: (B,S,K,G) fp32.

    Returns (dq, dk, dv). None defaults resolve via the kernel find-db and
    platform auto-detect, exactly like the forward (see
    ``repro.kernels.findb``); explicit arguments always win.
    """
    from repro.kernels import findb
    B, S, K, G, D = q.shape
    T = k.shape[1]
    if interpret is None:
        interpret = findb.default_interpret()
    if q_block is None or kv_block is None:
        tuned = findb.lookup_or_default(
            "flash_attention_bwd", findb.attention_shape_key(
                B=B, S=S, K=K, G=G, D=D, T=T, causal=causal, window=window))
        q_block = tuned["q_block"] if q_block is None else q_block
        kv_block = tuned["kv_block"] if kv_block is None else kv_block
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    nq, nk = -(-S // q_block), -(-T // kv_block)
    Sp, Tp = nq * q_block, nk * kv_block

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)

    def flat_q(x, d_last):
        x2 = jnp.moveaxis(x, 1, 3).reshape(B * K * G, S, *d_last)
        if Sp != S:
            pad = [(0, 0), (0, Sp - S)] + [(0, 0)] * len(d_last)
            x2 = jnp.pad(x2, pad)
        return x2

    q2, do2, o2 = flat_q(q, (D,)), flat_q(do, (D,)), flat_q(out, (D,))
    # per-row vectors travel as (BKG, 1, Sp) rows, so a block (1, 1, Bq)
    # satisfies the TPU's (8, 128) tiling rule
    lse2 = flat_q(lse, ())[:, None]
    dl2 = flat_q(delta, ())[:, None]
    k2 = jnp.moveaxis(k, 1, 2).reshape(B * K, T, D)
    v2 = jnp.moveaxis(v, 1, 2).reshape(B * K, T, D)
    if Tp != T:
        k2 = jnp.pad(k2, ((0, 0), (0, Tp - T), (0, 0)))
        v2 = jnp.pad(v2, ((0, 0), (0, Tp - T), (0, 0)))

    dq2 = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, q_block=q_block, kv_block=kv_block,
                          kv_len=T),
        grid=(B * K * G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, D), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, kv_block, D), lambda i, qi, ki: (i // G, ki, 0)),
            pl.BlockSpec((1, kv_block, D), lambda i, qi, ki: (i // G, ki, 0)),
            pl.BlockSpec((1, q_block, D), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, 1, q_block), lambda i, qi, ki: (i, 0, qi)),
            pl.BlockSpec((1, 1, q_block), lambda i, qi, ki: (i, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, q_block, D), lambda i, qi, ki: (i, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K * G, Sp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((q_block, D), jnp.float32)],
        interpret=interpret,
    )(q2, k2, v2, do2, lse2, dl2)
    dq = jnp.moveaxis(dq2[:, :S].reshape(B, K, G, S, D), 3, 1)

    # dkv: inner grid walks (nq * G) q-tiles per kv block; q-tile index maps
    # to (group, q block)
    def qmap(i, ki, qg):
        return (i * G + qg % G, qg // G, 0)

    def rowmap(i, ki, qg):
        return (i * G + qg % G, 0, qg // G)

    dk2, dv2 = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, q_block=q_block, kv_block=kv_block,
                          kv_len=T, groups=G),
        grid=(B * K, nk, nq * G),
        in_specs=[
            pl.BlockSpec((1, q_block, D), qmap),
            pl.BlockSpec((1, kv_block, D), lambda i, ki, qg: (i, ki, 0)),
            pl.BlockSpec((1, kv_block, D), lambda i, ki, qg: (i, ki, 0)),
            pl.BlockSpec((1, q_block, D), qmap),
            pl.BlockSpec((1, 1, q_block), rowmap),
            pl.BlockSpec((1, 1, q_block), rowmap),
        ],
        out_specs=[
            pl.BlockSpec((1, kv_block, D), lambda i, ki, qg: (i, ki, 0)),
            pl.BlockSpec((1, kv_block, D), lambda i, ki, qg: (i, ki, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B * K, Tp, D), k.dtype),
                   jax.ShapeDtypeStruct((B * K, Tp, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((kv_block, D), jnp.float32),
                        pltpu.VMEM((kv_block, D), jnp.float32)],
        interpret=interpret,
    )(q2, k2, v2, do2, lse2, dl2)
    dk = jnp.moveaxis(dk2[:, :T].reshape(B, K, T, D), 2, 1)
    dv = jnp.moveaxis(dv2[:, :T].reshape(B, K, T, D), 2, 1)
    return dq, dk, dv
