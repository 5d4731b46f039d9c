"""Chunkwise-parallel mLSTM Pallas kernel (xLSTM matrix memory).

Grid: (B, H, num_chunks); chunks minor-most so each core walks the sequence
carrying (C (D,D), n (D,1), m (1,1)) in VMEM scratch. Per chunk:

  intra: the stabilized quadratic form — two MXU matmuls (q@k^T, (w*s)@v) +
         VPU max/exp for the decay matrix;
  inter: q @ C_carry (MXU) weighted by the carried stabilizer;
  state: C <- exp(F_tot + m - m_new) C + (in_w * k)^T v (MXU outer).

The gate prefix sums F (cumulative log forget gate within a chunk) and
r = i - F are O(S*H) and computed in the wrapper; the kernel receives them
as columns (S, 2) = [F, r] and as a row (1, S) = r, so that every per-step
vector enters the kernel in the orientation it is used in and no block
breaks the TPU's (8, 128) tiling rule.

The GPU xLSTM kernel leans on shared-memory tiles per SM; the TPU analogue
keeps the whole (D,D) matrix memory resident in VMEM across the sequence
walk (D<=512 -> <=1MB fp32, well under the ~16MB VMEM budget).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlstm_kernel(q_ref, k_ref, v_ref, gcol_ref, grow_ref, h_ref,
                  C_ref, n_ref, m_ref, *, chunk: int, head_dim: int):
    si = pl.program_id(2)
    scale = 1.0 / math.sqrt(head_dim)

    @pl.when(si == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)

    q = q_ref[0, 0].astype(jnp.float32)            # (Q, D)
    k = k_ref[0, 0].astype(jnp.float32) * scale
    v = v_ref[0, 0].astype(jnp.float32)
    gcol = gcol_ref[0, 0]                          # (Q, 2)
    F = gcol[:, 0:1]                               # (Q, 1) cumsum log f
    r = gcol[:, 1:2]                               # (Q, 1) i - F
    r_row = grow_ref[0, 0]                         # (1, Q)
    Ftot = F[chunk - 1:chunk]                      # (1, 1)
    m_prev = m_ref[...]                            # (1, 1)

    # --- row stabilizers
    m_inter = F + m_prev                           # (Q, 1)
    logw = F + r_row                               # (Q s, Q t) = F_s - F_t + i_t
    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    logw = jnp.where(causal, logw, -jnp.inf)
    m_intra = jnp.max(logw, axis=1, keepdims=True)
    m_row = jnp.maximum(m_inter, m_intra)          # (Q, 1)

    w = jnp.exp(logw - m_row)
    s_qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    a = s_qk * w
    num = jax.lax.dot(a, v, preferred_element_type=jnp.float32)   # (Q, D)
    den = jnp.sum(a, axis=1, keepdims=True)                       # (Q, 1)

    w_state = jnp.exp(m_inter - m_row)
    num = num + w_state * jax.lax.dot(
        q, C_ref[...], preferred_element_type=jnp.float32)
    den = den + w_state * jax.lax.dot(
        q, n_ref[...], preferred_element_type=jnp.float32)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_row))
    h_ref[0, 0] = h.astype(h_ref.dtype)

    # --- state update
    m_new = jnp.maximum(Ftot + m_prev,
                        Ftot + jnp.max(r, axis=0, keepdims=True))  # (1, 1)
    # Mosaic broadcasts along one axis at a time: take the (1, 1) decay to
    # a (D, 1) column before exp, and let the product spread it over lanes
    carry_w = jnp.exp(jnp.broadcast_to(Ftot + m_prev - m_new, (head_dim, 1)))
    kw = k * jnp.exp(r + Ftot - m_new)             # (Q, D) = in_w * k
    C_ref[...] = carry_w * C_ref[...] + \
        jax.lax.dot_general(
            kw, v, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    n_ref[...] = carry_w * n_ref[...] + jax.lax.dot_general(
        kw, jnp.ones((chunk, 1), jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (D, 1)
    m_ref[...] = m_new


def mlstm_chunkwise(q, k, v, i_gate, f_gate, *, chunk=None, interpret=None):
    """q,k,v: (B, S, H, D); gates: (B, S, H). Returns h (B, S, H, D).

    Kernel computes the sequence outputs; final state stays in scratch (the
    decode path carries state explicitly via repro.models.xlstm). None
    defaults resolve via the kernel find-db / platform auto-detect
    (``repro.kernels.findb``); explicit arguments always win.
    """
    from repro.kernels import findb
    B, S, H, D = q.shape
    if interpret is None:
        interpret = findb.default_interpret()
    if chunk is None:
        chunk = findb.lookup_or_default(
            "mlstm", findb.mlstm_shape_key(B=B, S=S, H=H, D=D))["chunk"]
    chunk = min(chunk, S)
    assert S % chunk == 0, f"S={S} must be divisible by chunk={chunk}"
    ns = S // chunk

    def arrange(x):
        return jnp.moveaxis(x, 2, 1)               # (B, H, S, ...)

    q2, k2, v2 = arrange(q), arrange(k), arrange(v)
    logf = jax.nn.log_sigmoid(arrange(f_gate).astype(jnp.float32))
    F = jnp.cumsum(logf.reshape(B, H, ns, chunk), axis=-1).reshape(B, H, S)
    r = arrange(i_gate).astype(jnp.float32) - F
    gcol = jnp.stack([F, r], axis=-1)              # (B, H, S, 2)
    grow = r[:, :, None, :]                        # (B, H, 1, S)

    out = pl.pallas_call(
        functools.partial(_mlstm_kernel, chunk=chunk, head_dim=D),
        grid=(B, H, ns),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, chunk, 2), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, s: (b, h, 0, s)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, D), lambda b, h, s: (b, h, s, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((D, D), jnp.float32),       # matrix memory C
            pltpu.VMEM((D, 1), jnp.float32),       # normalizer n
            pltpu.VMEM((1, 1), jnp.float32),       # stabilizer m
        ],
        interpret=interpret,
    )(q2, k2, v2, gcol, grow)
    return jnp.moveaxis(out, 1, 2)                 # (B, S, H, D)
