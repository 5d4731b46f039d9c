"""Chunked RG-LRU linear-recurrence Pallas kernel.

Grid: (B, num_R_blocks, num_S_chunks) with the sequence axis minor-most —
the TPU core walks chunks sequentially carrying the hidden state h in VMEM
scratch. Inside a chunk, a fori_loop walks 8-row tiles (one f32 sublane
tile): each tile of (log_a, b) is loaded once, the 8 steps of
h = exp(log_a)*h + b run on the VPU against static rows of the tile, and
the 8 hidden states are stored back as one tile. The recurrence has no
matmul, so the kernel's job is purely to keep h and the chunk tiles
resident in VMEM and stream (log_a, b) through one DMA per chunk.

This is the TPU adaptation of Griffin's fused scan: the GPU version leans on
warp shuffles for the intra-warp scan; on TPU the sequential-grid + VMEM
carry is the idiomatic equivalent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8          # f32 sublane tile: rows handled per loop iteration


def _rglru_kernel(la_ref, b_ref, h0_ref, o_ref, h_ref, *, chunk: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)          # (1, Rb)

    def tile(i, h):
        rows = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
        a = jnp.exp(la_ref[0, rows, :].astype(jnp.float32))  # (ROWS, Rb)
        b = b_ref[0, rows, :].astype(jnp.float32)
        hs = []
        for r in range(ROWS):
            h = a[r:r + 1] * h + b[r:r + 1]
            hs.append(h)
        o_ref[0, rows, :] = jnp.concatenate(hs, axis=0).astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk // ROWS, tile, h_ref[...])


def rglru_scan(log_a, b, h0=None, *, chunk=None, r_block=None,
               interpret=None):
    """log_a, b: (B, S, R) fp32; h0: (B, R) fp32. Returns (h, h_last).

    None defaults resolve via the kernel find-db / platform auto-detect
    (``repro.kernels.findb``); explicit arguments always win. ``chunk`` is
    rounded up to a multiple of 8 rows.
    """
    from repro.kernels import findb
    B, S, R = log_a.shape
    if interpret is None:
        interpret = findb.default_interpret()
    if chunk is None or r_block is None:
        tuned = findb.lookup_or_default(
            "rglru", findb.rglru_shape_key(B=B, S=S, R=R))
        chunk = tuned["chunk"] if chunk is None else chunk
        r_block = tuned["r_block"] if r_block is None else r_block
    if h0 is None:
        h0 = jnp.zeros((B, R), jnp.float32)
    chunk = -(-min(chunk, S) // ROWS) * ROWS
    r_block = min(r_block, R)
    ns = -(-S // chunk)
    nr = -(-R // r_block)
    Sp, Rp = ns * chunk, nr * r_block
    if Sp != S or Rp != R:
        log_a = jnp.pad(log_a, ((0, 0), (0, Sp - S), (0, Rp - R)))
        b = jnp.pad(b, ((0, 0), (0, Sp - S), (0, Rp - R)))
        h0 = jnp.pad(h0, ((0, 0), (0, Rp - R)))

    out = pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=(B, nr, ns),
        in_specs=[
            pl.BlockSpec((1, chunk, r_block), lambda bi, ri, si: (bi, si, ri)),
            pl.BlockSpec((1, chunk, r_block), lambda bi, ri, si: (bi, si, ri)),
            pl.BlockSpec((1, 1, r_block), lambda bi, ri, si: (bi, 0, ri)),
        ],
        out_specs=pl.BlockSpec((1, chunk, r_block),
                               lambda bi, ri, si: (bi, si, ri)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, Rp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, r_block), jnp.float32)],
        interpret=interpret,
    )(log_a, b, h0[:, None, :])
    h = out[:, :S, :R]
    return h, h[:, -1]
