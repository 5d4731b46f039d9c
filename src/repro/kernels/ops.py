"""Public jit'd kernel ops with autodiff.

``flash_attention_fused``: Pallas forward and Pallas dq/dkv backward, what
the train step runs on the TPU. ``rglru`` and ``mlstm`` pair a Pallas
forward with a backward that recomputes through the jnp oracle.

Block sizes and ``interpret`` default to None and resolve through the
kernel find-db (``repro.kernels.findb``): tuned configs per (shape,
hardware) when present, its fallbacks otherwise, and interpret
auto-detected from the platform (compiled path on TPU, interpreted
elsewhere). Resolution happens in the public wrappers *before* the
``custom_vjp`` boundary so the backward passes see concrete block sizes.
Explicit arguments always win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import findb
from repro.kernels import flash_attention as fa_kernel
from repro.kernels import flash_attention_bwd as fa_bwd_kernel
from repro.kernels import mlstm as mlstm_kernel
from repro.kernels import rglru as rglru_kernel
from repro.kernels import ref


# the most bytes one block of q or kv rows may hold under the find-db's
# fallback: its blocks were timed at 256-byte rows (D=128, bf16), and the
# backward's 1024-row blocks overflow the 16 MiB scoped VMEM at 1024-byte
# rows (D=256, fp32)
FALLBACK_TILE_BYTES = 512 * 1024


def _resolve_blocks(kernel, q, k, causal, window, q_block, kv_block):
    """(q_block, kv_block) for ``kernel``: the find-db's entry for this
    (shape, hardware), or its fallback cut to ``FALLBACK_TILE_BYTES``,
    under the explicit arguments."""
    B, S, K, G, D = q.shape
    if q_block is None or kv_block is None:
        rows = FALLBACK_TILE_BYTES // (D * q.dtype.itemsize)
        tuned = findb.lookup_or_default(
            kernel, findb.attention_shape_key(
                B=B, S=S, K=K, G=G, D=D, T=k.shape[1],
                causal=causal, window=window),
            {name: min(b, rows) for name, b in findb.DEFAULTS[kernel].items()})
        q_block = tuned["q_block"] if q_block is None else q_block
        kv_block = tuned["kv_block"] if kv_block is None else kv_block
    return int(q_block), int(kv_block)


# ---------------------------------------------------------------- attention

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_fused(q, k, v, causal, window, fwd_blocks, bwd_blocks,
                           interpret):
    """Kernel forward AND kernel backward (dq/dk/dv Pallas kernels) —
    score blocks never touch HBM in either direction."""
    return _faf_fwd(q, k, v, causal, window, fwd_blocks, bwd_blocks,
                    interpret)[0]


def _faf_fwd(q, k, v, causal, window, fwd_blocks, bwd_blocks, interpret):
    out, lse = fa_kernel.flash_attention(
        q, k, v, causal=causal, window=window, q_block=fwd_blocks[0],
        kv_block=fwd_blocks[1], interpret=interpret, return_lse=True)
    return out, (q, k, v, out, lse)


def _faf_bwd(causal, window, fwd_blocks, bwd_blocks, interpret, res, g):
    q, k, v, out, lse = res
    return fa_bwd_kernel.flash_attention_bwd(
        q, k, v, out, lse, g, causal=causal, window=window,
        q_block=bwd_blocks[0], kv_block=bwd_blocks[1], interpret=interpret)


_flash_attention_fused.defvjp(_faf_fwd, _faf_bwd)


def flash_attention_fused(q, k, v, causal=True, window=None, q_block=None,
                          kv_block=None, interpret=None):
    """The forward's blocks resolve under ``flash_attention``, the two
    backward kernels' under ``flash_attention_bwd``; explicit blocks set
    both."""
    blocks = [_resolve_blocks(kernel, q, k, causal, window, q_block,
                              kv_block)
              for kernel in ("flash_attention", "flash_attention_bwd")]
    if interpret is None:
        interpret = findb.default_interpret()
    return _flash_attention_fused(q, k, v, causal, window, *blocks,
                                  bool(interpret))


# ------------------------------------------------------------------- rglru

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rglru(log_a, b, h0, chunk, r_block, interpret):
    h, h_last = rglru_kernel.rglru_scan(log_a, b, h0, chunk=chunk,
                                        r_block=r_block, interpret=interpret)
    return h, h_last


def _rglru_fwd(log_a, b, h0, chunk, r_block, interpret):
    out = _rglru(log_a, b, h0, chunk, r_block, interpret)
    return out, (log_a, b, h0)


def _rglru_bwd(chunk, r_block, interpret, res, g):
    log_a, b, h0 = res
    _, vjp = jax.vjp(lambda la, b, h0: ref.rglru_ref(la, b, h0),
                     log_a, b, h0)
    return vjp(g)


_rglru.defvjp(_rglru_fwd, _rglru_bwd)


def rglru(log_a, b, h0, chunk=None, r_block=None, interpret=None):
    B, S, R = log_a.shape
    if interpret is None:
        interpret = findb.default_interpret()
    if chunk is None or r_block is None:
        tuned = findb.lookup_or_default(
            "rglru", findb.rglru_shape_key(B=B, S=S, R=R))
        chunk = tuned["chunk"] if chunk is None else chunk
        r_block = tuned["r_block"] if r_block is None else r_block
    return _rglru(log_a, b, h0, int(chunk), int(r_block), bool(interpret))


# ------------------------------------------------------------------- mlstm

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _mlstm(q, k, v, i_gate, f_gate, chunk, interpret):
    return mlstm_kernel.mlstm_chunkwise(q, k, v, i_gate, f_gate, chunk=chunk,
                                        interpret=interpret)


def _mlstm_fwd(q, k, v, i_gate, f_gate, chunk, interpret):
    out = _mlstm(q, k, v, i_gate, f_gate, chunk, interpret)
    return out, (q, k, v, i_gate, f_gate)


def _mlstm_bwd(chunk, interpret, res, g):
    q, k, v, ig, fg = res
    _, vjp = jax.vjp(lambda q, k, v, ig, fg: ref.mlstm_ref(
        q, k, v, ig, fg, chunk=chunk)[0], q, k, v, ig, fg)
    return vjp(g)


_mlstm.defvjp(_mlstm_fwd, _mlstm_bwd)


def mlstm(q, k, v, i_gate, f_gate, chunk=None, interpret=None):
    B, S, H, D = q.shape
    if interpret is None:
        interpret = findb.default_interpret()
    if chunk is None:
        chunk = findb.lookup_or_default(
            "mlstm", findb.mlstm_shape_key(B=B, S=S, H=H, D=D))["chunk"]
    return _mlstm(q, k, v, i_gate, f_gate, int(chunk), bool(interpret))
