"""Shared transformer layers: norms, RoPE, GQA attention, MLPs.

Conventions
-----------
* Params are plain nested dicts of jnp arrays (pytrees); ``init_*`` builds them,
  ``apply_*``/functional ops consume them.
* Attention uses the *grouped* layout so the kv-head axis is a first-class,
  shardable dimension:  q: (B, S, K, G, D)   k/v: (B, T, K, D)
  where K = n_kv_heads, G = n_heads // n_kv_heads, D = head_dim.
* Long sequences route through ``chunked_attention`` — an online-softmax
  (flash-style) pure-jnp implementation that is also the oracle for the Pallas
  kernel in ``repro.kernels.flash_attention``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

Params = Any

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size=None, dtype=jnp.float32):
    """Truncated-normal fan-in init (LeCun-ish), matching common LM practice."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / math.sqrt(max(1, fan_in))
    return std * jax.random.truncated_normal(key, -3.0, 3.0, shape, dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * 0.02


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(dim, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


@jax.named_scope("norm")
def rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


def init_layernorm(dim, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


@jax.named_scope("norm")
def layernorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (B, S, *H, D); positions (B, S) (2-D required).

    Pairs adjacent elements (2i, 2i+1) via a divisible reshape — strided
    slicing (0::2) would defeat GSPMD when the head_dim axis is model-sharded;
    reshape (..., D) -> (..., D/2, 2) keeps the sharded D/2 axis expressible.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    pos = positions.astype(jnp.float32)
    angles = pos[..., None] * freqs                    # (B, S, D/2)
    while angles.ndim < x.ndim:                        # broadcast over head axes
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = xr[..., 0], xr[..., 1]
    y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (reference path; Pallas kernel mirrors this math)
# ---------------------------------------------------------------------------

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

_U = jax.sharding.PartitionSpec.UNCONSTRAINED


def shard_batch(x, batch_axes):
    """Pin dim 0 (batch) to the data axes, everything else unconstrained.

    Without this, FSDP-style (d_in -> 'data') weight sharding can make GSPMD
    resolve the batch-vs-contraction axis conflict by REPLICATING the batch —
    10x the flops. Pinning the batch forces the intended ZeRO-3 resolution
    (all-gather the weights instead).
    """
    if not batch_axes:
        return x
    spec = [_U] * x.ndim
    spec[0] = tuple(batch_axes) if len(batch_axes) > 1 else batch_axes[0]
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


def shard_heads(x, enabled: bool, axis: int = 2):
    """Constrain the kv-head axis to the 'model' mesh axis (padded if uneven).

    Head-sharded attention keeps softmax/score math device-local — the
    alternative (head_dim-sharded projections) all-reduces every score tensor.
    Only active when a mesh is in scope and ``enabled`` (sys.shard_attn).
    """
    if not enabled:
        return x
    spec = [_U] * x.ndim
    spec[axis] = "model"
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


def _mask_bias(q_pos, k_pos, causal: bool, window: Optional[int]):
    """Additive bias (Sq, Sk) in fp32: 0 where visible, NEG_INF elsewhere."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


@jax.named_scope("attn_core")
def attention(q, k, v, *, causal=True, window=None, q_offset=0,
              kv_mask=None, softmax_scale=None):
    """Direct (materialized-scores) GQA attention.

    q: (B, Sq, K, G, D)  k, v: (B, Sk, K, D)  ->  (B, Sq, K, G, D)
    kv_mask: optional (B, Sk) bool validity mask (decode caches).
    """
    B, Sq, K, G, D = q.shape
    Sk = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(Sk)
    bias = _mask_bias(q_pos, k_pos, causal, window)
    scores = scores + bias
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


@jax.named_scope("attn_core")
def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      kv_mask=None, q_chunk=1024, kv_chunk=1024,
                      softmax_scale=None):
    """Online-softmax attention; memory O(q_chunk * kv_chunk) per step.

    Mirrors the FlashAttention recurrence; ``repro.kernels.flash_attention.ref``
    delegates here, making this the single oracle for the Pallas kernel.
    """
    B, Sq, K, G, D = q.shape
    Sk = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq, nk = -(-Sq // q_chunk), -(-Sk // kv_chunk)
    pad_q, pad_k = nq * q_chunk - Sq, nk * kv_chunk - Sk

    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kmask = jnp.ones((B, Sk), bool) if kv_mask is None else kv_mask
    kmask = jnp.pad(kmask, ((0, 0), (0, pad_k)))

    qp = qp.reshape(B, nq, q_chunk, K, G, D)
    kp = kp.reshape(B, nk, kv_chunk, K, D)
    vp = vp.reshape(B, nk, kv_chunk, K, D)
    kmask = kmask.reshape(B, nk, kv_chunk)

    def q_step(qi):
        qc = qp[:, qi]                                   # (B, qc, K, G, D)
        q_pos = qi * q_chunk + jnp.arange(q_chunk) + q_offset

        def kv_step(carry, ki):
            acc, m, l = carry
            kc, vc, mc = kp[:, ki], vp[:, ki], kmask[:, ki]
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bskgd,btkd->bkgst", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            ok = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                ok &= k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                ok &= k_pos[None, :] > (q_pos[:, None] - window)
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            s = jnp.where(mc[:, None, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, K, G, q_chunk, D), jnp.float32)
        m0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
        (acc, m, l), _ = lax.scan(kv_step, (acc0, m0, l0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.transpose(out, (0, 3, 1, 2, 4))       # (B, qc, K, G, D)

    outs = lax.map(q_step, jnp.arange(nq))               # (nq, B, qc, K, G, D)
    out = jnp.transpose(outs, (1, 0, 2, 3, 4, 5)).reshape(B, nq * q_chunk, K, G, D)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None         # sliding-window size; None = full
    causal: bool = True

    @property
    def groups(self):
        return self.n_heads // self.n_kv_heads


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    K, G, D, d = cfg.n_kv_heads, cfg.groups, cfg.head_dim, cfg.d_model
    p = {
        "wq": dense_init(ks[0], (d, K, G, D), in_axis_size=d, dtype=dtype),
        "wk": dense_init(ks[1], (d, K, D), in_axis_size=d, dtype=dtype),
        "wv": dense_init(ks[2], (d, K, D), in_axis_size=d, dtype=dtype),
        "wo": dense_init(ks[3], (K, G, D, d), in_axis_size=K * G * D, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((K, G, D), dtype)
        p["bk"] = jnp.zeros((K, D), dtype)
        p["bv"] = jnp.zeros((K, D), dtype)
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(D, dtype)
        p["k_norm"] = init_rmsnorm(D, dtype)
    return p


@jax.named_scope("attn_proj")
def attention_qkv(params, x, cfg: AttnConfig, positions):
    """Project to grouped q, k, v and apply qk-norm + RoPE."""
    q = jnp.einsum("bsd,dkgh->bskgh", x, params["wq"])
    k = jnp.einsum("bsd,dkh->bskh", x, params["wk"])
    v = jnp.einsum("bsd,dkh->bskh", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def apply_attention(params, x, cfg: AttnConfig, *, positions=None,
                    chunked_threshold=2048, q_chunk=1024, kv_chunk=1024):
    """Full-sequence (train / prefill) attention block. x: (B, S, d)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
    q, k, v = attention_qkv(params, x, cfg, positions)
    if S > chunked_threshold:
        out = chunked_attention(q, k, v, causal=cfg.causal, window=cfg.window,
                                q_chunk=q_chunk, kv_chunk=kv_chunk)
    else:
        out = attention(q, k, v, causal=cfg.causal, window=cfg.window)
    return jnp.einsum("bskgh,kghd->bsd", out, params["wo"])


def apply_attention_decode(params, x, cfg: AttnConfig, cache, pos):
    """Single-token decode with a (possibly ring-buffered) KV cache.

    x: (B, 1, d);  cache: {"k": (B, W, K, D), "v": ..., } ; pos: () int32 —
    number of tokens already in context. Returns (out, new_cache).
    """
    B, S, _ = x.shape
    assert S == 1
    W = cache["k"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = attention_qkv(params, x, cfg, positions)
    slot = pos % W                                        # ring buffer for SWA
    quant = "k_scale" in cache
    new_cache = {}
    if quant:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ck = lax.dynamic_update_slice(cache["k"], kq, (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], vq, (0, slot, 0, 0))
        new_cache["k_scale"] = lax.dynamic_update_slice(
            cache["k_scale"], ks, (0, slot, 0))
        new_cache["v_scale"] = lax.dynamic_update_slice(
            cache["v_scale"], vs, (0, slot, 0))
    else:
        ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    new_cache["k"], new_cache["v"] = ck, cv
    if quant:
        ck = dequantize_kv(ck, new_cache["k_scale"])
        cv = dequantize_kv(cv, new_cache["v_scale"])
    # validity + causality via explicit per-slot positions
    idx = jnp.arange(W)
    slot_pos = jnp.where(idx <= slot, pos - slot + idx, pos - slot - W + idx)
    valid = slot_pos >= 0
    if cfg.window is not None:
        valid &= slot_pos > (pos - cfg.window)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum("bskgh,btkh->bkgst", q, ck,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", p, cv.astype(x.dtype),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = jnp.einsum("bskgh,kghd->bsd", out, params["wo"])
    return out, new_cache


def init_kv_cache(cfg: AttnConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16, quant: bool = False):
    W = max_len if cfg.window is None else min(cfg.window, max_len)
    shape = (batch, W, cfg.n_kv_heads, cfg.head_dim)
    if quant:
        # int8 KV with per-(token, head) scales: halves the decode-time
        # cache sweep (the dominant roofline term for decode cells)
        return {"k": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], jnp.bfloat16),
                "v": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.zeros(shape[:-1], jnp.bfloat16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def quantize_kv(x):
    """(..., D) -> (int8 values, per-row bf16 scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q, scale):
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model, d_ff, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
        "w_up": dense_init(ks[1], (d_model, d_ff), dtype=dtype),
        "w_down": dense_init(ks[2], (d_ff, d_model), in_axis_size=d_ff, dtype=dtype),
    }


@jax.named_scope("mlp")
def apply_swiglu(params, x):
    g = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    h = jax.nn.silu(g) * u
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"])


def init_mlp(key, d_model, d_ff, act="gelu", dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "w_up": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": dense_init(ks[1], (d_ff, d_model), in_axis_size=d_ff, dtype=dtype),
        "b_down": jnp.zeros((d_model,), dtype),
    }


@jax.named_scope("mlp")
def apply_mlp(params, x, act="gelu"):
    h = jnp.einsum("bsd,df->bsf", x, params["w_up"]) + params["b_up"]
    h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"]) + params["b_down"]
