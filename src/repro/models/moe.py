"""Mixture-of-Experts FFN: top-k router + GShard-style einsum dispatch.

Two dispatch modes:

* ``dropless=True`` (the model default, see ``ModelConfig.moe_cfg``): expert
  capacity equals the token count, so no token is ever dropped. This is the
  only mode that keeps the FFN a *per-token* function — capacity overflow
  makes token A's keep/drop depend on how tokens before it routed, which
  leaks content across positions (breaking sliding-window receptive-field
  guarantees and parallel-forward/decode agreement).
* ``dropless=False``: finite capacity C = ceil(top_k*T*capacity_factor/E)
  with position-priority overflow drops (the residual carries dropped
  tokens). This is the training-efficiency approximation whose FLOP count
  matches *active* experts (top_k x capacity_factor), not E x dense; use it
  for throughput experiments, never where the receptive field matters.
  (Roofline/param accounting is analytic — ``analysis.roofline`` — and does
  not depend on which mode executes.)

Supports DeepSeek/Qwen-MoE shared experts (always-on dense branch).

Expert tensors are (E, d_model, d_ff); sharding rules live in
``repro.distributed.sharding``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                    # per-expert hidden dim
    n_experts: int
    top_k: int
    n_shared: int = 0            # always-active shared experts (qwen2-moe: 4)
    shared_d_ff: Optional[int] = None
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    dropless: bool = False       # capacity = T: exact per-token routing


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": layers.dense_init(ks[0], (d, E), dtype=jnp.float32),
        "w_gate": layers.dense_init(ks[1], (E, d, f), in_axis_size=d, dtype=dtype),
        "w_up": layers.dense_init(ks[2], (E, d, f), in_axis_size=d, dtype=dtype),
        "w_down": layers.dense_init(ks[3], (E, f, d), in_axis_size=f, dtype=dtype),
    }
    if cfg.n_shared:
        sf = cfg.shared_d_ff or cfg.d_ff * cfg.n_shared
        p["shared"] = layers.init_swiglu(ks[4], d, sf, dtype=dtype)
    return p


def _top_k_gating(logits, cfg: MoEConfig):
    """Returns (weights (T,k), indices (T,k), aux_loss). logits: (T, E) fp32."""
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    one_hot = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)  # (T,k,E)
    f_e = one_hot.sum(axis=(0, 1)) / (logits.shape[0] * cfg.top_k)
    p_e = probs.mean(axis=0)
    aux = cfg.n_experts * jnp.sum(f_e * p_e)
    return weights, idx, aux


@jax.named_scope("moe")
def apply_moe(params, x, cfg: MoEConfig, token_chunk: int = 8192):
    """x: (B, S, d) -> (B, S, d), aux_loss scalar.

    Dispatch: each token is routed to top_k experts. With ``cfg.dropless``
    capacity is T (top_k experts are distinct, so an expert receives at most
    T (token, choice) pairs — no overflow is possible and routing stays a
    per-token function). Otherwise experts have capacity
    C = ceil(top_k * S * capacity_factor / E) per batch row and overflow
    drops (residual connection carries the token through unchanged).

    Long sequences are routed in ``token_chunk`` segments (capacity per
    segment) — bounds the (B,E,C,d) dispatch buffers for 32k+ prefill.
    """
    B, S, d = x.shape
    if S > token_chunk and S % token_chunk == 0:
        nc = S // token_chunk
        xs = x.reshape(B, nc, token_chunk, d).swapaxes(0, 1)
        ys, auxs = jax.lax.map(
            lambda xc: apply_moe(params, xc, cfg, token_chunk), xs)
        return ys.swapaxes(0, 1).reshape(B, S, d), auxs.mean()
    E, k = cfg.n_experts, cfg.top_k
    T = S
    C = T if cfg.dropless else \
        max(1, int(-(-k * T * cfg.capacity_factor // E)))

    xf = x.reshape(B, T, d)
    logits = jnp.einsum("btd,de->bte", xf.astype(jnp.float32), params["router"])
    weights, idx, aux = jax.vmap(lambda l: _top_k_gating(l, cfg))(logits)

    # position of each (token, choice) within its expert's capacity buffer
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)            # (B,T,k,E)
    flat = onehot.reshape(B, T * k, E)
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat              # (B,T*k,E)
    pos = (pos_in_expert * flat).sum(-1).reshape(B, T, k)        # (B,T,k)
    keep = pos < C
    w = jnp.where(keep, weights, 0.0)

    e_onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)              # (B,T,k,E)
    c_onehot = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                              dtype=jnp.float32)[..., :C]             # (B,T,k,C)
    disp = jnp.einsum("btke,btkc->btec", e_onehot, c_onehot).astype(x.dtype)
    comb = jnp.einsum("btk,btke,btkc->btec", w.astype(jnp.float32),
                      e_onehot, c_onehot)

    xe = jnp.einsum("btd,btec->becd", xf, disp)                  # (B,E,C,d)
    g = jnp.einsum("becd,edf->becf", xe, params["w_gate"])
    u = jnp.einsum("becd,edf->becf", xe, params["w_up"])
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("becf,efd->becd", h, params["w_down"])       # (B,E,C,d)
    y = jnp.einsum("becd,btec->btd", ye.astype(jnp.float32), comb)

    if cfg.n_shared:
        y = y + layers.apply_swiglu(params["shared"], xf).astype(jnp.float32)
    return y.reshape(B, S, d).astype(x.dtype), cfg.router_aux_weight * aux.mean()
