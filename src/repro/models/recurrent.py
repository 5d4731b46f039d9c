"""Griffin/RecurrentGemma-style recurrent block: temporal conv + RG-LRU.

RG-LRU recurrence (arXiv:2402.19427):
    r_t = sigmoid(W_a x_t + b_a)                      # recurrence gate
    i_t = sigmoid(W_x x_t + b_x)                      # input gate
    log a_t = -c * softplus(Lambda) * r_t             # c = 8
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Training path uses ``jax.lax.associative_scan`` (log-depth, parallel);
decode path is a single fused step carrying (h, conv_state).
The Pallas kernel in ``repro.kernels.rglru`` implements the chunked scan;
``rglru_scan`` here is its oracle.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers

RG_LRU_C = 8.0
CONV_WIDTH = 4


@dataclasses.dataclass(frozen=True)
class RecurrentConfig:
    d_model: int
    d_rnn: int


def init_recurrent(key, cfg: RecurrentConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 7)
    d, r = cfg.d_model, cfg.d_rnn
    return {
        "w_in_x": layers.dense_init(ks[0], (d, r), dtype=dtype),
        "w_in_gate": layers.dense_init(ks[1], (d, r), dtype=dtype),
        "conv_w": layers.dense_init(ks[2], (CONV_WIDTH, r), in_axis_size=CONV_WIDTH,
                                    dtype=dtype),
        "conv_b": jnp.zeros((r,), dtype),
        "w_a": layers.dense_init(ks[3], (r, r), dtype=dtype),
        "b_a": jnp.zeros((r,), dtype),
        "w_x": layers.dense_init(ks[4], (r, r), dtype=dtype),
        "b_x": jnp.zeros((r,), dtype),
        # Lambda init so that a ~ U[0.9, 0.999] at r=1 (paper appendix)
        "Lambda": jax.random.uniform(ks[5], (r,), jnp.float32, 2.0, 6.0),
        "w_out": layers.dense_init(ks[6], (r, d), in_axis_size=r, dtype=dtype),
    }


def _gates(params, x):
    """x: (..., r) post-conv activations -> (log_a, gated_input) in fp32."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ params["w_a"].astype(jnp.float32)
                       + params["b_a"].astype(jnp.float32))
    i = jax.nn.sigmoid(xf @ params["w_x"].astype(jnp.float32)
                       + params["b_x"].astype(jnp.float32))
    log_a = -RG_LRU_C * jax.nn.softplus(params["Lambda"]) * r
    a2 = jnp.exp(2.0 * log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-12)) * (i * xf)
    return log_a, b


def rglru_scan(params, x, h0=None):
    """Parallel linear-recurrence scan. x: (B, S, r) -> (B, S, r), h_last."""
    B, S, R = x.shape
    log_a, b = _gates(params, x)
    if h0 is not None:
        # fold initial state into the first step: h_1 = a_1 h_0 + b_1
        b = b.at[:, 0].add(jnp.exp(log_a[:, 0]) * h0.astype(jnp.float32))

    def combine(c1, c2):
        (la1, b1), (la2, b2) = c1, c2
        return la1 + la2, jnp.exp(la2) * b1 + b2

    la, h = lax.associative_scan(combine, (log_a, b), axis=1)
    return h.astype(x.dtype), h[:, -1]


def rglru_step(params, x_t, h_prev):
    """Single decode step. x_t: (B, r), h_prev: (B, r) fp32."""
    log_a, b = _gates(params, x_t)
    h = jnp.exp(log_a) * h_prev + b
    return h.astype(x_t.dtype), h


def _causal_conv(params, x, conv_state=None):
    """Depthwise width-4 causal conv. x: (B, S, r)."""
    w = params["conv_w"].astype(jnp.float32)           # (W, r)
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], CONV_WIDTH - 1, x.shape[2]), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)               # (B, W-1, r)
    xp = jnp.concatenate([pad, x], axis=1).astype(jnp.float32)
    out = sum(w[i] * lax.dynamic_slice_in_dim(xp, i, x.shape[1], axis=1)
              for i in range(CONV_WIDTH))
    new_state = xp[:, -(CONV_WIDTH - 1):]
    return (out + params["conv_b"].astype(jnp.float32)).astype(x.dtype), new_state


@jax.named_scope("recurrence")
def apply_recurrent(params, x, cfg: RecurrentConfig):
    """Full-sequence recurrent block. x: (B, S, d) -> (B, S, d)."""
    gate = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", x, params["w_in_gate"]))
    u = jnp.einsum("bsd,dr->bsr", x, params["w_in_x"])
    u, _ = _causal_conv(params, u)
    h, _ = rglru_scan(params, u)
    return jnp.einsum("bsr,rd->bsd", h * gate, params["w_out"])


@jax.named_scope("recurrence")
def apply_recurrent_decode(params, x, cfg: RecurrentConfig, state):
    """x: (B, 1, d); state: {"h": (B,r) f32, "conv": (B, W-1, r)}."""
    gate = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", x, params["w_in_gate"]))
    u = jnp.einsum("bsd,dr->bsr", x, params["w_in_x"])
    u, conv_state = _causal_conv(params, u, state["conv"])
    h_t, h_new = rglru_step(params, u[:, 0], state["h"])
    out = jnp.einsum("bsr,rd->bsd", h_t[:, None] * gate, params["w_out"])
    return out, {"h": h_new, "conv": conv_state.astype(state["conv"].dtype)}


def init_recurrent_state(cfg: RecurrentConfig, batch: int, dtype=jnp.bfloat16):
    return {"h": jnp.zeros((batch, cfg.d_rnn), jnp.float32),
            "conv": jnp.zeros((batch, CONV_WIDTH - 1, cfg.d_rnn), dtype)}
