"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory) and sLSTM.

mLSTM recurrence per head (head dim D):
    m_t = max(f~_t + m_{t-1}, i~_t)                     # stabilizer
    f'_t = exp(f~_t + m_{t-1} - m_t);  i'_t = exp(i~_t - m_t)
    C_t = f'_t C_{t-1} + i'_t v_t k_t^T                 # (D, D) matrix memory
    n_t = f'_t n_{t-1} + i'_t k_t
    h_t = C_t q_t / max(|n_t . q_t|, exp(-m_t))

Training path: chunkwise-parallel form (chunk size Q): intra-chunk quadratic
attention with decay matrix + inter-chunk recurrent state — this is also the
oracle for the Pallas kernel in ``repro.kernels.mlstm``.

sLSTM keeps scalar memory with recurrent gates -> strictly sequential
``lax.scan`` over time (O(1) HLO size).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers


@dataclasses.dataclass(frozen=True)
class MLSTMConfig:
    d_model: int
    n_heads: int
    proj_factor: float = 2.0
    conv_width: int = 4
    chunk: int = 256

    @property
    def d_inner(self):
        return int(self.d_model * self.proj_factor)

    @property
    def head_dim(self):
        return self.d_inner // self.n_heads


def init_mlstm(key, cfg: MLSTMConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 9)
    d, di, H, D = cfg.d_model, cfg.d_inner, cfg.n_heads, cfg.head_dim
    return {
        "w_up": layers.dense_init(ks[0], (d, di), dtype=dtype),
        "w_gate": layers.dense_init(ks[1], (d, di), dtype=dtype),
        "conv_w": layers.dense_init(ks[2], (cfg.conv_width, di),
                                    in_axis_size=cfg.conv_width, dtype=dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "wq": layers.dense_init(ks[3], (di, H, D), in_axis_size=di, dtype=dtype),
        "wk": layers.dense_init(ks[4], (di, H, D), in_axis_size=di, dtype=dtype),
        "wv": layers.dense_init(ks[5], (di, H, D), in_axis_size=di, dtype=dtype),
        "w_if": layers.dense_init(ks[6], (di, H, 2), in_axis_size=di,
                                  dtype=jnp.float32),
        "b_if": jnp.concatenate([jnp.zeros((cfg.n_heads, 1)),
                                 jnp.linspace(3.0, 6.0, cfg.n_heads)[:, None]],
                                axis=-1),
        "out_norm": layers.init_rmsnorm(D, dtype),
        "w_down": layers.dense_init(ks[7], (di, d), in_axis_size=di, dtype=dtype),
    }


def mlstm_parallel(q, k, v, i_gate, f_gate):
    """Stabilized quadratic parallel form for one chunk.

    q,k,v: (B, S, H, D); i_gate, f_gate: (B, S, H) pre-activations (fp32).
    Returns h: (B, S, H, D), plus per-chunk final state pieces
    (C_last (B,H,D,D), n_last (B,H,D), m_last (B,H)).
    """
    B, S, H, D = q.shape
    logf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))      # (B,S,H)
    F = jnp.cumsum(logf, axis=1)                                # prefix sums
    # decay from step t to s (s>=t): F_s - F_t ; log weight = F_s - F_t + i_t
    logw = F[:, :, None, :] - F[:, None, :, :] + i_gate.astype(jnp.float32)[:, None]
    causal = jnp.tril(jnp.ones((S, S), bool))
    logw = jnp.where(causal[None, :, :, None], logw, -jnp.inf)  # (B,s,t,H)
    m = jnp.max(logw, axis=2)                                   # (B,S,H) row max
    m = jnp.maximum(m, -1e30)
    w = jnp.exp(logw - m[:, :, None, :])                        # (B,s,t,H)
    scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum("bshd,bthd->bsth", q, k,
                        preferred_element_type=jnp.float32) * scale
    a = scores * w
    num = jnp.einsum("bsth,bthd->bshd", a, v.astype(jnp.float32))
    den = jnp.abs(jnp.einsum("bsth->bsh", a))
    h = num / jnp.maximum(den, jnp.exp(-m))[..., None]

    # final chunk state (for chunkwise composition)
    logf_tail = F[:, -1:, :] - F                                 # F_S - F_t
    wS = jnp.exp(logf_tail + i_gate.astype(jnp.float32)
                 - jnp.max(logf_tail + i_gate.astype(jnp.float32),
                           axis=1, keepdims=True))
    m_last = jnp.max(logf_tail + i_gate.astype(jnp.float32), axis=1)   # (B,H)
    C_last = jnp.einsum("bth,bthd,bthe->bhde", wS, v.astype(jnp.float32),
                        k.astype(jnp.float32) * scale)
    n_last = jnp.einsum("bth,bthd->bhd", wS, k.astype(jnp.float32) * scale)
    return h, (C_last, n_last, m_last)


def mlstm_chunkwise(q, k, v, i_gate, f_gate, chunk=256, state=None):
    """Chunkwise-parallel mLSTM over (B, S, H, D). Returns h, final state.

    state: optional (C (B,H,D,D), n (B,H,D), m (B,H)) fp32 carry.
    """
    B, S, H, D = q.shape
    Q = min(chunk, S)
    assert S % Q == 0, f"seq {S} not divisible by chunk {Q}"
    N = S // Q
    scale = 1.0 / math.sqrt(D)

    def split(x):
        return x.reshape(B, N, Q, *x.shape[2:]).swapaxes(0, 1)

    qs, ks_, vs = split(q), split(k), split(v)
    igs, fgs = split(i_gate.astype(jnp.float32)), split(f_gate.astype(jnp.float32))

    if state is None:
        C0 = jnp.zeros((B, H, D, D), jnp.float32)
        n0 = jnp.zeros((B, H, D), jnp.float32)
        m0 = jnp.full((B, H), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state

    def step(carry, xs):
        C, n, m = carry
        qc, kc, vc, ig, fg = xs
        logf = jax.nn.log_sigmoid(fg)                   # (B,Q,H)
        F = jnp.cumsum(logf, axis=1)
        Ftot = F[:, -1]                                 # (B,H)
        # --- inter-chunk: contribution of carried state to each position
        # weight for state at position s: exp(F_s + m)  (relative stabilizer)
        m_inter = F + m[:, None, :]                     # (B,Q,H)
        # --- intra-chunk quadratic part
        logw = F[:, :, None, :] - F[:, None, :, :] + ig[:, None]
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        logw = jnp.where(causal[None, :, :, None], logw, -jnp.inf)
        m_intra = jnp.max(logw, axis=2)                 # (B,Q,H)
        m_row = jnp.maximum(m_inter, m_intra)           # (B,Q,H) stabilizer
        w = jnp.exp(logw - m_row[:, :, None, :])
        s_qk = jnp.einsum("bshd,bthd->bsth", qc, kc,
                          preferred_element_type=jnp.float32) * scale
        a = s_qk * w
        num = jnp.einsum("bsth,bthd->bshd", a, vc.astype(jnp.float32))
        den = jnp.einsum("bsth->bsh", a)
        # inter-chunk contribution
        w_state = jnp.exp(m_inter - m_row)              # (B,Q,H)
        qf = qc.astype(jnp.float32)
        num = num + w_state[..., None] * jnp.einsum("bshe,bhde->bshd", qf, C)
        den = den + w_state * jnp.einsum("bshd,bhd->bsh", qf, n)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_row))[..., None]

        # --- state update
        m_new = jnp.maximum(Ftot + m, jnp.max(ig + Ftot[:, None] - F, axis=1))
        carry_w = jnp.exp(Ftot + m - m_new)             # (B,H)
        in_w = jnp.exp(ig + Ftot[:, None] - F - m_new[:, None])   # (B,Q,H)
        C_new = carry_w[:, :, None, None] * C + jnp.einsum(
            "bth,bthd,bthe->bhde", in_w, vc.astype(jnp.float32),
            kc.astype(jnp.float32) * scale)
        n_new = carry_w[:, :, None] * n + jnp.einsum(
            "bth,bthd->bhd", in_w, kc.astype(jnp.float32) * scale)
        return (C_new, n_new, m_new), h

    (C, n, m), hs = lax.scan(step, (C0, n0, m0), (qs, ks_, vs, igs, fgs))
    h = hs.swapaxes(0, 1).reshape(B, S, H, D)
    return h.astype(q.dtype), (C, n, m)


def mlstm_decode_step(q, k, v, i_gate, f_gate, state):
    """One-token recurrent step. q,k,v: (B,H,D); gates: (B,H)."""
    C, n, m = state
    D = q.shape[-1]
    scale = 1.0 / math.sqrt(D)
    logf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))
    ig = i_gate.astype(jnp.float32)
    m_new = jnp.maximum(logf + m, ig)
    fw = jnp.exp(logf + m - m_new)
    iw = jnp.exp(ig - m_new)
    kf = k.astype(jnp.float32) * scale
    C_new = fw[..., None, None] * C + iw[..., None, None] * (
        v.astype(jnp.float32)[..., :, None] * kf[..., None, :])
    n_new = fw[..., None] * n + iw[..., None] * kf
    qf = q.astype(jnp.float32)
    num = jnp.einsum("bhde,bhe->bhd", C_new, qf)
    den = jnp.einsum("bhd,bhd->bh", n_new, qf)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return h.astype(q.dtype), (C_new, n_new, m_new)


def _mlstm_qkv(params, u, cfg: MLSTMConfig):
    q = jnp.einsum("bsi,ihd->bshd", u, params["wq"])
    k = jnp.einsum("bsi,ihd->bshd", u, params["wk"])
    v = jnp.einsum("bsi,ihd->bshd", u, params["wv"])
    gates = jnp.einsum("bsi,ihg->bshg", u.astype(jnp.float32),
                       params["w_if"]) + params["b_if"]
    return q, k, v, gates[..., 0], gates[..., 1]


@jax.named_scope("recurrence")
def apply_mlstm(params, x, cfg: MLSTMConfig):
    """Full-sequence mLSTM block. x: (B, S, d)."""
    B, S, d = x.shape
    u = jnp.einsum("bsd,di->bsi", x, params["w_up"])
    gate = jnp.einsum("bsd,di->bsi", x, params["w_gate"])
    u, _ = _conv(params, u, cfg)
    u = jax.nn.silu(u)
    q, k, v, ig, fg = _mlstm_qkv(params, u, cfg)
    h, _ = mlstm_chunkwise(q, k, v, ig, fg, chunk=min(cfg.chunk, S))
    h = layers.rmsnorm(params["out_norm"], h)
    h = h.reshape(B, S, cfg.d_inner)
    return jnp.einsum("bsi,id->bsd", h * jax.nn.silu(gate), params["w_down"])


@jax.named_scope("recurrence")
def apply_mlstm_decode(params, x, cfg: MLSTMConfig, state):
    """x: (B,1,d); state {"C","n","m","conv"}."""
    u = jnp.einsum("bsd,di->bsi", x, params["w_up"])
    gate = jnp.einsum("bsd,di->bsi", x, params["w_gate"])
    u, conv_state = _conv(params, u, cfg, state["conv"])
    u = jax.nn.silu(u)
    q, k, v, ig, fg = _mlstm_qkv(params, u, cfg)
    h, (C, n, m) = mlstm_decode_step(q[:, 0], k[:, 0], v[:, 0], ig[:, 0],
                                     fg[:, 0], (state["C"], state["n"], state["m"]))
    h = layers.rmsnorm(params["out_norm"], h)[:, None]
    h = h.reshape(x.shape[0], 1, cfg.d_inner)
    out = jnp.einsum("bsi,id->bsd", h * jax.nn.silu(gate), params["w_down"])
    return out, {"C": C, "n": n, "m": m,
                 "conv": conv_state.astype(state["conv"].dtype)}


def _conv(params, u, cfg, conv_state=None):
    w = params["conv_w"].astype(jnp.float32)
    if conv_state is None:
        pad = jnp.zeros((u.shape[0], cfg.conv_width - 1, u.shape[2]), u.dtype)
    else:
        pad = conv_state.astype(u.dtype)
    up = jnp.concatenate([pad, u], axis=1).astype(jnp.float32)
    out = sum(w[i] * lax.dynamic_slice_in_dim(up, i, u.shape[1], axis=1)
              for i in range(cfg.conv_width))
    return (out + params["conv_b"].astype(jnp.float32)).astype(u.dtype), \
        up[:, -(cfg.conv_width - 1):]


def init_mlstm_state(cfg: MLSTMConfig, batch: int, dtype=jnp.bfloat16):
    H, D = cfg.n_heads, cfg.head_dim
    return {"C": jnp.zeros((batch, H, D, D), jnp.float32),
            "n": jnp.zeros((batch, H, D), jnp.float32),
            "m": jnp.full((batch, H), -1e30, jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, cfg.d_inner), dtype)}


# ---------------------------------------------------------------------------
# sLSTM (scalar memory, recurrent gates -> sequential scan)
# ---------------------------------------------------------------------------

def init_slstm(key, cfg: MLSTMConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    d, di = cfg.d_model, cfg.d_inner
    return {
        "w_in": layers.dense_init(ks[0], (d, 4 * di), dtype=dtype),
        "w_rec": layers.dense_init(ks[1], (di, 4 * di), dtype=dtype),
        "b": jnp.zeros((4 * di,), dtype),
        "out_norm": layers.init_rmsnorm(di, dtype),
        "w_down": layers.dense_init(ks[2], (di, d), in_axis_size=di, dtype=dtype),
    }


@jax.named_scope("recurrence")
def apply_slstm(params, x, cfg: MLSTMConfig, state=None):
    """Sequential sLSTM with exponential gating. x: (B, S, d)."""
    B, S, d = x.shape
    di = cfg.d_inner
    zx = jnp.einsum("bsd,dk->bsk", x, params["w_in"]) + params["b"]
    if state is None:
        state = init_slstm_state(cfg, B)

    def step(carry, z_t):
        c, n, h, m = carry
        z = z_t + jnp.einsum("bi,ik->bk", h.astype(z_t.dtype), params["w_rec"])
        zi, zf, zz, zo = jnp.split(z.astype(jnp.float32), 4, axis=-1)
        m_new = jnp.maximum(jax.nn.log_sigmoid(zf) + m, zi)
        i = jnp.exp(zi - m_new)
        f = jnp.exp(jax.nn.log_sigmoid(zf) + m - m_new)
        c_new = f * c + i * jnp.tanh(zz)
        n_new = f * n + i
        h_new = jax.nn.sigmoid(zo) * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, h_new, m_new), h_new

    init_carry = (state["c"], state["n"], state["h"], state["m"])
    carry, hs = lax.scan(step, init_carry, zx.swapaxes(0, 1))
    hs = hs.swapaxes(0, 1).astype(x.dtype)              # (B, S, di)
    hs = layers.rmsnorm(params["out_norm"], hs)
    out = jnp.einsum("bsi,id->bsd", hs, params["w_down"])
    new_state = dict(zip(("c", "n", "h", "m"), carry))
    return out, new_state


def init_slstm_state(cfg: MLSTMConfig, batch: int):
    di = cfg.d_inner
    z = jnp.zeros((batch, di), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.full((batch, di), -30.0, jnp.float32)}
