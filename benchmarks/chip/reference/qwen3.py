"""Plain float32 reference of a Qwen3 dense decoder and its AdamW steps.

Follows the published Qwen3 description (Qwen/Qwen3-0.6B ``config.json``
and the Hugging Face ``Qwen3`` modelling code) in ``jax.numpy`` at float32
with every matmul at ``Precision.HIGHEST``, and imports nothing of the
program. Per layer: RMSNorm, grouped-query attention with RMSNorm on each
head's queries and keys before RoPE, causal softmax at scale
``1/sqrt(head_dim)``, output projection, residual; RMSNorm, SwiGLU MLP,
residual. Then a final RMSNorm and the tied output head, and the mean
next-token cross entropy.

One departure, the program's convention and no change of the function
class: RoPE rotates adjacent channel pairs ``(2i, 2i+1)`` of a head, where
the Hugging Face code rotates ``(i, i + D/2)``. The two are the same model
up to a fixed permutation of each head's query and key channels. The
table has ``vocab`` rows, and the softmax of the loss runs over those
``vocab`` logits alone.

To fit one chip at the timed sizes the reference rematerialises each layer,
computes attention in blocks of queries and the loss in blocks of rows.

``mode="fp8"`` is the control: the same computation with both operands of
every matmul rounded to float8 e4m3 with one scale per tensor, the step
below the configuration's bfloat16. Gradients pass the rounding straight
through.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import lm

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0                          # largest finite float8_e4m3fn


def _fp8(x):
    scale = lax.stop_gradient(jnp.max(jnp.abs(x)) / FP8_MAX + 1e-30)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def _mm(mode):
    def mm(spec, a, b):
        if mode == "fp8":
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return mm


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x: (B, S, ..., D); rotates channel pairs (2i, 2i+1) by pos * f_i."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs   # (S, D/2)
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (D // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr = x.reshape(x.shape[:-1] + (D // 2, 2))
    x1, x2 = xr[..., 0], xr[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, mm, q_block):
    """Causal GQA. q: (B, S, K, G, D); k, v: (B, S, K, D)."""
    B, S, K, G, D = q.shape
    nb = S // q_block
    qb = q.reshape(B, nb, q_block, K, G, D).transpose(1, 0, 2, 3, 4, 5)
    k_pos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = mm("bqkgd,btkd->bkgqt", qi, k) / math.sqrt(D)
        q_pos = i * q_block + jnp.arange(q_block)
        s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bkgqt,btkd->bqkgd", p, v)

    out = lax.map(block, (jnp.arange(nb), qb))          # (nb, B, qb, K, G, D)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, K, G, D)


def loss_fn(params, tokens, labels, model: dict, mode: str = "float32",
            q_block: int = 256, row_block: int = 512):
    mm = _mm(mode)
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        a = p["attn"]
        h = _rmsnorm(x, p["attn_norm"]["scale"], eps)
        q = mm("bsd,dkgh->bskgh", h, a["wq"])
        k = mm("bsd,dkh->bskh", h, a["wk"])
        v = mm("bsd,dkh->bskh", h, a["wv"])
        if model["qk_norm"]:
            q = _rmsnorm(q, a["q_norm"]["scale"], eps)
            k = _rmsnorm(k, a["k_norm"]["scale"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        o = _attention(q, k, v, mm, min(q_block, q.shape[1]))
        x = x + mm("bskgh,kghd->bsd", o, a["wo"])
        h = _rmsnorm(x, p["mlp_norm"]["scale"], eps)
        m = p["mlp"]
        g = jax.nn.silu(mm("bsd,df->bsf", h, m["w_gate"])) \
            * mm("bsd,df->bsf", h, m["w_up"])
        return x + mm("bsf,fd->bsd", g, m["w_down"]), None

    x, _ = lax.scan(layer, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    head = params["embed"] if model["tie_embeddings"] else params["lm_head"].T
    B, S, d = x.shape
    rows = min(row_block, B * S)
    xs = x.reshape(B * S // rows, rows, d)
    ls = labels.reshape(B * S // rows, rows)

    @jax.checkpoint
    def nll(args):
        xr, lr = args
        logits = mm("rd,vd->rv", xr, head)
        gold = jnp.take_along_axis(logits, lr[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    return jnp.sum(lax.map(nll, (xs, ls))) / (B * S)


def _lr_at(opt: dict, step):
    """Learning rate of 0-based ``step``: linear warm-up from 0 over
    ``warmup_steps``, then cosine decay to ``final_frac`` of ``lr`` at
    ``total_steps`` (traceable)."""
    lr, warm = opt["lr"], opt["warmup_steps"]
    t = jnp.minimum((step - warm) / max(1, opt["total_steps"] - warm), 1.0)
    cos = lr * (opt["final_frac"] + (1 - opt["final_frac"])
                * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(step < warm, lr * jnp.minimum(1.0, step / max(1, warm)),
                     cos).astype(jnp.float32)


def _adamw(params, grads, m, v, step, opt: dict):
    """One AdamW step as the configuration states it: gradients clipped to
    a global norm, bias-corrected moments, decoupled weight decay on every
    weight. Returns the new weights and moments and the clipping factor."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["beta1"], opt["beta2"]
    t = step.astype(jnp.float32) + 1.0
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * clip * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(clip * g),
                     v, grads)
    lr = _lr_at(opt, step)

    def upd(p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
        return p - lr * (u + opt["weight_decay"] * p)
    return jax.tree.map(upd, params, m, v), m, v, clip


def train_readings(model: dict, opt: dict, seed: int, batches,
                   mode: str = "float32", batch_rows: int = None) -> dict:
    """Train ``len(batches)`` steps from the seed's weights; returns each
    step's loss, the norms of the first step's clipped gradient and of the
    weights' change after the last step, per ``lm.leaf_names`` leaf.

    ``batch_rows`` keeps only the first rows of every batch (a fault that
    leaves part of the batch out)."""
    key = lm.seed_key(seed)
    init = jax.jit(lambda key: lm.init_params(model, key))

    @jax.jit
    def delta_norms(params, key):
        p0 = lm.init_params(model, key)
        return lm.leaf_norms(model, jax.tree.map(jnp.subtract, params, p0))

    @jax.jit
    def zeros(params):
        return jax.tree.map(jnp.zeros_like, params)

    def step_fn(params, m, v, step, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels,
                                                  model, mode)
        gn = lm.leaf_norms(model, grads)
        params, m, v, clip = _adamw(params, grads, m, v, step, opt)
        return params, m, v, loss, clip * gn

    step_fn = jax.jit(step_fn, donate_argnums=(0, 1, 2))
    params = init(key)
    m, v = zeros(params), zeros(params)
    losses, gnorms = [], None
    for i, (tokens, labels) in enumerate(batches):
        if batch_rows is not None:
            tokens, labels = tokens[:batch_rows], labels[:batch_rows]
        params, m, v, loss, gn = step_fn(params, m, v, jnp.int32(i),
                                         jnp.asarray(tokens),
                                         jnp.asarray(labels))
        losses.append(float(loss))
        if gnorms is None:
            gnorms = jax.device_get(gn)
    del m, v
    dn = jax.device_get(delta_norms(params, key))
    del params
    return {"losses": losses, "grad_norms": gnorms, "delta_norms": dn}
