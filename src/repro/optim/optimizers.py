"""Minimal optax-style optimizers (optax is not installed offline).

An ``Optimizer`` is (init, update):
    state = opt.init(params)
    updates, state = opt.update(grads, state, params, step)
    params = apply_updates(params, updates)

Learning rate enters through a schedule ``step -> lr`` so one compiled
train_step serves every trial of an HPT job (lr is a traced scalar, not a
Python constant — switching lr between trials does NOT recompile, which is
part of what makes PipeTune's pipelined tuning cheap).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable
    update: Callable   # (grads, state, params, step, lr_scale) -> (updates, state)


def _zeros_like(params):
    return jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)


def constant_schedule(lr):
    return lambda step: jnp.float32(lr)


def cosine_schedule(lr, total_steps, final_frac=0.1):
    def f(step):
        t = jnp.minimum(step / max(1, total_steps), 1.0)
        return jnp.float32(lr) * (final_frac + (1 - final_frac)
                                  * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    return f


def warmup_cosine(lr, warmup_steps, total_steps, final_frac=0.1):
    cos = cosine_schedule(lr, max(1, total_steps - warmup_steps), final_frac)

    def f(step):
        warm = jnp.float32(lr) * jnp.minimum(1.0, step / max(1, warmup_steps))
        return jnp.where(step < warmup_steps, warm, cos(step - warmup_steps))
    return f


def clip_by_global_norm(grads, max_norm):
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), gnorm


def adamw(schedule, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.0,
          clip_norm: Optional[float] = 1.0):
    schedule = schedule if callable(schedule) else constant_schedule(schedule)

    def init(params):
        return {"m": _zeros_like(params), "v": _zeros_like(params)}

    @jax.named_scope("optimizer")
    def update(grads, state, params, step, lr_scale=1.0):
        if clip_norm is not None:
            grads, _ = clip_by_global_norm(grads, clip_norm)
        lr = schedule(step) * lr_scale
        t = step.astype(jnp.float32) + 1.0
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                         state["m"], grads)
        v = jax.tree.map(lambda v, g: b2 * v
                         + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                         state["v"], grads)
        mhat_scale = 1.0 / (1.0 - b1 ** t)
        vhat_scale = 1.0 / (1.0 - b2 ** t)

        def upd(p, m, v):
            u = (m * mhat_scale) / (jnp.sqrt(v * vhat_scale) + eps)
            if weight_decay:
                u = u + weight_decay * p.astype(jnp.float32)
            return (-lr * u).astype(p.dtype)
        updates = jax.tree.map(upd, params, m, v)
        return updates, {"m": m, "v": v}

    return Optimizer(init, update)


def sgd(schedule, momentum=0.9, nesterov=False,
        clip_norm: Optional[float] = None):
    schedule = schedule if callable(schedule) else constant_schedule(schedule)

    def init(params):
        return {"mu": _zeros_like(params)}

    @jax.named_scope("optimizer")
    def update(grads, state, params, step, lr_scale=1.0):
        if clip_norm is not None:
            grads, _ = clip_by_global_norm(grads, clip_norm)
        lr = schedule(step) * lr_scale
        mu = jax.tree.map(lambda mu, g: momentum * mu + g.astype(jnp.float32),
                          state["mu"], grads)
        if nesterov:
            upd = jax.tree.map(lambda g, mu: g.astype(jnp.float32)
                               + momentum * mu, grads, mu)
        else:
            upd = mu
        updates = jax.tree.map(lambda p, u: (-lr * u).astype(p.dtype),
                               params, upd)
        return updates, {"mu": mu}

    return Optimizer(init, update)


@jax.named_scope("optimizer")
def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)
