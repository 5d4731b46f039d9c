"""Step builders: train_step / prefill_step / decode_step per (arch, shape).

Everything here is mesh-agnostic jittable code; shardings enter only through
the ShapeDtypeStruct specs built by ``input_specs`` / ``abstract_state`` (for
AOT dry-runs) or through real device arrays (for execution).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs import ShapeSpec
from repro.distributed import sharding
from repro.models import encdec, transformer
from repro.models.transformer import ModelConfig, SystemConfig
from repro.optim import optimizers


def is_encdec(cfg) -> bool:
    return isinstance(cfg, encdec.EncDecConfig)


def model_loss(params, batch, cfg, sys):
    if is_encdec(cfg):
        return encdec.loss_fn(params, batch, cfg, sys)
    return transformer.loss_fn(params, batch, cfg, sys)


def model_init(key, cfg):
    if is_encdec(cfg):
        return encdec.init(key, cfg)
    return transformer.init(key, cfg)


# ---------------------------------------------------------------------------
# train state
# ---------------------------------------------------------------------------

def make_train_state(key, cfg, opt: optimizers.Optimizer):
    params = model_init(key, cfg)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_state(cfg, opt: optimizers.Optimizer):
    return jax.eval_shape(
        lambda: make_train_state(jax.random.PRNGKey(0), cfg, opt))


def default_sys(cfg, shape: ShapeSpec, *, dp=16, tp=16, pods=1) -> SystemConfig:
    """Baseline system config for a dry-run cell (hillclimbed in §Perf)."""
    dp_total = dp * pods
    micro = max(1, shape.global_batch // dp_total) if shape.kind == "train" else 1
    # memory-min default: recompute inside blocks (hillclimbed per-cell in
    # EXPERIMENTS.md §Perf — the compute/memory trade is a system parameter).
    remat = "block" if shape.kind == "train" else "none"
    baxes = ("pod", "data") if pods > 1 else ("data",)
    return SystemConfig(dp=dp, tp=tp, pods=pods, microbatches=micro,
                        remat=remat, precision="bf16", shard_attn=True,
                        batch_axes=baxes)


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------

@jax.named_scope("grad_accum")
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.named_scope("grad_accum")
def _tree_scale(a, s):
    return jax.tree.map(lambda x: x * s, a)


def make_train_step(cfg, sys: SystemConfig, opt: optimizers.Optimizer,
                    mesh=None) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    Gradient accumulation over ``sys.microbatches``; the microbatch reshape is
    sharding-constrained so the accumulation axis stays unsharded.
    """
    n_micro = sys.microbatches
    baxes = None
    if mesh is not None:
        ax = tuple(a for a in sharding.BATCH_AXES if a in mesh.axis_names)
        baxes = ax if len(ax) > 1 else (ax[0] if ax else None)

    def loss(params, mb):
        return model_loss(params, mb, cfg, sys)

    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def train_step(state, batch):
        params = state["params"]
        if n_micro > 1:
            def resh(x):
                y = x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
                if baxes is not None:
                    y = lax.with_sharding_constraint(
                        y, P(*([None, baxes] + [None] * (y.ndim - 2))))
                return y
            with jax.named_scope("grad_accum"):
                mbs = jax.tree.map(resh, batch)

            def micro(carry, mb):
                g_acc, loss_acc, acc_acc = carry
                (l, metrics), g = grad_fn(params, mb)
                return (_tree_add(g_acc, g), loss_acc + l,
                        acc_acc + metrics["accuracy"]), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (g_sum, loss_sum, acc_sum), _ = lax.scan(
                micro, (g0, jnp.float32(0), jnp.float32(0)), mbs)
            grads = _tree_scale(g_sum, 1.0 / n_micro)
            loss_val = loss_sum / n_micro
            acc_val = acc_sum / n_micro
        else:
            (loss_val, metrics), grads = grad_fn(params, batch)
            acc_val = metrics["accuracy"]

        updates, opt_state = opt.update(grads, state["opt"], params,
                                        state["step"])
        params = optimizers.apply_updates(params, updates)
        new_state = {"params": params, "opt": opt_state,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss_val, "accuracy": acc_val}

    return train_step


def make_prefill_step(cfg, sys: SystemConfig, max_len: Optional[int] = None
                      ) -> Callable:
    """prefill(params, batch) -> (last-token logits, decode cache).

    max_len sizes the (full-attention) decode cache; default = prompt length.
    """
    if is_encdec(cfg):
        def prefill(params, batch):
            cparams = transformer._cast(params, sys.compute_dtype)
            enc = encdec.encode(cparams, batch["frames"].astype(
                sys.compute_dtype), cfg, sys)
            logits, sk, sv = encdec.decode_train(
                cparams, batch["tokens"], enc, cfg, sys, collect_cache=True,
                last_only=True)
            ck, cv = encdec.build_cross_cache(cparams, enc, cfg)
            cache = {"self_k": sk, "self_v": sv, "cross_k": ck, "cross_v": cv}
            return logits, cache
        return prefill

    def prefill(params, batch):
        S = (batch["tokens"].shape[1] if "tokens" in batch
             else batch["embeddings"].shape[1])
        logits, _, cache = transformer.forward(
            params, batch, cfg, sys, collect_cache=True, last_only=True,
            max_cache=max_len or S)
        return logits, cache
    return prefill


def make_decode_step(cfg, sys: SystemConfig) -> Callable:
    """decode(params, cache, tokens, pos) -> (logits, cache)."""
    if is_encdec(cfg):
        def decode(params, cache, tokens, pos):
            return encdec.decode_step(params, cache, tokens, pos, cfg, sys)
        return decode

    def decode(params, cache, tokens, pos):
        return transformer.decode_step(params, cache, tokens, pos, cfg, sys)
    return decode


# ---------------------------------------------------------------------------
# abstract inputs (ShapeDtypeStruct, no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype, mesh=None, spec=None):
    if mesh is not None and spec is not None:
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg, shape: ShapeSpec, mesh=None) -> dict:
    """Abstract stand-ins for every model input of this (arch, shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    baxes = None
    if mesh is not None:
        ax = tuple(a for a in sharding.BATCH_AXES if a in mesh.axis_names)
        baxes = ax if len(ax) > 1 else (ax[0] if ax else None)
        nshards = 1
        for a in (baxes if isinstance(baxes, tuple) else
                  ((baxes,) if baxes else ())):
            nshards *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
        if B % max(1, nshards) != 0:
            baxes = None                     # tiny batches stay replicated

    def row(shape_, dtype):
        spec = P(*([baxes] + [None] * (len(shape_) - 1)))
        return _sds(shape_, dtype, mesh, spec)

    if shape.kind == "decode":
        tok = row((B, 1), jnp.int32)
        return {"tokens": tok, "pos": _sds((), jnp.int32, mesh, P())}

    if is_encdec(cfg):
        d = {"frames": row((B, cfg.n_enc_frames, cfg.d_model), jnp.bfloat16),
             "tokens": row((B, S), jnp.int32)}
        if shape.kind == "train":
            d["labels"] = row((B, S), jnp.int32)
        return d
    if getattr(cfg, "takes_embeddings", False):
        d = {"embeddings": row((B, S, cfg.d_model), jnp.bfloat16)}
        if shape.kind == "train":
            d["labels"] = row((B, S), jnp.int32)
        return d
    d = {"tokens": row((B, S), jnp.int32)}
    if shape.kind == "train":
        d["labels"] = row((B, S), jnp.int32)
    return d


def cache_specs_abstract(cfg, shape: ShapeSpec, mesh=None, quant=False):
    """Abstract decode-cache pytree with shardings."""
    B, S = shape.global_batch, shape.seq_len
    if is_encdec(cfg):
        tree = jax.eval_shape(
            lambda: encdec.init_cache(cfg, B, min(S, 32768)))
    else:
        tree = jax.eval_shape(
            lambda: transformer.init_cache(cfg, B, S, quant=quant))
    if mesh is None:
        return tree
    specs = sharding.cache_specs(tree, cfg, mesh)
    return jax.tree.map(
        lambda l, s: _sds(l.shape, l.dtype, mesh, s), tree, specs)


def state_specs_abstract(cfg, opt, mesh, sys):
    tree = abstract_state(cfg, opt)
    specs = sharding.state_specs(tree, cfg, mesh, sys)
    return jax.tree.map(
        lambda l, s: _sds(l.shape, l.dtype, mesh, s), tree, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def param_specs_abstract(cfg, mesh, sys):
    tree = jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))
    specs = sharding.param_specs(tree, cfg, mesh, sys)
    return jax.tree.map(
        lambda l, s: _sds(l.shape, l.dtype, mesh, s), tree, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
