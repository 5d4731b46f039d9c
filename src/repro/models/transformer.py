"""TransformerLM: one scan-over-layers decoder covering the LM-family archs.

Families
--------
dense / moe / vlm : homogeneous attention blocks (MLP or MoE), single scan.
hybrid            : Griffin pattern — super-block (rec, rec, local-attn) scanned
                    over groups, plus a tail of leftover recurrent layers.
ssm (xlstm)       : super-block (7 mLSTM + 1 sLSTM) scanned over groups.

HLO size is O(1) in depth (every family scans over stacked per-layer params),
which is what lets 62-layer 33B configs `.lower().compile()` in seconds on the
CPU host with 512 fake devices.

``vlm`` consumes precomputed patch embeddings (modality frontend is a stub per
the assignment); ``audio`` lives in ``repro.models.encdec``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers, moe as moe_lib, recurrent as rec_lib, xlstm as xlstm_lib


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | vlm | hybrid | ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None          # sliding-window attention
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared: int = 0
    capacity_factor: float = 1.25
    # exact per-token routing: capacity drops couple token outputs across
    # positions, which would break SWA receptive-field guarantees and
    # forward/decode agreement (set False only for capacity-drop
    # throughput experiments)
    moe_dropless: bool = True
    # --- hybrid (Griffin) ---
    rec_per_attn: int = 2                 # recurrent layers per attention layer
    d_rnn: Optional[int] = None
    # --- ssm (xlstm) ---
    mlstm_per_slstm: int = 7
    proj_factor: float = 2.0
    # --- misc ---
    tie_embeddings: bool = False
    dtype: Any = jnp.float32              # param dtype

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Embedding-table rows padded to a multiple of 256 so the vocab axis
        always shards over the model axis (standard practice; the published
        `vocab` stays the label space — pad logits train as junk tokens)."""
        return -(-self.vocab // 256) * 256

    def attn_cfg(self, window=None) -> layers.AttnConfig:
        return layers.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.resolved_head_dim,
            qkv_bias=self.qkv_bias, qk_norm=self.qk_norm,
            rope_theta=self.rope_theta,
            window=window if window is not None else self.window)

    def moe_cfg(self) -> moe_lib.MoEConfig:
        return moe_lib.MoEConfig(
            d_model=self.d_model, d_ff=self.d_ff, n_experts=self.n_experts,
            top_k=self.top_k, n_shared=self.n_shared,
            capacity_factor=self.capacity_factor,
            dropless=self.moe_dropless)

    def rec_cfg(self) -> rec_lib.RecurrentConfig:
        return rec_lib.RecurrentConfig(d_model=self.d_model,
                                       d_rnn=self.d_rnn or self.d_model)

    def mlstm_cfg(self) -> xlstm_lib.MLSTMConfig:
        return xlstm_lib.MLSTMConfig(d_model=self.d_model, n_heads=self.n_heads,
                                     proj_factor=self.proj_factor)

    @property
    def sub_quadratic(self) -> bool:
        """True if serve memory/compute is O(window) or O(1) per token."""
        return self.family in ("hybrid", "ssm") or self.window is not None

    @property
    def takes_embeddings(self) -> bool:
        return self.family == "vlm"

    # layer grouping for scan -------------------------------------------------
    @property
    def hybrid_groups(self) -> int:
        return self.n_layers // (self.rec_per_attn + 1)

    @property
    def hybrid_tail(self) -> int:
        return self.n_layers - self.hybrid_groups * (self.rec_per_attn + 1)

    @property
    def ssm_groups(self) -> int:
        return self.n_layers // (self.mlstm_per_slstm + 1)


@dataclasses.dataclass(frozen=True)
class SystemConfig:
    """The paper's 'system parameters', TPU edition (see DESIGN.md §2).

    These are the knobs PipeTune tunes per-epoch; none of them change the
    model function, only how it executes.
    """
    dp: int = 1
    tp: int = 1
    pods: int = 1
    microbatches: int = 1
    remat: str = "none"                 # none | block | dots
    precision: str = "bf16"             # bf16 | fp32
    donate: bool = True
    zero1: bool = True
    compression: str = "none"           # none | int8 | topk
    param_sharding: str = "2d"          # 2d (TP+FSDP) | tp (model axis only)
    shard_attn: bool = False            # constrain q/k/v to head sharding
    batch_axes: tuple = ()              # mesh axes carrying the batch dim
    q_chunk: int = 1024
    kv_chunk: int = 1024
    use_pallas: bool = False            # TPU runtime only; CPU dry-run = False
    kv_quant: bool = False              # int8 KV cache (decode memory term /2)

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.precision == "bf16" else jnp.float32

    @property
    def chips(self) -> int:
        return self.dp * self.tp * self.pods


DEFAULT_SYS = SystemConfig()


def _cast(params, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        params)


def _remat(fn, sys: SystemConfig):
    if sys.remat == "none":
        return fn
    if sys.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)           # "block": save block boundaries only


# ---------------------------------------------------------------------------
# per-layer blocks
# ---------------------------------------------------------------------------

def _init_attn_block(key, cfg: ModelConfig, window=None):
    ks = jax.random.split(key, 4)
    p = {"attn_norm": layers.init_rmsnorm(cfg.d_model, cfg.dtype),
         "attn": layers.init_attention(ks[0], cfg.attn_cfg(window), cfg.dtype),
         "mlp_norm": layers.init_rmsnorm(cfg.d_model, cfg.dtype)}
    if cfg.family in ("moe",):
        p["moe"] = moe_lib.init_moe(ks[1], cfg.moe_cfg(), cfg.dtype)
    else:
        p["mlp"] = layers.init_swiglu(ks[2], cfg.d_model, cfg.d_ff, cfg.dtype)
    return p


def _apply_attn_block(p, x, cfg: ModelConfig, sys: SystemConfig, window=None,
                      collect_cache=False, max_cache=None):
    acfg = cfg.attn_cfg(window)
    x = layers.shard_batch(x, sys.batch_axes)
    h = layers.rmsnorm(p["attn_norm"], x)
    B, S, _ = h.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    q, k, v = layers.attention_qkv(p["attn"], h, acfg, positions)
    q = layers.shard_heads(q, sys.shard_attn)
    k = layers.shard_heads(k, sys.shard_attn)
    v = layers.shard_heads(v, sys.shard_attn)
    if sys.use_pallas:
        # TPU runtime path: the flash kernel keeps score blocks in VMEM.
        # (interpret resolves through findb.default_interpret(): compiled
        # on TPU, interpreted elsewhere; the dry-run keeps the jnp path,
        # whose score traffic the roofline's kernelized memory term
        # subtracts.)
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.flash_attention(
            q, k, v, True, acfg.window, sys.q_chunk, sys.kv_chunk)
    elif S > 2048:
        out = layers.chunked_attention(q, k, v, causal=True, window=acfg.window,
                                       q_chunk=sys.q_chunk, kv_chunk=sys.kv_chunk)
    else:
        out = layers.attention(q, k, v, causal=True, window=acfg.window)
    x = x + jnp.einsum("bskgh,kghd->bsd", out, p["attn"]["wo"])
    h = layers.rmsnorm(p["mlp_norm"], x)
    aux = jnp.float32(0)
    if "moe" in p:
        y, aux = moe_lib.apply_moe(p["moe"], h, cfg.moe_cfg())
    else:
        y = layers.apply_swiglu(p["mlp"], h)
    x = x + y
    cache = None
    if collect_cache:
        # Ring invariant: position p lives at slot p % W (decode relies on
        # it). Full attention: pad to max_cache (slots 0..S-1 = positions).
        # SWA: keep the last W positions and roll so slot = p % W.
        cache = {"k": _ring_layout(k, S, acfg.window, max_cache),
                 "v": _ring_layout(v, S, acfg.window, max_cache)}
    return x, aux, cache


def _ring_layout(kv, S, window, max_cache):
    kv = kv.astype(jnp.bfloat16)
    if window is None:
        W = max(max_cache or S, S)
        if W > S:
            kv = jnp.pad(kv, ((0, 0), (0, W - S)) + ((0, 0),) * (kv.ndim - 2))
        return kv
    W = window
    if S >= W:
        return jnp.roll(kv[:, -W:], S % W, axis=1)
    return jnp.pad(kv, ((0, 0), (0, W - S)) + ((0, 0),) * (kv.ndim - 2))


def _apply_attn_block_decode(p, x, cfg: ModelConfig, cache, pos, window=None):
    acfg = cfg.attn_cfg(window)
    h = layers.rmsnorm(p["attn_norm"], x)
    out, cache = layers.apply_attention_decode(p["attn"], h, acfg, cache, pos)
    x = x + out
    h = layers.rmsnorm(p["mlp_norm"], x)
    if "moe" in p:
        y, _ = moe_lib.apply_moe(p["moe"], h, cfg.moe_cfg())
    else:
        y = layers.apply_swiglu(p["mlp"], h)
    return x + y, cache


def _init_rec_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {"rec_norm": layers.init_rmsnorm(cfg.d_model, cfg.dtype),
            "rec": rec_lib.init_recurrent(ks[0], cfg.rec_cfg(), cfg.dtype),
            "mlp_norm": layers.init_rmsnorm(cfg.d_model, cfg.dtype),
            "mlp": layers.init_swiglu(ks[1], cfg.d_model, cfg.d_ff, cfg.dtype)}


def _apply_rec_block(p, x, cfg: ModelConfig):
    h = layers.rmsnorm(p["rec_norm"], x)
    x = x + rec_lib.apply_recurrent(p["rec"], h, cfg.rec_cfg())
    h = layers.rmsnorm(p["mlp_norm"], x)
    return x + layers.apply_swiglu(p["mlp"], h)


def _apply_rec_block_decode(p, x, cfg: ModelConfig, state):
    h = layers.rmsnorm(p["rec_norm"], x)
    out, state = rec_lib.apply_recurrent_decode(p["rec"], h, cfg.rec_cfg(), state)
    x = x + out
    h = layers.rmsnorm(p["mlp_norm"], x)
    return x + layers.apply_swiglu(p["mlp"], h), state


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_init(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    V = cfg.padded_vocab
    params = {"final_norm": layers.init_rmsnorm(cfg.d_model, cfg.dtype)}
    params["embed"] = layers.embed_init(ks[0], (V, cfg.d_model), cfg.dtype)
    if cfg.takes_embeddings:
        # VLM stub frontend: a single linear adapter on precomputed patch
        # embeddings + the text embedding table for label space.
        params["adapter"] = layers.dense_init(ks[4], (cfg.d_model, cfg.d_model),
                                              dtype=cfg.dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(ks[1], (cfg.d_model, V),
                                              dtype=cfg.dtype)

    if cfg.family in ("dense", "moe", "vlm"):
        params["layers"] = _stack_init(
            lambda k: _init_attn_block(k, cfg), ks[2], cfg.n_layers)
    elif cfg.family == "hybrid":
        def group(k):
            sk = jax.random.split(k, cfg.rec_per_attn + 1)
            return {"recs": jax.vmap(lambda kk: _init_rec_block(kk, cfg))(
                        sk[:cfg.rec_per_attn]),
                    "attn": _init_attn_block(sk[-1], cfg, window=cfg.window)}
        params["layers"] = _stack_init(group, ks[2], cfg.hybrid_groups)
        if cfg.hybrid_tail:
            params["tail"] = _stack_init(lambda k: _init_rec_block(k, cfg),
                                         ks[3], cfg.hybrid_tail)
    elif cfg.family == "ssm":
        mcfg = cfg.mlstm_cfg()

        def group(k):
            sk = jax.random.split(k, 2)
            return {"mlstms": _stack_init(
                        lambda kk: {"norm": layers.init_rmsnorm(cfg.d_model,
                                                                cfg.dtype),
                                    "cell": xlstm_lib.init_mlstm(kk, mcfg,
                                                                 cfg.dtype)},
                        sk[0], cfg.mlstm_per_slstm),
                    "slstm": {"norm": layers.init_rmsnorm(cfg.d_model, cfg.dtype),
                              "cell": xlstm_lib.init_slstm(sk[1], mcfg,
                                                           cfg.dtype)}}
        params["layers"] = _stack_init(group, ks[2], cfg.ssm_groups)
    else:
        raise ValueError(f"unknown family {cfg.family}")
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(params, batch, cfg: ModelConfig, sys: SystemConfig = DEFAULT_SYS,
            collect_cache=False, max_cache=None, last_only=False):
    """batch: {"tokens": (B,S) int32} or {"embeddings": (B,S,d)} for vlm.

    Returns (logits, aux_loss) or (logits, aux_loss, cache) with collect_cache.
    last_only projects the LM head on the final position only (prefill).
    """
    cparams = _cast(params, sys.compute_dtype)
    if cfg.takes_embeddings:
        x = batch["embeddings"].astype(sys.compute_dtype)
        x = jnp.einsum("bsd,de->bse", x, cparams["adapter"])
    else:
        x = cparams["embed"][batch["tokens"]]

    aux_total = jnp.float32(0)
    caches = None

    if cfg.family in ("dense", "moe", "vlm"):
        def body(x, lp):
            x, aux, cache = _apply_attn_block(lp, x, cfg, sys,
                                              collect_cache=collect_cache,
                                              max_cache=max_cache)
            return x, (aux, cache) if collect_cache else (aux, 0)
        x, (auxs, caches) = lax.scan(_remat(body, sys), x, cparams["layers"])
        aux_total = auxs.sum()
    elif cfg.family == "hybrid":
        def body(x, lp):
            x = layers.shard_batch(x, sys.batch_axes)
            def rec_body(x, rp):
                return _apply_rec_block(rp, x, cfg), 0
            x, _ = lax.scan(rec_body, x, lp["recs"])
            x, aux, cache = _apply_attn_block(lp["attn"], x, cfg, sys,
                                              window=cfg.window,
                                              collect_cache=collect_cache,
                                              max_cache=max_cache)
            return x, (aux, cache) if collect_cache else (aux, 0)
        x, (auxs, caches) = lax.scan(_remat(body, sys), x, cparams["layers"])
        aux_total = auxs.sum()
        if cfg.hybrid_tail:
            def tail_body(x, rp):
                return _apply_rec_block(rp, x, cfg), 0
            x, _ = lax.scan(_remat(tail_body, sys), x, cparams["tail"])
    elif cfg.family == "ssm":
        mcfg = cfg.mlstm_cfg()

        def body(x, lp):
            x = layers.shard_batch(x, sys.batch_axes)
            def mbody(x, mp):
                h = layers.rmsnorm(mp["norm"], x)
                return x + xlstm_lib.apply_mlstm(mp["cell"], h, mcfg), 0
            x, _ = lax.scan(mbody, x, lp["mlstms"])
            h = layers.rmsnorm(lp["slstm"]["norm"], x)
            out, _ = xlstm_lib.apply_slstm(lp["slstm"]["cell"], h, mcfg)
            return x + out, (jnp.float32(0), 0)
        x, (auxs, _) = lax.scan(_remat(body, sys), x, cparams["layers"])
        caches = None

    if last_only:
        x = x[:, -1:]
    x = layers.rmsnorm(params["final_norm"], x)
    head = (cparams["embed"].T if cfg.tie_embeddings else cparams["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    if collect_cache:
        return logits, aux_total, caches
    return logits, aux_total


def loss_fn(params, batch, cfg: ModelConfig, sys: SystemConfig = DEFAULT_SYS):
    logits, aux = forward(params, batch, cfg, sys)
    labels = batch["labels"]
    V = logits.shape[-1]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = (lse - gold) * mask
    loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
    metrics = {"loss": loss, "aux_loss": aux,
               "tokens": mask.sum(),
               "accuracy": ((jnp.argmax(logits, -1) == labels) * mask).sum()
               / jnp.maximum(mask.sum(), 1.0)}
    return loss + aux, metrics


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               quant: bool = False):
    """Build the decode cache pytree (stacked on the layer/group axis)."""
    acfg = cfg.attn_cfg()
    if cfg.family in ("dense", "moe", "vlm"):
        one = layers.init_kv_cache(acfg, batch, max_len, dtype, quant=quant)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), one)
    if cfg.family == "hybrid":
        rstate = rec_lib.init_recurrent_state(cfg.rec_cfg(), batch, dtype)
        attn = layers.init_kv_cache(acfg, batch, max_len, dtype, quant=quant)
        g = cfg.hybrid_groups
        group = {"recs": jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (g, cfg.rec_per_attn) + a.shape), rstate),
                 "attn": jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (g,) + a.shape), attn)}
        if cfg.hybrid_tail:
            group["tail"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.hybrid_tail,) + a.shape),
                rstate)
        return group
    if cfg.family == "ssm":
        mcfg = cfg.mlstm_cfg()
        m = xlstm_lib.init_mlstm_state(mcfg, batch, dtype)
        s = xlstm_lib.init_slstm_state(mcfg, batch)
        g = cfg.ssm_groups
        return {"mlstms": jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (g, cfg.mlstm_per_slstm) + a.shape), m),
                "slstm": jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (g,) + a.shape), s)}
    raise ValueError(cfg.family)


def decode_step(params, cache, tokens, pos, cfg: ModelConfig,
                sys: SystemConfig = DEFAULT_SYS):
    """One new token for every sequence in the batch.

    tokens: (B, 1) int32; pos: () int32 current context length.
    Returns (logits (B, 1, V), new_cache).
    """
    cparams = _cast(params, sys.compute_dtype)
    if cfg.takes_embeddings:
        x = cparams["embed"][tokens]
        x = jnp.einsum("bsd,de->bse", x, cparams["adapter"])
    else:
        x = cparams["embed"][tokens]

    if cfg.family in ("dense", "moe", "vlm"):
        def body(x, xs):
            lp, c = xs
            x, c = _apply_attn_block_decode(lp, x, cfg, c, pos)
            return x, c
        x, new_cache = lax.scan(body, x, (cparams["layers"], cache))
    elif cfg.family == "hybrid":
        def body(x, xs):
            lp, c = xs

            def rec_body(x, rxs):
                rp, rc = rxs
                x, rc = _apply_rec_block_decode(rp, x, cfg, rc)
                return x, rc
            x, rcs = lax.scan(rec_body, x, (lp["recs"], c["recs"]))
            x, ac = _apply_attn_block_decode(lp["attn"], x, cfg, c["attn"], pos,
                                             window=cfg.window)
            return x, {"recs": rcs, "attn": ac}
        x, new_groups = lax.scan(body, x, (cparams["layers"],
                                           {"recs": cache["recs"],
                                            "attn": cache["attn"]}))
        new_cache = dict(new_groups)
        if cfg.hybrid_tail:
            def tail_body(x, rxs):
                rp, rc = rxs
                x, rc = _apply_rec_block_decode(rp, x, cfg, rc)
                return x, rc
            x, tcs = lax.scan(tail_body, x, (cparams["tail"], cache["tail"]))
            new_cache["tail"] = tcs
    elif cfg.family == "ssm":
        mcfg = cfg.mlstm_cfg()

        def body(x, xs):
            lp, c = xs

            def mbody(x, mxs):
                mp, mc = mxs
                h = layers.rmsnorm(mp["norm"], x)
                out, mc = xlstm_lib.apply_mlstm_decode(mp["cell"], h, mcfg, mc)
                return x + out, mc
            x, mcs = lax.scan(mbody, x, (lp["mlstms"], c["mlstms"]))
            h = layers.rmsnorm(lp["slstm"]["norm"], x)
            out, sc = xlstm_lib.apply_slstm(lp["slstm"]["cell"], h, mcfg,
                                            state=c["slstm"])
            return x + out, {"mlstms": mcs, "slstm": sc}
        x, new_cache = lax.scan(body, x, (cparams["layers"], cache))
    else:
        raise ValueError(cfg.family)

    x = layers.rmsnorm(params["final_norm"], x)
    head = (cparams["embed"].T if cfg.tie_embeddings else cparams["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    return logits, new_cache
