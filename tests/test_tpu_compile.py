"""The Pallas kernels compile for a TPU v5e at the widths of the configs
that call them, on a described (not attached) chip: Mosaic's tiling and
layout rules are checked here, where the interpreter checks none.

The topology is described inside a fixture only: one process at a time may
load the TPU library, and every test worker imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops

B, S = 8, 1024


@pytest.fixture(scope="module")
def one_chip():
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep it out of the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler: nothing to check here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    if prev is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev


def _flash_args():
    cfg = configs.get_config("qwen3-0.6b")
    K, D = cfg.n_kv_heads, cfg.head_dim
    G = cfg.n_heads // K
    return [((B, S, K, G, D), jnp.bfloat16), ((B, S, K, D), jnp.bfloat16),
            ((B, S, K, D), jnp.bfloat16)]


def _mlstm_args():
    mc = configs.get_config("xlstm-350m").mlstm_cfg()
    qkv = ((B, S, mc.n_heads, mc.head_dim), jnp.bfloat16)
    gate = ((B, S, mc.n_heads), jnp.float32)
    return [qkv, qkv, qkv, gate, gate]


def _rglru_args():
    R = configs.get_config("recurrentgemma-9b").d_rnn
    return [((B, S, R), jnp.float32), ((B, S, R), jnp.float32),
            ((B, R), jnp.float32)]


def _flash_bwd(q, k, v):
    return jax.grad(lambda q, k, v: ops.flash_attention_fused(
        q, k, v, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _local_fp32_args():
    # recurrentgemma-9b's local attention in fp32: the widest rows (D=256,
    # 4 bytes) that the find-db's fallback blocks must fit in VMEM
    cfg = configs.get_config("recurrentgemma-9b")
    G, D = cfg.n_heads // cfg.n_kv_heads, cfg.resolved_head_dim
    return [((1, 4096, cfg.n_kv_heads, G, D), jnp.float32),
            ((1, 4096, cfg.n_kv_heads, D), jnp.float32),
            ((1, 4096, cfg.n_kv_heads, D), jnp.float32)]


def _flash_local_bwd(q, k, v):
    window = configs.get_config("recurrentgemma-9b").window
    return jax.grad(lambda q, k, v: ops.flash_attention_fused(
        q, k, v, True, window, interpret=False).sum(),
        argnums=(0, 1, 2))(q, k, v)


# name -> (function, argument shapes, Pallas calls the program must hold)
CASES = {
    "flash_fwd": (lambda q, k, v: ops.flash_attention_fused(
        q, k, v, interpret=False),
                  _flash_args, 1),
    "flash_fwd_bwd": (_flash_bwd, _flash_args, 3),
    "flash_local_fp32_fwd_bwd": (_flash_local_bwd, _local_fp32_args, 3),
    "mlstm": (lambda *a: ops.mlstm(*a, interpret=False), _mlstm_args, 1),
    "rglru": (lambda *a: ops.rglru(*a, interpret=False), _rglru_args, 1),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args, n_kernels = CASES[name]
    sds = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
           for shape, dtype in args()]
    compiled = jax.jit(fn).lower(*sds).compile()
    assert compiled.as_text().count("custom_call_target=\"tpu_custom_call\"") \
        >= n_kernels


def test_train_step_compiles_with_flash_kernels_for_v5e(one_chip,
                                                        monkeypatch):
    """qwen3-0.6b's train step at its widths (two layers), B=1, S=4096,
    bf16, remat=block, on the fused flash kernels at the find-db's
    fallback blocks: the forward, dq and dkv kernels compile within
    Mosaic's tile and VMEM limits and sit under the ``attn_core`` scope."""
    from repro.kernels import findb
    from repro.launch import steps
    from repro.models.transformer import SystemConfig
    from repro.optim import optimizers
    monkeypatch.setattr(findb, "default_interpret", lambda: False)
    cfg = dataclasses.replace(configs.get_config("qwen3-0.6b"), n_layers=2)
    opt = optimizers.adamw(1e-3)
    step = steps.make_train_step(
        cfg, SystemConfig(remat="block", precision="bf16", use_pallas=True),
        opt)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        steps.abstract_state(cfg, opt))
    batch = {k: jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    hlo = jax.jit(step, donate_argnums=(0,)).lower(state, batch).compile() \
        .as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) >= 3
    for line in calls:
        assert "attn_core" in re.search(r'op_name="([^"]*)"', line).group(1)
