"""The Pallas kernels compile for a TPU v5e at the widths of the configs
that call them, on a described (not attached) chip: Mosaic's tiling and
layout rules are checked here, where the interpreter checks none.

The topology is described inside a fixture only: one process at a time may
load the TPU library, and every test worker imports this file.
"""
import os

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


# name -> (function, argument shapes, Pallas calls the program must hold)
CASES = {
    "flash_fwd": (lambda q, k, v: ops.flash_attention(q, k, v,
                                                      interpret=False),
                  _flash_args, 1),
    "flash_fwd_bwd": (_flash_bwd, _flash_args, 3),
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
