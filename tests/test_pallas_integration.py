"""sys.use_pallas routes attention through the fused flash kernels
(interpreted on CPU), which must agree with the jnp path end to end; its
default of None takes the kernels where compiled Pallas exists."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer as T


@pytest.mark.parametrize("arch", ["yi-34b", "mixtral-8x22b"])
def test_use_pallas_matches_jnp_forward(arch):
    cfg = configs.get_reduced(arch)
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params = T.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    base_sys = T.SystemConfig(precision="fp32", q_chunk=16, kv_chunk=16)
    l1, _ = T.forward(params, {"tokens": toks}, cfg, base_sys)
    l2, _ = T.forward(params, {"tokens": toks}, cfg,
                      dataclasses.replace(base_sys, use_pallas=True))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("arch", ["yi-34b", "mixtral-8x22b"])
def test_use_pallas_grads_finite(arch):
    """The fused kernels' backward gives the jnp path's gradients (mixtral's
    reduced config attends through a window of 8)."""
    cfg = configs.get_reduced(arch)
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params = T.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    base_sys = T.SystemConfig(precision="fp32", q_chunk=16, kv_chunk=16)

    def grads(sys):
        return jax.grad(lambda p: T.loss_fn(
            p, {"tokens": toks, "labels": toks}, cfg, sys)[0])(params)

    g_jnp = grads(dataclasses.replace(base_sys, use_pallas=False))
    g_kernel = grads(dataclasses.replace(base_sys, use_pallas=True))
    for a, b in zip(jax.tree.leaves(g_kernel), jax.tree.leaves(g_jnp)):
        assert jnp.isfinite(a).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("interpret,sys_kw,path", [
    (True, {}, "jnp"),                  # no compiled Pallas (CPU)
    (False, {}, "kernel"),              # compiled Pallas, one chip (TPU)
    (False, {"dp": 2}, "jnp"),          # a mesh: Mosaic is not partitioned
])
def test_default_sys_dispatch(monkeypatch, interpret, sys_kw, path):
    """``SystemConfig()`` runs the fused kernel where compiled Pallas exists
    and the step runs on one chip, the jnp attention elsewhere."""
    from repro.kernels import findb, ops
    from repro.models import layers
    calls = []
    fused, direct = ops.flash_attention_fused, layers.attention

    def spy_fused(*a, **kw):
        calls.append("kernel")
        return fused(*a, **kw, interpret=True)   # traced only, never run

    def spy_direct(*a, **kw):
        calls.append("jnp")
        return direct(*a, **kw)

    monkeypatch.setattr(findb, "default_interpret", lambda: interpret)
    monkeypatch.setattr(ops, "flash_attention_fused", spy_fused)
    monkeypatch.setattr(layers, "attention", spy_direct)
    cfg = configs.get_reduced("yi-34b")
    params = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))
    toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    sys = T.SystemConfig(**sys_kw)
    assert sys.use_pallas is None
    jax.eval_shape(lambda p, t: T.forward(p, {"tokens": t}, cfg, sys),
                   params, toks)
    assert calls and set(calls) == {path}
