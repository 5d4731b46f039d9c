"""The float32 reference against the program's own loss at float32, at a
tiny size on the CPU, on the direct attention path and on the chunked one
that sequences over 2048 tokens take."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import lm

import chip_cells as cells


def _program(model, seq):
    from repro.models import transformer
    fields = {f.name for f in dataclasses.fields(transformer.ModelConfig)}
    mc = transformer.ModelConfig(**{k: v for k, v in model.items()
                                    if k in fields})
    sys = transformer.SystemConfig(precision="fp32")

    def loss(params, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return transformer.loss_fn(params, {"tokens": tokens,
                                                "labels": labels}, mc, sys)[0]
    return jax.jit(jax.value_and_grad(loss))


@pytest.mark.parametrize("batch,seq", [(3, 64), (1, 2560)])
def test_reference_matches_the_program_at_float32(batch, seq):
    model = cells.spec()["config"]["model"]
    ref = cells.reference()
    params = lm.init_params(model, lm.seed_key(7))
    (tokens, labels), = lm.token_pool(7, 1, batch, seq, model["vocab"])
    loss_p, grad_p = _program(model, seq)(params, tokens, labels)
    loss_r, grad_r = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, jnp.asarray(tokens), jnp.asarray(labels),
                              model)))(params)
    assert float(loss_r) == pytest.approx(float(loss_p), rel=1e-5)
    np.testing.assert_allclose(lm.leaf_norms(model, grad_r),
                               lm.leaf_norms(model, grad_p), rtol=2e-4)
