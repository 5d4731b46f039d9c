"""Dry-run machinery: in-process AOT lower+compile on a 1x1 mesh for reduced
configs of every family (the 256/512-chip production runs live in
dryrun_all.json; this guards the plumbing in CI time)."""
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.analysis import hlo_analysis, roofline
from repro.launch import mesh as mesh_lib, steps
from repro.models.transformer import SystemConfig
from repro.optim import optimizers


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "mixtral-8x22b",
                                  "recurrentgemma-9b", "xlstm-350m",
                                  "whisper-small"])
def test_lower_compile_train_reduced(arch):
    cfg = configs.get_reduced(arch)
    mesh = mesh_lib.single_device_mesh()
    sys = SystemConfig(microbatches=2, remat="block", batch_axes=("data",))
    opt = optimizers.adamw(1e-3)
    with mesh:
        step = steps.make_train_step(cfg, sys, opt, mesh=mesh)
        state_sds = steps.state_specs_abstract(cfg, opt, mesh, sys)
        if steps.is_encdec(cfg):
            B, S = 4, 16
            batch_sds = {
                "frames": jax.ShapeDtypeStruct((B, cfg.n_enc_frames,
                                                cfg.d_model), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        elif getattr(cfg, "takes_embeddings", False):
            batch_sds = {
                "embeddings": jax.ShapeDtypeStruct((4, 16, cfg.d_model),
                                                   jnp.bfloat16),
                "labels": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
        else:
            batch_sds = {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32),
                         "labels": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
        compiled = jax.jit(step).lower(state_sds, batch_sds).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes >= 0
    cost = hlo_analysis.analyze(compiled.as_text())
    assert cost.flops > 0 and cost.bytes > 0
    terms = roofline.terms_from_hlo(cost, chips=1, model_flops=1.0)
    assert terms.step_time_s > 0


def test_model_flops_moe_uses_active_params():
    cfg = configs.get_config("mixtral-8x22b")
    aparams = jax.eval_shape(
        lambda: steps.model_init(jax.random.PRNGKey(0), cfg))
    total, active = roofline.count_params(
        aparams, cfg.top_k / cfg.n_experts)
    assert total > 100e9           # ~141B
    assert active < 0.45 * total   # 2-of-8 experts + dense trunk


def test_shape_applicability_matrix():
    table = {a: [s for s in configs.SHAPES
                 if configs.shape_applicable(configs.get_config(a),
                                             configs.SHAPES[s])]
             for a in configs.ARCH_IDS}
    # sub-quadratic archs keep long_500k, full-attention archs drop it
    assert "long_500k" in table["mixtral-8x22b"]
    assert "long_500k" in table["recurrentgemma-9b"]
    assert "long_500k" in table["xlstm-350m"]
    assert "long_500k" not in table["yi-34b"]
    assert "long_500k" not in table["whisper-small"]
    runnable = sum(len(v) for v in table.values())
    assert runnable == 33          # 40 cells - 7 documented skips
