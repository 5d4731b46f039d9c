"""The train step names its layers with ``jax.named_scope``.

The profiler copies each operation's ``op_name`` into the device trace,
and the chip benchmark splits the step's device time by these names
(``benchmarks/chip/chipbench/scopes.py``), so the names are a contract: a
refactor that drops one fails here. Each case compiles the program's own
``make_train_step`` (remat=block) for a tiny dense model on the CPU and
reads the ``op_name``s of the optimized HLO.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.launch import steps
from repro.models.transformer import ModelConfig, SystemConfig
from repro.optim import optimizers

SCOPES = ("embed", "cast", "norm", "attn_proj", "attn_core", "mlp",
          "layer_scan", "head", "xent", "grad_accum", "optimizer")
# not differentiated: no transposed form
FORWARD_ONLY = ("grad_accum", "optimizer")
# score and value contractions of layers.attention and chunked_attention
ATTN_EINSUMS = ("bskgd,btkd->bkgst", "bkgst,btkd->bskgd",
                "bkgst,btkd->bkgsd")
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def innermost_scope(op_name):
    for part in reversed(op_name.split("/")):
        m = _WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER.match(part)
        if part in SCOPES:
            return part
    return None


def compiled_op_names(seq, microbatches):
    cfg = ModelConfig(name="scopes", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=256,
                      qk_norm=True, tie_embeddings=True)
    sys = SystemConfig(remat="block", microbatches=microbatches)
    opt = optimizers.adamw(1e-3)
    step = steps.make_train_step(cfg, sys, opt)
    batch = {k: jax.ShapeDtypeStruct((2, seq), jnp.int32)
             for k in ("tokens", "labels")}
    hlo = jax.jit(step).lower(steps.abstract_state(cfg, opt),
                              batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.mark.parametrize("seq,microbatches,path", [
    (64, 1, "direct"), (64, 2, "direct"), (2056, 1, "chunked")])
def test_train_step_names_its_layers(seq, microbatches, path):
    names = compiled_op_names(seq, microbatches)
    forward = {innermost_scope(n) for n in names if "transpose(" not in n}
    backward = {innermost_scope(n) for n in names if "transpose(" in n}
    recompute = {innermost_scope(n) for n in names
                 if "rematted_computation" in n}
    expected = set(SCOPES) - ({"grad_accum"} if microbatches == 1 else set())
    assert expected <= forward
    assert expected - set(FORWARD_ONLY) <= backward
    assert {"attn_core", "attn_proj", "mlp", "norm"} <= recompute
    attn = [n for n in names if any(e in n for e in ATTN_EINSUMS)]
    assert any("transpose(" in n for n in attn)
    assert {innermost_scope(n) for n in attn} == {"attn_core"}
    # above 2048 tokens the block scans of chunked attention run inside it
    chunked = [n for n in attn if "/while/body/" in n.split("attn_core")[-1]]
    assert bool(chunked) == (path == "chunked")
