"""Kernel validation: shape/dtype sweeps against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as fa_raw
from repro.kernels.mlstm import mlstm_chunkwise as mlstm_raw
from repro.kernels.rglru import rglru_scan as rglru_raw
from repro.models import layers


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ flash attention

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,K,G,D,causal,window", [
    (2, 256, 2, 2, 64, True, None),
    (1, 128, 4, 1, 32, True, 48),
    (2, 192, 2, 3, 64, True, None),        # ragged vs block size
    (1, 256, 1, 4, 128, False, None),
    (1, 64, 8, 1, 128, True, 16),
])
def test_flash_attention_sweep(B, S, K, G, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, D)).astype(dtype)
    out = fa_raw(q, k, v, causal=causal, window=window,
                 q_block=64, kv_block=64)
    exp = ref.attention_direct_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.integers(1, 3),
       st.sampled_from([32, 64]), st.booleans())
def test_flash_attention_property(B, K, G, D, causal):
    S = 96
    ks = jax.random.split(jax.random.PRNGKey(B * 100 + K * 10 + G), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    out = fa_raw(q, k, v, causal=causal, q_block=32, kv_block=32)
    exp = ref.attention_direct_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    g1 = jax.grad(lambda q, k, v: ops.flash_attention_fused(q, k, v).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: ref.attention_direct_ref(q, k, v).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------------- rglru

@pytest.mark.parametrize("B,S,R,chunk,rb", [
    (2, 256, 128, 64, 64),
    (1, 100, 96, 32, 64),      # ragged
    (3, 512, 256, 128, 128),
])
def test_rglru_sweep(B, S, R, chunk, rb):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    la = -jnp.abs(jax.random.normal(ks[0], (B, S, R))) * 0.5
    b = jax.random.normal(ks[1], (B, S, R))
    h0 = jax.random.normal(ks[2], (B, R))
    h, hl = rglru_raw(la, b, h0, chunk=chunk, r_block=rb)
    he, hle = ref.rglru_ref(la, b, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hle), rtol=1e-5,
                               atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([33, 64, 100]),
       st.sampled_from([32, 64]))
def test_rglru_property_matches_sequential(B, S, R):
    """Kernel == naive per-step recurrence for arbitrary shapes."""
    ks = jax.random.split(jax.random.PRNGKey(S * 7 + R), 2)
    la = -jnp.abs(jax.random.normal(ks[0], (B, S, R))) * 0.4
    b = jax.random.normal(ks[1], (B, S, R))
    h, _ = rglru_raw(la, b, None, chunk=32, r_block=32)
    hs = np.zeros((B, R))
    seq = []
    la_n, b_n = np.asarray(la), np.asarray(b)
    for t in range(S):
        hs = np.exp(la_n[:, t]) * hs + b_n[:, t]
        seq.append(hs.copy())
    np.testing.assert_allclose(np.asarray(h), np.stack(seq, 1), rtol=1e-4,
                               atol=1e-4)


# -------------------------------------------------------------------- mlstm

@pytest.mark.parametrize("B,S,H,D,chunk", [
    (2, 256, 2, 64, 64),
    (1, 128, 4, 32, 32),
    (2, 512, 1, 128, 128),
])
def test_mlstm_sweep(B, S, H, D, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, D)) for i in range(3))
    ig = jax.random.normal(ks[3], (B, S, H))
    fg = jax.random.normal(ks[4], (B, S, H)) + 2.0
    h = mlstm_raw(q, k, v, ig, fg, chunk=chunk)
    he, _ = ref.mlstm_ref(q, k, v, ig, fg, chunk=chunk)
    scale = float(jnp.max(jnp.abs(he))) + 1e-9
    np.testing.assert_allclose(np.asarray(h) / scale, np.asarray(he) / scale,
                               rtol=1e-4, atol=1e-4)


def test_mlstm_chunk_invariance():
    """Output must not depend on the chunk size."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    B, S, H, D = 1, 256, 2, 64
    q, k, v = (jax.random.normal(ks[i], (B, S, H, D)) for i in range(3))
    ig = jax.random.normal(ks[3], (B, S, H))
    fg = jax.random.normal(ks[4], (B, S, H)) + 2.0
    h64 = mlstm_raw(q, k, v, ig, fg, chunk=64)
    h128 = mlstm_raw(q, k, v, ig, fg, chunk=128)
    np.testing.assert_allclose(np.asarray(h64), np.asarray(h128), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------- fused backward

@pytest.mark.parametrize("B,S,K,G,D,causal,window", [
    (1, 128, 2, 2, 64, True, None),
    (2, 96, 2, 3, 32, True, None),      # ragged + multi-group
    (1, 128, 4, 1, 32, True, 48),       # sliding window
    (1, 64, 1, 4, 64, False, None),     # bidirectional
])
def test_flash_attention_fused_bwd(B, S, K, G, D, causal, window):
    """Pallas backward kernels (dq/dk/dv) vs autodiff through the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, K, G, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    co = jax.random.normal(ks[3], (B, S, K, G, D))
    g1 = jax.grad(lambda q, k, v: (ops.flash_attention_fused(
        q, k, v, causal, window, 32, 32) * co).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: (ref.attention_direct_ref(
        q, k, v, causal=causal, window=window) * co).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


BF16_TOL = 2e-2       # max |x - ref| / max |ref|, as chip_smoke.py holds


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.mark.parametrize("B,S,window", [
    (1, 256, None),
    (1, 256, 72),                       # sliding window
    (2, 200, None),                     # ragged vs the blocks
])
def test_flash_attention_fused_bf16(B, S, window):
    """bf16 operands on the MXU (qwen3-0.6b's head layout, scaled down:
    K=2 kv heads, G=2, D=128, causal): the fused forward and backward stay
    within bf16 rounding of the f32 oracle, and of the chunked jnp path
    they replace run on the same bf16 inputs."""
    K, G, D = 2, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (B, S, K, G, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, D)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, D)).astype(jnp.bfloat16)
    co = jax.random.normal(ks[3], (B, S, K, G, D))

    def grads(attn, *args):
        def loss(q, k, v):
            out = attn(q, k, v)
            return (out.astype(jnp.float32) * co).sum(), out
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(*args)

    fused = grads(lambda q, k, v: ops.flash_attention_fused(
        q, k, v, True, window, 64, 64), q, k, v)
    chunked = grads(lambda q, k, v: layers.chunked_attention(
        q, k, v, causal=True, window=window, q_chunk=64, kv_chunk=64),
        q, k, v)
    with jax.default_matmul_precision("highest"):
        oracle = grads(lambda q, k, v: ref.attention_direct_ref(
            q, k, v, causal=True, window=window),
            *(x.astype(jnp.float32) for x in (q, k, v)))
    assert fused[1].dtype == jnp.bfloat16
    for got, want in zip(jax.tree.leaves(fused), jax.tree.leaves(oracle)):
        assert _rel_err(got, want) <= BF16_TOL
    for got, want in zip(jax.tree.leaves(fused), jax.tree.leaves(chunked)):
        assert _rel_err(got, want) <= BF16_TOL


def test_flash_attention_lse_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 1, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))
    out, lse = fa_raw(q, k, v, causal=True, q_block=32, kv_block=32,
                      return_lse=True)
    import math
    s = jnp.einsum("bskgd,btkd->bkgst", q, k) / math.sqrt(32)
    mask = jnp.tril(jnp.ones((64, 64), bool))
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    lse_ref = jnp.moveaxis(jax.nn.logsumexp(s, axis=-1), 3, 1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=1e-5, atol=1e-5)
