"""What the language-model cells share: the weights the benchmark makes from
the seed, the per-leaf norms the output comparison reads, and the token
feed.

The weights are the benchmark's, not the program's: made on the device in
one jitted call from the seed, in the layout the program's train state
holds (the driver checks the layout against the program's own abstract
state). The reference makes the same weights with the same function.

A decoder of the ``dense`` family with grouped-query attention: query
weights ``(d, K, G, D)`` with ``K`` key/value heads of ``G`` query heads
each, key and value weights ``(d, K, D)``, output ``(K, G, D, d)``, RMSNorm
scales on the residual stream and, with ``qk_norm``, on each head's
queries and keys; a SwiGLU MLP. Layer weights are stacked on a leading
layer axis.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

# a leaf: (path, shape, kind, fan_in); kind is "normal" (std 0.02),
# "dense" (truncated normal, std 1/sqrt(fan_in)) or "ones"
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], str, int]


def leaves(model: dict) -> List[Leaf]:
    """Every weight of ``model`` with its shape and initialiser, in a fixed
    order (sorted by path)."""
    L, d, ff = model["n_layers"], model["d_model"], model["d_ff"]
    K, D = model["n_kv_heads"], model["head_dim"]
    G = model["n_heads"] // K
    V = model["vocab"]
    out: List[Leaf] = [
        (("embed",), (V, d), "normal", 0),
        (("final_norm", "scale"), (d,), "ones", 0),
        (("layers", "attn", "wq"), (L, d, K, G, D), "dense", d),
        (("layers", "attn", "wk"), (L, d, K, D), "dense", d),
        (("layers", "attn", "wv"), (L, d, K, D), "dense", d),
        (("layers", "attn", "wo"), (L, K, G, D, d), "dense", K * G * D),
        (("layers", "attn_norm", "scale"), (L, d), "ones", 0),
        (("layers", "mlp", "w_gate"), (L, d, ff), "dense", d),
        (("layers", "mlp", "w_up"), (L, d, ff), "dense", d),
        (("layers", "mlp", "w_down"), (L, ff, d), "dense", ff),
        (("layers", "mlp_norm", "scale"), (L, d), "ones", 0),
    ]
    if model["qk_norm"]:
        out += [(("layers", "attn", "q_norm", "scale"), (L, D), "ones", 0),
                (("layers", "attn", "k_norm", "scale"), (L, D), "ones", 0)]
    if not model["tie_embeddings"]:
        out.append((("lm_head",), (d, V), "dense", d))
    return sorted(out)


def _set(tree: dict, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _get(tree: dict, path):
    for p in path:
        tree = tree[p]
    return tree


def seed_key(seed: int):
    """The PRNG key of a run's ``--seed`` (any whole number)."""
    import jax
    return jax.random.PRNGKey(seed % 2**32)


def init_params(model: dict, key):
    """The weights of ``model`` for ``key`` (``seed_key(seed)``), float32.
    Traceable; the key is an argument so that one compiled program serves
    every seed. Leaf ``i`` draws from ``fold_in(key, i)``."""
    import jax
    import jax.numpy as jnp
    params: Dict = {}
    for i, (path, shape, kind, fan_in) in enumerate(leaves(model)):
        k = jax.random.fold_in(key, i)
        if kind == "ones":
            v = jnp.ones(shape, jnp.float32)
        elif kind == "normal":
            v = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            v = jax.random.truncated_normal(k, -3.0, 3.0, shape, jnp.float32) \
                / math.sqrt(fan_in)
        _set(params, path, v)
    return params


def leaf_names(model: dict) -> List[str]:
    """Names of the compared leaves: a stacked layer weight is one leaf per
    layer."""
    names = []
    for path, shape, _, _ in leaves(model):
        base = "/".join(path)
        if path[0] == "layers":
            names += [f"{base}[{i}]" for i in range(shape[0])]
        else:
            names.append(base)
    return names


def leaf_norms(model: dict, tree):
    """Float32 L2 norm of every compared leaf of ``tree`` (a params-shaped
    tree), in ``leaf_names`` order (traceable)."""
    import jax.numpy as jnp
    out = []
    for path, shape, _, _ in leaves(model):
        x = _get(tree, path).astype(jnp.float32)
        if path[0] == "layers":
            out.append(jnp.sqrt(jnp.sum(jnp.square(x),
                                        axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)


def token_pool(seed: int, n_batches: int, batch: int, seq: int,
               vocab: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``n_batches`` host batches of ``(tokens, labels)``, each ``(batch,
    seq)`` int32, drawn uniformly from the label space; labels are the
    next tokens. Every row differs."""
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, size=(n_batches, batch, seq + 1),
                        dtype=np.int32)
    return [(np.ascontiguousarray(t[:, :-1]), np.ascontiguousarray(t[:, 1:]))
            for t in toks]
