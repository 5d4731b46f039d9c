"""Operation counts the benchmark computes from shapes, not from the program.

A decoder layer of the ``dense`` family: grouped-query attention with
``n_heads`` query heads and ``n_kv_heads`` key/value heads of ``head_dim``,
and a SwiGLU MLP of width ``d_ff``. The output head is tied or not; either
way it is one ``d_model x vocab`` matmul per token.
"""
from __future__ import annotations


def lm_matmul_params(model: dict) -> int:
    """Parameters that take part in a matmul for every token (the embedding
    lookup does not; the output head does)."""
    d, h, k = model["d_model"], model["n_heads"], model["n_kv_heads"]
    hd, ff = model["head_dim"], model["d_ff"]
    per_layer = d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * ff
    return model["n_layers"] * per_layer + d * model["vocab"]


def lm_train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs of one trained token, forward and backward.

    Matmuls: 2 FLOPs per parameter forward, 4 backward. Causal attention is
    counted once: a query at position i scores i + 1 keys, so on average
    (seq + 1) / 2 of them, at 2 * head_dim FLOPs each for the scores and as
    many for the weighted values, in every query head; backward is twice
    forward. Recomputation (remat) and padding are not model FLOPs.
    """
    attn_fwd = (4 * model["n_heads"] * model["head_dim"] * (seq + 1) / 2
                * model["n_layers"])
    return 6.0 * lm_matmul_params(model) + 3.0 * attn_fwd
