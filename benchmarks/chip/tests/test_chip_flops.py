"""The qwen3 FLOP count against a count by hand."""
import json
import os

from chipbench import flops

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def qwen3():
    with open(os.path.join(CHIP, "configs", "qwen3-0.6b.json")) as f:
        return json.load(f)["model"]


def test_matmul_params_by_hand():
    # per layer: q 1024x2048, k and v 1024x1024, o 2048x1024, MLP 3x1024x3072
    per_layer = 2_097_152 + 1_048_576 + 1_048_576 + 2_097_152 + 9_437_184
    head = 1024 * 151808          # the vocabulary the cells hold
    assert flops.lm_matmul_params(qwen3()) == 28 * per_layer + head
    assert 28 * per_layer + head == 595_853_312


def test_train_flops_per_token_by_hand():
    m = qwen3()
    matmul = 6 * 595_853_312
    for seq in (4096, 1024):
        # 16 heads x 128 dims; scores and values 2 FLOPs per dim each,
        # (seq + 1) / 2 keys per query on average, backward twice forward
        attn = 3 * 28 * 16 * (2 * 128 + 2 * 128) * (seq + 1) / 2
        assert flops.lm_train_flops_per_token(m, seq) == matmul + attn
    assert round(flops.lm_train_flops_per_token(m, 4096) / 1e9, 2) == 4.98
    assert round(flops.lm_train_flops_per_token(m, 1024) / 1e9, 2) == 3.93
