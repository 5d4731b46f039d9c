"""Model FLOP utilisation of the training step: model FLOPs per token
(``chipbench.flops``: causal attention once, no recomputation) times the
window's tokens per second, over the chips' bf16 peak, in percent."""


def read(r):
    c = r.counters
    if "flops_per_token" not in c:
        return None
    return 100.0 * c["tokens_per_s"] * c["flops_per_token"] / (
        c["chips"] * r.peaks["bf16_flops"])
