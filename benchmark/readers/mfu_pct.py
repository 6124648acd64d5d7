"""Model FLOP/s utilisation: model operations per token (recomputation not
counted, causal attention counted as the half it needs) x tokens per second
per chip, over the chip's peak bf16 FLOP/s."""

from benchmark.harness import flops


def read(ctx):
    if "flops_per_token" not in ctx:
        return None
    return flops.mfu_pct(ctx["tokens_per_s_per_chip"], ctx["flops_per_token"],
                         ctx["peaks"]["bf16_flops_per_s"])
