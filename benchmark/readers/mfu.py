"""Model FLOP/s utilisation of a training run: required FLOPs per token
(the configuration's reference counts them) times tokens per second over
the chips' bf16 peak."""

from benchmark import flops


def read(ctx: dict):
    if "flops_per_token" not in ctx:
        return None
    return flops.mfu_pct(ctx["tokens_per_s"], ctx["flops_per_token"],
                         ctx["chips"], ctx["peak"]["bf16_flops"])
