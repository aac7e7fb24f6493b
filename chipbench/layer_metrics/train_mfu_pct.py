"""The whole step's share of the chips' peak: model FLOPs per token
(chipbench.flops) x tokens/s over chips x peak. Recomputed work (flash's
backward recomputes QK^T) is not counted."""
from chipbench import flops


def read(ctx):
    f = ctx.facts
    if "tokens_per_s" not in f or ctx.peak is None:
        return None
    need = flops.train_flops_per_token(ctx.cfg, f["seq"]) * f["tokens_per_s"]
    return 100.0 * need / (ctx.chips * ctx.peak["flops_per_s"])
