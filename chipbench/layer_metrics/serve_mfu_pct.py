"""The whole serve step's share of the chip's peak: 2 x matmul parameters
x every token a step processed inside the window (decode rows, prompt
tokens streamed through decode slots, bucketed prefill tokens; padding
rows and padded prefill positions are not counted) over window x peak."""
from chipbench import flops
from chipbench.layer_metrics._common import in_window


def read(ctx):
    if ctx.peak is None:
        return None
    ev = in_window(ctx, ctx.events)
    tokens = sum(e[2] for e in ev if e[0] in ("decode", "prefill"))
    if not tokens:
        return None
    need = flops.serve_flops_per_token(ctx.cfg) * tokens
    return 100.0 * need / (ctx.facts["window_s"] * ctx.peak["flops_per_s"])
