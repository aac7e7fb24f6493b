"""Host clock around `engine.decode`, the fetch of the logits included."""
from chipbench.layer_metrics._common import median


def read(ctx):
    d = ctx.spans.durations("engine_decode", ctx.facts["t_start"], ctx.facts["t_end"])
    return median(d) * 1e3 if d else None
