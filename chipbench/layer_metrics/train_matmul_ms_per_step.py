"""Device time of the matmul fusions per step, first device, from the trace."""
from chipbench import xplane
from chipbench.layer_metrics._common import is_matmul, steps_in_trace


def read(ctx):
    if ctx.ir is None:
        return None
    steps = steps_in_trace(ctx.ir)
    secs = xplane.seconds_by(ctx.ir, is_matmul)
    if not steps or secs <= 0:
        return None
    return secs / steps * 1e3
