"""Collective time per step during which no compute runs on that device."""
from chipbench import xplane
from chipbench.layer_metrics._common import steps_in_trace


def read(ctx):
    if ctx.ir is None:
        return None
    if xplane.count_by(ctx.ir, xplane.is_collective) == 0:
        return None
    steps = steps_in_trace(ctx.ir)
    return xplane.exposed_seconds(ctx.ir, xplane.is_collective) / steps * 1e3
