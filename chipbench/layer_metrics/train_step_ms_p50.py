"""Median over fetch groups of the group's time over its steps."""
from chipbench.layer_metrics._common import median


def read(ctx):
    f = ctx.facts
    if not f.get("group_s"):
        return None
    return median(f["group_s"]) / f["fetch_every"] * 1e3
