"""Rows per engine decode call over the window (generating rows and
prompts streaming through decode slots alike)."""
from chipbench.layer_metrics._common import in_window


def read(ctx):
    calls = [e for e in in_window(ctx, ctx.events) if e[0] == "decode"]
    if not calls:
        return None
    return sum(e[2] for e in calls) / len(calls)
