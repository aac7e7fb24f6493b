"""`pool.used()` high-water inside the window over the pool's usable pages."""
from chipbench.layer_metrics._common import in_window


def read(ctx):
    used = [e[2] for e in in_window(ctx, ctx.events) if e[0] == "pool"]
    if not used:
        return None
    return 100.0 * max(used) / ctx.facts["pool_pages"]
