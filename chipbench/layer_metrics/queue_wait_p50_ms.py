"""Due time to the first step in which the request holds a decode slot,
median over the window's requests (seen from the harness after each
`scheduler.step`, so to within one step)."""
from chipbench.layer_metrics._common import median


def read(ctx):
    w = ctx.facts.get("queue_wait_s")
    if not w or not ctx.facts.get("open_loop"):
        return None
    return max(median(w), 0.0) * 1e3
