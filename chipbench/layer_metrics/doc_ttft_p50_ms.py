"""Due time to first token, median over the window's requests: the cell's
TTFT where too few requests fit a window for a tail."""
from chipbench.layer_metrics._common import median


def read(ctx):
    t = ctx.facts.get("ttft_s")
    if not t or ctx.facts.get("open_loop"):
        return None
    return median(t) * 1e3
