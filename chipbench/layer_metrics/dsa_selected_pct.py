"""Cached positions the queries of the window attended to, over the positions
they could see: sum of `index_positions_selected` over sum of
`index_positions_live` of the window's engine calls. How far the selector cuts
the attention's work: 100 where every context is inside `index_topk`. None
for a program whose spans lack the counters."""
from chipbench.layer_metrics._dsa_spans import window_totals


def read(ctx):
    t = window_totals(ctx)
    if not t or not t["index_positions_live"]:
        return None
    return 100.0 * t["index_positions_selected"] / t["index_positions_live"]
