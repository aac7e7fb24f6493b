"""The index-score kernel against its roofline: the least time for the traced
engine calls' index scores (`flops_dsa_moe.dsa_index_least_seconds`: the LARGER
of 2 x heads x width FLOPs a (query, live position) pair over the peak and the
index keys' bytes, each row's context once a tile of queries, over the HBM
bandwidth; the tiles that went through the selector, by the program's
counters) over the device time of the ops named `dsa_index`. None for a
program whose spans lack the counters or whose trace holds no such op."""
from chipbench import flops_dsa_moe as fl
from chipbench import xplane
from chipbench.layer_metrics._dsa_spans import traced_totals
from chipbench.layer_metrics._program_spans import named


def read(ctx):
    if ctx.ir is None or ctx.peak is None or "index_topk" not in ctx.cfg:
        return None
    t = traced_totals(ctx)
    took = xplane.seconds_by(ctx.ir, named("dsa_index"))
    if not t or took <= 0:
        return None
    return 100.0 * fl.dsa_index_least_seconds(t["index_positions_scored"], t["index_keys_read"],
                                              ctx.cfg, ctx.peak) / took
