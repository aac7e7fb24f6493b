"""The attention over chosen positions against its roofline: the least time
for the traced engine calls' sparse tiles (`flops_dsa_moe.
dsa_sparse_attn_least_seconds`: the LARGER of 2 x heads x (entry + value width)
FLOPs a (query, chosen position) pair over the peak and the chosen entries'
bytes, each query's own, over the HBM bandwidth) over the device time of the
ops named `mla_sparse_paged_attn`. None for a program whose spans lack the
counters or whose trace holds no such op."""
from chipbench import flops_dsa_moe as fl
from chipbench import xplane
from chipbench.layer_metrics._dsa_spans import traced_totals
from chipbench.layer_metrics._program_spans import named


def read(ctx):
    if ctx.ir is None or ctx.peak is None or "index_topk" not in ctx.cfg:
        return None
    t = traced_totals(ctx)
    took = xplane.seconds_by(ctx.ir, named("mla_sparse_paged_attn"))
    if not t or took <= 0:
        return None
    return 100.0 * fl.dsa_sparse_attn_least_seconds(t["sparse_positions_attended"], ctx.cfg, ctx.peak) / took
