"""The gated grouped matmul over the experts held against its roofline, as
`moe_gmm_roofline` with an expert's THREE matrices: the least time for the
traced engine calls (`flops_mla_moe.gated_moe_gmm_least_seconds`: the larger
of `moe_experts_touched` x an expert's bytes over the HBM bandwidth and
`moe_assignments` x an expert's FLOPs over the peak) over the device time of
the ops named `moe_gmm`."""
from chipbench import flops_mla_moe as fl
from chipbench import xplane
from chipbench.layer_metrics._moe_spans import traced_calls
from chipbench.layer_metrics._program_spans import named


def read(ctx):
    if ctx.ir is None or ctx.peak is None or "moe_intermediate_size" not in ctx.cfg:
        return None
    calls = traced_calls(ctx)
    took = xplane.seconds_by(ctx.ir, named("moe_gmm"))
    if not calls or took <= 0:
        return None
    least = fl.gated_moe_gmm_least_seconds(sum(c["moe_assignments"] for c in calls),
                                           sum(c["moe_experts_touched"] for c in calls), ctx.cfg, ctx.peak)
    return 100.0 * least / took
