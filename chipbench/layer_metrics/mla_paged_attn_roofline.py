"""The latent paged kernel against its roofline: the least time for the
traced `engine.decode` calls (`flops_mla_moe.mla_paged_attn_least_seconds`:
the LARGER of the latent bytes they had to read over the HBM bandwidth and 2 x
heads x (entry + value width) FLOPs a query-token pair, a chunk's own triangle
counted as half, over the peak) over the device time of the ops named
`mla_paged_attn`. None for a program whose spans lack `chunk_context` or whose
trace holds no such op."""
from chipbench import flops_mla_moe as fl
from chipbench import xplane
from chipbench.layer_metrics._program_spans import named, traced_records


def read(ctx):
    if ctx.ir is None or ctx.peak is None:
        return None
    recs = traced_records(ctx)
    took = xplane.seconds_by(ctx.ir, named("mla_paged_attn"))
    if not recs or took <= 0:
        return None
    calls = [x[6] for x in recs if x[0] == "engine.decode" and x[6] and "context" in x[6]]
    if not calls or any(c.get("chunk_tokens") and "chunk_context" not in c for c in calls):
        return None
    return 100.0 * fl.mla_paged_attn_least_seconds(calls, ctx.cfg, ctx.peak) / took
