"""paged_attn_roofline's arithmetic on the kernel found by its name: the KV
bytes the traced decode steps had to read (`context` of the program's
`engine.decode` spans in the traced stretch, flops.paged_attn_bytes) over the
HBM bandwidth, over the device time of the ops named `paged_attn`."""
from chipbench import flops, xplane
from chipbench.layer_metrics._program_spans import named, traced_records


def read(ctx):
    if ctx.ir is None or ctx.peak is None:
        return None
    recs = traced_records(ctx)
    if not recs:
        return None
    took = xplane.seconds_by(ctx.ir, named("paged_attn"))
    context = sum(x[6]["context"] for x in recs if x[0] == "engine.decode" and x[6])
    if took <= 0 or not context:
        return None
    least = flops.paged_attn_bytes(context, ctx.cfg) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / took
