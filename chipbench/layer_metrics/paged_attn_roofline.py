"""The paged decode kernel's share of its roofline: the KV bytes the
traced steps had to read (context tokens of every row of every decode call
in the traced stretch, chipbench.flops.paged_attn_bytes) over the chip's
HBM bandwidth, over the device time of the Pallas kernels in the trace that
read the pool (told by the pool's shape among their operands: the kernel
has no name of its own in the trace today). Memory-bound by construction
(one query token a row)."""
from chipbench import flops, xplane
from chipbench.layer_metrics._common import pallas_with_operand, traced_host_window


def read(ctx):
    if ctx.ir is None or ctx.peak is None:
        return None
    win = traced_host_window(ctx)
    if win is None:
        return None
    eng, cfg = ctx.mix["engine"], ctx.cfg
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    pool = f"bf16[{eng['num_blocks']},{cfg['num_key_value_heads']},{eng['block_size']},{d}]"
    took = xplane.seconds_by(ctx.ir, pallas_with_operand(pool))
    context = sum(e[3] for e in ctx.events if e[0] == "decode" and win[0] <= e[1] < win[1])
    if took <= 0 or not context:
        return None
    least = flops.paged_attn_bytes(context, ctx.cfg) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / took
