"""The flash forward, dq and dkdv kernels' share of their roofline, taken
together: the least time the chip could take for the three (FLOPs and bytes
from shapes, chipbench.flops; each kernel by its own bound) over the device
time of the Pallas kernels in the trace whose operands are [batch x heads,
seq, head_dim] (the three have no names of their own in the trace today).
On a mesh the shapes are one device's share (heads over mp, rows over dp)."""
from chipbench import flops, xplane
from chipbench.layer_metrics._common import pallas_with_operand, steps_in_trace


def read(ctx):
    if ctx.ir is None or ctx.peak is None:
        return None
    steps = steps_in_trace(ctx.ir)
    mesh = ctx.mix.get("mesh") or {}
    f = ctx.facts
    batch = f["batch"] // int(mesh.get("dp", 1))
    heads = f["heads"] // int(mesh.get("mp", 1))
    d = ctx.cfg["hidden_size"] // ctx.cfg["num_attention_heads"]
    layers = ctx.cfg["num_hidden_layers"]
    took = xplane.seconds_by(ctx.ir, pallas_with_operand(f"bf16[{batch * heads},{f['seq']},{d}]"))
    if took <= 0 or not steps:
        return None  # no kernel the reduction can hold on to: no number
    need_f = flops.flash_attn_flops(batch, heads, f["seq"], d)
    need_b = flops.flash_attn_bytes(batch, heads, f["seq"], d)
    least = sum(flops.roofline_seconds(need_f[k], need_b[k], ctx.peak)[0] for k in need_f)
    return 100.0 * steps * layers * least / took
