"""The flash dq kernel's share of its roofline, the kernel found by its
name in the trace (`flash_dq`): see _program_spans.flash_kernel_roofline."""
from chipbench.layer_metrics._program_spans import flash_kernel_roofline


def read(ctx):
    return flash_kernel_roofline(ctx, "dq")
