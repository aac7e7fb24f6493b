"""Share of the traced window in which the device is idle (xplane.idle_gaps)
while the host is inside an `engine.*.fetch` span of the program: the slice
of the logits and their copy to the host."""
from chipbench.layer_metrics._program_spans import idle_share


def read(ctx):
    return idle_share(ctx, "fetch")
