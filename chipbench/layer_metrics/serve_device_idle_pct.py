"""1 - union of device op intervals over the traced window."""
from chipbench import xplane


def read(ctx):
    if ctx.ir is None or ctx.mix["loop"] == "train":
        return None
    b = xplane.busy_seconds(ctx.ir)
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
