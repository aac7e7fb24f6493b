"""Device time of `copy` ops a call of the engine: seconds of the first
device's ops of category `copy` inside the traced window, over the program's
`engine.decode`, `engine.prefill` and `engine.extend` spans of the traced
stretch. A K/V write that XLA cannot make in place shows here as two copies of
a layer's whole pool an array a call, whatever the rows; a write in place
leaves the small copies of a step's activations. None without a trace, a
device plane or a call."""
from chipbench import xplane
from chipbench.layer_metrics._program_spans import traced_records

CALLS = ("engine.decode", "engine.prefill", "engine.extend")


def read(ctx):
    if ctx.ir is None or not ctx.ir["devices"]:
        return None
    calls = sum(1 for x in traced_records(ctx) or () if x[0] in CALLS)
    if not calls:
        return None
    return xplane.seconds_by(ctx.ir, lambda name, cat: cat == "copy") / calls * 1e3
