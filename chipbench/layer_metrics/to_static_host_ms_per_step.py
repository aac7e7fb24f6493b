"""What a `to_static` call spends on the host outside the executable's call:
median over the traced stretch's `to_static.call` spans of the span less its
`to_static.dispatch` child (guard, gathering the state, writing it back). A
floor under the host's cost of a step: the dispatch itself also holds Python,
and blocks when the device's queue is full."""
from chipbench.layer_metrics._common import median
from chipbench.layer_metrics._program_spans import child_seconds, traced_records


def read(ctx):
    if ctx.mix["loop"] != "train":
        return None
    recs = traced_records(ctx)
    if not recs:
        return None
    calls = [x for x in recs if x[0] == "to_static.call"]
    if not calls:
        return None
    dispatch = child_seconds(recs, ("to_static.dispatch",))
    return median([(c[2] - c[1]) - dispatch.get(c[3], 0.0) for c in calls]) * 1e3
