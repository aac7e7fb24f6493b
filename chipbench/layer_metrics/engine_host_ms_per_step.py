"""Host time of a decode call before the device has its work: the program's
`engine.decode.inputs` (numpy rows, block tables) and `engine.decode.dispatch`
(transfers, the executable's call, adopting the state) spans, mean over the
window's `engine.decode` spans."""
from chipbench.layer_metrics._program_spans import child_seconds, window_records


def read(ctx):
    recs = window_records(ctx)
    if not recs:
        return None
    calls = [x for x in recs if x[0] == "engine.decode"]
    if not calls:
        return None
    host = child_seconds(recs, ("engine.decode.inputs", "engine.decode.dispatch"))
    return sum(host.get(c[3], 0.0) for c in calls) / len(calls) * 1e3
