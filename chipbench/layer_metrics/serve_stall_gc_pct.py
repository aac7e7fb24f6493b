"""The share of the window's longest step period (`serve_stall_ms_max`)
that the program's `host.gc` records cover: whether that stall was a
collection. None for a program that stamps no `read_at` and where no step
was dispatched ahead."""
from chipbench.layer_metrics._step_spans import longest


def read(ctx):
    got = longest(ctx)
    if got is None or got[1] is None:
        return None
    recs, (_, a, b) = got
    inside = sum(min(x[2], b) - max(x[1], a) for x in recs if x[0] == "host.gc" and x[2] > a and x[1] < b)
    return 100.0 * inside / (b - a)
