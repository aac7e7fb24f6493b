"""Rows that carry a request over rows the decode program runs: sum of
`rows` over sum of `bucket` of the window's `engine.decode` spans. The rest
are pad rows the paged kernel's grid visits for nothing."""
from chipbench.layer_metrics._program_spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if not recs:
        return None
    calls = [x[6] for x in recs if x[0] == "engine.decode" and x[6]]
    bucket = sum(a["bucket"] for a in calls)
    if not bucket:
        return None
    return 100.0 * sum(a["rows"] for a in calls) / bucket
