"""Helpers for the readers of a served step's period (not a metric: no entry
names it).

Since PR 36 the first read of a decode step's outputs stamps `read_at`
(`time.perf_counter`, the ring's clock) on the step's own `engine.decode`
args; the span itself ends at the dispatch. A step's period is `read_at` of
step j less `read_at` of step j - 1 (in the order of dispatch), counted only
where step j was dispatched ahead: its span ended before step j - 1's
`read_at`. That leaves out the first step after the engine went idle and a
step behind a bucketed prefill or a sync; a pause of the host between two
scheduler calls falls inside a period.

A program that stamps no `read_at` (a commit before PR 36), or a ring that no
longer reaches back to the window, gives None here, and every reader then
reports nothing.
"""
from chipbench.layer_metrics._program_spans import window_records


def periods(recs, hi=None):
    """[(step j's `engine.decode` record, read_at of step j - 1, read_at of
    step j)] for each step j of `recs` dispatched ahead and, with `hi`, read
    by then (the step in flight at the window's close is read in the drain,
    after whatever the harness does there)."""
    steps = sorted((x for x in recs if x[0] == "engine.decode"), key=lambda x: x[1])
    out = []
    for prev, cur in zip(steps, steps[1:]):
        a, b = (prev[6] or {}).get("read_at"), (cur[6] or {}).get("read_at")
        if a is not None and b is not None and cur[2] < a and (hi is None or b <= hi):
            out.append((cur, a, b))
    return out


def window_steps(ctx):
    """(the window's records, their step periods), or None where no step of
    the window carries `read_at` or the ring does not reach the window."""
    recs = window_records(ctx)
    if not recs or not any(x[0] == "engine.decode" and x[6] and "read_at" in x[6] for x in recs):
        return None
    return recs, periods(recs, ctx.facts["t_end"])


def longest(ctx):
    """(the window's records, the longest step period as (record, start,
    end)), None where there is nothing to read; (records, None) where no
    period was counted."""
    got = window_steps(ctx)
    if got is None:
        return None
    recs, per = got
    return recs, max(per, key=lambda p: p[2] - p[1]) if per else None
