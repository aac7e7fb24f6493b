"""The host's share of a served step: over the window's step periods
(`_step_spans.py`), the `sched.step` span that read each period's step less
the `engine.*.fetch` spans inside it (the wait for the device), summed, over
the periods summed. The host's work of a step runs beside the device's step
before it; at 100 the host sets the pace. None for a program that stamps no
`read_at` and where no period's read lies inside a `sched.step`."""
import bisect

from chipbench.layer_metrics._step_spans import window_steps


def read(ctx):
    got = window_steps(ctx)
    if got is None:
        return None
    recs, per = got
    steps = sorted((x for x in recs if x[0] == "sched.step"), key=lambda x: x[1])
    starts = [x[1] for x in steps]
    fetch = sorted((x[1], x[2]) for x in recs if x[0].startswith("engine.") and x[0].endswith(".fetch"))
    host = period = 0.0
    for _, a, b in per:
        i = bisect.bisect_right(starts, b) - 1  # the step that read it (steps do not overlap)
        if i < 0 or steps[i][2] < b:
            continue
        lo, hi = steps[i][1], steps[i][2]
        host += hi - lo
        j = bisect.bisect_left(fetch, (lo,))
        while j < len(fetch) and fetch[j][1] <= hi:  # the waits inside it
            host -= fetch[j][1] - fetch[j][0]
            j += 1
        period += b - a
    return 100.0 * host / period if period > 0 else None
