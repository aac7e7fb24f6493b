"""Milliseconds a second the host spent in the garbage collector inside the
window: the program's `host.gc` records (every collection of generation 1
or 2, and any of 1 ms or more) over the window's seconds. 0.0 where no
collection was recorded; None for a program that stamps no `read_at` (a
commit before the `host.gc` records)."""
from chipbench.layer_metrics._step_spans import window_steps


def read(ctx):
    got = window_steps(ctx)
    if got is None:
        return None
    seconds = ctx.facts["t_end"] - ctx.facts["t_start"]
    return 1e3 * sum(x[2] - x[1] for x in got[0] if x[0] == "host.gc") / seconds
