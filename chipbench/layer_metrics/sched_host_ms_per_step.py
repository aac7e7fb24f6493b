"""Host time of `scheduler.step` outside the engine: the span of each step
less the spans of the engine calls inside it, mean over the window's steps."""


def read(ctx):
    lo, hi = ctx.facts["t_start"], ctx.facts["t_end"]
    steps = ctx.spans.durations("sched_step", lo, hi)
    if not steps:
        return None
    inner = ctx.spans.total("engine_decode", lo, hi) + ctx.spans.total("engine_prefill", lo, hi)
    return (sum(steps) - inner) / len(steps) * 1e3
