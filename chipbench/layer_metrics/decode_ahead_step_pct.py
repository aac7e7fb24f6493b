"""Steps whose program was dispatched before the step before it was read, over
the steps that ran a program: `ahead` 1 over the window's `sched.step` spans
that carry `ahead` or `sync` (a step that ran no program carries neither). How
often the device has its next program queued behind the one that runs: the
rest are the first step after the engine went idle, a step behind a bucketed
prefill, and the steps behind a preemption, a cancellation, an expiry or a
handoff. None where no step ran a program inside the window, and for a
program whose steps do not say (a commit before the counter)."""
from chipbench.layer_metrics._program_spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if not recs:
        return None
    steps = [x[6] for x in recs if x[0] == "sched.step" and x[6] and ("ahead" in x[6] or "sync" in x[6])]
    if not steps:
        return None
    return 100.0 * sum(1 for a in steps if a.get("ahead") == 1) / len(steps)
