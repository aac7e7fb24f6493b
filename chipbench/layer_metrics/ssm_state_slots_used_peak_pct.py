"""High-water of the recurrent-state slots bound to a sequence
(`state_slots` of the program's `sched.step` spans inside the window) over
the slots the pool holds (`max_batch`)."""
from chipbench.layer_metrics._program_spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if not recs:
        return None
    used = [x[6]["state_slots"] for x in recs if x[0] == "sched.step" and x[6] and "state_slots" in x[6]]
    if not used:
        return None
    return 100.0 * max(used) / int(ctx.mix["engine"]["max_batch"])
