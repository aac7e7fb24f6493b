"""Prompt tokens that entered in chunks beside the decode rows, over all the
prompt tokens that entered (chunks, streamed rows, bucketed prefills): sum of
`chunk_tokens` over sum of `prompt_tokens` of the window's `sched.step` spans.
How often the chunk step engages: the rest arrived with nothing in flight and
took a bucketed prefill, or streamed a token a step (a pool with recurrent
state). None where no prompt token entered inside the window, and for a
program whose steps do not count them."""
from chipbench.layer_metrics._program_spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if not recs:
        return None
    steps = [x[6] for x in recs if x[0] == "sched.step" and x[6] and "prompt_tokens" in x[6]]
    entered = sum(a["prompt_tokens"] for a in steps)
    if not entered:
        return None
    return 100.0 * sum(a.get("chunk_tokens", 0) for a in steps) / entered
