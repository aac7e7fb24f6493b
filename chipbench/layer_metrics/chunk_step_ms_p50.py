"""Median of the program's `engine.decode` spans that carried a chunk of a
prompt (`chunk_tokens` > 0) inside the window: the step a long prompt is
paid in, inputs, dispatch and the fetch of its logits included. None where
no step carried one, and for a program whose spans do not say."""
from chipbench.layer_metrics._common import median
from chipbench.layer_metrics._program_spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if not recs:
        return None
    took = [x[2] - x[1] for x in recs if x[0] == "engine.decode" and x[6] and x[6].get("chunk_tokens", 0) > 0]
    return median(took) * 1e3 if took else None
