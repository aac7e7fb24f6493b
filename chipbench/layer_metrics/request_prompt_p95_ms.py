"""p95 of the program's `request.prompt` spans (decode slot to first token:
the prompt streaming one token a step, or one bucketed prefill) over the
requests submitted inside the window."""
from chipbench import harness
from chipbench.layer_metrics._program_spans import request_spans


def read(ctx):
    w = request_spans(ctx, "request.prompt")
    return harness.percentile(w, 95) * 1e3 if w else None
