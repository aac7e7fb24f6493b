"""Median of the program's `request.queue` spans (submit to the step that
gives the request a decode slot) over requests submitted inside the window."""
from chipbench.layer_metrics._common import median
from chipbench.layer_metrics._program_spans import request_spans


def read(ctx):
    w = request_spans(ctx, "request.queue")
    return median(w) * 1e3 if w else None
