"""Median step period (`_step_spans.py`) of the window's decode steps that
carried a chunk of a prompt (`chunk_tokens` > 0): the step a long prompt is
paid in, from `read_at` to `read_at`. None for a program that stamps no
`read_at` and where no chunk step was dispatched ahead."""
from chipbench.layer_metrics._common import median
from chipbench.layer_metrics._step_spans import window_steps


def read(ctx):
    got = window_steps(ctx)
    if got is None:
        return None
    took = [b - a for cur, a, b in got[1] if cur[6].get("chunk_tokens", 0) > 0]
    return median(took) * 1e3 if took else None
