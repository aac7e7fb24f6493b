"""Median step period (`_step_spans.py`) of the window's plain decode steps
(`chunk_tokens` 0): `read_at` to `read_at` of steps dispatched ahead, the
step the users of a served stream feel. None for a program that stamps no
`read_at` and where no plain step was dispatched ahead."""
from chipbench.layer_metrics._common import median
from chipbench.layer_metrics._step_spans import window_steps


def read(ctx):
    got = window_steps(ctx)
    if got is None:
        return None
    took = [b - a for cur, a, b in got[1] if not cur[6].get("chunk_tokens")]
    return median(took) * 1e3 if took else None
