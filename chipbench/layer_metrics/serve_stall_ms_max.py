"""The longest step period of the window (`_step_spans.py`): a stall of the
host or of the device between two steps, whole. Near the step in a quiet
run. None for a program that stamps no `read_at` and where no step was
dispatched ahead."""
from chipbench.layer_metrics._step_spans import longest


def read(ctx):
    got = longest(ctx)
    if got is None or got[1] is None:
        return None
    _, a, b = got[1]
    return (b - a) * 1e3
