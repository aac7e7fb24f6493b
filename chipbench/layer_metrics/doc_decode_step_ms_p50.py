"""`decode_step_ms_p50` where the cell's end-to-end metric is tokens per
second, not the gap between tokens: one row a step, so a step is a token."""
from chipbench.layer_metrics.decode_step_ms_p50 import read  # noqa: F401
