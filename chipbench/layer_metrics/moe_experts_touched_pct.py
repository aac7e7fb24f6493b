"""Held experts that got at least one token, over the experts held, a layer
a decode step of the window: the share of the experts' weights a step has to
read."""
from chipbench.layer_metrics._moe_spans import window_calls


def read(ctx):
    calls = [c for c in window_calls(ctx) or () if "rows" in c]
    held = sum(c["moe_layers"] for c in calls) * int(ctx.cfg["experts_held"][1]) if calls else 0
    if not held:
        return None
    return 100.0 * sum(c["moe_experts_touched"] for c in calls) / held
