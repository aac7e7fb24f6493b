"""(token, expert) pairs the expert layers computed here a token a layer,
over the engine calls of the window: `num_experts_per_tok` x held / routed
where routing is even (22 x 128 / 512 = 5.5)."""
from chipbench.layer_metrics._moe_spans import tokens, window_calls


def read(ctx):
    calls = window_calls(ctx)
    if not calls:
        return None
    slots = sum(tokens(c) * c["moe_layers"] for c in calls)
    return sum(c["moe_assignments"] for c in calls) / slots if slots else None
