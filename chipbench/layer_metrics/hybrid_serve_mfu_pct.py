"""The whole serve step's share of the chip's peak for the hybrid decoder:
per token processed inside the window, 2 x the matmul parameters it passes
through by layer kind and the recurrence's FLOPs (`flops_nemotron_h.
dense_flops_per_token`), plus 2 x an expert's parameters for every
(token, expert) pair the program computed (`moe_assignments` of its engine
spans), over window x peak. Padding rows and padded prefill positions are
not counted."""
from chipbench import flops_nemotron_h as fl
from chipbench.layer_metrics._common import in_window
from chipbench.layer_metrics._moe_spans import window_calls


def read(ctx):
    calls = window_calls(ctx)
    if ctx.peak is None or not calls:
        return None
    toks = sum(e[2] for e in in_window(ctx, ctx.events) if e[0] in ("decode", "prefill"))
    if not toks:
        return None
    need = (fl.dense_flops_per_token(ctx.cfg) * toks
            + fl.expert_flops_per_assignment(ctx.cfg) * sum(c["moe_assignments"] for c in calls))
    return 100.0 * need / (ctx.facts["window_s"] * ctx.peak["flops_per_s"])
