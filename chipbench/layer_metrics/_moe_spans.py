"""Helpers for the readers of the expert layer's counters (not a metric: no
entry names it). The program's `engine.decode` / `engine.prefill` spans carry
`moe_assignments` (token-expert pairs computed here, summed over the expert
layers), `moe_experts_touched` (held experts with at least one token, summed
over the expert layers) and `moe_layers`. A program whose spans lack them (a
commit before the expert layer, a model without one) gives None."""
from chipbench.layer_metrics._program_spans import traced_records, window_records

CALLS = ("engine.decode", "engine.prefill")


def _calls(recs):
    if not recs:
        return None
    calls = [x[6] for x in recs if x[0] in CALLS and x[6] and "moe_assignments" in x[6]]
    return calls or None


def window_calls(ctx):
    """Args of the engine calls with expert counters inside the measured window."""
    return _calls(window_records(ctx))


def traced_calls(ctx):
    """The same inside the traced stretch."""
    return _calls(traced_records(ctx))


def tokens(call):
    """Real tokens of an engine call: a decode step's rows, a prefill's prompt."""
    return call.get("rows", call.get("tokens", 0))
