"""Helpers for the readers of the token selector's counters (not a metric: no
entry names it). The program's `engine.decode` / `.prefill` / `.extend` spans of
a model that selects cached tokens carry, a layer: `index_positions_live` (the
sum over the step's queries of their contexts), `index_positions_selected`
(positions attended, `index_topk` a query at most), `sparse_queries` (queries
past `index_topk`) and, for the tiles of queries that went through the
selector, `index_positions_scored`, `index_keys_read` and
`sparse_positions_attended`. A program whose spans lack them (a commit before
the selector, a model without one) gives None."""
from chipbench.layer_metrics._program_spans import traced_records, window_records

CALLS = ("engine.decode", "engine.prefill", "engine.extend")
KEYS = ("index_positions_live", "index_positions_selected", "sparse_queries", "index_positions_scored",
        "index_keys_read", "sparse_positions_attended")


def _totals(recs):
    if not recs:
        return None
    calls = [x[6] for x in recs if x[0] in CALLS and x[6] and "index_positions_live" in x[6]]
    if not calls:
        return None
    return {k: sum(c.get(k, 0) for c in calls) for k in KEYS}


def window_totals(ctx):
    """Sums of the counters over the engine calls inside the measured window."""
    return _totals(window_records(ctx))


def traced_totals(ctx):
    """The same inside the traced stretch."""
    return _totals(traced_records(ctx))
