"""Grid steps of the paged kernel that read pages someone wrote, over the
steps its grid has: sum of `page_blocks_live` over sum of `page_blocks_grid`
of the program's `engine.decode` and `engine.extend` spans in the traced
stretch (one layer's grid a call; every layer runs the same one). The rest
are steps past a row's frontier, which compute nothing and start no copy. A
program whose spans do not carry the two counts gives None."""
from chipbench.layer_metrics._program_spans import traced_records

CALLS = ("engine.decode", "engine.extend")


def read(ctx):
    recs = traced_records(ctx)
    if not recs:
        return None
    calls = [x[6] for x in recs if x[0] in CALLS and x[6] and "page_blocks_grid" in x[6]]
    grid = sum(a["page_blocks_grid"] for a in calls)
    if not grid:
        return None
    return 100.0 * sum(a["page_blocks_live"] for a in calls) / grid
