"""The whole serve step's share of the chip's peak for a decoder with latent
attention, a token selector and gated experts, from the PROGRAM's spans: every
token that entered inside the window by any path (an `engine.decode`'s rows
and its chunk's tokens, an `engine.prefill`'s prompt) x 2 x the matmul
parameters it passes through by layer kind (the indexer's among them); the
head for the tokens whose logits are computed; the index scores by
`index_positions_scored`; the attention, absorbed on every path, over the
chosen positions of the tiles that selected and over the whole context of the
others; 2 x an expert's parameters for every (token, expert) pair the program
computed (`moe_assignments`); over window x peak. Padding is not counted. None
for a program whose spans lack the counters."""
from chipbench import flops_dsa_moe as fl
from chipbench.layer_metrics._program_spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if ctx.peak is None or not recs or "index_topk" not in ctx.cfg:
        return None
    cfg, need, seen = ctx.cfg, 0.0, 0
    for x in recs:
        a = x[6] or {}
        if "moe_assignments" not in a or "index_positions_live" not in a:
            continue
        if x[0] == "engine.decode":
            tokens, heads = a["rows"] + a.get("chunk_tokens", 0), a["rows"] + bool(a.get("chunk_tokens"))
        elif x[0] == "engine.prefill":
            tokens, heads = a["tokens"], 1
        else:
            continue
        seen += 1
        scored, attended = a.get("index_positions_scored", 0), a.get("sparse_positions_attended", 0)
        pairs = attended + a["index_positions_live"] - scored
        need += (tokens * fl.layer_flops_per_token(cfg) + heads * fl.head_flops_per_token(cfg)
                 + cfg["num_hidden_layers"] * (scored * fl.index_pair_flops(cfg) + pairs * fl.sparse_pair_flops(cfg))
                 + a["moe_assignments"] * fl.expert_flops_per_assignment(cfg))
    if not seen:
        return None
    return 100.0 * need / (ctx.facts["window_s"] * ctx.peak["flops_per_s"])
