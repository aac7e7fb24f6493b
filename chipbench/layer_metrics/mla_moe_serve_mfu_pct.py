"""The whole serve step's share of the chip's peak for a decoder with latent
attention and gated experts, from the PROGRAM's spans (the harness's own
events miss the chunk steps): every token that entered inside the window by
any path (an `engine.decode`'s rows and its chunk's tokens, an
`engine.prefill`'s prompt) x 2 x the matmul parameters it passes through by
layer kind; the head for the tokens whose logits are computed (a row, a
chunk's or a prefill's last); attention by context (absorbed pairs over the
cache, expanded pairs in a bucketed prefill); 2 x an expert's parameters for
every (token, expert) pair the program computed (`moe_assignments`); over
window x peak. Padding rows and padded positions are not counted. None for a
program whose spans lack the counters."""
from chipbench import flops_mla_moe as fl
from chipbench.layer_metrics._program_spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if ctx.peak is None or not recs or "kv_lora_rank" not in ctx.cfg:
        return None
    cfg, need, seen = ctx.cfg, 0.0, 0
    for x in recs:
        a = x[6] or {}
        if "moe_assignments" not in a:
            continue
        if x[0] == "engine.decode" and (not a.get("chunk_tokens") or "chunk_context" in a):
            tokens, heads = a["rows"] + a.get("chunk_tokens", 0), a["rows"] + bool(a.get("chunk_tokens"))
            attention = cfg["num_hidden_layers"] * fl.call_pairs(a)[0] * fl.absorbed_pair_flops(cfg)
        elif x[0] == "engine.prefill":
            tokens, heads = a["tokens"], 1
            attention = (cfg["num_hidden_layers"] * a["tokens"] * (a["tokens"] + 1) / 2.0
                         * fl.expanded_pair_flops(cfg))
        else:
            continue
        seen += 1
        need += (tokens * fl.layer_flops_per_token(cfg) + heads * fl.head_flops_per_token(cfg) + attention
                 + a["moe_assignments"] * fl.expert_flops_per_assignment(cfg))
    if not seen:
        return None
    return 100.0 * need / (ctx.facts["window_s"] * ctx.peak["flops_per_s"])
