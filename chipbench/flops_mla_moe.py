"""Operations and bytes that a decoder with multi-head latent attention and
gated routed experts (`pangu_ultra_moe`) needs, from the configuration's
shapes alone. `flops.py` counts the dense models and `flops_nemotron_h.py` the
hybrid; this file has the same rules: matmul parameters are the weights a
token is multiplied by, embeddings looked up by index do no FLOPs, recomputed
work is never counted.

At the published widths (hidden 7680): MLA 196.58 M parameters a layer, a
dense layer's MLP 424.67 M, a sparse layer outside its routed experts 49.15 M
(shared expert 47.19 M, router 1.97 M), a routed expert 47.19 M.
"""


def entry_width(cfg: dict) -> int:
    """Numbers the latent cache keeps a token a layer: c_kv then k_r."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def mla_matmul_params(cfg: dict) -> int:
    """q_a, q_b, kv_a, kv_b and o. The absorbed path multiplies a token by
    W_UK and W_UV, the two halves of kv_b: the same count."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * (nope + rope)
            + h * entry_width(cfg) + cfg["kv_lora_rank"] * heads * (nope + v) + heads * v * h)


def mla_layer_params(cfg: dict) -> int:
    """The matrices and the two inner norms' gains."""
    return mla_matmul_params(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down at the expert's width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def sparse_mlp_matmul_params_outside_experts(cfg: dict) -> int:
    """The router and the shared expert."""
    return (cfg["hidden_size"] * cfg["n_routed_experts"]
            + 3 * cfg["hidden_size"] * cfg["n_shared_experts"] * cfg["moe_intermediate_size"])


def layers(cfg: dict):
    """(leading dense layers, sparse layers) at the configuration's depth."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def held_params(cfg: dict) -> int:
    """Everything this share holds: the layers with the experts held and
    their four sandwich norms, the embedding and the head over the
    vocabulary slice, the final norm."""
    dense, sparse = layers(cfg)
    h = cfg["hidden_size"]
    per_layer = mla_layer_params(cfg) + 4 * h
    return (dense * (per_layer + dense_mlp_params(cfg))
            + sparse * (per_layer + sparse_mlp_matmul_params_outside_experts(cfg)
                        + cfg["experts_held"][1] * expert_params(cfg))
            + 2 * cfg["vocab_size"] * h + h)


def layer_flops_per_token(cfg: dict) -> float:
    """One token through every layer but its routed experts and its
    attention's scores: 2 x the matmul parameters by layer kind."""
    dense, sparse = layers(cfg)
    return 2.0 * (cfg["num_hidden_layers"] * mla_matmul_params(cfg) + dense * dense_mlp_params(cfg)
                  + sparse * sparse_mlp_matmul_params_outside_experts(cfg))


def head_flops_per_token(cfg: dict) -> float:
    """One token through the head over the vocabulary slice (the tokens whose
    logits are computed: a decode row, a chunk's or a prefill's last)."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def expert_flops_per_assignment(cfg: dict) -> float:
    """One (token, expert) pair: 2 x the expert's three matrices."""
    return 2.0 * expert_params(cfg)


def expert_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of one expert's three matrices: what the gated `moe_gmm` must
    read for an expert that has at least one token."""
    return float(expert_params(cfg) * itemsize)


def gated_moe_gmm_least_seconds(assignments: int, experts_touched: int, cfg: dict, peak: dict) -> float:
    """Least time for the grouped matmuls of the traced calls: the larger of
    the touched experts' bytes over the HBM bandwidth and the pairs' FLOPs
    over the peak."""
    return max(experts_touched * expert_bytes(cfg) / peak["hbm_bytes_per_s"],
               assignments * expert_flops_per_assignment(cfg) / peak["flops_per_s"])


def absorbed_pair_flops(cfg: dict) -> float:
    """One query token against one cached token in ONE layer, absorbed form:
    every head scores the whole entry and sums the value's columns."""
    return 2.0 * cfg["num_attention_heads"] * (entry_width(cfg) + cfg["kv_lora_rank"])


def expanded_pair_flops(cfg: dict) -> float:
    """The same pair in the expanded form (a bucketed prefill): keys
    `nope + rope` wide, values `v_head_dim` wide a head."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def call_pairs(call: dict):
    """(query-token pairs, cached tokens read) of one `engine.decode` span's
    args, a layer: the decode rows each against their context, the chunk's
    queries against what its sequence cached before it (`chunk_context`) and
    against the chunk's own triangle, counted as half."""
    take, start = call.get("chunk_tokens", 0), call.get("chunk_context", 0)
    rows_context = call["context"] - (start + take if take else 0)
    return rows_context + take * start + take * (take + 1) / 2.0, rows_context + (start + take if take else 0)


def mla_paged_attn_least_seconds(calls, cfg: dict, peak: dict, itemsize: int = 2) -> float:
    """Least time for the latent paged kernel over the traced `engine.decode`
    calls (each runs it in every layer): the LARGER of the latent bytes the
    calls had to read (each cached token's entry once a call a layer) over the
    HBM bandwidth and the pairs' FLOPs over the peak."""
    pairs = tokens = 0.0
    for c in calls:
        p, t = call_pairs(c)
        pairs, tokens = pairs + p, tokens + t
    n = cfg["num_hidden_layers"]
    return max(n * tokens * entry_width(cfg) * itemsize / peak["hbm_bytes_per_s"],
               n * pairs * absorbed_pair_flops(cfg) / peak["flops_per_s"])
