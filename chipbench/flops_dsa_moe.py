"""Operations and bytes that a decoder with multi-head latent attention, a
learned token selector (DeepSeek sparse attention) and gated routed experts
(`deepseek_v32`) needs, from the configuration's shapes alone. The same rules
as `flops_mla_moe.py`, whose counts of the shared parts this file takes:
matmul parameters are the weights a token is multiplied by, embeddings looked
up by index do no FLOPs, recomputed work is never counted.

At the published widths (hidden 7168): MLA 187.11 M parameters a layer, the
indexer 13.96 M, a dense layer's MLP 396.36 M, a sparse layer outside its
routed experts 45.88 M (shared expert 44.04 M, router 1.84 M), a routed
expert 44.04 M.
"""
from chipbench import flops_mla_moe as fl

entry_width = fl.entry_width
expert_flops_per_assignment = fl.expert_flops_per_assignment
head_flops_per_token = fl.head_flops_per_token


def indexer_matmul_params(cfg: dict) -> int:
    """The index queries from the query's latent, the index key and the head
    weights from the layer's input."""
    j, d = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * j * d + cfg["hidden_size"] * (d + j)


def layer_flops_per_token(cfg: dict) -> float:
    """One token through every layer but its routed experts, its index scores
    and its attention's scores: 2 x the matmul parameters by layer kind."""
    return fl.layer_flops_per_token(cfg) + 2.0 * cfg["num_hidden_layers"] * indexer_matmul_params(cfg)


def held_params(cfg: dict) -> int:
    """Everything this share holds (the indexer's LayerNorm and the router's
    correction bias counted; two norms a layer where `flops_mla_moe` counts
    the sandwich's four)."""
    _, sparse = fl.layers(cfg)
    n, h = cfg["num_hidden_layers"], cfg["hidden_size"]
    return (fl.held_params(cfg) - 2 * n * h + n * (indexer_matmul_params(cfg) + 2 * cfg["index_head_dim"])
            + sparse * cfg["n_routed_experts"])


def index_pair_flops(cfg: dict) -> float:
    """One query against one live cached position in ONE layer: every index
    head's dot product (the ReLU, the weight and the sum are not matmul work)."""
    return 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]


def index_key_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of one cached index key."""
    return float(cfg["index_head_dim"] * itemsize)


def sparse_pair_flops(cfg: dict) -> float:
    """One query against one CHOSEN position in ONE layer, absorbed form."""
    return fl.absorbed_pair_flops(cfg)


def entry_bytes(cfg: dict, itemsize: int = 2, lanes: int = 128) -> float:
    """Bytes of one cached latent entry as the pool keeps it: its width in
    whole lane tiles (576 -> 640)."""
    return float(-(-entry_width(cfg) // lanes) * lanes * itemsize)


def dsa_index_least_seconds(live: int, tiles_live: int, cfg: dict, peak: dict) -> float:
    """Least time for the index scores of the traced calls, all layers: the
    larger of the (query, live position) pairs' FLOPs over the peak and the
    index keys' bytes (`tiles_live`: live positions summed over query TILES,
    each position's key read once a tile) over the HBM bandwidth."""
    n = cfg["num_hidden_layers"]
    return max(n * live * index_pair_flops(cfg) / peak["flops_per_s"],
               n * tiles_live * index_key_bytes(cfg) / peak["hbm_bytes_per_s"])


def dsa_sparse_attn_least_seconds(selected: int, cfg: dict, peak: dict) -> float:
    """Least time for the attention over the chosen positions of the traced
    calls' SPARSE queries, all layers: the larger of the (query, chosen
    position) pairs' FLOPs over the peak and their entries' bytes (each
    query's own) over the HBM bandwidth."""
    n = cfg["num_hidden_layers"]
    return max(n * selected * sparse_pair_flops(cfg) / peak["flops_per_s"],
               n * selected * entry_bytes(cfg) / peak["hbm_bytes_per_s"])
