"""Operations and bytes that the hybrid decoder (`nemotron_h`: Mamba-2,
attention, LatentMoE layers) needs, from the configuration's shapes alone.
`flops.py` counts the dense models; this file is the hybrid's, with the same
rules: matmul parameters are the weights a token is multiplied by, embeddings
looked up by index do no FLOPs, recomputed work is never counted.

Per layer, at the published widths (hidden 4096): `M` 109.64 M parameters,
`*` 35.66 M, `E` 54.53 M outside the experts and 5.505 M an expert.
"""


def _dims(cfg: dict) -> dict:
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return {"h": cfg["hidden_size"], "inner": inner, "conv": inner + 2 * gn,
            "in": 2 * inner + 2 * gn + cfg["mamba_num_heads"],
            "q": cfg["num_attention_heads"] * cfg["head_dim"],
            "kv": cfg["num_key_value_heads"] * cfg["head_dim"]}


def mamba_layer_params(cfg: dict) -> int:
    """Every leaf of an `M` layer: in_proj, conv taps and bias, dt_bias,
    A_log, D, the gated norm, out_proj, the layer's RMSNorm."""
    d = _dims(cfg)
    return (d["h"] * d["in"] + cfg["conv_kernel"] * d["conv"] + d["conv"] + 3 * cfg["mamba_num_heads"]
            + d["inner"] + d["inner"] * d["h"] + d["h"])


def mamba_matmul_params(cfg: dict) -> int:
    d = _dims(cfg)
    return d["h"] * d["in"] + d["inner"] * d["h"]


def attention_layer_params(cfg: dict) -> int:
    d = _dims(cfg)
    return 2 * d["h"] * d["q"] + 2 * d["h"] * d["kv"] + d["h"]


def attention_matmul_params(cfg: dict) -> int:
    return attention_layer_params(cfg) - cfg["hidden_size"]


def moe_layer_params_outside_experts(cfg: dict) -> int:
    """Router and its correction bias, the two latent projections, the
    shared expert, the layer's RMSNorm."""
    h = cfg["hidden_size"]
    return (h * cfg["n_routed_experts"] + cfg["n_routed_experts"] + 2 * h * cfg["moe_latent_size"]
            + 2 * h * cfg["moe_shared_expert_intermediate_size"] + h)


def moe_matmul_params_outside_experts(cfg: dict) -> int:
    return moe_layer_params_outside_experts(cfg) - cfg["n_routed_experts"] - cfg["hidden_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert: up and down between the latent and its width."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def held_params(cfg: dict) -> int:
    """Everything this share holds: the layers of the pattern with the
    experts held, the embedding and the head over the vocabulary slice, the
    final norm."""
    pat = cfg["hybrid_override_pattern"]
    per = {"M": mamba_layer_params(cfg), "*": attention_layer_params(cfg),
           "E": moe_layer_params_outside_experts(cfg) + cfg["experts_held"][1] * expert_params(cfg)}
    return sum(per[c] for c in pat) + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def scan_flops_per_token(cfg: dict) -> float:
    """One step of one `M` layer's recurrence: decay x h, dt x (x outer B),
    their sum (3 a state element), and h . C (2), over heads x head_dim x
    state; and the conv's taps."""
    state = cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state_size"]
    return 5.0 * state + 2.0 * cfg["conv_kernel"] * _dims(cfg)["conv"]


def dense_flops_per_token(cfg: dict) -> float:
    """One token through everything but the routed experts: 2 x matmul
    parameters by layer kind, the head over the vocabulary slice, and the
    recurrence (attention's own FLOPs left out, as `flops.serve_flops_per_token`
    leaves them: one layer of eleven at contexts under 1024)."""
    pat = cfg["hybrid_override_pattern"]
    per = {"M": 2.0 * mamba_matmul_params(cfg) + scan_flops_per_token(cfg),
           "*": 2.0 * attention_matmul_params(cfg),
           "E": 2.0 * moe_matmul_params_outside_experts(cfg)}
    return sum(per[c] for c in pat) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def expert_flops_per_assignment(cfg: dict) -> float:
    """One (token, expert) pair: 2 x the expert's two matrices."""
    return 2.0 * expert_params(cfg)


def expert_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of one expert's two matrices: what `moe_gmm` must read for an
    expert that has at least one token."""
    return float(expert_params(cfg) * itemsize)


def moe_gmm_least_seconds(assignments: int, experts_touched: int, cfg: dict, peak: dict) -> float:
    """Least time for the grouped matmuls of the traced calls: the larger
    of the touched experts' bytes over the HBM bandwidth and the pairs'
    FLOPs over the peak."""
    return max(experts_touched * expert_bytes(cfg) / peak["hbm_bytes_per_s"],
               assignments * expert_flops_per_assignment(cfg) / peak["flops_per_s"])
