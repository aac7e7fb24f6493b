"""Operations and bytes that the algorithm needs, from shapes alone.

Matmul parameters are the weights that a token is multiplied by; embeddings
looked up by index do no FLOPs. Recomputed work is never counted.
"""


def ernie_matmul_params(cfg: dict) -> int:
    """Weights every token passes through in ErnieForMaskedLM: per layer
    q, k, v, out (4 h^2) and the two ffn matrices (2 h f); the MLM head's
    transform (h^2) and the tied decoder (h V)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 4 * h * h + 2 * h * f
    return cfg["num_hidden_layers"] * per_layer + h * h + h * cfg["vocab_size"]


def llama_matmul_params(cfg: dict) -> int:
    """Per layer q, o (2 h^2), k, v (2 h kv d) and gate, up, down (3 h f);
    plus the LM head (h V)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    d = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    per_layer = 2 * h * h + 2 * h * kv + 3 * h * f
    return cfg["num_hidden_layers"] * per_layer + h * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of a bidirectional encoder: 6 x matmul
    parameters, and attention's QK^T and PV: 2 matmuls x 2 FLOPs x seq x h
    forward per layer, three times that with the backward = 12 L h seq."""
    return (6.0 * ernie_matmul_params(cfg)
            + 12.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq)


def serve_flops_per_token(cfg: dict) -> float:
    """One token through the decoder: 2 x matmul parameters (attention's
    own FLOPs left out: under 2% at these contexts, and leaving them out
    keeps the share below the truth, never above)."""
    return 2.0 * llama_matmul_params(cfg)


def flash_attn_flops(batch: int, heads: int, seq: int, head_dim: int) -> dict:
    """Matmul FLOPs the algorithm needs, per kernel, full (non-causal)
    attention: forward QK^T and PV (2 matmuls); dq needs dP = dO V^T and
    dQ = dS K (2); dkdv needs dV = P^T dO and dK = dS^T Q (2). The
    recomputation of S = QK^T inside dq and dkdv is recomputed work and is
    not counted."""
    one = 2.0 * batch * heads * seq * seq * head_dim
    return {"fwd": 2 * one, "dq": 2 * one, "dkdv": 2 * one}


def flash_attn_bytes(batch: int, heads: int, seq: int, head_dim: int,
                     itemsize: int = 2) -> dict:
    """Least HBM traffic per kernel: each operand read once, each result
    written once (q, k, v, o, do, dq, dk, dv are [B, H, S, D])."""
    t = batch * heads * seq * head_dim * itemsize
    return {"fwd": 4 * t, "dq": 5 * t, "dkdv": 6 * t}


def paged_attn_bytes(context_tokens: int, cfg: dict, itemsize: int = 2) -> float:
    """KV bytes one decode step must read for `context_tokens` tokens of
    context summed over the rows of the step, all layers: K and V, kv heads
    x head_dim each."""
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2.0 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * d * itemsize * context_tokens


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """(least seconds the chip could take, which bound)."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
