"""Builds the program's DeepseekV32ForCausalLM from a configuration file's
sizes WITHOUT materialising its float32 initial weights: the constructor runs
under `jax.eval_shape` (see llama_causal_lm.py: 4.6 B parameters would be
18.5 GB in float32), and the caller assigns the served bfloat16 values from
chipbench.weights. The configuration file holds the published config's keys;
the model takes the ones that shape it.
"""
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "index_n_heads", "index_head_dim", "index_topk", "intermediate_size", "moe_intermediate_size",
        "n_routed_experts", "experts_held", "num_experts_per_tok", "n_shared_experts", "n_group", "topk_group",
        "routed_scaling_factor", "rms_norm_eps", "rope_theta", "rope_scaling", "initializer_range")


def build(cfg: dict):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v32 import DeepseekV32ForCausalLM

    box = {}

    def construct():
        box["model"] = DeepseekV32ForCausalLM(**{k: cfg[k] for k in KEYS})
        return 0

    jax.eval_shape(construct)
    paddle.seed(0)  # the constructor's draws left a traced key behind
    model = box["model"]
    model.eval()
    return model
