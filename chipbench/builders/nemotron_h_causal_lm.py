"""Builds the program's NemotronHForCausalLM from a configuration file's
sizes WITHOUT materialising its float32 initial weights: the constructor runs
under `jax.eval_shape` (see llama_causal_lm.py: 4.6 B parameters would be
18.6 GB in float32), and the caller assigns the served bfloat16 values from
chipbench.weights. The configuration file holds the published config's keys;
the model takes the ones that shape it.
"""
KEYS = ("vocab_size", "hidden_size", "hybrid_override_pattern", "num_attention_heads",
        "num_key_value_heads", "head_dim", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
        "n_groups", "conv_kernel", "n_routed_experts", "experts_held", "num_experts_per_tok",
        "moe_latent_size", "moe_intermediate_size", "moe_shared_expert_intermediate_size",
        "routed_scaling_factor", "layer_norm_epsilon", "initializer_range")


def build(cfg: dict):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM

    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers disagree")
    box = {}

    def construct():
        box["model"] = NemotronHForCausalLM(**{k: cfg[k] for k in KEYS})
        return 0

    jax.eval_shape(construct)
    paddle.seed(0)  # the constructor's draws left a traced key behind
    model = box["model"]
    model.eval()
    return model
