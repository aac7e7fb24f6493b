"""Builds the program's LlamaForCausalLM from a configuration file's sizes,
WITHOUT materialising its float32 initial weights.

`nn.Layer` fixes the parameter dtype to float32, so a plain constructor
call makes 4 bytes a parameter on the device before anything can be cast:
14.9 GB for 16 Mistral layers, which does not fit. The constructor is run
under `jax.eval_shape`, so every parameter holds a shape and no bytes; the
caller then assigns the served (bfloat16) values from chipbench.weights.
Only the program can give its layers a dtype or a lazy constructor
(PERF.md, Open questions).
"""


def build(cfg: dict):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM

    box = {}

    def construct():
        box["model"] = LlamaForCausalLM(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            intermediate_size=cfg["intermediate_size"],
            rms_norm_eps=cfg["rms_norm_eps"])
        return 0

    jax.eval_shape(construct)
    paddle.seed(0)  # the constructor's draws left a traced key behind
    model = box["model"]
    model.eval()
    return model
