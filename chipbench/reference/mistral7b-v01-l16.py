"""Plain reference of the Mistral-7B-v0.1 decoder (depth as the
configuration file gives it): a full forward pass over prompt + served
tokens in straightforward jax.numpy, float32 activations, matmuls at
"highest" precision, one layer at a time so that it fits beside nothing
else. No cache, no batching tricks, no program code.

Follows mistralai's reference: token embedding; per layer RMSNorm ->
attention with rotary embeddings on consecutive pairs (theta 10000), 32
query heads sharing 8 key/value heads, causal; residual; RMSNorm -> SwiGLU
(down(silu(gate x) * up x)); residual; final RMSNorm; LM head. Departures,
noted: the sliding window (4096) is left out, as in the program, and every
context of the traffic stays under it; weights are the served bfloat16
values (made from the seed by chipbench.weights) read up to float32.

`precision="int8"` is the control: every linear layer's weight is rounded
to int8 per output channel and its input to int8 per token (W8A8, the step
below bfloat16 that would tempt a later PR on a chip with 393 int8 TOP/s).
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

LAYER_LEAVES = {
    "input_layernorm.weight": ("h",),
    "self_attn.q_proj.weight": ("h", "q"), "self_attn.k_proj.weight": ("h", "kv"),
    "self_attn.v_proj.weight": ("h", "kv"), "self_attn.o_proj.weight": ("q", "h"),
    "post_attention_layernorm.weight": ("h",),
    "mlp.gate_proj.weight": ("h", "f"), "mlp.up_proj.weight": ("h", "f"),
    "mlp.down_proj.weight": ("f", "h"),
}


def _dims(cfg):
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    return {"h": h, "q": h, "kv": cfg["num_key_value_heads"] * d,
            "f": cfg["intermediate_size"]}


def layer_specs(cfg: dict, i: int) -> dict:
    dims, std = _dims(cfg), cfg["initializer_range"]
    return {f"llama.layers.{i}.{leaf}": (tuple(dims[s] for s in sym),
                                         "ones" if "norm" in leaf else "normal", std)
            for leaf, sym in LAYER_LEAVES.items()}


def outer_specs(cfg: dict) -> dict:
    h, v, std = cfg["hidden_size"], cfg["vocab_size"], cfg["initializer_range"]
    return {"llama.embed_tokens.weight": ((v, h), "normal", std),
            "llama.norm.weight": ((h,), "ones", 0.0),
            "lm_head.weight": ((h, v), "normal", std)}


def leaf_specs(cfg: dict) -> dict:
    s = outer_specs(cfg)
    for i in range(cfg["num_hidden_layers"]):
        s.update(layer_specs(cfg, i))
    return s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rope(x, theta):
    # x [S, H, D]; consecutive pairs (x0, x1), (x2, x3), ... rotate together
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _linear(x, w, int8):
    if int8:
        ws = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        w = jnp.round(w / ws) * ws
        xs = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127.0
        x = jnp.round(x / xs) * xs
    return x @ w


def _layer(x, w, cfg, int8):
    """x [N, S, h] float32 -> the same, one decoder layer, one sequence at
    a time through attention."""
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = x.shape[1]
    mask = jnp.tril(jnp.ones((s, s), bool))

    def attend(xs):
        y = _rms(xs, w["input_layernorm.weight"], eps)
        q = _rope(_linear(y, w["self_attn.q_proj.weight"], int8).reshape(s, heads, d), theta)
        k = _rope(_linear(y, w["self_attn.k_proj.weight"], int8).reshape(s, kvh, d), theta)
        v = _linear(y, w["self_attn.v_proj.weight"], int8).reshape(s, kvh, d)
        k, v = jnp.repeat(k, heads // kvh, axis=1), jnp.repeat(v, heads // kvh, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, heads * d)
        return xs + _linear(a, w["self_attn.o_proj.weight"], int8)

    x = jax.lax.map(attend, x)
    y = _rms(x, w["post_attention_layernorm.weight"], eps)
    g = jax.nn.silu(_linear(y, w["mlp.gate_proj.weight"], int8)) * _linear(y, w["mlp.up_proj.weight"], int8)
    return x + _linear(g, w["mlp.down_proj.weight"], int8)


def token_gaps(cfg: dict, seed: int, seqs, served_from, precisions=("f32",), w_dtype=jnp.bfloat16):
    """seqs: list of token-id lists (prompt + served tokens); served_from[i]
    is the index in seqs[i] of the first served token. Runs the forward pass
    over every sequence once for each precision and returns

        ({precision: logits [n_served, V] float32}, served ids [n_served])

    where row j holds the logits that predict served token j; `gaps()`
    below reduces them. One layer's weights are on the device at a time."""
    n = len(seqs)
    pad = -(-max(len(s) for s in seqs) // 128) * 128
    ids = np.zeros((n, pad), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    # positions whose logits predict a served token: served_from-1 .. len-2
    rows, cols, served = [], [], []
    for i, s in enumerate(seqs):
        for t in range(served_from[i], len(s)):
            rows.append(i), cols.append(t - 1), served.append(s[t])
    rows, cols = np.asarray(rows), np.asarray(cols)

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(jnp.float32)

    def make_layer(int8):
        @jax.jit
        def f(x, w):
            w = {k: v.astype(jnp.float32) for k, v in w.items()}
            with jax.default_matmul_precision("highest"):
                return _layer(x, w, cfg, int8)
        return f

    def make_head(int8):
        @jax.jit
        def f(xsel, norm_w, lm):
            with jax.default_matmul_precision("highest"):
                y = _rms(xsel, norm_w.astype(jnp.float32), cfg["rms_norm_eps"])
                return _linear(y, lm.astype(jnp.float32), int8)
        return f

    outer = weights.make(outer_specs(cfg), seed, w_dtype)
    xs = {p: embed(outer["llama.embed_tokens.weight"], jnp.asarray(ids)) for p in precisions}
    fns = {p: make_layer(p == "int8") for p in precisions}
    for i in range(cfg["num_hidden_layers"]):
        w = weights.make(layer_specs(cfg, i), seed, w_dtype)
        w = {k.split(f"layers.{i}.")[1]: v for k, v in w.items()}
        for p in precisions:
            xs[p] = fns[p](xs[p], w)
        del w
    out = {p: np.asarray(make_head(p == "int8")(xs[p][rows, cols], outer["llama.norm.weight"],
                                                 outer["lm_head.weight"]))
           for p in precisions}
    return out, np.asarray(served)


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """By how much each token's reference logit lies below the reference's
    best at its position (0 where the token is the reference's own)."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]
