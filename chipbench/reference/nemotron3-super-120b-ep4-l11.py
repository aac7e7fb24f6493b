"""Plain reference of NVIDIA-Nemotron-3-Super-120B-A12B (`nemotron_h`), at
the depth, the expert share and the vocabulary slice the configuration file
gives: a full forward pass over prompt + served tokens in straightforward
jax.numpy, float32 activations, matmuls at "highest" precision, one layer at
a time, a sequential scan for the state-space layers. No cache, no kernels,
no grouping of tokens by expert, no program code.

`x` is [tokens, hidden]. Every layer is ONE mixer behind one RMSNorm:
`x = x + mixer(rmsnorm(x; eps))`, the kind read from
`hybrid_override_pattern` (`M`, `*`, `E`); after the last layer RMSNorm, then
`lm_head` (untied; no bias anywhere except the conv).

`M`, Mamba-2 (heads H of width P, groups G, state N; head h uses group
h // (H / G)):
    [z | xBC | dt] = x W_in            widths H P, H P + 2 G N, H
    xBC = silu(causal_depthwise_conv1d(xBC, kernel 4) + b_conv)
    xBC -> xs [H, P], B [G, N], C [G, N]
    dt = softplus(dt + dt_bias);  A = -exp(A_log)   (one a head)
    h_t = exp(dt_t A) h_{t-1} + dt_t (xs_t outer B_t),  h_{-1} = 0
    y_t = h_t C_t + D xs_t
    y = group_rmsnorm(y * silu(z); G groups, eps) * w;  out = y W_out
`*`, attention: q, k, v = x W_q, x W_k, x W_v (heads of `head_dim`, kv heads
shared by groups of query heads), causal softmax(q k^T / sqrt(d)) v, W_o.
`E`, LatentMoE:
    s = sigmoid(x W_r)  (float32, `n_routed_experts` wide)
    choose the `num_experts_per_tok` largest of s + b_corr
    w_e = routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)
    u = x W_fc1;  f_e(u) = relu(u W_up,e)^2 W_down,e
    out = (sum over chosen e of w_e f_e(u)) W_fc2 + relu(x W_sup)^2 W_sdown
The share: the sum runs over chosen experts that are HELD here
(`experts_held` = [first, count]); the weights are still normalised over all
the chosen; what the absent experts would add is left out, and that partial
result goes on to the next layer. W_fc2 is linear, so the shares' routed
parts add up to the whole layer's.

Departures and assumptions (the configuration file lists them under
`assumed`): no rotary embedding in attention; dt is not clamped; the gate
`y * silu(z)` comes BEFORE the group norm; router scores and the choice in
float32; `b_corr` is a seeded leaf; weights are the served bfloat16 values
(made from the seed by chipbench.weights) read up to float32.

`precision="int8"` is the control: every linear layer's weight (the experts'
too, each expert on its own) is rounded to int8 per output channel and its
input to int8 per token (W8A8); the router, the conv and the recurrence stay
float32.
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

PREFIX = "backbone.layers"


def dims(cfg: dict) -> dict:
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * p
    return {"h": cfg["hidden_size"], "inner": inner, "conv": inner + 2 * g * n,
            "in": 2 * inner + 2 * g * n + heads, "heads": heads,
            "q": cfg["num_attention_heads"] * cfg["head_dim"],
            "kv": cfg["num_key_value_heads"] * cfg["head_dim"],
            "routed": cfg["n_routed_experts"], "held": int(cfg["experts_held"][1]),
            "latent": cfg["moe_latent_size"], "f": cfg["moe_intermediate_size"],
            "sf": cfg["moe_shared_expert_intermediate_size"]}


def layer_specs(cfg: dict, i: int) -> dict:
    """{leaf name: (shape, kind, scale)} of layer i, by its kind."""
    d, std = dims(cfg), cfg["initializer_range"]
    kind = cfg["hybrid_override_pattern"][i]
    pre = f"{PREFIX}.{i}"
    out = {f"{pre}.norm.weight": ((d["h"],), "ones", 0.0)}
    if kind == "M":
        leaves = {
            "in_proj.weight": ((d["h"], d["in"]), "normal", std),
            "conv1d.weight": ((cfg["conv_kernel"], d["conv"]), "normal", 0.5),
            "conv1d.bias": ((d["conv"],), "normal", std),
            "dt_bias": ((d["heads"],), "normal", 2.0),
            "A_log": ((d["heads"],), "normal", 1.0),
            "D": ((d["heads"],), "ones", 0.0),
            "norm.weight": ((d["inner"],), "ones", 0.0),
            "out_proj.weight": ((d["inner"], d["h"]), "normal", std),
        }
    elif kind == "*":
        leaves = {
            "q_proj.weight": ((d["h"], d["q"]), "normal", std),
            "k_proj.weight": ((d["h"], d["kv"]), "normal", std),
            "v_proj.weight": ((d["h"], d["kv"]), "normal", std),
            "o_proj.weight": ((d["q"], d["h"]), "normal", std),
        }
    elif kind == "E":
        leaves = {
            "gate.weight": ((d["h"], d["routed"]), "normal", std),
            "gate.e_score_correction_bias": ((d["routed"],), "normal", std),
            "fc1_latent_proj.weight": ((d["h"], d["latent"]), "normal", std),
            "fc2_latent_proj.weight": ((d["latent"], d["h"]), "normal", std),
            "experts_up": ((d["held"], d["latent"], d["f"]), "normal", std),
            "experts_down": ((d["held"], d["f"], d["latent"]), "normal", std),
            "shared_experts.up_proj.weight": ((d["h"], d["sf"]), "normal", std),
            "shared_experts.down_proj.weight": ((d["sf"], d["h"]), "normal", std),
        }
    else:
        raise ValueError(f"unknown layer kind {kind!r} in hybrid_override_pattern")
    out.update({f"{pre}.mixer.{k}": v for k, v in leaves.items()})
    return out


def outer_specs(cfg: dict) -> dict:
    h, v, std = cfg["hidden_size"], cfg["vocab_size"], cfg["initializer_range"]
    return {"backbone.embeddings.weight": ((v, h), "normal", std),
            "backbone.norm_f.weight": ((h,), "ones", 0.0),
            "lm_head.weight": ((h, v), "normal", std)}


def leaf_specs(cfg: dict) -> dict:
    s = outer_specs(cfg)
    for i in range(len(cfg["hybrid_override_pattern"])):
        s.update(layer_specs(cfg, i))
    return s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _linear(x, w, int8):
    if int8:
        ws = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 127.0
        w = jnp.round(w / ws) * ws
        xs = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127.0
        x = jnp.round(x / xs) * xs
    return x @ w


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba(x, w, cfg, int8=False):
    """x [S, h] float32 (one sequence) -> the mixer's output [S, h]."""
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    inner, s = heads * p, x.shape[0]
    zxd = _linear(x, w["in_proj.weight"], int8)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:2 * inner + 2 * g * n], zxd[:, 2 * inner + 2 * g * n:]
    # causal depthwise conv: tap j multiplies the row k-1-j steps back
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), x.dtype), xbc])
    conv = sum(padded[j:j + s] * w["conv1d.weight"][j] for j in range(k)) + w["conv1d.bias"]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :inner].reshape(s, heads, p)
    b = jnp.repeat(xbc[:, inner:inner + g * n].reshape(s, g, n), heads // g, axis=1)
    c = jnp.repeat(xbc[:, inner + g * n:].reshape(s, g, n), heads // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                 # [S, H]
    a = -jnp.exp(w["A_log"])                                # [H]

    def step(h, t):
        xs_t, b_t, c_t, dt_t = t
        h = jnp.exp(dt_t * a)[:, None, None] * h + (dt_t[:, None] * xs_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), jnp.float32), (xs, b, c, dt))
    y = (y + w["D"][:, None] * xs).reshape(s, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(s, g, inner // g), 1.0, cfg["layer_norm_epsilon"]).reshape(s, inner)
    return _linear(y * w["norm.weight"], w["out_proj.weight"], int8)


def attention(x, w, cfg, int8=False):
    """x [S, h] float32 (one sequence), causal, no positional encoding."""
    heads, kvh, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    s = x.shape[0]
    q = _linear(x, w["q_proj.weight"], int8).reshape(s, heads, d)
    k = _linear(x, w["k_proj.weight"], int8).reshape(s, kvh, d)
    v = _linear(x, w["v_proj.weight"], int8).reshape(s, kvh, d)
    k, v = jnp.repeat(k, heads // kvh, axis=1), jnp.repeat(v, heads // kvh, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", pr, v).reshape(s, heads * d)
    return _linear(a, w["o_proj.weight"], int8)


def route(x, w, cfg):
    """[T, n_routed_experts] float32: the weight of each chosen expert, 0
    for the others; normalised over ALL the chosen, held here or not."""
    s = jax.nn.sigmoid(x @ w["gate.weight"])
    _, chosen = jax.lax.top_k(s + w["gate.e_score_correction_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    wts = cfg["routed_scaling_factor"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(wts)


def routed_part(x, w, cfg, int8=False):
    """The held experts' part of the routed sum, in the latent: [T, latent]."""
    first, count = cfg["experts_held"]
    wts = route(x, w, cfg)[:, first:first + count]          # [T, held]
    u = _linear(x, w["fc1_latent_proj.weight"], int8)

    def one(acc, e):
        up, down, w_e = e
        f = _linear(_relu2(_linear(u, up.astype(jnp.float32), int8)), down.astype(jnp.float32), int8)
        return acc + w_e[:, None] * f, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (w["experts_up"], w["experts_down"], wts.T))
    return acc


def moe(x, w, cfg, int8=False):
    """x [T, h] float32 -> this share's output of the layer [T, h]."""
    routed = _linear(routed_part(x, w, cfg, int8), w["fc2_latent_proj.weight"], int8)
    shared = _linear(_relu2(_linear(x, w["shared_experts.up_proj.weight"], int8)),
                     w["shared_experts.down_proj.weight"], int8)
    return routed + shared


def layer(x, w, cfg, kind, int8=False):
    """x [N, S, h] float32 -> the same, one layer; sequences one at a time
    through the mixers that look along the sequence."""
    y = _rms(x, w["norm.weight"], cfg["layer_norm_epsilon"])
    m = {k[len("mixer."):]: v for k, v in w.items() if k.startswith("mixer.")}
    if kind == "E":
        n, s, h = y.shape
        return x + moe(y.reshape(n * s, h), m, cfg, int8).reshape(n, s, h)
    f = mamba if kind == "M" else attention
    return x + jax.lax.map(lambda ys: f(ys, m, cfg, int8), y)


def _up(w):
    """Leaves read up to float32, but for the stacked experts: those are
    read up one expert at a time, inside the loop over them."""
    return {k: (v if k.startswith("mixer.experts_") else v.astype(jnp.float32)) for k, v in w.items()}


def forward(params: dict, ids, cfg: dict, int8=False):
    """Logits [N, S, V] float32 of a whole model whose leaves are all in
    `params` (the small sizes of the tests)."""
    with jax.default_matmul_precision("highest"):
        x = params["backbone.embeddings.weight"][jnp.asarray(ids)].astype(jnp.float32)
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            pre = f"{PREFIX}.{i}."
            w = _up({k[len(pre):]: v for k, v in params.items() if k.startswith(pre)})
            x = layer(x, w, cfg, kind, int8)
        y = _rms(x, params["backbone.norm_f.weight"].astype(jnp.float32), cfg["layer_norm_epsilon"])
        return _linear(y, params["lm_head.weight"].astype(jnp.float32), int8)


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
    rows, cols, served = [], [], []
    for i, s in enumerate(seqs):
        for t in range(served_from[i], len(s)):
            rows.append(i), cols.append(t - 1), served.append(s[t])
    rows, cols = np.asarray(rows), np.asarray(cols)

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(jnp.float32)

    fns = {}

    def layer_fn(kind, int8):
        if (kind, int8) not in fns:
            @jax.jit
            def f(x, w):
                with jax.default_matmul_precision("highest"):
                    return layer(x, _up(w), cfg, kind, int8)
            fns[kind, int8] = f
        return fns[kind, int8]

    def make_head(int8):
        @jax.jit
        def f(xsel, norm_w, lm):
            with jax.default_matmul_precision("highest"):
                y = _rms(xsel, norm_w.astype(jnp.float32), cfg["layer_norm_epsilon"])
                return _linear(y, lm.astype(jnp.float32), int8)
        return f

    outer = weights.make(outer_specs(cfg), seed, w_dtype)
    xs = {p: embed(outer["backbone.embeddings.weight"], jnp.asarray(ids)) for p in precisions}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        w = weights.make(layer_specs(cfg, i), seed, w_dtype)
        w = {k.split(f"layers.{i}.")[1]: v for k, v in w.items()}
        for p in precisions:
            xs[p] = layer_fn(kind, p == "int8")(xs[p], w)
        del w
    out = {p: np.asarray(make_head(p == "int8")(xs[p][rows, cols], outer["backbone.norm_f.weight"],
                                                 outer["lm_head.weight"]))
           for p in precisions}
    return out, np.asarray(served)


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """By how much each token's reference logit lies below the reference's
    best at its position (0 where the token is the reference's own)."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]
