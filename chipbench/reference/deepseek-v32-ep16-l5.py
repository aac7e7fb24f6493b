"""Plain reference of DeepSeek-V3.2 (`deepseek_v32`), at the depth, the expert
share and the vocabulary slice the configuration file gives: a full forward
pass over prompt + served tokens in straightforward jax.numpy, float32
activations, matmuls at "highest" precision, one layer at a time, one sequence
at a time, EXPANDED attention only (keys and values a head from the latent; no
absorbed form, no cache, no kernels, no gathering of chosen entries, no
grouping of tokens by expert, no program code). Blocked over queries and heads
so that a 17k-token request fits.

`x` is [tokens, hidden], RMSNorm eps `rms_norm_eps`, no biases but the
indexer's LayerNorm.

Block (pre-norm):  h = x + MLA(N1(x));  y = h + FFN(N2(h));  a final RMSNorm,
then `lm_head` (untied).
MLA on a = N1(x) (H heads; nope, rope, v the head widths):
    c_q = RMSNorm(a W_qa);  q = c_q W_qb -> H x [q_nope | q_rope]
    [c | k_r] = a W_kva;  c_kv = RMSNorm(c);  k_r is ONE key for all heads
    RoPE on q_rope and k_r: rotate-half (column i pairs with i + rope/2), YaRN's
    inverse frequencies at every length:
        f_i = theta^(-2i/rope);  low = max(0, floor(rope ln(L/(beta_fast 2 pi)) / (2 ln theta)))
        high = min(rope - 1, ceil(rope ln(L/(beta_slow 2 pi)) / (2 ln theta)))
        ramp_i = clip((i - low)/(high - low), 0, 1);  inv_i = (f_i/factor) ramp_i + f_i (1 - ramp_i)
    [k_nope | v] a head = c_kv W_kvb
    scale = (nope + rope)^-0.5 (0.1 mscale_all_dim ln(factor) + 1)^2
Indexer (a layer, its own weights), J heads of D:
    q_idx = c_q W_iq -> J x D;  k_idx = LayerNorm(a W_ik) (gain and bias, eps `rms_norm_eps`)
    the first `rope` columns of q_idx and k_idx take the rotary of k_r
    w = (a W_iw) J^-0.5 D^-0.5
    I[t, s] = sum_j w[t, j] relu(q_idx[t, j] . k_idx[s]),  s <= t
    S_t = the min(index_topk, t + 1) positions of largest I[t, :]
    out = softmax over s in S_t of (scale (q_nope . k_nope + q_rope . k_r)) v;  then W_o.
Dense FFN (layers before `first_k_dense_replace`):
    (silu(m W_g) * (m W_u)) W_d, width `intermediate_size`.
Sparse FFN:
    s = sigmoid(m W_r)  (float32, `n_routed_experts` wide);  c = s + b  (b the correction bias)
    the experts stand in `n_group` groups; a group's score is the sum of its two largest c;
    only experts of the `topk_group` best groups can be chosen; choose the
    `num_experts_per_tok` largest c among them
    w_e = routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)
    out = sum over chosen e of w_e f_e(m) + f_shared(m), every f the gated form
The share: the sum runs over chosen experts that are HELD here
(`experts_held` = [first, count]); the weights are still normalised over all
the chosen; what the absent experts would add is left out, and that partial
result goes on to the next layer. The shared expert is whole on every chip.

Departures and assumptions (the configuration file lists them under
`assumed`): the published code turns q_idx and k_idx by a Hadamard matrix and
keeps them in FP8 with block scales: the turn is orthogonal (no score changes
in exact arithmetic) and is left out, and the keys are kept in the
configuration's bfloat16; rotate-half layout of the rotary columns, in the
indexer the FIRST `rope` columns; LayerNorm eps = `rms_norm_eps`; norm gains 1,
the LayerNorm's bias 0; the correction bias a seeded leaf; weights are the
served bfloat16 values (made from the seed by chipbench.weights) read up to
float32, a sparse layer's experts one at a time.

`precision="int8"` is the control: every linear layer's weight (the experts'
and the indexer's too, each expert on its own) is rounded to int8 per output
channel and its input to int8 per token (W8A8); the router, the norms, the
index scores' sum and the softmax stay float32. `select=False` is the planted
fault: the selection switched off (every query attends to all it may see);
`token_gaps` reads it, as precision `no_select`, beside the control.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

PREFIX = "model.layers"
HEAD_BLOCK = 8     # heads a step of the attention holds
QUERY_BLOCK = 512  # queries a step of the attention holds
INDEX_BLOCK = 64   # queries a step of the indexer holds (all its heads, all the positions)
BIAS_STD = 0.1     # the seeded correction bias: wide enough to change which experts are chosen


def layer_specs(cfg: dict, i: int) -> dict:
    """{leaf name: (shape, kind, scale)} of layer i."""
    h, std, heads = cfg["hidden_size"], cfg["initializer_range"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    ij, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    leaves = {f"{n}.weight": ((h,), "ones", 0.0) for n in ("input_layernorm", "post_attention_layernorm")}
    leaves.update({
        "self_attn.q_a_proj.weight": ((h, rq), "normal", std),
        "self_attn.q_a_layernorm.weight": ((rq,), "ones", 0.0),
        "self_attn.q_b_proj.weight": ((rq, heads * (nope + rope)), "normal", std),
        "self_attn.kv_a_proj_with_mqa.weight": ((h, rkv + rope), "normal", std),
        "self_attn.kv_a_layernorm.weight": ((rkv,), "ones", 0.0),
        "self_attn.kv_b_proj.weight": ((rkv, heads * (nope + v)), "normal", std),
        "self_attn.o_proj.weight": ((heads * v, h), "normal", std),
        "self_attn.indexer.wq_b.weight": ((rq, ij * idim), "normal", std),
        "self_attn.indexer.wk.weight": ((h, idim), "normal", std),
        "self_attn.indexer.k_norm.weight": ((idim,), "ones", 0.0),
        "self_attn.indexer.k_norm.bias": ((idim,), "zeros", 0.0),
        "self_attn.indexer.weights_proj.weight": ((h, ij), "normal", std),
    })
    if i < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        leaves.update({"mlp.gate_proj.weight": ((h, f), "normal", std),
                       "mlp.up_proj.weight": ((h, f), "normal", std),
                       "mlp.down_proj.weight": ((f, h), "normal", std)})
    else:
        f, held = cfg["moe_intermediate_size"], int(cfg["experts_held"][1])
        sf = cfg["n_shared_experts"] * f
        leaves.update({"mlp.router": ((h, cfg["n_routed_experts"]), "normal", std),
                       "mlp.router_bias": ((cfg["n_routed_experts"],), "normal", BIAS_STD),
                       "mlp.experts_gate": ((held, h, f), "normal", std),
                       "mlp.experts_up": ((held, h, f), "normal", std),
                       "mlp.experts_down": ((held, f, h), "normal", std),
                       "mlp.shared_experts.gate_proj.weight": ((h, sf), "normal", std),
                       "mlp.shared_experts.up_proj.weight": ((h, sf), "normal", std),
                       "mlp.shared_experts.down_proj.weight": ((sf, h), "normal", std)})
    return {f"{PREFIX}.{i}.{k}": v for k, v in leaves.items()}


def outer_specs(cfg: dict) -> dict:
    h, v, std = cfg["hidden_size"], cfg["vocab_size"], cfg["initializer_range"]
    return {"model.embed_tokens.weight": ((v, h), "normal", std),
            "model.norm.weight": ((h,), "ones", 0.0),
            "lm_head.weight": ((h, v), "normal", std)}


def leaf_specs(cfg: dict) -> dict:
    s = outer_specs(cfg)
    for i in range(cfg["num_hidden_layers"]):
        s.update(layer_specs(cfg, i))
    return s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(jnp.mean(jnp.square(x - mu), -1, keepdims=True) + eps) * w + b


def _linear(x, w, int8):
    if int8:
        ws = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 127.0
        w = jnp.round(w / ws) * ws
        xs = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127.0
        x = jnp.round(x / xs) * xs
    return x @ w


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The rotary columns' inverse frequencies [rope / 2] (float64), by the
    formula of the header; plain `theta^(-2i/rope)` without `rope_scaling`."""
    d, theta, sc = cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), cfg.get("rope_scaling")
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not sc:
        return f
    length = sc["original_max_position_embeddings"]
    low = max(0, math.floor(d * math.log(length / (sc["beta_fast"] * 2 * math.pi)) / (2 * math.log(theta))))
    high = min(d - 1, math.ceil(d * math.log(length / (sc["beta_slow"] * 2 * math.pi)) / (2 * math.log(theta))))
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / sc["factor"]) * ramp + f * (1.0 - ramp)


def softmax_scale(cfg: dict) -> float:
    sc = cfg.get("rope_scaling") or {}
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0 if sc.get("mscale_all_dim") else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, cfg):
    """Rotate-half rotary embedding of x [S, ..., d], tokens at 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    ang = jnp.asarray(np.outer(np.arange(s, dtype=np.float64), yarn_inv_freq(cfg)), jnp.float32)
    ang = ang.reshape(s, *(1,) * (x.ndim - 2), d // 2)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def index_scores(a, c_q, w, cfg, int8=False):
    """I [S, S] float32 of one sequence: -inf above the diagonal. Queries go
    through in blocks of INDEX_BLOCK (their [block, J, S] products fit)."""
    s, j, d, rope = a.shape[0], cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    q = _linear(c_q, w["indexer.wq_b.weight"], int8).reshape(s, j, d)
    k = _layer_norm(_linear(a, w["indexer.wk.weight"], int8), w["indexer.k_norm.weight"],
                    w["indexer.k_norm.bias"], cfg["rms_norm_eps"])
    q = jnp.concatenate([_rope(q[..., :rope], cfg), q[..., rope:]], -1)
    k = jnp.concatenate([_rope(k[..., :rope], cfg), k[..., rope:]], -1)
    wts = _linear(a, w["indexer.weights_proj.weight"], int8) * (j ** -0.5 * d ** -0.5)
    ib = min(INDEX_BLOCK, s)
    if s % ib:
        raise ValueError(f"index blocks of {ib} queries do not divide {s} tokens")
    key_pos = jnp.arange(s)

    def block(args):
        q_b, w_b, q0 = args
        sc = jnp.sum(jax.nn.relu(jnp.einsum("qjd,kd->qjk", q_b, k)) * w_b[..., None], axis=1)
        return jnp.where(key_pos[None, :] <= (q0 + jnp.arange(ib))[:, None], sc, -jnp.inf)

    out = jax.lax.map(block, (q.reshape(s // ib, ib, j, d), wts.reshape(s // ib, ib, j), jnp.arange(s // ib) * ib))
    return out.reshape(s, s)


def selected(scores, topk):
    """[S, S] bool from the index scores: query t's `min(topk, t + 1)`
    positions of largest score (`lax.top_k`: of equal scores the earlier)."""
    s = scores.shape[0]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    if s <= topk:
        return causal
    _, chosen = jax.lax.top_k(scores, topk)
    return jnp.zeros((s, s), bool).at[jnp.arange(s)[:, None], chosen].set(True) & causal


def attention(a, w, cfg, int8=False, select=True):
    """a [S, h] float32 (one sequence, already through N1) -> [S, h]: the
    expanded form over the selector's choice. Heads go through in blocks
    (their queries, keys and values projected a block at a time) and queries
    in blocks, so that a long sequence's heads and scores fit. `select=False`
    switches the selection off (a planted fault: every query attends to all
    it may see)."""
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rkv, s = cfg["kv_lora_rank"], a.shape[0]
    c_q = _rms(_linear(a, w["q_a_proj.weight"], int8), w["q_a_layernorm.weight"], eps)
    kv_a = _linear(a, w["kv_a_proj_with_mqa.weight"], int8)
    c_kv = _rms(kv_a[:, :rkv], w["kv_a_layernorm.weight"], eps)
    k_r = _rope(kv_a[:, rkv:], cfg)  # [S, rope], one key for all heads
    scale = softmax_scale(cfg)
    if select:
        mask = selected(index_scores(a, c_q, w, cfg, int8), cfg["index_topk"])
    else:
        mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    hb, qb = min(HEAD_BLOCK, heads), min(QUERY_BLOCK, s)
    if heads % hb or s % qb:
        raise ValueError(f"attention blocks ({hb} heads, {qb} queries) do not divide {heads} heads, {s} tokens")
    blocks = s // qb

    def head_block(ws):
        w_q, w_kv = ws  # the block's columns of W_qb and W_kvb
        q = _linear(c_q, w_q, int8).reshape(s, hb, nope + rope)
        kv = _linear(c_kv, w_kv, int8).reshape(s, hb, nope + vd)
        q_rope = _rope(q[..., nope:], cfg)
        k_nope, v = kv[..., :nope].swapaxes(0, 1), kv[..., nope:].swapaxes(0, 1)  # [hb, S, .]

        def query_block(args):
            q_n, q_r, m = args  # [qb, hb, .], the block's rows of the mask
            sc = (jnp.einsum("qhd,hkd->hqk", q_n, k_nope) + jnp.einsum("qhd,kd->hqk", q_r, k_r)) * scale
            return jnp.einsum("hqk,hkd->qhd", jax.nn.softmax(jnp.where(m[None], sc, -jnp.inf), -1), v)

        out = jax.lax.map(query_block, (q[..., :nope].reshape(blocks, qb, hb, nope),
                                        q_rope.reshape(blocks, qb, hb, rope), mask.reshape(blocks, qb, s)))
        return out.reshape(s, hb * vd)

    def by_block(wm, width):  # [r, H * width] -> [H / hb, r, hb * width]
        return wm.reshape(wm.shape[0], heads // hb, hb * width).swapaxes(0, 1)

    out = jax.lax.map(head_block, (by_block(w["q_b_proj.weight"], nope + rope),
                                   by_block(w["kv_b_proj.weight"], nope + vd)))
    out = out.swapaxes(0, 1).reshape(s, heads * vd)
    return _linear(out, w["o_proj.weight"], int8)


def gated(x, w_g, w_u, w_d, int8=False):
    return _linear(jax.nn.silu(_linear(x, w_g, int8)) * _linear(x, w_u, int8), w_d, int8)


def route(m, w, cfg):
    """[T, n_routed_experts] float32: the weight of each chosen expert, 0
    for the others; normalised over ALL the chosen, held here or not."""
    s = jax.nn.sigmoid(m @ w["router"])
    c = s + w["router_bias"]
    t, e, g = c.shape[0], c.shape[1], cfg["n_group"]
    by_group = c.reshape(t, g, e // g)
    _, best = jax.lax.top_k(jax.lax.top_k(by_group, 2)[0].sum(-1), cfg["topk_group"])
    kept = jnp.zeros((t, g), bool).at[jnp.arange(t)[:, None], best].set(True)
    _, chosen = jax.lax.top_k(jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(t, e),
                              cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    wts = cfg["routed_scaling_factor"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(s).at[jnp.arange(m.shape[0])[:, None], chosen].set(wts)


def routed_part(m, w, cfg, int8=False, held=None):
    """The part of the routed sum the experts `held` = [first, count] give
    (default: the configuration's share), [T, h]; `w`'s stacked experts are
    those, read up to float32 one at a time."""
    first, count = held or cfg["experts_held"]
    wts = route(m, w, cfg)[:, first:first + count]

    def one(acc, e):
        g, u, d, w_e = e
        f32 = jnp.float32
        return acc + w_e[:, None] * gated(m, g.astype(f32), u.astype(f32), d.astype(f32), int8), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m), (w["experts_gate"], w["experts_up"], w["experts_down"], wts.T))
    return acc


def sparse_ffn(m, w, cfg, int8=False):
    """m [T, h] float32 -> this share's output of the layer [T, h]."""
    shared = gated(m, w["shared_experts.gate_proj.weight"], w["shared_experts.up_proj.weight"],
                   w["shared_experts.down_proj.weight"], int8)
    return routed_part(m, w, cfg, int8) + shared


def layer_one(x, w, cfg, dense, int8=False, select=True):
    """x [S, h] float32 (one sequence) -> the same, one layer."""
    eps = cfg["rms_norm_eps"]
    attn = {k[len("self_attn."):]: v for k, v in w.items() if k.startswith("self_attn.")}
    mlp = {k[len("mlp."):]: v for k, v in w.items() if k.startswith("mlp.")}
    h = x + attention(_rms(x, w["input_layernorm.weight"], eps), attn, cfg, int8, select)
    m = _rms(h, w["post_attention_layernorm.weight"], eps)
    if dense:
        return h + gated(m, mlp["gate_proj.weight"], mlp["up_proj.weight"], mlp["down_proj.weight"], int8)
    return h + sparse_ffn(m, mlp, cfg, int8)


def layer(x, w, cfg, dense, int8=False, select=True):
    """x [N, S, h] float32 -> the same, one layer, a sequence at a time."""
    return jax.lax.map(lambda xs: layer_one(xs, w, cfg, dense, int8, select), x)


def _up(w):
    """Leaves read up to float32, but for the stacked experts: those are
    read up one expert at a time, inside the loop over them."""
    return {k: (v if k.startswith("mlp.experts_") else v.astype(jnp.float32)) for k, v in w.items()}


def forward(params: dict, ids, cfg: dict, int8=False, select=True):
    """Logits [N, S, V] float32 of a whole model whose leaves are all in
    `params` (the small sizes of the tests)."""
    with jax.default_matmul_precision("highest"):
        x = params["model.embed_tokens.weight"][jnp.asarray(ids)].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            pre = f"{PREFIX}.{i}."
            w = _up({k[len(pre):]: v for k, v in params.items() if k.startswith(pre)})
            x = layer(x, w, cfg, i < cfg["first_k_dense_replace"], int8, select)
        y = _rms(x, params["model.norm.weight"].astype(jnp.float32), cfg["rms_norm_eps"])
        return _linear(y, params["lm_head.weight"].astype(jnp.float32), int8)


def token_gaps(cfg: dict, seed: int, seqs, served_from, precisions=("f32",), w_dtype=jnp.bfloat16):
    """seqs: list of token-id lists (prompt + served tokens); served_from[i]
    is the index in seqs[i] of the first served token. Runs the forward pass
    over every sequence once for each precision and returns

        ({precision: logits [n_served, V] float32}, served ids [n_served])

    where row j holds the logits that predict served token j; `gaps()`
    below reduces them. One layer's weights are on the device at a time.
    Precisions: `f32`, `int8` (the control), `no_select` (the planted fault:
    float32 with the selection switched off). A run that reads the control
    reads the fault beside it and logs how the served tokens stand against it:
    a comparison that passed there could not tell a program that selects from
    one that does not."""
    fault = "int8" in precisions and "no_select" not in precisions
    if fault:
        precisions = (*precisions, "no_select")
    n = len(seqs)
    longest = max(len(s) for s in seqs)
    unit = QUERY_BLOCK if longest > QUERY_BLOCK else 128
    pad = -(-longest // unit) * unit
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

    def layer_fn(dense, precision):
        if (dense, precision) not in fns:
            def f(x, w):
                with jax.default_matmul_precision("highest"):
                    return layer(x, _up(w), cfg, dense, precision == "int8", precision != "no_select")
            fns[dense, precision] = jax.jit(f, donate_argnums=0)  # one copy of the activations a precision
        return fns[dense, precision]

    def make_head(int8):
        @jax.jit
        def f(xsel, norm_w, lm):
            with jax.default_matmul_precision("highest"):
                y = _rms(xsel, norm_w.astype(jnp.float32), cfg["rms_norm_eps"])
                return _linear(y, lm.astype(jnp.float32), int8)
        return f

    outer = weights.make(outer_specs(cfg), seed, w_dtype)
    xs = {p: embed(outer["model.embed_tokens.weight"], jnp.asarray(ids)) for p in precisions}
    for i in range(cfg["num_hidden_layers"]):
        w = weights.make(layer_specs(cfg, i), seed, w_dtype)
        w = {k.split(f"layers.{i}.")[1]: v for k, v in w.items()}
        for p in precisions:
            xs[p] = layer_fn(i < cfg["first_k_dense_replace"], p)(xs[p], w)
        del w
    out = {p: np.asarray(make_head(p == "int8")(xs[p][rows, cols], outer["model.norm.weight"],
                                                 outer["lm_head.weight"]))
           for p in precisions}
    served = np.asarray(served)
    if fault:
        from chipbench import harness

        g = gaps(out.pop("no_select"), served)
        harness.log("fault control", fault="no_select", gap_max=float(g.max()), gap_mean=float(g.mean()),
                    gap_p99=float(np.percentile(g, 99)), tokens_off_best=int((g > 0).sum()))
    return out, served


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """By how much each token's reference logit lies below the reference's
    best at its position (0 where the token is the reference's own)."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]
