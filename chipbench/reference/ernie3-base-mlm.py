"""Plain reference of ERNIE-3.0-base masked-LM pretraining: forward, loss,
gradients and AdamW in straightforward jax.numpy, float32, matmuls at
"highest" precision. No kernels, no program code.

Follows PaddleNLP's ErnieModel + ErnieForMaskedLM (BERT post-LN encoder):
word + position + token-type embeddings -> LayerNorm; per layer
self-attention (softmax(QK^T / sqrt(d)) V, no mask), residual, LayerNorm,
gelu (erf) feed-forward, residual, LayerNorm; head: dense, gelu, LayerNorm,
decoder tied to the word embeddings plus a bias; loss = mean cross-entropy
over all labelled positions. Departures, noted: dropout is 0 (the
configuration lists it under `reduced`); the pooler is left out (the MLM
loss never reads it, it gets no gradient and, as in Paddle's optimizer, a
parameter without a gradient is not stepped); token types are all 0.

`precision` selects the control: "bf16" keeps parameters, gradients,
moments and activations in bfloat16 (the step below the float32 state the
configuration states). `rows` lets a planted fault leave rows out.
"""
import jax
import jax.numpy as jnp

LN_EPS = 1e-5
LAYER_LEAVES = {
    "self_attn.q_proj.weight": ("h", "h"), "self_attn.q_proj.bias": ("h",),
    "self_attn.k_proj.weight": ("h", "h"), "self_attn.k_proj.bias": ("h",),
    "self_attn.v_proj.weight": ("h", "h"), "self_attn.v_proj.bias": ("h",),
    "self_attn.out_proj.weight": ("h", "h"), "self_attn.out_proj.bias": ("h",),
    "linear1.weight": ("h", "f"), "linear1.bias": ("f",),
    "linear2.weight": ("f", "h"), "linear2.bias": ("h",),
    "norm1.weight": ("h",), "norm1.bias": ("h",),
    "norm2.weight": ("h",), "norm2.bias": ("h",),
}


def leaf_specs(cfg: dict) -> dict:
    """{program state name: (shape, init kind, scale)}: every Linear and
    Embedding weight Normal(0, initializer_range), LayerNorm 1/0, biases 0
    (ErnieModel.init_weights). Leaves the loss never reads (pooler) are
    given too, so the program's model is fully assigned."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    std = cfg["initializer_range"]
    dims = {"h": h, "f": f}

    def kind(name):
        if name.endswith("bias"):
            return "zeros"
        if "norm" in name:
            return "ones"
        return "normal"

    s = {
        "ernie.embeddings.word_embeddings.weight": ((v, h), "normal_pad0", std),
        "ernie.embeddings.position_embeddings.weight": ((cfg["max_position_embeddings"], h), "normal", std),
        "ernie.embeddings.token_type_embeddings.weight": ((cfg["type_vocab_size"], h), "normal", std),
        "ernie.embeddings.layer_norm.weight": ((h,), "ones", 0.0),
        "ernie.embeddings.layer_norm.bias": ((h,), "zeros", 0.0),
        "ernie.pooler.dense.weight": ((h, h), "normal", std),
        "ernie.pooler.dense.bias": ((h,), "zeros", 0.0),
        "transform.weight": ((h, h), "normal", std),
        "transform.bias": ((h,), "zeros", 0.0),
        "layer_norm.weight": ((h,), "ones", 0.0),
        "layer_norm.bias": ((h,), "zeros", 0.0),
        "decoder_bias": ((v,), "zeros", 0.0),
    }
    for i in range(cfg["num_hidden_layers"]):
        for leaf, sym in LAYER_LEAVES.items():
            name = f"ernie.encoder.layers.{i}.{leaf}"
            s[name] = (tuple(dims[d] for d in sym), kind(leaf), std)
    return s


def trained_names(cfg: dict):
    return [n for n in leaf_specs(cfg) if not n.startswith("ernie.pooler.")]


def _ln(x, w, b):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = jnp.square(xf - mu).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + LN_EPS)).astype(x.dtype) * w + b


def _stack(p, cfg):
    L = cfg["num_hidden_layers"]
    return {leaf: jnp.stack([p[f"ernie.encoder.layers.{i}.{leaf}"] for i in range(L)])
            for leaf in LAYER_LEAVES}


def loss_fn(p, ids, labels, cfg):
    """Mean cross-entropy of the masked-LM head over every position."""
    heads = cfg["num_attention_heads"]
    h = cfg["hidden_size"]
    d = h // heads
    b, s = ids.shape
    pre = "ernie.embeddings."
    word = p[pre + "word_embeddings.weight"]
    x = word[ids] + p[pre + "position_embeddings.weight"][:s][None] \
        + p[pre + "token_type_embeddings.weight"][0]
    x = _ln(x, p[pre + "layer_norm.weight"], p[pre + "layer_norm.bias"])

    def layer(x, w):
        def proj(n):
            y = x @ w[f"self_attn.{n}_proj.weight"] + w[f"self_attn.{n}_proj.bias"]
            return y.reshape(b, s, heads, d).transpose(0, 2, 1, 3)
        q, k, v = proj("q"), proj("k"), proj("v")
        sc = (q @ k.transpose(0, 1, 3, 2)).astype(jnp.float32) / jnp.sqrt(jnp.float32(d))
        a = jax.nn.softmax(sc, axis=-1).astype(x.dtype) @ v
        a = a.transpose(0, 2, 1, 3).reshape(b, s, h)
        x = _ln(x + a @ w["self_attn.out_proj.weight"] + w["self_attn.out_proj.bias"],
                w["norm1.weight"], w["norm1.bias"])
        f = jax.nn.gelu(x @ w["linear1.weight"] + w["linear1.bias"], approximate=False)
        x = _ln(x + f @ w["linear2.weight"] + w["linear2.bias"],
                w["norm2.weight"], w["norm2.bias"])
        return x, None

    x, _ = jax.lax.scan(layer, x, _stack(p, cfg))
    t = jax.nn.gelu(x @ p["transform.weight"] + p["transform.bias"], approximate=False)
    t = _ln(t, p["layer_norm.weight"], p["layer_norm.bias"])
    logits = (t @ word.T + p["decoder_bias"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (lse - picked).mean()


def train_steps(params: dict, ids, labels, cfg: dict, hp: dict, steps: int = 3,
                row_block: int = 4, precision: str = "f32", rows=None):
    """Steps of AdamW on the one batch. Returns
    {"losses": [..], "grad_norms": {leaf: ||g_1||}, "change_norms": {leaf:
    ||p_steps - p_0||}} as Python floats. `rows` (a slice) plants the
    half-batch fault: the mean is taken over those rows alone."""
    names = trained_names(cfg)
    dt = jnp.float32 if precision == "f32" else jnp.bfloat16
    mm = "highest" if precision == "f32" else "default"
    ids, labels = jnp.asarray(ids, jnp.int32), jnp.asarray(labels, jnp.int32)
    if rows is not None:
        ids, labels = ids[rows], labels[rows]
    n = ids.shape[0]
    row_block = min(row_block, n)
    assert n % row_block == 0, "the row block must divide the batch"
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["epsilon"]
    lr, wd = hp["learning_rate"], hp["weight_decay"]

    @jax.jit
    def block_grad(p, i, l):
        with jax.default_matmul_precision(mm):
            return jax.value_and_grad(lambda q: loss_fn(q, i, l, cfg))(p)

    @jax.jit
    def adamw(p, g, m, v, t):
        def one(p, g, m, v):
            g = g.astype(dt)
            m = (b1 * m + (1 - b1) * g).astype(dt)
            v = (b2 * v + (1 - b2) * g * g).astype(dt)
            upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * p
            return (p - lr * upd).astype(dt), m, v
        out = {k: one(p[k], g[k], m[k], v[k]) for k in p}
        return ({k: o[0] for k, o in out.items()}, {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()})

    @jax.jit
    def norms(a):
        return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for k, x in a.items()}

    p = {k: params[k].astype(dt) for k in names}
    p0 = p
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    losses, grad_norms = [], None
    for t in range(1, steps + 1):
        loss, grad = 0.0, None
        for r in range(0, n, row_block):
            lo, g = block_grad(p, ids[r:r + row_block], labels[r:r + row_block])
            loss = loss + lo.astype(jnp.float32)
            grad = g if grad is None else jax.tree_util.tree_map(jnp.add, grad, g)
        k = n // row_block
        grad = jax.tree_util.tree_map(lambda x: x / k, grad)
        losses.append(float(loss) / k)
        if t == 1:
            grad_norms = {a: float(b) for a, b in norms(grad).items()}
        p, m, v = adamw(p, grad, m, v, jnp.float32(t))
    change = norms({k: p[k].astype(jnp.float32) - p0[k].astype(jnp.float32) for k in p})
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {a: float(b) for a, b in change.items()}}
