"""openPangu-Ultra-MoE style decoder (`pangu_ultra_moe`): multi-head latent
attention, sandwich norm, leading dense layers then sparse ones with gated
routed experts and a shared expert.

- Block (sandwich norm, four RMSNorms a layer): `h = x + N2(MLA(N1(x)))`,
  `y = h + N4(FFN(N3(h)))`; a final RMSNorm and an untied head.
- MLA on `a = N1(x)`: `c_q = RMSNorm(a W_qa)`, `q = c_q W_qb` -> H heads of
  `[q_nope | q_rope]`; `[c | k_r] = a W_kva`, `c_kv = RMSNorm(c)`, `k_r` ONE
  rotary key shared by all heads; rotate-half RoPE on `q_rope` and `k_r`;
  `[k_nope | v]` a head `= c_kv W_kvb`; causal `softmax((q_nope . k_nope +
  q_rope . k_r) / sqrt(nope + rope)) v`, then `W_o`.
- Dense FFN: `(silu(m W_g) * (m W_u)) W_d`. Sparse FFN: sigmoid router over
  ALL `n_routed_experts` in float32, the `num_experts_per_tok` largest,
  weights normalised over the chosen and scaled; `sum_e w_e f_e(m) +
  f_shared(m)`, every `f` the gated form. The layer is told which experts it
  HOLDS (`experts_held = [first, count]`, one chip's share under expert
  parallelism; `expert_share.py`).

Two attention paths, the same mathematics:

- EXPANDED (no cache, and a bucketed prefill): `k`, `v` a head from `W_kvb`,
  heads `nope + rope` wide against values `v_head_dim` wide, through the
  flash kernel where it pays (the value padded to the key's width there:
  the kernel wants one width).
- ABSORBED (positioned rows, a prompt's chunk, `extend`, over the cache): the
  cache keeps `[c_kv | k_r]` alone, `kv_lora_rank + qk_rope_head_dim` numbers
  a token a layer (a LATENT pool, `inference/kv_cache.py`); the key
  up-projection moves to the query (`q_nope W_UK`, so every head scores
  against the one cached vector), the context is summed in the latent and
  the value up-projection `W_UV` comes after
  (`ops.pallas.mla_paged_attention`).

Serving (`forward(ids, cache=, positions=, last_index=)`, as
LlamaForCausalLM has it); which segment of a step writes and reads what is
`cache_segments.attend_through_cache`'s. The cache path is inference-only.
"""
from __future__ import annotations

import inspect
import math

import jax
from jax import numpy as jnp

from .. import nn
from ..core.apply import apply
from ..core.tensor import Tensor
from ..ops import pallas as pk
from .cache_segments import attend_through_cache, positions_2d, take_positions
from .expert_share import route_topk, routed_experts
from .llama import _rope_tables, _ROPE_POS_GRANULE

__all__ = ["PanguUltraMoEForCausalLM", "PanguUltraMoEModel"]


def _dot_f32(x, w):
    """x @ w in the storage dtype with a float32 result."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    out = (xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)).astype(x.dtype)
    return out * w.astype(x.dtype)


def rope_half(x, positions, theta, max_pos):
    """Rotate-half rotary embedding of x [B, S, ..., d] (any axes between the
    sequence and the last): column i pairs with column i + d/2. positions
    [B, S] int32, or None for tokens at 0..S-1; `max_pos` bounds the table
    (static under trace)."""
    d, s = x.shape[-1], x.shape[1]
    cap = -(-max(int(max_pos), 1) // _ROPE_POS_GRANULE) * _ROPE_POS_GRANULE
    cos_np, sin_np = _rope_tables(cap, d, float(theta))
    if positions is None:
        cos, sin = jnp.asarray(cos_np[:s])[None], jnp.asarray(sin_np[:s])[None]
    else:
        cos, sin = jnp.asarray(cos_np)[positions], jnp.asarray(sin_np)[positions]
    mid = (1,) * (x.ndim - 3)
    cos, sin = cos.reshape(*cos.shape[:2], *mid, d // 2), sin.reshape(*sin.shape[:2], *mid, d // 2)
    x1, x2 = x[..., :d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _causal_attention(q, k, v, scale):
    """Plain causal attention [B, S, H, D]; the flash kernel where it pays
    (it wants one width: the narrower value is padded to the key's)."""
    if pk.flash_attention_profitable(q, True, 0.0, k, k):
        dv = v.shape[-1]
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, k.shape[-1] - dv),))
        return pk.flash_attention_bshd(q, k, v, causal=True, sm_scale=scale)[..., :dv]
    return pk._ref_attention_bshd(q, k, v, True, scale)


# ---------------------------------------------------------------------------
# multi-head latent attention
# ---------------------------------------------------------------------------

def mla_project(a, w_qa, g_qa, w_qb, w_kva, g_kva, *, heads, nope, rope, rank, eps, theta,
                positions, max_pos):
    """From the normed input a [B, S, hidden]: (q_nope [B, S, H, nope], q_rope
    [B, S, H, rope] rotated, c_kv [B, S, rank] normed, k_r [B, S, rope]
    rotated)."""
    b, s, _ = a.shape
    q = jnp.dot(_rms(jnp.dot(a, w_qa), g_qa, eps), w_qb).reshape(b, s, heads, nope + rope)
    kv = jnp.dot(a, w_kva)
    c_kv = _rms(kv[..., :rank], g_kva, eps)
    q_rope = rope_half(q[..., nope:], positions, theta, max_pos)
    k_r = rope_half(kv[..., rank:], positions, theta, max_pos)
    return q[..., :nope], q_rope, c_kv, k_r


_HEAD_GROUP = 16  # heads the expanded path holds keys and values of at once


def mla_expanded(q_nope, q_rope, c_kv, k_r, w_kvb, *, heads, nope, v_dim, scale):
    """The expanded path: keys and values a head from the latent, plain
    causal attention. Returns [B, S, H * v_dim]. Many heads go through in
    groups of `_HEAD_GROUP` (their keys and values projected a group at a
    time): at 128 heads an 8,192-token prefill's q, k, v and the kernel's
    head-major copies of them are 2.8 GB whole, and with the expert layer's rows in
    blocks of 1024 tokens the 8,192 bucket's temporaries are 2.7 GB where they
    were 4.2 (compiled for a described v5e)."""
    b, s = c_kv.shape[:2]
    hg = _HEAD_GROUP if heads > _HEAD_GROUP and heads % _HEAD_GROUP == 0 else heads

    def group(args):
        q_n, q_r, w = args  # [B, S, hg, .] queries, the group's columns of W_kvb
        kv = jnp.dot(c_kv, w).reshape(b, s, hg, nope + v_dim)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, :, None], (b, s, hg, k_r.shape[-1]))], -1)
        return _causal_attention(jnp.concatenate([q_n, q_r], -1), k, kv[..., nope:], scale)

    if hg == heads:
        return group((q_nope, q_rope, w_kvb)).reshape(b, s, heads * v_dim)
    n = heads // hg

    def by_group(x):  # [B, S, H, d] -> [n, B, S, hg, d]
        return jnp.moveaxis(x.reshape(b, s, n, hg, x.shape[-1]), 2, 0)

    out = jax.lax.map(group, (by_group(q_nope), by_group(q_rope),
                              jnp.moveaxis(w_kvb.reshape(-1, n, hg * (nope + v_dim)), 1, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, heads * v_dim)


def mla_absorb_query(q_nope, q_rope, w_kvb, *, heads, nope, v_dim):
    """The query as the latent cache is read: `q_nope W_UK` then `q_rope`,
    [B, S, H, rank + rope]."""
    w_uk = w_kvb.reshape(w_kvb.shape[0], heads, nope + v_dim)[..., :nope]  # [rank, H, nope]
    q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_uk, preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat.astype(q_nope.dtype), q_rope], -1)


def mla_unabsorb_context(ctx, w_kvb, *, heads, nope, v_dim):
    """The context summed in the latent [..., H, rank] through `W_UV`:
    [..., H * v_dim]."""
    w_uv = w_kvb.reshape(w_kvb.shape[0], heads, nope + v_dim)[..., nope:]  # [rank, H, v]
    out = jnp.einsum("...hc,chd->...hd", ctx, w_uv, preferred_element_type=jnp.float32)
    return out.astype(ctx.dtype).reshape(*ctx.shape[:-2], heads * v_dim)


class PanguMLAttention(nn.Layer):
    def __init__(self, hidden_size, num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, eps, rope_theta):
        super().__init__()
        self.layer_idx = 0  # place in the decoder stack (set by the model)
        self.dims = dict(heads=num_heads, nope=qk_nope_head_dim, rope=qk_rope_head_dim,
                         rank=kv_lora_rank, eps=eps, theta=rope_theta)
        self.v_dim = v_head_dim
        self.scale = 1.0 / math.sqrt(qk_nope_head_dim + qk_rope_head_dim)
        self.q_a_proj = nn.Linear(hidden_size, q_lora_rank, bias_attr=False)
        self.q_a_layernorm = nn.RMSNorm(q_lora_rank, eps)
        self.q_b_proj = nn.Linear(q_lora_rank, num_heads * (qk_nope_head_dim + qk_rope_head_dim), bias_attr=False)
        self.kv_a_proj_with_mqa = nn.Linear(hidden_size, kv_lora_rank + qk_rope_head_dim, bias_attr=False)
        self.kv_a_layernorm = nn.RMSNorm(kv_lora_rank, eps)
        self.kv_b_proj = nn.Linear(kv_lora_rank, num_heads * (qk_nope_head_dim + v_head_dim), bias_attr=False)
        self.o_proj = nn.Linear(num_heads * v_head_dim, hidden_size, bias_attr=False)

    def _leaves(self):
        return (self.q_a_proj.weight, self.q_a_layernorm.weight, self.q_b_proj.weight,
                self.kv_a_proj_with_mqa.weight, self.kv_a_layernorm.weight, self.kv_b_proj.weight)

    def _head_dims(self):
        return dict(heads=self.dims["heads"], nope=self.dims["nope"], v_dim=self.v_dim)

    def forward(self, x, cache=None, positions=None):
        b, s = x.shape[0], x.shape[1]
        hd, scale = self._head_dims(), self.scale
        if cache is None:
            dims = dict(self.dims, positions=None, max_pos=s)

            def f(xv, *w):
                return mla_expanded(*mla_project(xv, *w[:5], **dims), w[5], **hd, scale=scale)

            return self.o_proj(apply("mla", f, x, *self._leaves()))
        # ---- serving cache mode (inference-only) ----
        idx, rank = self.layer_idx, self.dims["rank"]
        w = [t.value for t in self._leaves()]
        pos2d = positions_2d(positions, b)
        q_nope, q_rope, c_kv, k_r = mla_project(
            x.value, *w[:5], **self.dims, positions=pos2d,
            max_pos=cache.block_tables.shape[1] * cache.block_size)
        entry = jnp.concatenate([c_kv, k_r], -1)  # what the layer caches: [B, S, rank + rope]

        def prefill():
            return mla_expanded(q_nope, q_rope, c_kv, k_r, w[5], **hd, scale=scale).reshape(b, s, hd["heads"], -1)

        def read_many(qs, table, q_positions):
            ctx = pk.mla_paged_attention(qs, cache.k_pages[idx], table, q_positions, rank, scale)
            return mla_unabsorb_context(ctx, w[5], **hd).reshape(*ctx.shape[:3], -1)

        def read_one(qs, table, seq_lens):
            return read_many(qs[:, None], table, (seq_lens - 1)[:, None])[:, 0]

        q = None if pos2d is None else mla_absorb_query(q_nope, q_rope, w[5], **hd)  # a prefill reads no cache
        out = attend_through_cache(cache, idx, q, (entry,), pos2d, prefill=prefill,
                                   read_one=read_one, read_many=read_many)
        return self.o_proj(Tensor(out.reshape(b, s, -1)))


# ---------------------------------------------------------------------------
# feed-forward: dense, and the share of a sparse layer
# ---------------------------------------------------------------------------

def gated_mlp(x, w_gate, w_up, w_down):
    """`(silu(x W_g) * (x W_u)) W_d`, float32 out. The two wide products come
    out in the storage dtype (a prefill's are [tokens, width] each: float32
    would double them), the gate is applied in float32."""
    h = jax.nn.silu(jnp.dot(x, w_gate).astype(jnp.float32)) * jnp.dot(x, w_up).astype(jnp.float32)
    return _dot_f32(h.astype(x.dtype), w_down)


def sparse_mlp(x, w_router, e_gate, e_up, e_down, s_gate, s_up, s_down, *, top_k, scale, first, valid=None):
    """x [T, hidden] -> (this share's output [T, hidden], assignments,
    experts touched): the held experts' part of the routed sum plus the
    shared expert, whole on every chip."""
    chosen, weights = route_topk(x, w_router, None, top_k, scale)
    routed, n_assign, n_touched = routed_experts(x, chosen, weights, e_up, e_down, first, valid,
                                                 w_gate=e_gate, activation="silu")
    return (routed + gated_mlp(x, s_gate, s_up, s_down)).astype(x.dtype), n_assign, n_touched


class PanguMLP(nn.Layer):
    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.gate_proj = nn.Linear(hidden_size, intermediate_size, bias_attr=False)
        self.up_proj = nn.Linear(hidden_size, intermediate_size, bias_attr=False)
        self.down_proj = nn.Linear(intermediate_size, hidden_size, bias_attr=False)

    def _leaves(self):
        return (self.gate_proj.weight, self.up_proj.weight, self.down_proj.weight)

    def forward(self, x, cache=None, positions=None):
        return apply("gated_mlp", lambda xv, *w: gated_mlp(xv, *w).astype(xv.dtype), x, *self._leaves())


class PanguSparseMLP(nn.Layer):
    def __init__(self, hidden_size, n_routed_experts, experts_held, top_k, moe_intermediate_size,
                 shared_intermediate_size, routed_scaling_factor, initializer_range=0.02):
        super().__init__()
        from ..nn.initializer import Normal

        first, count = (int(v) for v in experts_held)
        if first < 0 or count < 1 or first + count > n_routed_experts:
            raise ValueError(f"experts_held {experts_held} outside the {n_routed_experts} routed experts")
        self.kw = dict(top_k=int(top_k), scale=float(routed_scaling_factor), first=first)
        init = Normal(0.0, initializer_range)
        self.router = self.create_parameter([hidden_size, n_routed_experts], default_initializer=init)
        shape = [count, hidden_size, moe_intermediate_size]
        self.experts_gate = self.create_parameter(shape, default_initializer=init)
        self.experts_up = self.create_parameter(shape, default_initializer=init)
        self.experts_down = self.create_parameter([count, moe_intermediate_size, hidden_size],
                                                  default_initializer=init)
        self.shared_experts = PanguMLP(hidden_size, shared_intermediate_size)

    def _leaves(self):
        return (self.router, self.experts_gate, self.experts_up, self.experts_down,
                *self.shared_experts._leaves())

    def forward(self, x, cache=None, positions=None):
        b, s, h = x.shape
        if cache is None:
            return apply("sparse_mlp",
                         lambda xv, *w: sparse_mlp(xv.reshape(b * s, h), *w, **self.kw)[0].reshape(b, s, h),
                         x, *self._leaves())
        valid = cache.token_mask(b, s, positions)
        out, n_assign, n_touched = sparse_mlp(x.value.reshape(b * s, h), *[t.value for t in self._leaves()],
                                              valid=valid.reshape(-1), **self.kw)
        cache.count_moe(n_assign, n_touched)
        return Tensor(out.reshape(b, s, h))


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

class PanguDecoderLayer(nn.Layer):
    def __init__(self, hidden_size, eps, attention, mlp):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(hidden_size, eps)
        self.self_attn = attention
        self.post_attention_layernorm = nn.RMSNorm(hidden_size, eps)
        self.pre_mlp_layernorm = nn.RMSNorm(hidden_size, eps)
        self.mlp = mlp
        self.post_mlp_layernorm = nn.RMSNorm(hidden_size, eps)

    def forward(self, x, cache=None, positions=None):
        x = x + self.post_attention_layernorm(
            self.self_attn(self.input_layernorm(x), cache=cache, positions=positions))
        return x + self.post_mlp_layernorm(
            self.mlp(self.pre_mlp_layernorm(x), cache=cache, positions=positions))


class PanguUltraMoEModel(nn.Layer):
    def __init__(self, vocab_size=1024, hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
                 num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160, moe_intermediate_size=48,
                 n_routed_experts=16, experts_held=None, num_experts_per_tok=4, n_shared_experts=1,
                 routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=25600000.0,
                 initializer_range=0.02):
        super().__init__()
        held = list(experts_held) if experts_held is not None else [0, n_routed_experts]
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size)

        def layer(i):
            attn = PanguMLAttention(hidden_size, num_attention_heads, q_lora_rank, kv_lora_rank,
                                    qk_nope_head_dim, qk_rope_head_dim, v_head_dim, rms_norm_eps, rope_theta)
            attn.layer_idx = i
            if i < first_k_dense_replace:
                mlp = PanguMLP(hidden_size, intermediate_size)
            else:
                mlp = PanguSparseMLP(hidden_size, n_routed_experts, held, num_experts_per_tok,
                                     moe_intermediate_size, n_shared_experts * moe_intermediate_size,
                                     routed_scaling_factor, initializer_range)
            return PanguDecoderLayer(hidden_size, rms_norm_eps, attn, mlp)

        self.layers = nn.LayerList([layer(i) for i in range(num_hidden_layers)])
        self.norm = nn.RMSNorm(hidden_size, rms_norm_eps)

    def forward(self, input_ids, cache=None, positions=None):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, cache=cache, positions=positions)
        return self.norm(x)


class PanguUltraMoEForCausalLM(nn.Layer):
    """`.config` holds what the serving engine reads: `num_hidden_layers`,
    `layer_kinds` ("attention" for a leading dense layer, "attention+moe" for
    a sparse one: every layer caches, the sparse ones report expert counters),
    `vocab_size`, `num_attention_heads`, and `cache_entry`: the latent vector a
    layer keeps a token (`kv_lora_rank + qk_rope_head_dim` wide, the first
    `kv_lora_rank` columns also the value)."""

    def __init__(self, **config):
        super().__init__()
        self.model = PanguUltraMoEModel(**config)
        defaults = {k: p.default for k, p in inspect.signature(PanguUltraMoEModel.__init__).parameters.items()
                    if p.default is not inspect.Parameter.empty}
        cfg = self.config = {**defaults, **config}
        if cfg["experts_held"] is None:
            cfg["experts_held"] = [0, cfg["n_routed_experts"]]
        dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
        cfg["layer_kinds"] = ["attention"] * dense + ["attention+moe"] * (cfg["num_hidden_layers"] - dense)
        cfg["cache_entry"] = {"layout": "latent", "width": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
                              "value_width": cfg["kv_lora_rank"]}
        self.lm_head = nn.Linear(cfg["hidden_size"], cfg["vocab_size"], bias_attr=False)

    def forward(self, input_ids, cache=None, positions=None, last_index=None):
        h = self.model(input_ids, cache=cache, positions=positions)
        if last_index is not None:
            h = Tensor(take_positions(h.value, last_index))
        return self.lm_head(h)
