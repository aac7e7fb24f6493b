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

from jax import numpy as jnp

from .. import nn
from ..core.apply import apply
from ..core.tensor import Tensor
from ..ops import pallas as pk
from .cache_segments import attend_through_cache, positions_2d, take_positions
from .mla_moe import (GatedMLP, SparseMLP, gated_mlp, mla_absorb_query, mla_expanded, mla_project,  # noqa: F401
                      mla_unabsorb_context, sparse_mlp)

__all__ = ["PanguUltraMoEForCausalLM", "PanguUltraMoEModel"]


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
                return mla_expanded(*mla_project(xv, *w[:5], **dims)[:4], w[5], **hd, scale=scale)

            return self.o_proj(apply("mla", f, x, *self._leaves()))
        # ---- serving cache mode (inference-only) ----
        idx, rank = self.layer_idx, self.dims["rank"]
        w = [t.value for t in self._leaves()]
        pos2d = positions_2d(positions, b)
        q_nope, q_rope, c_kv, k_r, _ = mla_project(
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
                mlp = GatedMLP(hidden_size, intermediate_size)
            else:
                mlp = SparseMLP(hidden_size, n_routed_experts, held, num_experts_per_tok,
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
