"""DeepSeek-V3.2 style decoder (`deepseek_v32`): multi-head latent attention
with a LEARNED TOKEN SELECTOR (DeepSeek sparse attention: a lightning indexer
scores every cached position for a query and the attention runs over the
`index_topk` best alone), pre-norm blocks, leading dense layers then sparse
ones whose router chooses inside the best groups of experts, a shared expert.

- Block: `h = x + MLA(N1(x))`, `y = h + FFN(N2(h))`; a final RMSNorm and an
  untied head.
- MLA as `mla_moe` has it, rotary by YaRN's inverse frequencies at every
  length, softmax scale `(nope + rope)^-0.5 x (0.1 ln(factor) + 1)^2`.
- Indexer, a layer, on the same normed input `a` and the query's latent `c_q`:
  `q_idx = c_q W_iq` -> J heads of D; `k_idx = LayerNorm(a W_ik)` (D, ONE key a
  token); the first `rope` columns of both take the rotary of `k_r`;
  `w = (a W_iw) J^-0.5 D^-0.5` (float32). Index score
  `I[t, s] = sum_j w[t, j] relu(q_idx[t, j] . k_idx[s])`, s <= t; the query
  attends to the `min(index_topk, t + 1)` positions of largest score.
- Sparse FFN: sigmoid router in float32, a correction bias that steers the
  choice alone, the experts in `n_group` groups, a group's score the sum of its
  two best, the `topk_group` best groups kept, top-k inside them, weights
  normalised over the chosen and scaled (`expert_share.route_topk`); the layer
  is told which experts it HOLDS.

The cache keeps two things a token a layer under one page and slot: the latent
entry `[c_kv | k_r]` and the index key `k_idx` (`cache_entry`: `layout`
"latent", `width`, `index_width`). EVERY way a step's tokens enter reads
through that cache by ONE path (`dsa_attend`): a decode row, a prompt's chunk,
`extend`, and a bucketed prefill too (its entries are written to its pages
first, then its queries attend through the cache a tile at a time, so a long
bucket's scores and gathered entries stay one tile's). A tile of queries none
of which stands past `index_topk` attends to everything it may see, through
`mla_paged_attn` as the dense latent decoder does; a tile with a query past it
goes `dsa_index` -> `dsa_select` -> `mla_sparse_paged_attn` (`ops.pallas`).
"""
from __future__ import annotations

import inspect
import math

import jax
from jax import lax
from jax import numpy as jnp

from .. import nn
from ..core.apply import apply
from ..core.tensor import Tensor
from ..ops import pallas as pk
from .cache_segments import attend_through_cache, positions_2d, take_positions
from .mla_moe import (GatedMLP, SparseMLP, mla_absorb_query, mla_project, mla_unabsorb_context, rope_half,
                      yarn_softmax_factor)

__all__ = ["DeepseekV32ForCausalLM", "DeepseekV32Model", "dsa_attend"]

QUERY_TILE = 128  # queries of a row that score, select and attend together


def index_project(a, c_q, w_iq, w_ik, g_ik, b_ik, w_iw, *, heads, dim, rope, eps, theta, positions, max_pos, yarn):
    """The indexer's side of a layer, from the normed input a [B, S, hidden]
    and the query's latent c_q [B, S, q rank]: (q_idx [B, S, J, D] and k_idx
    [B, S, D], their first `rope` columns rotated; w [B, S, J] float32)."""
    b, s, _ = a.shape
    q = jnp.dot(c_q, w_iq).reshape(b, s, heads, dim)
    k = jnp.dot(a, w_ik).astype(jnp.float32)
    mu = jnp.mean(k, -1, keepdims=True)
    k = (k - mu) * lax.rsqrt(jnp.mean(jnp.square(k - mu), -1, keepdims=True) + eps)
    k = (k * g_ik.astype(jnp.float32) + b_ik.astype(jnp.float32)).astype(a.dtype)
    q = jnp.concatenate([rope_half(q[..., :rope], positions, theta, max_pos, yarn), q[..., rope:]], -1)
    k = jnp.concatenate([rope_half(k[..., :rope], positions, theta, max_pos, yarn), k[..., rope:]], -1)
    w = jnp.dot(a, w_iw, preferred_element_type=jnp.float32) * (heads ** -0.5 * dim ** -0.5)
    return q, k, w


def dsa_attend(q_abs, q_idx, w_idx, latent_pages, index_pages, table, q_positions, *, topk, value_width, scale):
    """One tile of queries through the cache: q_abs [R, Q, H, E] absorbed
    queries, q_idx [R, Q, J, D] and w_idx [R, Q, J] the indexer's, at
    consecutive positions `q_positions` [R, Q] (pad slots 0). Where no query
    stands past `topk` every position a query may see is chosen, and the dense
    kernel reads them; else index scores, the exact `topk` best a query, and
    the attention over those alone. Returns the context in the latent
    [R, Q, H, value_width]."""
    def dense():
        return pk.mla_paged_attention(q_abs, latent_pages, table, q_positions, value_width, scale)

    def sparse():
        scores = pk.dsa_index_scores(q_idx, w_idx, index_pages, table, q_positions)
        rows = pk.dsa_select(scores, topk, carry=pk.pool_rows(table, latent_pages.shape[1]),
                             frontier=jnp.max(q_positions) + 1)
        counts = jnp.minimum(q_positions + 1, rows.shape[-1])
        return pk.mla_sparse_attention(q_abs, latent_pages, rows, counts, value_width, scale)

    if table.shape[1] * latent_pages.shape[1] <= topk:  # no context this table holds passes topk
        return dense()
    return lax.cond(jnp.max(q_positions) >= topk, sparse, dense)


def _selected_mask(q_idx, k_idx, w_idx, topk):
    """[B, S, S] bool for a whole sequence at 0..S-1 (the cacheless forward):
    True where query t attends to position s."""
    s = q_idx.shape[1]
    dots = jnp.einsum("btjd,bsd->btjs", q_idx, k_idx, preferred_element_type=jnp.float32)
    scores = jnp.sum(jnp.maximum(dots, 0.0) * w_idx[..., None], axis=2)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    if s <= topk:
        return jnp.broadcast_to(causal[None], scores.shape)
    chosen = pk.dsa_select(scores, topk)
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None, None], jnp.arange(s)[None, :, None], chosen].set(True)
    return picked & causal[None]


class DeepseekV32Attention(nn.Layer):
    def __init__(self, hidden_size, num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                 v_head_dim, index_n_heads, index_head_dim, index_topk, eps, rope_theta, rope_scaling):
        super().__init__()
        self.layer_idx = 0  # place in the decoder stack (set by the model)
        yarn, factor = None, 1.0
        if rope_scaling:
            yarn = (rope_scaling["factor"], rope_scaling["original_max_position_embeddings"],
                    rope_scaling["beta_fast"], rope_scaling["beta_slow"])
            factor = yarn_softmax_factor(rope_scaling["factor"], rope_scaling.get("mscale_all_dim", 0))
        self.dims = dict(heads=num_heads, nope=qk_nope_head_dim, rope=qk_rope_head_dim, rank=kv_lora_rank,
                         eps=eps, theta=rope_theta, yarn=yarn)
        self.index_dims = dict(heads=index_n_heads, dim=index_head_dim, rope=qk_rope_head_dim, eps=eps,
                               theta=rope_theta, yarn=yarn)
        self.topk = int(index_topk)
        self.v_dim = v_head_dim
        self.scale = factor / math.sqrt(qk_nope_head_dim + qk_rope_head_dim)
        self.q_a_proj = nn.Linear(hidden_size, q_lora_rank, bias_attr=False)
        self.q_a_layernorm = nn.RMSNorm(q_lora_rank, eps)
        self.q_b_proj = nn.Linear(q_lora_rank, num_heads * (qk_nope_head_dim + qk_rope_head_dim), bias_attr=False)
        self.kv_a_proj_with_mqa = nn.Linear(hidden_size, kv_lora_rank + qk_rope_head_dim, bias_attr=False)
        self.kv_a_layernorm = nn.RMSNorm(kv_lora_rank, eps)
        self.kv_b_proj = nn.Linear(kv_lora_rank, num_heads * (qk_nope_head_dim + v_head_dim), bias_attr=False)
        self.o_proj = nn.Linear(num_heads * v_head_dim, hidden_size, bias_attr=False)
        self.indexer = nn.Layer()
        self.indexer.wq_b = nn.Linear(q_lora_rank, index_n_heads * index_head_dim, bias_attr=False)
        self.indexer.wk = nn.Linear(hidden_size, index_head_dim, bias_attr=False)
        self.indexer.k_norm = nn.LayerNorm(index_head_dim, eps)
        self.indexer.weights_proj = nn.Linear(hidden_size, index_n_heads, bias_attr=False)

    def _leaves(self):
        ix = self.indexer
        return (self.q_a_proj.weight, self.q_a_layernorm.weight, self.q_b_proj.weight,
                self.kv_a_proj_with_mqa.weight, self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                ix.wq_b.weight, ix.wk.weight, ix.k_norm.weight, ix.k_norm.bias, ix.weights_proj.weight)

    def _head_dims(self):
        return dict(heads=self.dims["heads"], nope=self.dims["nope"], v_dim=self.v_dim)

    def _whole(self, x, *w):
        """The cacheless forward of a whole sequence at 0..S-1: expanded keys
        and values, every head's scores masked to the selector's choice.
        [B, S, S] scores a head: small sizes (tests, a check against the
        reference)."""
        b, s, _ = x.shape
        hd, at = self._head_dims(), dict(positions=None, max_pos=s)
        q_nope, q_rope, c_kv, k_r, c_q = mla_project(x, *w[:5], **self.dims, **at)
        mask = _selected_mask(*index_project(x, c_q, *w[6:], **self.index_dims, **at), self.topk)
        kv = jnp.dot(c_kv, w[5]).reshape(b, s, hd["heads"], hd["nope"] + hd["v_dim"])
        logits = (jnp.einsum("bthd,bshd->bhts", q_nope, kv[..., :hd["nope"]], preferred_element_type=jnp.float32)
                  + jnp.einsum("bthd,bsd->bhts", q_rope, k_r, preferred_element_type=jnp.float32)) * self.scale
        p = jax.nn.softmax(jnp.where(mask[:, None], logits, -1e30), axis=-1).astype(x.dtype)
        out = jnp.einsum("bhts,bshd->bthd", p, kv[..., hd["nope"]:], preferred_element_type=jnp.float32)
        return out.astype(x.dtype).reshape(b, s, -1)

    def forward(self, x, cache=None, positions=None):
        b, s = x.shape[0], x.shape[1]
        if cache is None:
            return self.o_proj(apply("dsa_mla", self._whole, x, *self._leaves()))
        # ---- serving cache mode (inference-only) ----
        idx, rank, hd = self.layer_idx, self.dims["rank"], self._head_dims()
        w = [t.value for t in self._leaves()]
        pos2d = positions_2d(positions, b)
        at = dict(positions=pos2d, max_pos=cache.block_tables.shape[1] * cache.block_size)
        q_nope, q_rope, c_kv, k_r, c_q = mla_project(x.value, *w[:5], **self.dims, **at)
        q_idx, k_idx, w_idx = index_project(x.value, c_q, *w[6:], **self.index_dims, **at)
        entry = (jnp.concatenate([c_kv, k_r], -1), k_idx)  # what the layer caches: the latent entry, the index key

        def tile(args, table):
            """[R, q] queries at consecutive positions -> [R, q, H * v]."""
            q_n, q_r, q_i, w_i, at_pos = args
            ctx = dsa_attend(mla_absorb_query(q_n, q_r, w[5], **hd), q_i, w_i, cache.k_pages[idx],
                             cache.index_pages[idx], table, at_pos, topk=self.topk, value_width=rank,
                             scale=self.scale)
            return mla_unabsorb_context(ctx, w[5], **hd)

        def read_many(qs, table, q_positions):
            r, n = q_positions.shape
            if n <= QUERY_TILE or n % QUERY_TILE:
                return tile((*qs, q_positions), table).reshape(r, n, hd["heads"], -1)
            tiles = n // QUERY_TILE

            def split(a):  # [R, n, ...] -> [tiles, R, QUERY_TILE, ...]
                return jnp.moveaxis(a.reshape(r, tiles, QUERY_TILE, *a.shape[2:]), 1, 0)

            out = lax.map(lambda args: tile(args, table), tuple(split(a) for a in (*qs, q_positions)))
            return jnp.moveaxis(out, 0, 1).reshape(r, n, hd["heads"], -1)

        def read_one(qs, table, seq_lens):
            return read_many(tuple(a[:, None] for a in qs), table, (seq_lens - 1)[:, None])[:, 0]

        q = (q_nope, q_rope, q_idx, w_idx)

        def prefill():
            # the bucket's entries are in its pages: its queries read them there, the padding at position 0
            at_pos = jnp.arange(s, dtype=jnp.int32)[None, :]
            return read_many(q, cache.block_tables, jnp.where(at_pos < cache.seq_lens[:, None], at_pos, 0))

        out = attend_through_cache(cache, idx, q, entry, pos2d, prefill=prefill,
                                   read_one=read_one, read_many=read_many)
        return self.o_proj(Tensor(out.reshape(b, s, -1)))


class DeepseekV32DecoderLayer(nn.Layer):
    def __init__(self, hidden_size, eps, attention, mlp):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(hidden_size, eps)
        self.self_attn = attention
        self.post_attention_layernorm = nn.RMSNorm(hidden_size, eps)
        self.mlp = mlp

    def forward(self, x, cache=None, positions=None):
        x = x + self.self_attn(self.input_layernorm(x), cache=cache, positions=positions)
        return x + self.mlp(self.post_attention_layernorm(x), cache=cache, positions=positions)


class DeepseekV32Model(nn.Layer):
    def __init__(self, vocab_size=1024, hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
                 num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=128, index_topk=16,
                 intermediate_size=160, moe_intermediate_size=48, n_routed_experts=16, experts_held=None,
                 num_experts_per_tok=4, n_shared_experts=1, n_group=4, topk_group=2,
                 routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 initializer_range=0.02):
        super().__init__()
        held = list(experts_held) if experts_held is not None else [0, n_routed_experts]
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size)

        def layer(i):
            attn = DeepseekV32Attention(hidden_size, num_attention_heads, q_lora_rank, kv_lora_rank,
                                        qk_nope_head_dim, qk_rope_head_dim, v_head_dim, index_n_heads,
                                        index_head_dim, index_topk, rms_norm_eps, rope_theta, rope_scaling)
            attn.layer_idx = i
            if i < first_k_dense_replace:
                mlp = GatedMLP(hidden_size, intermediate_size)
            else:
                mlp = SparseMLP(hidden_size, n_routed_experts, held, num_experts_per_tok, moe_intermediate_size,
                                n_shared_experts * moe_intermediate_size, routed_scaling_factor,
                                initializer_range, n_group=n_group, topk_group=topk_group)
            return DeepseekV32DecoderLayer(hidden_size, rms_norm_eps, attn, mlp)

        self.layers = nn.LayerList([layer(i) for i in range(num_hidden_layers)])
        self.norm = nn.RMSNorm(hidden_size, rms_norm_eps)

    def forward(self, input_ids, cache=None, positions=None):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, cache=cache, positions=positions)
        return self.norm(x)


class DeepseekV32ForCausalLM(nn.Layer):
    """`.config` holds what the serving engine reads: `num_hidden_layers`,
    `layer_kinds` ("attention" for a leading dense layer, "attention+moe" for a
    sparse one), `vocab_size`, `num_attention_heads`, `index_topk`,
    `index_query_tile` (queries of a row that select together), and
    `cache_entry`: the latent vector a layer keeps a token (`kv_lora_rank +
    qk_rope_head_dim` wide) and beside it the selector's key (`index_width`)."""

    def __init__(self, **config):
        super().__init__()
        self.model = DeepseekV32Model(**config)
        defaults = {k: p.default for k, p in inspect.signature(DeepseekV32Model.__init__).parameters.items()
                    if p.default is not inspect.Parameter.empty}
        cfg = self.config = {**defaults, **config}
        if cfg["experts_held"] is None:
            cfg["experts_held"] = [0, cfg["n_routed_experts"]]
        dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
        cfg["layer_kinds"] = ["attention"] * dense + ["attention+moe"] * (cfg["num_hidden_layers"] - dense)
        cfg["cache_entry"] = {"layout": "latent", "width": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
                              "index_width": cfg["index_head_dim"]}
        cfg["index_query_tile"] = QUERY_TILE
        self.lm_head = nn.Linear(cfg["hidden_size"], cfg["vocab_size"], bias_attr=False)

    def forward(self, input_ids, cache=None, positions=None, last_index=None):
        h = self.model(input_ids, cache=cache, positions=positions)
        if last_index is not None:
            h = Tensor(take_positions(h.value, last_index))
        return self.lm_head(h)
