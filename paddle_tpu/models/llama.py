"""Llama-style decoder-only LM (the hybrid-parallel pretrain workload).

Reference parity: the architecture PaddleNLP's llama / ERNIE-4.5 pretrain
configs train (BASELINE configs[4]): RMSNorm pre-norm, rotary embeddings,
SwiGLU MLP, causal flash attention, optional GQA. Written so every weight
carries a logical sharding axis name — the distributed layer shards these
over the mesh (tp on heads/ffn, dp/fsdp on batch/params).

Round 11 adds the serving decode mode: `forward(..., cache=, positions=)`
threads a paged KV cache (inference/kv_cache.PagedCacheView) through the
attention layers — prefill writes the prompt's K/V into the cache pages and
runs the normal causal attention; single-token decode writes the new K/V at
`positions` and reads the whole context back through the Pallas paged
flash-decode kernel (jnp reference off-TPU); a view with a `chunk_table`
is the engine's chunk step, decode rows and a chunk of one prompt in one
row of tokens. Which segment writes and reads what, in which order, is
`cache_segments.attend_through_cache`'s (shared with the other decoders).
The cache path is inference-only (no grad is taped through it).
"""
from __future__ import annotations

import functools
import inspect

import numpy as np
from jax import numpy as jnp

from .. import nn
from ..core.apply import apply
from ..core.tensor import Tensor
from ..nn import functional as F
from ..ops import creation, manipulation as manip

_ROPE_POS_GRANULE = 512  # table cap rounds up to this (bounds cache entries)


@functools.lru_cache(maxsize=8)
def _rope_tables(max_pos: int, d: int, pos_base: float):
    """cos/sin [max_pos, d/2] precomputed ONCE per (max_pos, head_dim, base)
    — rebuilding them inside every forward trace cost retrace time on both
    the train and decode paths. The cache holds NUMPY arrays (a jnp value
    created inside a trace would be a tracer and must never be cached);
    callers jnp.asarray them, which inside a trace is a cheap constant."""
    inv = 1.0 / (pos_base ** (np.arange(0, d, 2, dtype=np.float32) / d))
    t = np.arange(max_pos, dtype=np.float32)
    freqs = np.outer(t, inv)  # [max_pos, D/2]
    return np.cos(freqs), np.sin(freqs)


def _rope(q, k, pos_base=10000.0, positions=None, max_pos=None):
    """Rotary position embeddings applied to [B, S, H, D] q/k (raw jax).

    positions=None: tokens sit at 0..S-1 (the train/prefill layout).
    positions=[B, S] int32: per-token absolute positions (the decode
    layout — each in-flight sequence is at its own offset). `max_pos`
    bounds the precomputed table; it must be static under trace (the
    engine derives it from the block-table capacity)."""
    b, s, h, d = q.shape
    if max_pos is None:
        hi = s if positions is None else int(np.max(np.asarray(positions))) + 1
        max_pos = hi
    cap = -(-max(int(max_pos), 1) // _ROPE_POS_GRANULE) * _ROPE_POS_GRANULE
    cos_np, sin_np = _rope_tables(cap, d, float(pos_base))
    cos_t, sin_t = jnp.asarray(cos_np), jnp.asarray(sin_np)
    if positions is None:
        cos = cos_t[:s][None, :, None, :]
        sin = sin_t[:s][None, :, None, :]
    else:
        positions = jnp.asarray(positions, jnp.int32)
        cos = cos_t[positions][:, :, None, :]  # [B, S, 1, D/2]
        sin = sin_t[positions][:, :, None, :]

    def rot(x):
        x1, x2 = x[..., 0::2], x[..., 1::2]
        xr1 = x1 * cos - x2 * sin
        xr2 = x2 * cos + x1 * sin
        out = jnp.stack([xr1, xr2], axis=-1).reshape(x.shape)
        return out.astype(x.dtype)

    return rot(q), rot(k)


class LlamaAttention(nn.Layer):
    def __init__(self, hidden_size, num_heads, num_kv_heads=None):
        super().__init__()
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.head_dim = hidden_size // num_heads
        self.layer_idx = 0  # position in the decoder stack (set by LlamaModel)
        self.q_proj = nn.Linear(hidden_size, num_heads * self.head_dim, bias_attr=False)
        self.k_proj = nn.Linear(hidden_size, self.num_kv_heads * self.head_dim, bias_attr=False)
        self.v_proj = nn.Linear(hidden_size, self.num_kv_heads * self.head_dim, bias_attr=False)
        self.o_proj = nn.Linear(num_heads * self.head_dim, hidden_size, bias_attr=False)

    def forward(self, x, cache=None, positions=None):
        b, s = x.shape[0], x.shape[1]
        q = manip.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = manip.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        v = manip.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])

        if cache is None:
            qk = apply("rope", lambda qv, kv: _rope(qv, kv), q, k)
            q, k = qk
            # GQA: k/v go in at num_kv_heads — the flash kernel maps q-head
            # groups to their kv head natively (no repeated-KV materialization;
            # the dense fallback repeats inside the dispatched op)
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True, training=self.training)
            out = manip.reshape(out, [b, s, self.num_heads * self.head_dim])
            return self.o_proj(out)

        # ---- serving cache mode (inference-only) ----
        from .cache_segments import attend_through_cache, kv_readers, positions_2d

        idx = self.layer_idx
        max_pos = cache.block_tables.shape[1] * cache.block_size
        pos2d = positions_2d(positions, b)  # None for a prefill: tokens at 0..S-1
        qr, kr = _rope(q.value, k.value, positions=pos2d, max_pos=max_pos)

        def prefill():
            return F.scaled_dot_product_attention(
                Tensor(qr), Tensor(kr), v, is_causal=True, training=False
            ).value

        out_t = Tensor(attend_through_cache(cache, idx, qr, (kr, v.value), pos2d, prefill=prefill,
                                            **kv_readers(cache, idx)))
        return self.o_proj(manip.reshape(out_t, [b, s, self.num_heads * self.head_dim]))


class LlamaMLP(nn.Layer):
    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.gate_proj = nn.Linear(hidden_size, intermediate_size, bias_attr=False)
        self.up_proj = nn.Linear(hidden_size, intermediate_size, bias_attr=False)
        self.down_proj = nn.Linear(intermediate_size, hidden_size, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, hidden_size, num_heads, intermediate_size, num_kv_heads=None, rms_eps=1e-6):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(hidden_size, rms_eps)
        self.self_attn = LlamaAttention(hidden_size, num_heads, num_kv_heads)
        self.post_attention_layernorm = nn.RMSNorm(hidden_size, rms_eps)
        self.mlp = LlamaMLP(hidden_size, intermediate_size)

    def forward(self, x, cache=None, positions=None):
        x = x + self.self_attn(self.input_layernorm(x), cache=cache, positions=positions)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(nn.Layer):
    def __init__(
        self,
        vocab_size=32000,
        hidden_size=512,
        num_hidden_layers=4,
        num_attention_heads=8,
        num_key_value_heads=None,
        intermediate_size=1376,
        rms_norm_eps=1e-6,
        recompute=False,
    ):
        super().__init__()
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size)
        self.layers = nn.LayerList(
            [
                LlamaDecoderLayer(hidden_size, num_attention_heads, intermediate_size, num_key_value_heads, rms_norm_eps)
                for _ in range(num_hidden_layers)
            ]
        )
        for i, layer in enumerate(self.layers):
            layer.self_attn.layer_idx = i
        self.norm = nn.RMSNorm(hidden_size, rms_norm_eps)
        # activation recompute on the decoder blocks: trade ~1/3 more compute
        # for O(layers) less activation memory — the bench's OOM-fallback
        # ladder flips this on before shrinking the workload further
        self.recompute = recompute

    def forward(self, input_ids, cache=None, positions=None):
        from ..distributed.fleet.recompute import recompute as _ckpt

        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            if cache is not None:
                x = layer(x, cache=cache, positions=positions)
            elif self.recompute and self.training:
                x = _ckpt(layer, x)
            else:
                x = layer(x)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, **config):
        super().__init__()
        self.llama = LlamaModel(**config)
        # full constructor signature with defaults filled in — the serving
        # artifact (.pdllm) needs a complete config to rebuild the model
        defaults = {
            k: p.default
            for k, p in inspect.signature(LlamaModel.__init__).parameters.items()
            if p.default is not inspect.Parameter.empty
        }
        self.config = {**defaults, **config}
        hidden = self.llama.norm.weight.shape[0]
        vocab = self.llama.embed_tokens.weight.shape[0]
        self.lm_head = nn.Linear(hidden, vocab, bias_attr=False)

    def forward(self, input_ids, labels=None, cache=None, positions=None, last_index=None):
        h = self.llama(input_ids, cache=cache, positions=positions)
        if labels is not None:
            # fused LM-head + shifted CE (no [N, vocab] f32 logits)
            from ..incubate.nn import functional as IF

            loss = IF.fused_linear_cross_entropy(
                h[:, :-1], self.lm_head.weight, labels[:, 1:]
            )
            return loss, None
        if last_index is not None:
            from .cache_segments import take_positions

            h = Tensor(take_positions(h.value, last_index))
        return self.lm_head(h)


def llama_tiny(**kw):
    cfg = dict(vocab_size=1024, hidden_size=64, num_hidden_layers=2, num_attention_heads=4, intermediate_size=176)
    cfg.update(kw)
    return LlamaForCausalLM(**cfg)
