"""What the decoders with multi-head latent attention and gated routed
experts share (`pangu_ultra_moe`, `deepseek_v32`): the latent projections, the
two forms of the attention, the rotate-half rotary embedding (with YaRN's
inverse frequencies where the model scales them) and the gated feed-forward,
dense and as one chip's share of a sparse layer. Raw jax on arrays: the
models' layers hand in their leaves.

MLA on the normed input `a`: `c_q = RMSNorm(a W_qa)`, `q = c_q W_qb` -> H heads
of `[q_nope | q_rope]`; `[c | k_r] = a W_kva`, `c_kv = RMSNorm(c)`, `k_r` ONE
rotary key shared by all heads; rotate-half RoPE on `q_rope` and `k_r`;
`[k_nope | v]` a head `= c_kv W_kvb`; `softmax(scale (q_nope . k_nope + q_rope
. k_r)) v`.

- EXPANDED: `k`, `v` a head from `W_kvb`, heads `nope + rope` wide against
  values `v_head_dim` wide, through the flash kernel where it pays.
- ABSORBED (over the cache): the cache keeps `[c_kv | k_r]` alone; the key
  up-projection moves to the query (`q_nope W_UK`), the context is summed in
  the latent and the value up-projection `W_UV` comes after.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax import numpy as jnp

from .. import nn
from ..core.apply import apply
from ..core.tensor import Tensor
from ..ops import pallas as pk
from .expert_share import route_topk, routed_experts
from .llama import _rope_tables, _ROPE_POS_GRANULE

__all__ = ["rope_half", "yarn_inv_freq", "yarn_softmax_factor", "mla_project", "mla_expanded",
           "mla_absorb_query", "mla_unabsorb_context", "gated_mlp", "sparse_mlp", "GatedMLP", "SparseMLP"]


def _dot_f32(x, w):
    """x @ w in the storage dtype with a float32 result."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    out = (xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)).astype(x.dtype)
    return out * w.astype(x.dtype)


def yarn_inv_freq(d, theta, factor, original, beta_fast, beta_slow):
    """YaRN's inverse frequencies of a rotary width `d` (float64 [d / 2]):
    column pairs that turn more than `beta_fast` times over the `original`
    context keep `theta^(-2i/d)`, those that turn fewer than `beta_slow`
    times are divided by `factor`, a linear ramp between."""
    f = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def turns_at(beta):  # the pair index whose wave turns `beta` times over the original context
        return d * np.log(original / (beta * 2 * np.pi)) / (2 * np.log(theta))

    low = max(0, int(np.floor(turns_at(beta_fast))))
    high = min(d - 1, int(np.ceil(turns_at(beta_slow))))
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def yarn_softmax_factor(factor, mscale_all_dim):
    """What YaRN multiplies the softmax scale by: `(0.1 m ln(factor) + 1)^2`."""
    m = 1.0 if factor <= 1 or not mscale_all_dim else 0.1 * mscale_all_dim * np.log(factor) + 1.0
    return float(m * m)


@functools.lru_cache(maxsize=8)
def _yarn_tables(max_pos: int, d: int, theta: float, yarn: tuple):
    ang = np.outer(np.arange(max_pos, dtype=np.float64), yarn_inv_freq(d, theta, *yarn))
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def rope_half(x, positions, theta, max_pos, yarn=None):
    """Rotate-half rotary embedding of x [B, S, ..., d] (any axes between the
    sequence and the last): column i pairs with column i + d/2. positions
    [B, S] int32, or None for tokens at 0..S-1; `max_pos` bounds the table
    (static under trace). `yarn` = (factor, original context, beta_fast,
    beta_slow) scales the inverse frequencies (`yarn_inv_freq`), at every
    length."""
    d, s = x.shape[-1], x.shape[1]
    cap = -(-max(int(max_pos), 1) // _ROPE_POS_GRANULE) * _ROPE_POS_GRANULE
    if yarn is None:
        cos_np, sin_np = _rope_tables(cap, d, float(theta))
    else:
        cos_np, sin_np = _yarn_tables(cap, d, float(theta), tuple(float(v) for v in yarn))
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
                positions, max_pos, yarn=None):
    """From the normed input a [B, S, hidden]: (q_nope [B, S, H, nope], q_rope
    [B, S, H, rope] rotated, c_kv [B, S, rank] normed, k_r [B, S, rope]
    rotated, c_q [B, S, q rank] normed: what a second head over the query's
    latent, an indexer, projects from)."""
    b, s, _ = a.shape
    c_q = _rms(jnp.dot(a, w_qa), g_qa, eps)
    q = jnp.dot(c_q, w_qb).reshape(b, s, heads, nope + rope)
    kv = jnp.dot(a, w_kva)
    c_kv = _rms(kv[..., :rank], g_kva, eps)
    q_rope = rope_half(q[..., nope:], positions, theta, max_pos, yarn)
    k_r = rope_half(kv[..., rank:], positions, theta, max_pos, yarn)
    return q[..., :nope], q_rope, c_kv, k_r, c_q


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


# ---------------------------------------------------------------------------
# feed-forward: dense, and the share of a sparse layer
# ---------------------------------------------------------------------------

def gated_mlp(x, w_gate, w_up, w_down):
    """`(silu(x W_g) * (x W_u)) W_d`, float32 out. The two wide products come
    out in the storage dtype (a prefill's are [tokens, width] each: float32
    would double them), the gate is applied in float32."""
    h = jax.nn.silu(jnp.dot(x, w_gate).astype(jnp.float32)) * jnp.dot(x, w_up).astype(jnp.float32)
    return _dot_f32(h.astype(x.dtype), w_down)


def sparse_mlp(x, w_router, e_gate, e_up, e_down, s_gate, s_up, s_down, *, top_k, scale, first, valid=None,
               b_corr=None, n_group=None, topk_group=None):
    """x [T, hidden] -> (this share's output [T, hidden], assignments,
    experts touched): the held experts' part of the routed sum plus the
    shared expert, whole on every chip. `b_corr` (a correction bias that
    steers the choice alone) and `n_group` / `topk_group` (the choice held to
    the best groups of experts) are `expert_share.route_topk`'s."""
    chosen, weights = route_topk(x, w_router, b_corr, top_k, scale, n_group=n_group, topk_group=topk_group)
    routed, n_assign, n_touched = routed_experts(x, chosen, weights, e_up, e_down, first, valid,
                                                 w_gate=e_gate, activation="silu")
    return (routed + gated_mlp(x, s_gate, s_up, s_down)).astype(x.dtype), n_assign, n_touched


class GatedMLP(nn.Layer):
    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.gate_proj = nn.Linear(hidden_size, intermediate_size, bias_attr=False)
        self.up_proj = nn.Linear(hidden_size, intermediate_size, bias_attr=False)
        self.down_proj = nn.Linear(intermediate_size, hidden_size, bias_attr=False)

    def _leaves(self):
        return (self.gate_proj.weight, self.up_proj.weight, self.down_proj.weight)

    def forward(self, x, cache=None, positions=None):
        return apply("gated_mlp", lambda xv, *w: gated_mlp(xv, *w).astype(xv.dtype), x, *self._leaves())


class SparseMLP(nn.Layer):
    """One chip's share of a sparse layer: the router over all the routed
    experts, the experts held, the shared expert. `n_group` / `topk_group`
    hold the choice to the best groups, and with them comes the router's
    correction bias (`router_bias`, a leaf: it steers the choice alone)."""

    def __init__(self, hidden_size, n_routed_experts, experts_held, top_k, moe_intermediate_size,
                 shared_intermediate_size, routed_scaling_factor, initializer_range=0.02,
                 n_group=None, topk_group=None):
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
        self.shared_experts = GatedMLP(hidden_size, shared_intermediate_size)
        self.router_bias = None
        if n_group:
            if n_routed_experts % n_group or not 0 < topk_group <= n_group:
                raise ValueError(f"{n_routed_experts} experts in {n_group} groups, the best {topk_group} kept")
            self.kw.update(n_group=int(n_group), topk_group=int(topk_group))
            self.router_bias = self.create_parameter([n_routed_experts], default_initializer=init)

    def _leaves(self):
        return (self.router, self.experts_gate, self.experts_up, self.experts_down,
                *self.shared_experts._leaves(), *(() if self.router_bias is None else (self.router_bias,)))

    def _run(self, x, *w, valid=None):
        return sparse_mlp(x, *w[:7], valid=valid, **({"b_corr": w[7]} if len(w) > 7 else {}), **self.kw)

    def forward(self, x, cache=None, positions=None):
        b, s, h = x.shape
        if cache is None:
            return apply("sparse_mlp",
                         lambda xv, *w: self._run(xv.reshape(b * s, h), *w)[0].reshape(b, s, h),
                         x, *self._leaves())
        valid = cache.token_mask(b, s, positions)
        out, n_assign, n_touched = self._run(x.value.reshape(b * s, h), *[t.value for t in self._leaves()],
                                             valid=valid.reshape(-1))
        cache.count_moe(n_assign, n_touched)
        return Tensor(out.reshape(b, s, h))
