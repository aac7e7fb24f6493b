"""Nemotron-H style hybrid decoder: Mamba-2, attention and LatentMoE layers
in the order a pattern string gives (`M`, `*`, `E`), each layer ONE mixer
behind one RMSNorm: `x = x + mixer(norm(x))`.

- `M`, Mamba-2: `[z | xBC | dt] = x W_in`; a causal depthwise conv (kernel
  `conv_kernel`) and silu over `xBC`; per head `h_t = exp(dt_t A) h_{t-1} +
  dt_t (xs_t outer B_t)`, `y_t = h_t C_t + D xs_t`; `y * silu(z)` through a
  group RMSNorm; `W_out`. The recurrence runs in float32.
- `*`, attention: GQA, causal, NO positional encoding (the state-space layers
  carry the order).
- `E`, LatentMoE: a sigmoid router over ALL `n_routed_experts` (float32, with
  a correction bias that only steers the choice), top-k weights normalised
  over the chosen and scaled; the experts live in a latent between two
  projections, `f_e(u) = relu(u W_up,e)^2 W_down,e`; a shared expert on the
  full hidden beside them. The layer is told which experts it HOLDS
  (`experts_held = [first, count]`, one chip's share under expert
  parallelism): it routes over all, computes its own experts' part and leaves
  the rest out (the other shares add theirs; `fc2` is linear). Dropless: the
  (token, expert) pairs that land on held experts are grouped by expert and
  multiplied in ONE grouped product a matrix (`ops.pallas.moe_gmm`); there is
  no capacity and no `[tokens, experts, capacity]` tensor.

Serving (`forward(ids, cache=, positions=, last_index=)`, as
LlamaForCausalLM has it): attention layers read and write the cache's K/V
pages (their index is the layer's place AMONG THE ATTENTION LAYERS); Mamba
layers read and write the cache's recurrent state, one slot a sequence
(`cache.slots`): a bucketed prefill starts from the zero state and leaves the
state as the last REAL token left it, a decode step gathers each row's slot,
steps it once and scatters it back; a row at position 0 starts from zero.
A chunk step (`cache.chunk_table` set: the decode rows and then a chunk of ONE
more sequence's prompt, `models/cache_segments.py`) steps the rows so and
moves the chunk's slot forward over the chunk's tokens in block form
(`ssm_block`), from zero where the chunk starts its sequence. Going BACK over
a live state (`extend`: speculative verify, a suffix after a prefix hit) needs
state snapshots and is refused. The cache path is inference-only; the plain
forward is differentiable through `core.apply`.
"""
from __future__ import annotations

import inspect

import jax
from jax import numpy as jnp

from .. import nn
from ..core.apply import apply
from ..core.tensor import Tensor
from ..nn import functional as F
from ..ops import manipulation as manip
from .cache_segments import attend_through_cache, kv_readers, positions_2d, take_positions
from .expert_share import route_topk, routed_experts

__all__ = ["NemotronHForCausalLM", "NemotronHModel", "layer_kinds"]

KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def layer_kinds(pattern: str):
    """The kind of each layer of a `hybrid_override_pattern`."""
    try:
        return [KINDS[c] for c in pattern]
    except KeyError as e:
        raise ValueError(f"hybrid_override_pattern {pattern!r}: unknown layer kind {e.args[0]!r} "
                         f"(known: {sorted(KINDS)})") from None


def _f32(x):
    return x.astype(jnp.float32)


def _dot_f32(x, w):
    """x @ w in the storage dtype with a float32 result."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------

def mamba2_in_proj(x, w_in, inner, conv_dim):
    """`[z | xBC | dt] = x W_in` over x [B, S, hidden]: z [B, S, inner] and dt
    [B, S, H] float32, xBC [B, S, conv_dim] in x's dtype."""
    zxd = _dot_f32(x, w_in)
    z, dt = zxd[..., :inner], zxd[..., inner + conv_dim:]
    # the conv's inputs are what the state keeps: rounded to its dtype
    return z, zxd[..., inner:inner + conv_dim].astype(x.dtype), dt


def ssm_scan(xs, bm, cm, dt, a, h0):
    """The recurrence a token at a time: `h_t = exp(dt_t a) h_{t-1} + dt_t (xs_t
    outer B_t)`, `y_t = h_t C_t`, over xs [B, S, H, P], bm / cm [B, S, G, N], dt
    [B, S, H] from h0 [B, H, P, N], all float32. Returns (h after the last
    token, y [B, S, H, P]). One step at S == 1 (a decode row), a `lax.scan`
    whose carry is the whole state otherwise (a bucketed prefill)."""
    b, s, heads, head_dim = xs.shape
    groups, state = bm.shape[2:]
    per = heads // groups

    def step(h, t):
        xs_t, b_t, c_t, dt_t = t  # [B, H, P], [B, G, N], [B, G, N], [B, H]
        hg = h.reshape(b, groups, per, head_dim, state)
        add = (dt_t[..., None] * xs_t).reshape(b, groups, per, head_dim)[..., None] * b_t[:, :, None, None, :]
        hg = jnp.exp(dt_t * a).reshape(b, groups, per)[..., None, None] * hg + add
        y = jnp.einsum("bgrpn,bgn->bgrp", hg, c_t, preferred_element_type=jnp.float32)
        return hg.reshape(h.shape), y.reshape(b, heads, head_dim)

    if s == 1:
        h, y = step(h0, (xs[:, 0], bm[:, 0], cm[:, 0], dt[:, 0]))
        return h, y[:, None]
    h, y = jax.lax.scan(step, h0, tuple(jnp.swapaxes(v, 0, 1) for v in (xs, bm, cm, dt)))
    return h, jnp.swapaxes(y, 0, 1)


def _segsum(a):
    """s[..., i, j] = the sum of a[..., k] over j < k <= i (0 on the diagonal),
    -inf above it: masked and then accumulated, as the published `segsum`
    builds it, never the difference of two cumulative sums."""
    t = a.shape[-1]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = jnp.cumsum(jnp.where(i > j, a[..., :, None], 0.0), axis=-2)
    return jnp.where(i >= j, s, -jnp.inf)


def ssm_block(xs, bm, cm, dt, a, h0):
    """The same recurrence (operands and results as `ssm_scan`) over the S
    tokens as ONE block of the published state-space-duality form, which is
    matmuls and no loop over the tokens: with `a_t = dt_t a` and `s(i, j)` the
    sum of `a_k` over j < k <= i, `y_i = sum_{j<=i} exp(s(i, j)) (C_i . B_j)
    dt_j xs_j + exp(s(i, -1)) h0 C_i` and `h = exp(s(S-1, -1)) h0 + sum_j
    exp(s(S-1, j)) dt_j xs_j outer B_j`. Every exponent is <= 0; float32, every
    contraction at the highest precision (the loop's arithmetic is float32)."""
    b, s, heads, head_dim = xs.shape
    groups, state = bm.shape[2:]
    per = heads // groups
    hi = jax.lax.Precision.HIGHEST
    da = jnp.moveaxis(dt * a, 1, -1)  # [B, H, S]
    decay = jnp.exp(_segsum(da))  # [B, H, S, S]: exp(s(i, j)), 0 above the diagonal
    since_start = jnp.exp(jnp.cumsum(da, -1))  # exp(s(i, -1))
    to_end = decay[..., -1, :]  # exp(s(S-1, j))

    def by_token(v):  # [B, H, S] -> [B, S, G, per, 1]
        return jnp.moveaxis(v, -1, 1).reshape(b, s, groups, per, 1)

    xdt = (dt[..., None] * xs).reshape(b, s, groups, per, head_dim)
    hg = h0.reshape(b, groups, per, head_dim, state)
    cb = jnp.einsum("bign,bjgn->bgij", cm, bm, precision=hi)
    mix = decay.reshape(b, groups, per, s, s) * cb[:, :, None]
    y = jnp.einsum("bgrij,bjgrp->bigrp", mix, xdt, precision=hi)
    y = y + jnp.einsum("bign,bgrpn->bigrp", cm, hg, precision=hi) * by_token(since_start)
    h = since_start[..., -1].reshape(b, groups, per, 1, 1) * hg + jnp.einsum(
        "bjgrp,bjgn->bgrpn", xdt * by_token(to_end), bm, precision=hi)
    return h.reshape(h0.shape), y.reshape(b, s, heads, head_dim)


def mamba2_core(z, xbc, dt, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w, *,
                heads, head_dim, groups, state, eps, h0=None, conv0=None, valid_len=None,
                recurrence=ssm_scan):
    """The mixer between its two projections, over what `mamba2_in_proj` gives
    of S tokens a row: the causal conv and silu over xBC, the recurrence
    (`ssm_scan`, or `ssm_block` for a chunk of one sequence), the skip, the
    gate and the group RMSNorm, from the state (h0 [B, H, P, N] float32, conv0
    [B, K-1, C]: the rows of pre-conv xBC before this call; zeros when None).
    `valid_len` [B]: positions at or past it are padding and leave the state
    as the last real token left it. Returns (y [B, S, inner] in xBC's dtype,
    h [B, H, P, N] float32, conv tail [B, K-1, C])."""
    b, s, _ = xbc.shape
    k = conv_w.shape[0]
    inner, gn = heads * head_dim, groups * state
    if conv0 is None:
        conv0 = jnp.zeros((b, k - 1, xbc.shape[-1]), xbc.dtype)
    window = jnp.concatenate([conv0.astype(xbc.dtype), xbc], axis=1)  # [B, K-1+S, C]
    conv = sum(_f32(window[:, j:j + s]) * _f32(conv_w[j]) for j in range(k)) + _f32(conv_b)
    act = jax.nn.silu(conv)
    xs = act[..., :inner].reshape(b, s, heads, head_dim)
    bm = act[..., inner:inner + gn].reshape(b, s, groups, state)
    cm = act[..., inner + gn:].reshape(b, s, groups, state)
    dt = jax.nn.softplus(dt + _f32(dt_bias))  # [B, S, H]
    if valid_len is not None:
        real = jnp.arange(s)[None, :] < jnp.asarray(valid_len).reshape(b, 1)
        dt = jnp.where(real[..., None], dt, 0.0)  # exp(0 A) = 1, 0 * (x outer B) = 0
        last = jnp.asarray(valid_len, jnp.int32).reshape(b, 1) + jnp.arange(k - 1)[None, :]
        tail = jnp.take_along_axis(window, last[..., None], axis=1)
    else:
        tail = window[:, s:]
    a = -jnp.exp(_f32(a_log))
    if h0 is None:
        h0 = jnp.zeros((b, heads, head_dim, state), jnp.float32)
    h, y = recurrence(xs, bm, cm, dt, a, h0)
    y = (y + _f32(d_skip)[:, None] * xs).reshape(b, s, inner) * jax.nn.silu(z)
    yg = y.reshape(b, s, groups, inner // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True) + eps)
    return (yg.reshape(b, s, inner) * _f32(norm_w)).astype(xbc.dtype), h, tail


def mamba2_mix(x, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w, w_out, *,
               heads, head_dim, groups, state, eps, h0=None, conv0=None, valid_len=None):
    """The Mamba-2 mixer over x [B, S, hidden], a token at a time from the
    state (`mamba2_core`'s h0, conv0 and valid_len). Returns (out [B, S,
    hidden], h [B, H, P, N] float32, conv tail [B, K-1, C])."""
    inner = heads * head_dim
    y, h, tail = mamba2_core(*mamba2_in_proj(x, w_in, inner, inner + 2 * groups * state), conv_w, conv_b,
                             dt_bias, a_log, d_skip, norm_w, heads=heads, head_dim=head_dim, groups=groups,
                             state=state, eps=eps, h0=h0, conv0=conv0, valid_len=valid_len)
    return jnp.dot(y, w_out), h, tail


class _Leaves(nn.Layer):
    """Bare leaves under a name of their own (`mixer.conv1d.weight`,
    `mixer.conv1d.bias`, `mixer.norm.weight`): {leaf: (shape, initializer)}."""

    def __init__(self, **leaves):
        super().__init__()
        for name, (shape, init) in leaves.items():
            setattr(self, name, self.create_parameter(list(shape), default_initializer=init))


class Mamba2Mixer(nn.Layer):
    def __init__(self, hidden_size, num_heads, head_dim, n_groups, state_size, conv_kernel, eps,
                 initializer_range=0.02):
        super().__init__()
        from ..nn.initializer import Constant, Normal

        self.num_heads, self.head_dim = num_heads, head_dim
        self.n_groups, self.state_size, self.eps = n_groups, state_size, eps
        self.state_idx = 0  # place among the recurrent layers (set by the model)
        inner = num_heads * head_dim
        self.conv_dim = inner + 2 * n_groups * state_size
        self.in_proj = nn.Linear(hidden_size, inner + self.conv_dim + num_heads, bias_attr=False)
        self.conv1d = _Leaves(weight=([conv_kernel, self.conv_dim], Normal(0.0, 0.5)),
                              bias=([self.conv_dim], Normal(0.0, initializer_range)))
        self.dt_bias = self.create_parameter([num_heads], default_initializer=Normal(0.0, 2.0))
        self.A_log = self.create_parameter([num_heads], default_initializer=Normal(0.0, 1.0))
        self.D = self.create_parameter([num_heads], default_initializer=Constant(1.0))
        self.norm = _Leaves(weight=([inner], Constant(1.0)))
        self.out_proj = nn.Linear(inner, hidden_size, bias_attr=False)

    def _leaves(self):
        return (self.in_proj.weight, self.conv1d.weight, self.conv1d.bias, self.dt_bias, self.A_log,
                self.D, self.norm.weight, self.out_proj.weight)

    def _dims(self):
        return dict(heads=self.num_heads, head_dim=self.head_dim, groups=self.n_groups,
                    state=self.state_size, eps=self.eps)

    def forward(self, x, cache=None, positions=None):
        dims = self._dims()
        if cache is None:
            return apply("mamba2", lambda xv, *w: mamba2_mix(xv, *w, **dims)[0], x, *self._leaves())
        w = [t.value for t in self._leaves()]
        if positions is None:
            # bucketed prefill: from the zero state, the state left at true_len - 1
            out, h, tail = mamba2_mix(x.value, *w, **dims, valid_len=cache.seq_lens)
        elif cache.chunk_table is not None:
            return Tensor(self._rows_and_chunk(x.value, w, cache, positions))
        elif x.shape[1] == 1:
            # by row, or over every slot in place (the cache chooses by the
            # share of the slots this step holds)
            h0, conv0 = cache.read_state(self.state_idx, positions)
            xv = cache.to_slots(x.value) if cache.slot_major else x.value
            out, h, tail = mamba2_mix(xv, *w, **dims, h0=h0, conv0=conv0)
            if cache.slot_major:
                out = cache.from_slots(out)
        else:
            raise NotImplementedError(
                "a recurrent layer cannot take several query tokens a row over a live state "
                "(extend / speculative verify need state snapshots)")
        cache.write_state(self.state_idx, h, tail)
        return Tensor(out)

    def _rows_and_chunk(self, x, w, cache, positions):
        """A chunk step's ONE row of tokens [1, n + C, hidden] (the n decode
        rows' one token each, then C consecutive prompt tokens of one more
        sequence): the two projections run once over all of them; between
        them the rows step their slots' state once, as a decode step does,
        and the chunk moves ITS slot's state (zeros at position 0) forward
        over its real tokens as one block. Forward only: nothing is kept to
        go back to. The chunk's sequence holds no decode row, so the rows'
        write leaves its slot alone, and the chunk reads that slot from the
        arrays AS THE ROWS' WRITE LEFT THEM: one chain of updates, each in
        place on the donated arrays (a read of the arrays from before the
        rows' write keeps them alive past it, and costs a copy of a layer's
        whole state)."""
        w_in, *mid, w_out = w
        dims, idx = self._dims(), self.state_idx
        n, s = cache.block_tables.shape[0], x.shape[1]
        pos = positions_2d(positions, 1)[0]
        parts = mamba2_in_proj(x, w_in, self.num_heads * self.head_dim, self.conv_dim)  # z, xBC, dt: [1, n + C, ...]
        h0, conv0 = cache.read_state(idx, pos[:n])
        rows = [v[0, :n, None] for v in parts]
        if cache.slot_major:
            rows = [cache.to_slots(v) for v in rows]
        y, h, tail = mamba2_core(*rows, *mid, **dims, h0=h0, conv0=conv0)
        if cache.slot_major:
            y = cache.from_slots(y)
        cache.write_state(idx, h, tail)
        h0, conv0 = cache.read_chunk_state(idx, pos[n])
        real = cache.token_mask(1, s, positions)[0, n:].sum()[None]  # pad slots stand behind the real tokens
        y_c, h, tail = mamba2_core(*(v[:, n:] for v in parts), *mid, **dims, h0=h0, conv0=conv0,
                                   valid_len=real, recurrence=ssm_block)
        cache.write_chunk_state(idx, h, tail)
        return jnp.dot(jnp.concatenate([y[None, :, 0], y_c], axis=1), w_out)


# ---------------------------------------------------------------------------
# attention without positional encoding
# ---------------------------------------------------------------------------

class NemotronHAttention(nn.Layer):
    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim):
        super().__init__()
        self.num_heads, self.num_kv_heads, self.head_dim = num_heads, num_kv_heads, head_dim
        self.layer_idx = 0  # place among the attention layers (set by the model)
        self.q_proj = nn.Linear(hidden_size, num_heads * head_dim, bias_attr=False)
        self.k_proj = nn.Linear(hidden_size, num_kv_heads * head_dim, bias_attr=False)
        self.v_proj = nn.Linear(hidden_size, num_kv_heads * head_dim, bias_attr=False)
        self.o_proj = nn.Linear(num_heads * head_dim, hidden_size, bias_attr=False)

    def forward(self, x, cache=None, positions=None):
        b, s = x.shape[0], x.shape[1]
        q = manip.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = manip.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        v = manip.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        if cache is None:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True, training=self.training)
        else:
            idx = self.layer_idx

            def prefill():
                return F.scaled_dot_product_attention(q, k, v, is_causal=True, training=self.training).value

            out = Tensor(attend_through_cache(cache, idx, q.value, (k.value, v.value),
                                              positions_2d(positions, b), prefill=prefill,
                                              **kv_readers(cache, idx)))
        return self.o_proj(manip.reshape(out, [b, s, self.num_heads * self.head_dim]))


# ---------------------------------------------------------------------------
# LatentMoE over the experts held
# ---------------------------------------------------------------------------

def latent_moe(x, w_router, b_corr, w_fc1, w_fc2, w_up, w_down, w_sup, w_sdown, *,
               top_k, scale, first, valid=None):
    """x [T, hidden] -> (this share's output [T, hidden], assignments,
    experts touched)."""
    chosen, weights = route_topk(x, w_router, b_corr, top_k, scale)
    routed, n_assign, n_touched = routed_experts(jnp.dot(x, w_fc1), chosen, weights, w_up, w_down,
                                                 first, valid)
    shared = _dot_f32(_relu2(_dot_f32(x, w_sup)).astype(x.dtype), w_sdown)
    out = _dot_f32(routed.astype(x.dtype), w_fc2) + shared
    return out.astype(x.dtype), n_assign, n_touched


class _SharedExpert(nn.Layer):
    def __init__(self, hidden_size, width):
        super().__init__()
        self.up_proj = nn.Linear(hidden_size, width, bias_attr=False)
        self.down_proj = nn.Linear(width, hidden_size, bias_attr=False)


class LatentMoE(nn.Layer):
    def __init__(self, hidden_size, n_routed_experts, experts_held, top_k, latent_size,
                 intermediate_size, shared_intermediate_size, routed_scaling_factor,
                 initializer_range=0.02):
        super().__init__()
        from ..nn.initializer import Normal

        first, count = (int(v) for v in experts_held)
        if first < 0 or count < 1 or first + count > n_routed_experts:
            raise ValueError(f"experts_held {experts_held} outside the {n_routed_experts} routed experts")
        self.first, self.count, self.top_k = first, count, int(top_k)
        self.scale = float(routed_scaling_factor)
        init = Normal(0.0, initializer_range)
        self.gate = _Leaves(weight=([hidden_size, n_routed_experts], init),
                            e_score_correction_bias=([n_routed_experts], init))
        self.fc1_latent_proj = nn.Linear(hidden_size, latent_size, bias_attr=False)
        self.fc2_latent_proj = nn.Linear(latent_size, hidden_size, bias_attr=False)
        self.experts_up = self.create_parameter([count, latent_size, intermediate_size],
                                                default_initializer=init)
        self.experts_down = self.create_parameter([count, intermediate_size, latent_size],
                                                  default_initializer=init)
        self.shared_experts = _SharedExpert(hidden_size, shared_intermediate_size)

    def _leaves(self):
        return (self.gate.weight, self.gate.e_score_correction_bias, self.fc1_latent_proj.weight,
                self.fc2_latent_proj.weight, self.experts_up, self.experts_down,
                self.shared_experts.up_proj.weight, self.shared_experts.down_proj.weight)

    def forward(self, x, cache=None, positions=None):
        b, s, h = x.shape
        kw = dict(top_k=self.top_k, scale=self.scale, first=self.first)
        if cache is None:
            return apply("latent_moe",
                         lambda xv, *w: latent_moe(xv.reshape(b * s, h), *w, **kw)[0].reshape(b, s, h),
                         x, *self._leaves())
        valid = cache.token_mask(b, s, positions)
        out, n_assign, n_touched = latent_moe(x.value.reshape(b * s, h), *[t.value for t in self._leaves()],
                                              valid=valid.reshape(-1), **kw)
        cache.count_moe(n_assign, n_touched)
        return Tensor(out.reshape(b, s, h))


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

class NemotronHBlock(nn.Layer):
    def __init__(self, hidden_size, eps, mixer):
        super().__init__()
        self.norm = nn.RMSNorm(hidden_size, eps)
        self.mixer = mixer

    def forward(self, x, cache=None, positions=None):
        return x + self.mixer(self.norm(x), cache=cache, positions=positions)


class NemotronHModel(nn.Layer):
    def __init__(self, vocab_size=1024, hidden_size=64, hybrid_override_pattern="MEM*E",
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
                 n_routed_experts=16, experts_held=None, num_experts_per_tok=4,
                 moe_latent_size=32, moe_intermediate_size=48,
                 moe_shared_expert_intermediate_size=96, routed_scaling_factor=5.0,
                 layer_norm_epsilon=1e-5, initializer_range=0.02):
        super().__init__()
        kinds = layer_kinds(hybrid_override_pattern)
        held = list(experts_held) if experts_held is not None else [0, n_routed_experts]
        self.embeddings = nn.Embedding(vocab_size, hidden_size)

        def mixer(kind):
            if kind == "mamba":
                return Mamba2Mixer(hidden_size, mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size,
                                   conv_kernel, layer_norm_epsilon, initializer_range)
            if kind == "attention":
                return NemotronHAttention(hidden_size, num_attention_heads, num_key_value_heads, head_dim)
            return LatentMoE(hidden_size, n_routed_experts, held, num_experts_per_tok, moe_latent_size,
                             moe_intermediate_size, moe_shared_expert_intermediate_size,
                             routed_scaling_factor, initializer_range)

        self.layers = nn.LayerList([NemotronHBlock(hidden_size, layer_norm_epsilon, mixer(k)) for k in kinds])
        n_attn = n_state = 0
        for kind, layer in zip(kinds, self.layers):
            if kind == "attention":
                layer.mixer.layer_idx, n_attn = n_attn, n_attn + 1
            elif kind == "mamba":
                layer.mixer.state_idx, n_state = n_state, n_state + 1
        self.norm_f = nn.RMSNorm(hidden_size, layer_norm_epsilon)

    def forward(self, input_ids, cache=None, positions=None):
        x = self.embeddings(input_ids)
        for layer in self.layers:
            x = layer(x, cache=cache, positions=positions)
        return self.norm_f(x)


class NemotronHForCausalLM(nn.Layer):
    """`.config` holds what the serving engine reads: `layer_kinds` (one of
    "attention", "mamba", "moe" a layer), `head_dim`, `num_key_value_heads`,
    `vocab_size`, and for the recurrent layers `mamba_num_heads`,
    `mamba_head_dim`, `ssm_state_size`, `n_groups`, `conv_kernel`."""

    def __init__(self, **config):
        super().__init__()
        self.backbone = NemotronHModel(**config)
        defaults = {k: p.default for k, p in inspect.signature(NemotronHModel.__init__).parameters.items()
                    if p.default is not inspect.Parameter.empty}
        self.config = {**defaults, **config}
        if self.config["experts_held"] is None:
            self.config["experts_held"] = [0, self.config["n_routed_experts"]]
        kinds = layer_kinds(self.config["hybrid_override_pattern"])
        self.config["layer_kinds"] = kinds
        self.config["num_hidden_layers"] = len(kinds)
        self.lm_head = nn.Linear(self.config["hidden_size"], self.config["vocab_size"], bias_attr=False)

    def forward(self, input_ids, cache=None, positions=None, last_index=None):
        h = self.backbone(input_ids, cache=cache, positions=positions)
        if last_index is not None:
            h = Tensor(take_positions(h.value, last_index))
        return self.lm_head(h)

