"""One chip's share of a routed expert layer, as the decoders with sparse
feed-forward layers use it (`nemotron_h.LatentMoE`, `pangu_ultra_moe`'s
sparse layers).

Under expert parallelism a layer is told which experts it HOLDS
(`experts_held = [first, count]`): it routes over ALL the routed experts,
computes its own experts' part of the weighted sum and leaves the rest out
(the other shares add theirs). Dropless: the (token, expert) pairs that land
on held experts are grouped by expert and multiplied in ONE grouped product a
matrix (`ops.pallas.moe_gmm`); there is no capacity and no `[tokens,
experts, capacity]` tensor.
"""
from __future__ import annotations

import jax
from jax import numpy as jnp

from ..ops import pallas as pk

__all__ = ["route_topk", "routed_experts", "MOE_TOKEN_BLOCK"]

# Tokens the grouped products take at once. The padded layout is sized for
# every assignment landing here (tokens x top_k rows of the experts' input
# width, and as many of their output in float32): a long bucketed prefill
# goes through in blocks of this many tokens so that those rows stay a few
# hundred MB (at hidden 7680 and top-8: 0.13 GB in, 0.26 GB out, 0.25 GB
# gathered back), at one more read of the held experts a block.
MOE_TOKEN_BLOCK = 1024


def _f32(x):
    return x.astype(jnp.float32)


def route_topk(x, w_router, b_corr, top_k, scale, *, n_group=None, topk_group=None):
    """(chosen [T, k] int32, weights [T, k] float32): sigmoid scores in
    float32; the correction bias (None where the router has none) steers the
    choice only; the weights are normalised over all the chosen and scaled.
    With `n_group` the choice is group-limited: the experts stand in `n_group`
    equal groups, a group scores the sum of its two largest biased scores, and
    only experts of the `topk_group` best groups can be chosen."""
    s = jax.nn.sigmoid(jnp.dot(_f32(x), _f32(w_router), precision=jax.lax.Precision.HIGHEST))
    c = s if b_corr is None else s + _f32(b_corr)
    if n_group:
        t, e = c.shape
        by_group = c.reshape(t, n_group, e // n_group)
        _, best = jax.lax.top_k(jax.lax.top_k(by_group, 2)[0].sum(-1), topk_group)
        kept = jnp.zeros((t, n_group), bool).at[jnp.arange(t)[:, None], best].set(True)
        c = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(t, e)
    _, chosen = jax.lax.top_k(c, top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen.astype(jnp.int32), scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)


def _routed_block(u, chosen, weights, w_up, w_down, first, valid, w_gate, activation):
    """`routed_experts` for one block of tokens: (out [T, n] float32, the
    assignments of each held expert [count])."""
    t, k = chosen.shape
    count = w_up.shape[0]
    local = chosen - first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held &= jnp.asarray(valid, bool).reshape(t, 1)
    dest, tile_group, live, sizes = pk.moe_group_layout(jnp.where(held, local, count).reshape(-1), count)
    rows = pk.moe_padded_rows(t * k, count)
    token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    # each padded row's token (the zero row T for padding), then one gather
    row_token = jnp.full((rows,), t, jnp.int32).at[dest].set(token, mode="drop")
    x_rows = jnp.concatenate([u, jnp.zeros((1, u.shape[1]), u.dtype)])[row_token]
    gate = {} if w_gate is None else {"w_gate": w_gate}
    mid = pk.moe_gmm(x_rows, w_up, tile_group, live, activation=activation, **gate)
    y_rows = pk.moe_gmm(mid, w_down, tile_group, live, out_dtype=jnp.float32)
    # dead tiles are unwritten: read only rows an assignment owns
    picked = jnp.take(y_rows, jnp.minimum(dest, rows - 1), axis=0).reshape(t, k, -1)
    wts = jnp.where(held, weights, 0.0)
    out = jnp.sum(jnp.where(held[..., None], picked, 0.0) * wts[..., None], axis=1)
    return out, sizes


def routed_experts(u, chosen, weights, w_up, w_down, first, valid=None, *, w_gate=None,
                   activation="relu2"):
    """The held experts' part of the routed sum: u [T, m], chosen and weights
    [T, k] over ALL experts, w_up [count, m, f] and w_down [count, f, n] the
    experts `first .. first + count - 1`: `f_e(u) = activation(u W_up,e)
    W_down,e`, or with `w_gate` [count, m, f] the gated form
    `(activation(u W_gate,e) * (u W_up,e)) W_down,e`. Pairs whose expert is
    absent, or whose token is padding (`valid` [T] false), are computed
    nowhere. Returns ([T, n] float32, assignments computed, held experts with
    at least one token)."""
    t = chosen.shape[0]
    if t <= MOE_TOKEN_BLOCK or t % MOE_TOKEN_BLOCK:
        out, sizes = _routed_block(u, chosen, weights, w_up, w_down, first, valid, w_gate, activation)
    else:
        blocks = t // MOE_TOKEN_BLOCK
        mask = jnp.ones((t,), bool) if valid is None else jnp.asarray(valid, bool).reshape(t)

        def one(args):
            return _routed_block(*args[:3], w_up, w_down, first, args[3], w_gate, activation)

        out, sizes = jax.lax.map(one, tuple(a.reshape(blocks, MOE_TOKEN_BLOCK, *a.shape[1:])
                                            for a in (u, chosen, weights, mask)))
        out, sizes = out.reshape(t, -1), sizes.sum(0)
    return out, sizes.sum(), (sizes > 0).sum()
