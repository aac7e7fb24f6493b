"""The segments of a serving step, for an attention layer that keeps what it
caches in the paged pool (`inference/kv_cache.PagedCacheView`).

A decoder's attention sees one of four steps, told apart by the view and the
positions it is handed:

- a bucketed prefill (`positions` None): tokens at 0..S-1, the context IS
  this call's own keys, so the layer writes whole pages and attends plainly;
- positioned rows, one query each (decode): write at the positions, read the
  paged context up to each row's frontier;
- positioned rows, several queries each (`extend`: speculative verify, a
  suffix after a prefix hit): the same, every query up to its own position;
- rows and a chunk (`cache.chunk_table` set): ONE row of tokens, the n decode
  rows' one token each and then C consecutive prompt tokens of one more
  sequence. The projections around the attention ran over all of them
  together; here each segment writes its entries and reads its own context:
  the rows positioned and one query each, the chunk by whole pages and as
  one row of C queries, which sees what its sequence cached before it and
  itself causally.

What a layer writes (K and V a kv head, or one latent vector) and how it
reads (which paged kernel, with which operands) are the layer's own: it
hands them in. The order of writes and reads is this module's alone.
"""
from __future__ import annotations

import jax
from jax import numpy as jnp

__all__ = ["attend_through_cache", "kv_readers", "positions_2d", "take_positions"]


def positions_2d(positions, b: int):
    """[B, S] int32 of a step's positions (a Tensor or an array), None for a
    prefill."""
    if positions is None:
        return None
    raw = getattr(positions, "value", positions)
    return jnp.asarray(raw, jnp.int32).reshape(b, -1)


def take_positions(h, last_index):
    """h [B, S, hidden] at ONE position a row before the vocabulary head (a
    prefill takes the prompt's true last token and skips the [B, S, V]
    logits), or at several positions of the one row of a chunk step."""
    idx = jnp.asarray(getattr(last_index, "value", last_index), jnp.int32).reshape(-1)
    if idx.shape[0] == 1 and h.shape[0] != 1:
        idx = jnp.broadcast_to(idx, (h.shape[0],))
    return jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]


def kv_readers(cache, idx):
    """`read_one` / `read_many` of a layer that caches K and V a kv head: the
    paged kernel over layer `idx`'s pages (and scale planes, on an int8 pool)
    as they are when the read runs."""
    from ..ops.pallas import flash_decode_paged, flash_decode_paged_multi

    def read_one(q, table, seq_lens):
        (kp, vp), (ks, vs) = cache.layer(idx), cache.scales(idx)
        return flash_decode_paged(q, kp, vp, table, seq_lens, k_scales=ks, v_scales=vs)

    def read_many(q, table, q_positions):
        (kp, vp), (ks, vs) = cache.layer(idx), cache.scales(idx)
        return flash_decode_paged_multi(q, kp, vp, table, q_positions, k_scales=ks, v_scales=vs)

    return {"read_one": read_one, "read_many": read_many}


def attend_through_cache(cache, idx, q, entry, pos, *, prefill, read_one, read_many):
    """Write `entry` (a tuple of arrays [B, S, ...]: what layer `idx` caches
    of this step's tokens) and attend `q` [B, S, H, D] (what the paged reads
    take; unused, and may be None, in a prefill; a tuple of arrays [B, S, ...]
    where a read takes more than the query, each cut to its segment alike)
    over the cache, by the step's segments (module docstring). Returns
    [B, S, H, Dv].

    - `prefill()` -> [B, S, H, Dv]: the plain causal attention of a bucketed
      prefill over this call's own keys.
    - `read_one(q [R, H, D], table [R, M], seq_lens [R])` -> [R, H, Dv]: one
      query a row over its pages.
    - `read_many(q [R, Q, H, D], table [R, M], positions [R, Q])` ->
      [R, Q, H, Dv]: Q consecutive queries a row, each up to its own position.

    The reads run AFTER the writes: they must fetch the layer's pages from
    `cache` when called, not before."""
    if cache.chunk_table is not None:
        n = cache.block_tables.shape[0]
        cache.write(idx, *(e[0, :n, None] for e in entry), positions=pos[0, :n, None])
        cache.write_chunk(idx, *(e[:, n:] for e in entry), first_position=pos[0, n])
        rows = read_one(jax.tree.map(lambda a: a[0, :n], q), cache.block_tables, cache.seq_lens)
        chunk = read_many(jax.tree.map(lambda a: a[:, n:], q), cache.chunk_table, pos[:, n:])
        return jnp.concatenate([rows[None], chunk], axis=1)  # [1, n + C, H, Dv]
    cache.write(idx, *entry, positions=pos)
    if pos is None:
        # the context IS this call's keys; padded tail positions produce
        # discarded rows (their queries only ever see real keys at or before
        # themselves)
        return prefill()
    if pos.shape[1] == 1:
        return read_one(jax.tree.map(lambda a: a[:, 0], q), cache.block_tables, cache.seq_lens)[:, None]
    # extend/verify: every query reads the PAGED context up through its own
    # position (the entries of all s tokens were just written above)
    return read_many(q, cache.block_tables, pos)
