"""Paged KV cache: fixed-size blocks in a preallocated per-layer pool.

The serving tier's memory manager (vLLM's PagedAttention layout, SURVEY's
L3c serving rebuild): context KV for every in-flight sequence lives in
fixed-size pages drawn from one preallocated pool per layer, addressed
through a per-sequence block table. Allocation is a host-side free-list
(O(1) alloc/free, no compaction — pages are interchangeable), the device
arrays are functional jax values the compiled prefill/decode steps thread
through, and pool pressure is observable: total/used/shared/retained
blocks, alloc/free counts, allocation failures (the scheduler's preemption
trigger), and internal fragmentation all export through the PR 1 telemetry
registry.

Page 0 is RESERVED as the trash page: block tables are padded with 0 past
a sequence's last real page, so masked reads land on a valid page (never a
fault) and padded-position writes scribble somewhere harmless.

Two kinds of state live in one pool. K/V pages grow with a sequence and exist
only for a model's ATTENTION layers. A recurrent layer (a state-space mixer)
keeps a state of FIXED size a sequence instead: `StateSpec` describes it, and
the pool then holds, a recurrent layer, `ssm` `[slots + 1, heads, head_dim,
state]` float32 and `conv` `[slots + 1, kernel - 1, channels]` arrays. Slot 0
is the trash slot (pad rows of a decode bucket step it); a slot is bound to a
sequence through its first page (`state_slot`) and released when that page
goes back to the free list. A recurrent state holds its whole prefix in one
value, so such a pool has no prefix reuse and no page migration.

A pool may instead keep LATENT pages (`layout="latent"`): ONE array a layer,
`[num_blocks, block_size, width]`, one vector a token (multi-head latent
attention caches the compressed key/value and the shared rotary key, and
every head reads that one vector: there is no kv-head axis and no V array).
A slot is the entry's width rounded up to whole lane tiles of 128 (576 -> 640,
the tail zeros): at 576 XLA's TPU backend lays the array out with the PAGE
axis minor, and copies a layer's whole pool to the other layout and back
around every write and every kernel call.
The allocator, the ref counts and the prefix index are page-granular and
the same; int8 storage and page migration are refused for such a pool.
A latent pool may keep a SECOND array a layer under the same page ids
(`index_width`: `[num_blocks, block_size, index_width]`, the keys a learned
token selector scores a query against, one whole lane tile wide): written in
place by the same three write paths, copied with its page, freed with it.

Round 17 — prefix sharing + int8 storage:

- Pages are REF-COUNTED. A page's KV depends on its whole token prefix, so
  the pool keeps a hash index over FULL pages keyed by the chain digest of
  every token up to and including the page (`prefix_chain_keys`): a new
  request whose prompt extends a resident chain `share()`s those pages
  (refcount+1) and prefill collapses to O(new suffix). Freeing decrements;
  at refcount zero an INDEXED page is RETAINED (resident, evictable)
  instead of returning to the free list, and `alloc()` reclaims retained
  pages LRU-first when the free list runs short — eviction is LRU over
  refcount-zero chains. The reserved trash page can never be registered.
  Callers that free pages whose content must not be reused (preemption,
  fleet evacuation) pass `retain=False`, which also drops index entries —
  a freed-for-reuse page never lingers in the index.
- Copy-on-write: `make_private()` clones a shared page into a fresh
  exclusive one (device-side copy of K/V + scale planes) so a writer can
  never scribble on a page another request still reads. Full-page-aligned
  sharing means steady-state writes land past shared pages, but the
  machinery guards every write range (scheduler growth loop) and is what
  makes speculative-decode rollback and evacuate-resume races safe.
- int8 KV (`kv_dtype="int8"`): pages store int8 with per-slot-per-kv-head
  f32 scale planes `[N, Hkv, bs]` alongside — written slots are quantized
  with the absmax observer rule (quantization/observers.absmax_scale — the
  SAME math, not a fork) and dequantized on read inside the paged-attention
  kernel/reference. ~4x pages per pool byte at head_dim 64 (scale overhead
  4/head_dim), halved-or-better decode HBM traffic.
"""
from __future__ import annotations

import math
import random
import zlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import hashlib

import numpy as np
from jax import lax
from jax import numpy as jnp

from .. import telemetry
from ..telemetry import metrics as _metrics
from ..telemetry import request_trace as _rt

__all__ = [
    "BlockPool",
    "PagedCacheView",
    "StateSpec",
    "PoolExhausted",
    "TRASH_PAGE",
    "chain_extend",
    "prefix_chain_keys",
    "export_pages",
    "convert_payload",
    "import_pages",
    "payload_page_crcs",
    "corrupt_payload",
]

TRASH_PAGE = 0  # reserved: block-table padding + padded-position writes
_LANES = 128  # a latent slot is whole lane tiles: XLA's TPU layouts keep a minor axis of those minor


class PoolExhausted(RuntimeError):
    """alloc() could not find enough free pages — the caller's cue to
    preempt (continuous-batching scheduler) or reject admission."""


def _pool_gauge(state: str):
    return _metrics.gauge(
        "paddle_tpu_kv_pool_blocks",
        "paged KV cache pool occupancy by state",
        label_names=("state",),
    ).labels(state=state)


def _prefix_counter(event: str):
    return _metrics.counter(
        "paddle_tpu_kv_prefix_lookups_total",
        "prefix-cache admission lookups by outcome",
        label_names=("event",),
    ).labels(event=event)


def chain_extend(h: bytes, page_tokens: Sequence[int]) -> bytes:
    """One chain-digest step: the key of the page holding `page_tokens`
    given `h`, the key of the previous page (b"" at the chain head). The
    key therefore commits to EVERY token up to and including this page —
    a page's KV depends on its entire prefix, so the key must too (two
    pages holding the same 16 tokens after different prefixes hold
    different K/V). Append-only, so incremental callers (the scheduler's
    per-step registration) pay O(block_size) per new page, not O(context)."""
    digest = hashlib.blake2b(digest_size=16)
    digest.update(h)
    digest.update(b",".join(str(int(t)).encode() for t in page_tokens))
    return digest.digest()


def prefix_chain_keys(tokens: Sequence[int], block_size: int) -> List[bytes]:
    """Chain digests for every FULL page of `tokens` (see chain_extend)."""
    keys: List[bytes] = []
    h = b""
    for i in range(len(tokens) // block_size):
        h = chain_extend(h, tokens[i * block_size:(i + 1) * block_size])
        keys.append(h)
    return keys


class StateSpec:
    """The recurrent state ONE sequence keeps in each recurrent layer: the
    SSM state `[heads, head_dim, state]` (float32) and the conv window, the
    last `kernel - 1` rows of `channels` pre-conv features (the pool's
    compute dtype)."""

    def __init__(self, heads: int, head_dim: int, state: int, conv_rows: int, channels: int):
        self.ssm_shape = (int(heads), int(head_dim), int(state))
        self.conv_shape = (int(conv_rows), int(channels))

    def slot_bytes(self, conv_itemsize: int) -> int:
        return 4 * math.prod(self.ssm_shape) + conv_itemsize * math.prod(self.conv_shape)


class PagedCacheView:
    """Functional view of the pool's device arrays for ONE traced step.

    Holds per-layer k/v page arrays (possibly jax tracers), the step's
    block tables [B, M] and seq_lens [B], and applies writes as functional
    `.at[].set` updates stored back on the view — the compiled step returns
    the updated arrays and the engine adopts them into the pool.

    Quantized pools add per-layer scale planes (k_scales/v_scales,
    [N, Hkv, bs] f32): `write` quantizes each slot with the absmax observer
    rule and scatters value + scale together. `write_mask` [B, S] bool
    (optional) redirects masked positions' writes to the trash page — the
    engine's extend/verify program uses it to neutralize pad queries.

    `chunk_table` [1, M] (optional) is the block table of ONE more sequence
    whose next prompt tokens ride this step behind the decode rows' one token
    each (the engine's chunk program): `write` serves the rows,
    `write_chunk` the chunk, and the model reads each segment through the
    paged kernel with its own table.

    The second kind of state: `ssm` / `conv` hold one array a RECURRENT
    layer (`[slots + 1, ...]`, see StateSpec) and `slots` [B] each row's slot
    (0, the trash slot, for a pad row). `read_state` gives the rows' state (a
    row at position 0 starts from zero) and `write_state` puts it back: by
    row, or over the whole array in slot order when the step holds a third of
    the slots or more (`slot_major`, with `to_slots` / `from_slots`).
    `chunk_slot` [1] is the slot of the chunk's sequence, which holds no row
    of the step: `read_chunk_state` gives what that slot holds (zeros where
    the chunk starts its sequence), the layer moves it forward over the
    chunk's tokens and `write_chunk_state` puts it back into that ONE slot,
    which the rows' write leaves alone. Forward only: no state is kept to go
    back to (prefix reuse, `extend` and rollback would need snapshots).
    `moe_counts` adds up what the expert layers report of one step.

    A LATENT pool's view has its one array a layer in `k_pages` (`[N, bs, W]`,
    no head axis) and no `v_pages`: `latent` is True, `write` / `write_chunk`
    take the entries `[B, S, width]` alone (zeros fill a slot's tail up to W),
    and the model reads `k_pages[idx]`. Where the pool keeps index keys too
    (`index_pages`, `[N, bs, index width]` a layer), the writes take them as
    the second array `[B, S, index width]` and put them at the same page and
    slot.
    """

    def __init__(self, k_pages: Sequence, v_pages: Sequence, block_tables,
                 seq_lens, block_size: int, k_scales: Optional[Sequence] = None,
                 v_scales: Optional[Sequence] = None, write_mask=None,
                 ssm: Optional[Sequence] = None, conv: Optional[Sequence] = None, slots=None,
                 chunk_table=None, chunk_slot=None, index_pages: Optional[Sequence] = None):
        self.k_pages = list(k_pages)
        self.v_pages = list(v_pages)
        self.latent = bool(self.k_pages) and self.k_pages[0].ndim == 3
        self.index_pages = list(index_pages) if index_pages is not None else None
        self.k_scales = list(k_scales) if k_scales is not None else None
        self.v_scales = list(v_scales) if v_scales is not None else None
        self.block_tables = jnp.asarray(block_tables, jnp.int32)
        self.seq_lens = jnp.asarray(seq_lens, jnp.int32)
        self.block_size = int(block_size)
        self.write_mask = write_mask
        self.chunk_table = None if chunk_table is None else jnp.asarray(chunk_table, jnp.int32)
        self.ssm = list(ssm) if ssm is not None else None
        self.conv = list(conv) if conv is not None else None
        self.slots = None if slots is None else jnp.asarray(slots, jnp.int32)
        self.chunk_slot = None if chunk_slot is None else jnp.asarray(chunk_slot, jnp.int32).reshape(())
        self.moe_counts = None  # [assignments, experts touched, layers] once an expert layer ran

    @classmethod
    def from_state(cls, state, block_tables, seq_lens, block_size, write_mask=None, slots=None,
                   chunk_table=None, chunk_slot=None):
        """A view over a pool's state pytree (`BlockPool.device_state()`)."""
        return cls(state["k"], state["v"], block_tables, seq_lens, block_size,
                   k_scales=state.get("k_scale"), v_scales=state.get("v_scale"),
                   write_mask=write_mask, ssm=state.get("ssm"), conv=state.get("conv"), slots=slots,
                   chunk_table=chunk_table, chunk_slot=chunk_slot, index_pages=state.get("index"))

    @staticmethod
    def state_of(view) -> Dict[str, List]:
        """The state pytree after the step, keys as `from_state` got them."""
        state = {"k": view.k_pages, "v": view.v_pages}
        if view.k_scales is not None:
            state["k_scale"] = view.k_scales
            state["v_scale"] = view.v_scales
        if view.ssm is not None:
            state["ssm"] = view.ssm
            state["conv"] = view.conv
        if view.index_pages is not None:
            state["index"] = view.index_pages
        return state

    @property
    def num_layers(self) -> int:
        return len(self.k_pages)

    @property
    def quantized(self) -> bool:
        return self.k_scales is not None

    def layer(self, idx: int) -> Tuple:
        return self.k_pages[idx], self.v_pages[idx]

    def scales(self, idx: int) -> Tuple:
        """(k_scales, v_scales) for layer `idx`, or (None, None) on an
        unquantized pool — shaped for flash_decode_paged's kwargs."""
        if self.k_scales is None:
            return None, None
        return self.k_scales[idx], self.v_scales[idx]

    # ---- recurrent state ----
    @property
    def slot_major(self) -> bool:
        """Whether this step visits the recurrent state in SLOT order. Rows
        that gather their slots, step them and scatter them back move each
        state three times (the gather's copy, the step, the scatter); a step
        over the whole array in place moves every slot once. With a third of
        the slots or more in the step the whole array is the cheaper, with
        few rows (a small decode bucket, a prefill) the rows are."""
        return 3 * self.slots.shape[0] >= self.ssm[0].shape[0]

    def to_slots(self, x):
        """Rows [B, ...] laid out by slot [slots + 1, ...] (a slot no row of
        this step holds, and the trash slot, get some row's values: what they
        compute is dropped by `write_state`)."""
        n = self.ssm[0].shape[0]
        row_of_slot = jnp.zeros((n,), jnp.int32).at[self.slots].set(
            jnp.arange(self.slots.shape[0], dtype=jnp.int32))
        return x[row_of_slot]

    def from_slots(self, y):
        """The inverse: each row's entry of a slot-ordered [slots + 1, ...]."""
        return y[self.slots]

    def read_state(self, idx: int, positions) -> Tuple:
        """(ssm [R, heads, head_dim, state] f32, conv [R, rows, channels]) of
        recurrent layer `idx`: one entry a row of this step, or one a slot
        when `slot_major`. A sequence whose token sits at position 0 starts
        here and reads zeros, whatever its slot held."""
        raw = getattr(positions, "value", positions)
        keep = jnp.asarray(raw, jnp.int32).reshape(-1) != 0
        h, c = self.ssm[idx], self.conv[idx]
        if self.slot_major:
            keep = self.to_slots(keep)
        else:
            h, c = h[self.slots], c[self.slots]
        return self._zero_at_start(keep, h, c)

    @staticmethod
    def _zero_at_start(keep, h, c):
        """The states h, c [R, ...] where `keep` [R]; zeros for a sequence
        that starts in this step."""
        return (jnp.where(keep[:, None, None, None], h, 0.0),
                jnp.where(keep[:, None, None], c, jnp.zeros((), c.dtype)))

    def write_state(self, idx: int, h, conv_rows) -> None:
        """The step's new state back into the layer's arrays: row entries
        scatter into their slots (pad rows all land on the trash slot 0),
        slot entries replace the slots a real row of this step holds."""
        h, conv_rows = h.astype(self.ssm[idx].dtype), conv_rows.astype(self.conv[idx].dtype)
        n = self.ssm[idx].shape[0]
        if h.shape[0] == n:  # slot entries (a step never has as many rows as the array has slots)
            held = jnp.zeros((n,), bool).at[self.slots].set(True).at[0].set(False)
            self.ssm[idx] = jnp.where(held[:, None, None, None], h, self.ssm[idx])
            self.conv[idx] = jnp.where(held[:, None, None], conv_rows, self.conv[idx])
        else:
            self.ssm[idx] = self.ssm[idx].at[self.slots].set(h)
            self.conv[idx] = self.conv[idx].at[self.slots].set(conv_rows)

    def read_chunk_state(self, idx: int, first_position) -> Tuple:
        """(ssm [1, heads, head_dim, state] f32, conv [1, rows, channels]) of
        recurrent layer `idx` as the chunk's sequence left them in its slot:
        zeros where the chunk starts the sequence (`first_position` 0),
        whatever the slot held."""
        keep = jnp.asarray(first_position, jnp.int32).reshape(1) != 0
        return self._zero_at_start(keep, lax.dynamic_slice_in_dim(self.ssm[idx], self.chunk_slot, 1),
                                   lax.dynamic_slice_in_dim(self.conv[idx], self.chunk_slot, 1))

    def write_chunk_state(self, idx: int, h, conv_rows) -> None:
        """The chunk's new state [1, ...] into its ONE slot, in place."""
        self.ssm[idx] = lax.dynamic_update_slice_in_dim(
            self.ssm[idx], h.astype(self.ssm[idx].dtype), self.chunk_slot, 0)
        self.conv[idx] = lax.dynamic_update_slice_in_dim(
            self.conv[idx], conv_rows.astype(self.conv[idx].dtype), self.chunk_slot, 0)

    def token_mask(self, b: int, s: int, positions):
        """[B, S] bool: the tokens of this step that are real. A prefill
        (positions None) pads its row past `seq_lens`; a decode or extend row
        is real when it holds a page (`write_mask` narrows it further); a
        chunk step's one row holds the decode rows and then the chunk."""
        if positions is None:
            return jnp.arange(s, dtype=jnp.int32)[None, :] < self.seq_lens.reshape(b, 1)
        if self.chunk_table is not None:
            # one row of tokens: the n decode rows, then the chunk, whose pad
            # slots stand at position 0 behind its first token
            n = self.block_tables.shape[0]
            raw = getattr(positions, "value", positions)
            chunk_pos = jnp.asarray(raw, jnp.int32).reshape(-1)[n:]
            chunk = (jnp.arange(s - n) == 0) | (chunk_pos != 0)
            return jnp.concatenate([self.block_tables[:, 0] != TRASH_PAGE, chunk])[None]
        real = jnp.broadcast_to((self.block_tables[:, 0] != TRASH_PAGE)[:, None], (b, s))
        if self.write_mask is not None:
            real = real & jnp.asarray(self.write_mask, bool)
        return real

    def count_moe(self, assignments, experts_touched) -> None:
        """One expert layer's report: (token, expert) pairs it computed and
        held experts that got at least one."""
        new = jnp.stack([jnp.asarray(assignments, jnp.int32), jnp.asarray(experts_touched, jnp.int32),
                         jnp.ones((), jnp.int32)])
        self.moe_counts = new if self.moe_counts is None else self.moe_counts + new

    def write(self, idx: int, k_new, v_new=None, positions=None) -> None:
        """Put new K/V into layer `idx`'s pages, in place.

        k_new/v_new [B, S, Hkv, D] (a latent pool: the entries [B, S, W] as
        `k_new`, no `v_new`); positions [B, S] int32 absolute token
        positions, or None for a prefill, whose tokens sit at 0..S-1.
        Position p of row b lands in page block_tables[b, p//bs] slot p % bs.
        Pages are [N, Hkv, bs, D] and neither form below leaves XLA's TPU
        backend anything to re-lay: it updates the donated pool where it lies
        (a `[pages, :, slots]` index straddles the head axis, and the
        compiler then copies the whole pool to another layout before the
        scatter and back after it, whatever the rows).

        - Positioned rows (decode, extend): ONE scatter that indexes page, kv
          head and slot together, its window a single [D] vector. An update
          costs the chip about 70 ns, so this is for steps of few tokens.
          write_mask=False positions are redirected to the trash page, and
          pad rows all land on the trash page's slot 0: the indices are NOT
          unique. A latent pool's pages [N, bs, W] index page and slot, the
          window the whole [W] entry.
        - A prefill fills whole pages from each row's first: the tokens are
          cut into pages ([B, S/bs, Hkv, bs, D], zeros past S) and scattered
          along the page axis alone, 32 KB contiguous an update. Slots past
          the prompt in its last page, and the table's padding (the trash
          page), take what the bucket's padding computed: nobody reads them.
        """
        b, s = k_new.shape[:2]
        bs = self.block_size
        if positions is None:
            if self.write_mask is not None:
                raise ValueError("write_mask narrows positioned writes; a prefill has none")
            put = self._whole_pages(self.block_tables[:, :-(-s // bs)], b, s)
        else:
            positions = jnp.asarray(positions, jnp.int32)
            pages = jnp.take_along_axis(self.block_tables, positions // bs, axis=1)
            if self.write_mask is not None:
                pages = jnp.where(jnp.asarray(self.write_mask, bool), pages, TRASH_PAGE)
            if self.latent:
                at = (pages, positions % bs)
            else:
                at = (pages[..., None], jnp.arange(k_new.shape[2], dtype=jnp.int32), (positions % bs)[..., None])

            def put(pool, new):
                return pool.at[at].set(new)

        self._put(idx, k_new, v_new, put)

    def write_chunk(self, idx: int, k_new, v_new=None, first_position=None) -> None:
        """Put the chunk's K/V [1, C, Hkv, D] (a latent pool: its entries
        [1, C, W] as `k_new`) into layer `idx`'s pages: the
        prefill's whole-page write, from the page of `first_position` (a
        traced scalar, a multiple of the page size: a chunk starts where a
        shared prefix or an earlier chunk ended, on a page's edge) of
        `chunk_table` on. A last page the tokens fill in part is written
        whole: its tail holds what the chunk's padding computed, past the
        sequence's frontier, and the sequence's later writes replace it.
        Columns past the table's end, like its padding, are the trash page."""
        c = k_new.shape[1]
        n = -(-c // self.block_size)
        table = jnp.pad(self.chunk_table, ((0, 0), (0, n)), constant_values=TRASH_PAGE)
        first = jnp.asarray(first_position, jnp.int32) // self.block_size
        self._put(idx, k_new, v_new, self._whole_pages(
            lax.dynamic_slice_in_dim(table, first, n, axis=1), 1, c))

    def _whole_pages(self, pages, b: int, s: int):
        """put(pool, new) that cuts `new` [B, S, Hkv, ...] (latent: [B, S, W])
        into the pages `pages` [B, ceil(S / bs)] names (zeros past S) and
        scatters them along the page axis alone."""
        bs, n, latent = self.block_size, pages.shape[1], self.latent

        def put(pool, new):
            new = jnp.pad(new, [(0, 0), (0, n * bs - s)] + [(0, 0)] * (new.ndim - 2))
            cut = new.reshape(b, n, bs, *new.shape[2:])
            return pool.at[pages].set(cut if latent else jnp.swapaxes(cut, 2, 3))

        return put

    def _put(self, idx: int, k_new, v_new, put) -> None:
        """K/V into layer `idx` through `put`, quantized first on an int8
        pool (the scale planes through the same `put`)."""
        if self.k_scales is not None:
            # int8 storage: per-slot-per-kv-head absmax scales — the
            # observer rule (quantization/observers), applied per written
            # token so appends never requantize resident slots
            from ..quantization.observers import absmax_scale, quantize_absmax

            k_sc = absmax_scale(k_new, axis=-1)  # [B, S, Hkv] f32
            v_sc = absmax_scale(v_new, axis=-1)
            k_new = quantize_absmax(k_new, k_sc[..., None])
            v_new = quantize_absmax(v_new, v_sc[..., None])
            self.k_scales[idx] = put(self.k_scales[idx], k_sc)
            self.v_scales[idx] = put(self.v_scales[idx], v_sc)
        if self.latent:  # the slot's tail past the entry: zeros
            tail = self.k_pages[idx].shape[-1] - k_new.shape[-1]
            k_new = jnp.pad(k_new, [(0, 0)] * (k_new.ndim - 1) + [(0, tail)])
        self.k_pages[idx] = put(self.k_pages[idx], k_new)
        if not self.latent:
            self.v_pages[idx] = put(self.v_pages[idx], v_new)
        elif self.index_pages is not None:  # the entry's index key, same page and slot
            self.index_pages[idx] = put(self.index_pages[idx], v_new.astype(self.index_pages[idx].dtype))


class BlockPool:
    """Preallocated paged KV pool + host free-list allocator.

    Device layout, `layout="kv"` (the default): per layer, k/v pages of shape
    [num_blocks, num_kv_heads, block_size, head_dim] (kv-head major: the
    paged kernel fetches a page's whole (num_kv_heads, block_size,
    head_dim) slab, one contiguous read, eight pages of 16 a grid step).
    `layout="latent"`: per layer ONE array [num_blocks, block_size, W]
    (`head_dim` the entry's width, W that in whole lane tiles of 128;
    `num_kv_heads` must be 1: every query head reads the one vector a
    token), kept in `k_pages`; `v_pages` is empty.
    `index_width` (a latent pool's alone) adds a second array a layer,
    [num_blocks, block_size, index_width], in `index_pages`.
    `num_blocks` INCLUDES the reserved trash page 0; usable capacity is
    num_blocks - 1 pages.
    `kv_dtype="int8"` stores int8 pages with f32 scale planes alongside (kv
    layout only: a latent entry has no per-head absmax to scale by).

    `num_layers` counts the layers that keep K/V (a model's attention
    layers). `state_layers` recurrent layers, each a `state_spec` a sequence,
    add the second kind of state: `state_slots` slots beside the trash slot 0
    (module docstring); `used()`, `pool_bytes()` per page and `num_blocks`
    keep their meaning (pages), `state_slots_used()` counts the slots bound
    and `pool_bytes()` counts the state arrays too.
    """

    def __init__(self, num_blocks: int, block_size: int, num_layers: int,
                 num_kv_heads: int, head_dim: int, dtype=jnp.float32,
                 kv_dtype: Optional[str] = None, state_layers: int = 0,
                 state_spec: Optional[StateSpec] = None, state_slots: int = 0,
                 layout: str = "kv", index_width: int = 0):
        if num_blocks < 2:
            raise ValueError("BlockPool needs >= 2 blocks (page 0 is reserved)")
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r} (int8 or None)")
        if layout not in ("kv", "latent"):
            raise ValueError(f"unsupported page layout {layout!r} (kv or latent)")
        if layout == "latent" and kv_dtype is not None:
            raise ValueError(
                "a latent pool cannot store int8 pages: the quantizer scales each kv head's vector "
                "of a slot by its absmax, and a latent entry (a normed latent and a rotary key in "
                "one vector, read by every head) has no such axis")
        if layout == "latent" and int(num_kv_heads) != 1:
            raise ValueError("a latent pool keeps one vector a token: num_kv_heads must be 1")
        if state_layers and (state_spec is None or state_slots < 1):
            raise ValueError("a pool with recurrent layers needs their StateSpec and >= 1 slot")
        if index_width and (layout != "latent" or int(index_width) % _LANES):
            raise ValueError(
                f"index keys ({index_width} wide) ride a latent pool's pages, in whole lane tiles of {_LANES}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.num_layers = int(num_layers)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.kv_dtype = kv_dtype
        self.layout = layout
        self.compute_dtype = dtype
        self.dtype = jnp.int8 if kv_dtype == "int8" else dtype
        shape = self.page_shape
        self.k_pages: List = [jnp.zeros(shape, self.dtype) for _ in range(self.num_layers)]
        self.v_pages: List = [] if self.latent else [
            jnp.zeros(shape, self.dtype) for _ in range(self.num_layers)]
        # the selector's keys, a layer: a second array under the same page ids
        self.index_width = int(index_width)
        self.index_pages: Optional[List] = [
            jnp.zeros((*shape[:2], self.index_width), self.dtype) for _ in range(self.num_layers)
        ] if self.index_width else None
        if kv_dtype == "int8":
            sshape = shape[:3]
            self.k_scales: Optional[List] = [
                jnp.zeros(sshape, jnp.float32) for _ in range(self.num_layers)
            ]
            self.v_scales: Optional[List] = [
                jnp.zeros(sshape, jnp.float32) for _ in range(self.num_layers)
            ]
        else:
            self.k_scales = None
            self.v_scales = None
        # recurrent state: one array a recurrent layer, slot 0 the trash slot
        self.state_layers = int(state_layers)
        self.state_spec = state_spec if self.state_layers else None
        self.state_slots = int(state_slots) if self.state_layers else 0
        n_slots = self.state_slots + 1
        self.ssm: List = [jnp.zeros((n_slots,) + state_spec.ssm_shape, jnp.float32)
                          for _ in range(self.state_layers)]
        self.conv: List = [jnp.zeros((n_slots,) + state_spec.conv_shape, dtype)
                           for _ in range(self.state_layers)]
        self._free_slots: List[int] = list(range(self.state_slots, 0, -1))
        self._slot_of_page: Dict[int, int] = {}  # a sequence's FIRST page -> its slot
        # LIFO free list: recently-freed (cache-warm) pages hand out first
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        # page -> refcount, for every page a request currently holds
        self._refs: Dict[int, int] = {}
        # refcount-zero pages kept resident for prefix reuse, LRU order
        # (oldest first); values are the index keys they serve
        self._retained: "OrderedDict[int, bytes]" = OrderedDict()
        # prefix index: chain key -> page, page -> chain key
        self._prefix: Dict[bytes, int] = {}
        self._page_key: Dict[int, bytes] = {}
        self.cow_copies = 0
        if telemetry.enabled():
            _pool_gauge("total").set(self.num_blocks - 1)
            self._sync_gauges()

    @property
    def quantized(self) -> bool:
        return self.kv_dtype == "int8"

    @property
    def latent(self) -> bool:
        return self.layout == "latent"

    @property
    def page_shape(self) -> Tuple[int, ...]:
        """Shape of one layer's page array (every array of a layer has it)."""
        if self.latent:
            return (self.num_blocks, self.block_size, -(-self.head_dim // _LANES) * _LANES)
        return (self.num_blocks, self.num_kv_heads, self.block_size, self.head_dim)

    @property
    def arrays_per_layer(self) -> int:
        """Page arrays a layer keeps: K and V, or the one latent array."""
        return 1 if self.latent else 2

    # ---- accounting ----
    def blocks_for_tokens(self, n_tokens: int) -> int:
        return max(1, math.ceil(n_tokens / self.block_size))

    def available(self) -> int:
        """Pages alloc() can satisfy: free-list pages plus refcount-zero
        retained pages (reclaimed LRU-first on demand)."""
        return len(self._free) + len(self._retained)

    def used(self) -> int:
        """Pages some request currently holds (refcount >= 1); retained
        prefix pages are evictable cache, not usage."""
        return len(self._refs)

    def shared(self) -> int:
        """Pages held by more than one request."""
        return sum(1 for r in self._refs.values() if r >= 2)

    def retained(self) -> int:
        return len(self._retained)

    def occupancy(self) -> float:
        """Held fraction of the usable pool (used / (num_blocks - 1),
        page 0 is reserved) — the QoS brownout ladder's pool-pressure
        signal. Retained prefix pages are reclaimable cache and do not
        count as pressure."""
        return self.used() / max(1, self.num_blocks - 1)

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    def page_bytes(self) -> int:
        """Device bytes ONE page costs across all layers (K + V + scale
        planes, or the one latent array and its index keys) — the bench's
        same-pool-bytes comparisons use this."""
        slot = self.block_size * self.num_kv_heads
        data = (self.num_layers * slot * (self.arrays_per_layer * self.page_shape[-1] + self.index_width)
                * jnp.dtype(self.dtype).itemsize)
        scales = 0
        if self.quantized:
            scales = 2 * self.num_layers * slot * 4
        return data + scales

    def pool_bytes(self) -> int:
        """Device bytes of everything the pool holds: pages and, where the
        model has recurrent layers, their state arrays."""
        return self.num_blocks * self.page_bytes() + self.state_bytes()

    def state_bytes(self) -> int:
        if not self.state_layers:
            return 0
        per_slot = self.state_spec.slot_bytes(jnp.dtype(self.compute_dtype).itemsize)
        return self.state_layers * (self.state_slots + 1) * per_slot

    # ---- recurrent-state slots ----
    @property
    def has_recurrent_state(self) -> bool:
        return self.state_layers > 0

    def state_slots_used(self) -> int:
        """Slots bound to a sequence."""
        return len(self._slot_of_page)

    def state_slot(self, first_page: int) -> int:
        """The slot of the sequence whose first page is `first_page`, bound
        on first sight; it is released when that page returns to the free
        list. The trash page (a row with no pages) has the trash slot."""
        page = int(first_page)
        if page == TRASH_PAGE or not self.state_layers:
            return 0
        slot = self._slot_of_page.get(page)
        if slot is None:
            if page not in self._refs:
                raise ValueError(f"state_slot of page {page} that no sequence holds")
            if not self._free_slots:
                raise PoolExhausted(
                    f"recurrent-state slots exhausted: {self.state_slots} sequences hold one")
            slot = self._slot_of_page[page] = self._free_slots.pop()
        return slot

    def _sync_gauges(self) -> None:
        _pool_gauge("used").set(self.used())
        _pool_gauge("shared").set(self.shared())
        _pool_gauge("retained").set(self.retained())

    # ---- allocator ----
    def _evict_retained(self, n: int) -> int:
        """Reclaim up to `n` refcount-zero retained pages, LRU-first,
        dropping their index entries; returns the number reclaimed."""
        evicted = 0
        while evicted < n and self._retained:
            page, key = self._retained.popitem(last=False)
            self._prefix.pop(key, None)
            self._page_key.pop(page, None)
            self._free.append(page)
            evicted += 1
        if evicted and telemetry.enabled():
            _metrics.counter(
                "paddle_tpu_kv_prefix_evictions_total",
                "retained prefix pages reclaimed (LRU) to satisfy allocation",
            ).inc(evicted)
        return evicted

    def alloc(self, n: int, owner: Optional[int] = None) -> List[int]:
        """`owner` is the request id the pages are charged to (request-trace
        attribution only; the allocator itself is owner-blind)."""
        if n > len(self._free):
            self._evict_retained(n - len(self._free))
        if n > len(self._free):
            if telemetry.enabled():
                _metrics.counter(
                    "paddle_tpu_kv_pool_alloc_failures_total",
                    "paged KV pool allocations refused for lack of free pages",
                ).inc()
            if _rt.enabled():
                _rt.record_event("kv_pool", "alloc_failure", rid=owner,
                                 n=n, free=len(self._free))
            raise PoolExhausted(
                f"paged KV pool exhausted: want {n} pages, {self.available()} "
                f"reclaimable of {self.num_blocks - 1}"
            )
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._refs[p] = 1
        if telemetry.enabled():
            _metrics.counter(
                "paddle_tpu_kv_pool_allocs_total", "paged KV pool pages handed out"
            ).inc(n)
            self._sync_gauges()
        if _rt.enabled():
            # used-after rides every event: the report reconstructs the
            # pool-occupancy-over-time curve from these alone
            _rt.record_event("kv_pool", "alloc", rid=owner, n=n, used=self.used())
        return out

    def share(self, pages: Sequence[int], owner: Optional[int] = None) -> None:
        """Take an additional reference on already-resident pages (prefix
        reuse). Retained (refcount-zero) pages revive back to active."""
        for p in pages:
            p = int(p)
            if p == TRASH_PAGE:
                raise ValueError("page 0 is reserved and never shared")
            if p in self._refs:
                self._refs[p] += 1
            elif p in self._retained:
                self._retained.pop(p)
                self._refs[p] = 1
            else:
                raise ValueError(f"share of page {p} that is not resident")
        if telemetry.enabled() and pages:
            self._sync_gauges()
        if _rt.enabled() and pages:
            _rt.record_event("kv_pool", "share", rid=owner,
                             n=len(pages), used=self.used())

    def free(self, pages: Sequence[int], owner: Optional[int] = None,
             retain: bool = True) -> None:
        """Drop one reference per page. At refcount zero an INDEXED page is
        retained for prefix reuse when `retain` (completion paths) — else
        (preemption/evacuation: the content is conceptually discarded) its
        index entry is dropped and the page returns to the free list."""
        for p in pages:
            p = int(p)
            if p == TRASH_PAGE:
                raise ValueError("page 0 is reserved and never allocated")
            ref = self._refs.get(p)
            if ref is None:
                raise ValueError(f"double free of page {p}")
            if ref > 1:
                # another holder keeps the page alive: its content is
                # immutable and cannot be recycled while refcount >= 1, so
                # the index entry STAYS valid even when this freer is a
                # preemption (the stale-chain hazard only exists for pages
                # returning to the free list)
                self._refs[p] = ref - 1
                continue
            del self._refs[p]
            slot = self._slot_of_page.pop(p, None)
            if slot is not None:
                self._free_slots.append(slot)
            key = self._page_key.get(p)
            if retain and key is not None:
                self._retained[p] = key  # MRU end
            else:
                if key is not None:
                    self._page_key.pop(p, None)
                    self._prefix.pop(key, None)
                self._free.append(p)
        if telemetry.enabled() and pages:
            _metrics.counter(
                "paddle_tpu_kv_pool_frees_total", "paged KV pool pages returned"
            ).inc(len(pages))
            self._sync_gauges()
        if _rt.enabled() and pages:
            _rt.record_event("kv_pool", "free", rid=owner,
                             n=len(pages), used=self.used())

    def reset(self) -> None:
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._free_slots = list(range(self.state_slots, 0, -1))
        self._slot_of_page.clear()
        self._refs.clear()
        self._retained.clear()
        self._prefix.clear()
        self._page_key.clear()
        if telemetry.enabled():
            self._sync_gauges()

    def note_fragmentation(self, active_tokens: int) -> None:
        """Internal fragmentation: allocated slots minus live tokens — the
        cost of fixed-size pages, the number paged allocation exists to keep
        bounded (vs. one contiguous max-length buffer per sequence)."""
        if telemetry.enabled():
            _metrics.gauge(
                "paddle_tpu_kv_pool_frag_slots",
                "allocated-but-unwritten KV slots (internal fragmentation)",
            ).set(self.used() * self.block_size - int(active_tokens))

    # ---- prefix index ----
    def register_prefix(self, key: bytes, page: int) -> bool:
        """Publish a FULL, committed page under its chain key. First
        registration wins (an identical chain is already served by the
        earlier page); the reserved trash page and non-resident pages are
        rejected — a page must be actively held (its content stable) to
        enter the index."""
        page = int(page)
        if page == TRASH_PAGE:
            raise ValueError("page 0 is reserved and never enters the prefix index")
        if page not in self._refs:
            raise ValueError(
                f"page {page} is not actively held — only live pages register"
            )
        if key in self._prefix or page in self._page_key:
            return False
        self._prefix[key] = page
        self._page_key[page] = key
        return True

    def acquire_prefix(self, keys: Sequence[bytes],
                       owner: Optional[int] = None) -> List[int]:
        """Longest-prefix lookup + share in one atomic host step: walk the
        chain keys from page 0, stop at the first miss, take a reference on
        every hit page, and return them (possibly empty). Counts hit/miss
        lookups and cached tokens."""
        pages: List[int] = []
        for key in keys:
            page = self._prefix.get(key)
            if page is None or (page not in self._refs and page not in self._retained):
                break
            pages.append(page)
        if pages:
            self.share(pages, owner=owner)
        if telemetry.enabled():
            _prefix_counter("hit" if pages else "miss").inc()
            if pages:
                _metrics.counter(
                    "paddle_tpu_kv_prefix_cached_tokens_total",
                    "prompt tokens served from shared prefix pages instead of "
                    "recomputed",
                ).inc(len(pages) * self.block_size)
        return pages

    def prefix_index_size(self) -> int:
        return len(self._prefix)

    def invalidate_prefix(self) -> int:
        """Drop EVERY index entry and release retained pages to the free
        list; active pages stay held (their current readers are unaffected)
        but no future request can share them. The weight hot-swap hook:
        cached K/V was computed under the OLD parameters, so after
        `engine.load_weights` a prefix hit would silently mix old-weight
        keys/values into new-weight attention. Returns entries dropped."""
        n = len(self._prefix)
        self._prefix.clear()
        self._page_key.clear()
        while self._retained:
            page, _ = self._retained.popitem(last=False)
            self._free.append(page)
        if telemetry.enabled():
            if n:
                _metrics.counter(
                    "paddle_tpu_kv_prefix_invalidations_total",
                    "prefix-index entries dropped wholesale (weight swap)",
                ).inc(n)
            self._sync_gauges()
        return n

    def is_indexed(self, page: int) -> bool:
        return int(page) in self._page_key

    # ---- copy-on-write ----
    def make_private(self, page: int, owner: Optional[int] = None) -> int:
        """Clone `page` into a freshly allocated exclusive page (device-side
        copy of K/V and scale planes on every layer) and drop the caller's
        reference on the original. The write-side half of copy-on-write:
        call before writing into a page whose refcount > 1."""
        page = int(page)
        if page == TRASH_PAGE:
            raise ValueError("page 0 is reserved; writes there are scribbles")
        if page not in self._refs:
            raise ValueError(f"make_private of page {page} that is not held")
        (new,) = self.alloc(1, owner=owner)
        for layer in range(self.num_layers):
            self.k_pages[layer] = self.k_pages[layer].at[new].set(self.k_pages[layer][page])
            if not self.latent:
                self.v_pages[layer] = self.v_pages[layer].at[new].set(self.v_pages[layer][page])
            if self.index_pages is not None:
                self.index_pages[layer] = self.index_pages[layer].at[new].set(self.index_pages[layer][page])
            if self.k_scales is not None:
                self.k_scales[layer] = self.k_scales[layer].at[new].set(self.k_scales[layer][page])
                self.v_scales[layer] = self.v_scales[layer].at[new].set(self.v_scales[layer][page])
        # drop the caller's reference; the clone is NOT index-shareable (its
        # divergent future writes are exactly why it was cloned)
        self.free([page], owner=owner, retain=True)
        self.cow_copies += 1
        if telemetry.enabled():
            _metrics.counter(
                "paddle_tpu_kv_pool_cow_copies_total",
                "shared pages cloned copy-on-write before a divergent write",
            ).inc()
        if _rt.enabled():
            _rt.record_event("kv_pool", "cow", rid=owner, src=page, dst=new,
                             used=self.used())
        return new

    # ---- device-array plumbing ----
    def view(self, block_tables, seq_lens, write_mask=None, slots=None) -> PagedCacheView:
        """Eager-path view over the pool's current arrays: run the model
        with `cache=view`, then `adopt_state(PagedCacheView.state_of(view))`
        (`adopt(view.k_pages, view.v_pages)` does for a plain pool). A pool
        with recurrent state wants each row's `slots`."""
        return PagedCacheView.from_state(self.device_state(), block_tables, seq_lens,
                                         self.block_size, write_mask=write_mask, slots=slots)

    def device_state(self) -> Dict[str, List]:
        """The pool's device arrays as ONE pytree, for threading through
        compiled steps (donated whole; scale planes ride along when
        quantized, the recurrent layers' state arrays where there are any)."""
        state = {"k": list(self.k_pages), "v": list(self.v_pages)}
        if self.k_scales is not None:
            state["k_scale"] = list(self.k_scales)
            state["v_scale"] = list(self.v_scales)
        if self.state_layers:
            state["ssm"] = list(self.ssm)
            state["conv"] = list(self.conv)
        if self.index_pages is not None:
            state["index"] = list(self.index_pages)
        return state

    def adopt_state(self, state: Dict[str, List]) -> None:
        self.adopt(state["k"], state["v"])
        if self.k_scales is not None:
            if "k_scale" not in state:
                raise ValueError("quantized pool state is missing scale planes")
            self.k_scales = list(state["k_scale"])
            self.v_scales = list(state["v_scale"])
        if self.state_layers:
            if len(state.get("ssm", ())) != self.state_layers:
                raise ValueError("pool state is missing the recurrent layers' arrays")
            self.ssm = list(state["ssm"])
            self.conv = list(state["conv"])
        if self.index_pages is not None:
            if len(state.get("index", ())) != self.num_layers:
                raise ValueError("pool state is missing the index-key arrays")
            self.index_pages = list(state["index"])

    def adopt(self, k_pages: Sequence, v_pages: Sequence) -> None:
        """Install a step's updated page arrays back into the pool."""
        if len(k_pages) != self.num_layers or len(v_pages) != len(self.v_pages):
            raise ValueError("page-array layer count does not match the pool")
        self.k_pages = list(k_pages)
        self.v_pages = list(v_pages)

    def padded_table(self, pages: Sequence[int], n_cols: int):
        """One sequence's block-table row padded with the trash page."""
        row = list(pages)[:n_cols]
        return row + [TRASH_PAGE] * (n_cols - len(row))


# ---------------------------------------------------------------------------
# cross-pool page migration (round 20: disaggregated prefill/decode fleet)
# ---------------------------------------------------------------------------
#
# A migration moves one request's pages between two BlockPools (prefill
# replica -> decode replica) as a host-side payload: gather the block-table
# range out of the source pool's pytree, optionally re-encode for the
# destination's kv_dtype, scatter into freshly allocated destination pages.
# Integrity is per-page CRC32 over every byte the payload writes: the
# sender CRCs the CONVERTED payload, the receiver re-exports what actually
# landed and compares — a torn or corrupted handoff is detected before a
# single read, and the caller falls back to recompute-on-resume.


def _refuse_recurrent(pool: BlockPool, what: str) -> None:
    if pool.has_recurrent_state:
        raise ValueError(
            f"{what}: the pool holds recurrent-layer state, which pages do not carry — "
            "a sequence of such a model cannot migrate by its pages (recompute on the destination)")
    if pool.latent:
        raise ValueError(
            f"{what}: the pool keeps latent pages, and the migration payload (K and V planes a kv "
            "head, re-encoded by per-head absmax for an int8 destination) has no form for them — "
            "recompute on the destination")


def export_pages(pool: BlockPool, pages: Sequence[int]) -> Dict:
    """Gather `pages`' K/V (plus scale planes on a quantized pool) into a
    host payload for cross-pool migration. Page order is preserved — entry
    j of every plane is the content of pages[j]."""
    _refuse_recurrent(pool, "export_pages")
    idx = jnp.asarray(list(pages), jnp.int32)
    payload: Dict = {
        "kv_dtype": pool.kv_dtype,
        "k": [np.asarray(jnp.take(a, idx, axis=0)) for a in pool.k_pages],
        "v": [np.asarray(jnp.take(a, idx, axis=0)) for a in pool.v_pages],
    }
    if pool.quantized:
        payload["k_scale"] = [np.asarray(jnp.take(a, idx, axis=0)) for a in pool.k_scales]
        payload["v_scale"] = [np.asarray(jnp.take(a, idx, axis=0)) for a in pool.v_scales]
    return payload


def convert_payload(payload: Dict, kv_dtype: Optional[str]) -> Dict:
    """Re-encode a migration payload for a destination pool storing
    `kv_dtype`. f32 -> int8 quantizes every slot with the absmax observer
    rule (quantization/observers — the SAME math the destination's own
    write path runs), so the migrated pages are byte-identical to what the
    decode replica would have written had it prefilled the tokens itself.
    int8 -> f32 is refused: dequantization is lossy, and the exactness
    contract says recompute instead of silently degrading."""
    src = payload["kv_dtype"]
    if src == kv_dtype:
        return payload
    if src is None and kv_dtype == "int8":
        from ..quantization.observers import absmax_scale, quantize_absmax

        out: Dict = {"kv_dtype": "int8", "k": [], "v": [], "k_scale": [], "v_scale": []}
        for plane, scale_key in (("k", "k_scale"), ("v", "v_scale")):
            for arr in payload[plane]:
                x = jnp.asarray(arr)
                sc = absmax_scale(x, axis=-1)  # [n, Hkv, bs] f32
                out[plane].append(np.asarray(quantize_absmax(x, sc[..., None])))
                out[scale_key].append(np.asarray(sc))
        return out
    raise ValueError(
        f"unsupported KV migration {src!r} -> {kv_dtype!r} "
        "(int8 pages cannot re-expand losslessly; recompute instead)"
    )


def payload_page_crcs(payload: Dict) -> List[int]:
    """Per-page CRC32 over every byte the payload writes into the
    destination (K + V + scale planes across all layers) — computed on the
    converted payload before import and again on a readback export after,
    so a torn migration can never serve a corrupt page."""
    n = payload["k"][0].shape[0] if payload["k"] else 0
    crcs: List[int] = []
    for j in range(n):
        c = 0
        for key in ("k", "v", "k_scale", "v_scale"):
            for arr in payload.get(key) or ():
                c = zlib.crc32(np.ascontiguousarray(arr[j]).tobytes(), c)
        crcs.append(c)
    return crcs


def import_pages(pool: BlockPool, pages: Sequence[int], payload: Dict) -> None:
    """Scatter a (converted) payload into already-allocated `pages` of
    `pool`. The payload's kv_dtype must match the pool's — convert first."""
    _refuse_recurrent(pool, "import_pages")
    if payload["kv_dtype"] != pool.kv_dtype:
        raise ValueError(
            f"payload kv_dtype {payload['kv_dtype']!r} does not match the "
            f"destination pool's {pool.kv_dtype!r} — convert_payload first"
        )
    idx = jnp.asarray(list(pages), jnp.int32)
    for layer in range(pool.num_layers):
        pool.k_pages[layer] = pool.k_pages[layer].at[idx].set(
            jnp.asarray(payload["k"][layer], pool.dtype))
        pool.v_pages[layer] = pool.v_pages[layer].at[idx].set(
            jnp.asarray(payload["v"][layer], pool.dtype))
        if pool.quantized:
            pool.k_scales[layer] = pool.k_scales[layer].at[idx].set(
                jnp.asarray(payload["k_scale"][layer], jnp.float32))
            pool.v_scales[layer] = pool.v_scales[layer].at[idx].set(
                jnp.asarray(payload["v_scale"][layer], jnp.float32))


def corrupt_payload(payload: Dict, seed=0) -> Dict:
    """Flip ONE deterministic byte in the payload in place (the torn-write
    / bit-rot shape a mid-migration failure produces) — the in-memory
    analog of fault_injection.corrupt_file, applied by the fleet when a
    CORRUPT spec claims the kv_migrate site AFTER the source CRC was
    recorded. x ^ 0xFF never equals x, so detection is guaranteed."""
    rng = random.Random(seed)
    arr = np.ascontiguousarray(payload["k"][0])
    raw = bytearray(arr.tobytes())
    pos = rng.randrange(len(raw))
    raw[pos] ^= 0xFF
    payload["k"][0] = np.frombuffer(bytes(raw), dtype=arr.dtype).reshape(arr.shape)
    return payload
