"""`PagedCacheView.write` against a plain numpy loop over (row, token).

A positioned write indexes page, kv head and slot together (one scatter,
window `D`); a prefill (positions None: tokens at 0..S-1) scatters whole pages,
zeros past S in the last. The loop below says what either must put where:
position p of row b lands in page `block_tables[b, p // bs]`, slot `p % bs`,
every kv head; a position the `write_mask` rules out, a position past a row's
real pages and every pad row land on the trash page, where several writes may
collide (which of them the trash page keeps is nobody's business). Every other
page is compared bit for bit: the pages written, and the pages no row of the
step holds.
"""
import jax
import numpy as np
import pytest
from jax import numpy as jnp

import paddle_tpu as paddle  # noqa: F401  (turns the global x64 on, as callers have it)
from paddle_tpu.inference.kv_cache import TRASH_PAGE, PagedCacheView
from paddle_tpu.quantization.observers import absmax_scale, quantize_absmax

N, BS, D, M = 24, 4, 8, 5   # pages (page 0 the trash page), slots a page, head size, table width


def _decode():
    """Four rows one token each, at different depths of their pages."""
    tables = np.array([[3, 7, 0, 0, 0], [5, 0, 0, 0, 0], [9, 2, 11, 0, 0], [4, 6, 8, 10, 12]], np.int32)
    return tables, np.array([[6], [0], [11], [19]], np.int32), None


def _decode_pad_rows():
    """Two real rows among four pad rows: a pad row's table is all trash
    page and its position 0, so four writes collide on the trash page's slot 0."""
    tables = np.zeros((6, M), np.int32)
    tables[1, :2] = (13, 14)
    tables[4, :1] = (21,)
    return tables, np.array([[0], [5], [0], [0], [2], [0]], np.int32), None


def _prefill_past_seq_lens():
    """A prefill: one row of 16 positions whose prompt has 10 tokens: it holds
    3 pages, positions 10 and 11 land in its own last page past the prompt,
    12-15 hit the table's padding."""
    return np.array([[17, 3, 9, 0, 0]], np.int32), 16, None


def _prefill_bucket_ends_inside_a_page():
    """A prefill whose 10 positions end inside the third page: the page's
    last two slots take zeros."""
    return np.array([[17, 3, 9, 0, 0]], np.int32), 10, None


def _prefill_two_rows():
    """A prefill of two rows, the second a pad row (all trash page)."""
    return np.array([[5, 6, 0, 0, 0], [0, 0, 0, 0, 0]], np.int32), 8, None


def _positioned_like_a_prefill():
    """The same 16 positions given as positions: the scatter by slot."""
    return np.array([[17, 3, 9, 0, 0]], np.int32), np.arange(16, dtype=np.int32)[None, :], None


def _extend_masked():
    """Three rows of four positions with a `write_mask`: a row whose tail is
    masked, a row masked whole, a row written whole across a page boundary."""
    tables = np.array([[2, 4, 0, 0, 0], [6, 0, 0, 0, 0], [8, 15, 16, 0, 0]], np.int32)
    positions = np.array([[3, 4, 5, 6], [0, 1, 2, 3], [6, 7, 8, 9]], np.int32)
    mask = np.array([[1, 1, 0, 0], [0, 0, 0, 0], [1, 1, 1, 1]], bool)
    return tables, positions, mask


def _by_loop(pool, new, tables, positions, mask):
    """The plain loop, into a copy of `pool` ([N, Hkv, bs, ...]); `new` is
    [B, S, Hkv, ...]. A prefill (positions a number of tokens) visits its
    positions 0..S-1 and then, with zeros, the rest of the last page."""
    out = pool.copy()
    if isinstance(positions, int):
        whole = -(-positions // BS) * BS
        new = np.concatenate([new, np.zeros((new.shape[0], whole - positions) + new.shape[2:], new.dtype)], 1)
        positions = np.broadcast_to(np.arange(whole, dtype=np.int32), (new.shape[0], whole))
    for b in range(positions.shape[0]):
        for s in range(positions.shape[1]):
            p = int(positions[b, s])
            page = int(tables[b, p // BS])
            if mask is not None and not mask[b, s]:
                page = TRASH_PAGE
            out[page, :, p % BS] = new[b, s]
    return out


@pytest.mark.parametrize("kind", ["f32", "int8"])
@pytest.mark.parametrize("hkv", [1, 8], ids=["hkv1", "hkv8"])
@pytest.mark.parametrize("case", [_decode, _decode_pad_rows, _prefill_past_seq_lens,
                                  _prefill_bucket_ends_inside_a_page, _prefill_two_rows,
                                  _positioned_like_a_prefill, _extend_masked],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_write_lands_where_the_plain_loop_puts_it(case, hkv, kind):
    rng = np.random.default_rng(7)
    tables, positions, mask = case()
    prefill = isinstance(positions, int)
    b, s = (tables.shape[0], positions) if prefill else positions.shape
    k_new = rng.standard_normal((b, s, hkv, D)).astype(np.float32)
    v_new = rng.standard_normal((b, s, hkv, D)).astype(np.float32)
    if kind == "int8":
        pages = [rng.integers(-127, 128, (N, hkv, BS, D)).astype(np.int8) for _ in range(2)]
        scales = [rng.random((N, hkv, BS)).astype(np.float32) + 0.5 for _ in range(2)]
    else:
        pages = [rng.standard_normal((N, hkv, BS, D)).astype(np.float32) for _ in range(2)]
        scales = None

    def step(state, k_new, v_new):
        view = PagedCacheView.from_state(state, jnp.asarray(tables), jnp.zeros((b,), jnp.int32), BS,
                                         write_mask=None if mask is None else jnp.asarray(mask))
        view.write(0, k_new, v_new, None if prefill else jnp.asarray(positions))
        return PagedCacheView.state_of(view)

    state = {"k": [jnp.asarray(pages[0])], "v": [jnp.asarray(pages[1])]}
    if scales is not None:
        state.update(k_scale=[jnp.asarray(scales[0])], v_scale=[jnp.asarray(scales[1])])
    got = jax.jit(step)(state, jnp.asarray(k_new), jnp.asarray(v_new))

    real = np.arange(N) != TRASH_PAGE
    touched = np.zeros(N, bool)
    touched[tables[:, : -(-s // BS)] if prefill else tables[np.arange(b)[:, None], positions // BS]] = True
    for name, pool, new in (("k", pages[0], k_new), ("v", pages[1], v_new)):
        if scales is not None:
            sc = absmax_scale(new, axis=-1)
            want_sc = _by_loop(scales[name == "v"], np.asarray(sc), tables, positions, mask)
            got_sc = np.asarray(got[name + "_scale"][0])
            assert np.array_equal(got_sc[real], want_sc[real])
            new = np.asarray(quantize_absmax(new, sc[..., None]))
        want = _by_loop(pool, new, tables, positions, mask)
        have = np.asarray(got[name][0])
        assert have.dtype == pool.dtype
        assert np.array_equal(have[real], want[real])
        assert np.array_equal(have[real & ~touched], pool[real & ~touched])   # no page but the rows' own
