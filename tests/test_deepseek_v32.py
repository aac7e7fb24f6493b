"""DeepSeek-V3.2 style decoder (MLA + a learned token selector + group-limited
sigmoid routing) through the one engine, on the CPU at a tiny size with
`index_topk` 8-16 against contexts of 40-200, so that the selection engages.

The program against the plain reference's full forward
(`chipbench/reference/deepseek-v32-ep16-l5.py`, imported by path) on the
benchmark's seeded weights, by every way a step's tokens enter: a bucketed
prefill (through the cache, in query tiles), chunks beside decode rows, decode,
`extend`; the two kernels (interpret mode) against plain jnp; the selected sets
against the reference's on float32 scores; the grouped choice of `route_topk`
against a loop in numpy; the 16 shares of a layer against the uncut reference;
the index array's three write paths; what a preemption frees; the counters.
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.engine import InferenceEngine
from paddle_tpu.inference.kv_cache import BlockPool, PagedCacheView
from paddle_tpu.inference.scheduler import ContinuousBatchingScheduler, Request
from paddle_tpu.models import deepseek_v32 as dm
from paddle_tpu.models import expert_share, mla_moe
from paddle_tpu.ops import pallas as pk
from paddle_tpu.profiler import utils as spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1}
# the tiny preset: 1 dense + 2 sparse layers, 16 experts in 4 groups, 8 held; 4 index heads, 8 chosen a query
CFG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=4, index_head_dim=128, index_topk=8, intermediate_size=160, moe_intermediate_size=48,
    n_routed_experts=16, experts_held=[4, 8], num_experts_per_tok=4, n_shared_experts=1, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=YARN, initializer_range=0.02)
TOL = dict(rtol=3e-4, atol=3e-5)


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, ROOT)  # the reference imports chipbench.weights
    path = os.path.join(ROOT, "chipbench", "reference", "deepseek-v32-ep16-l5.py")
    spec = importlib.util.spec_from_file_location("deepseek_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def seeded(ref):
    """(model, its leaves as float32 arrays) on the benchmark's seeded weights,
    the matrices ten times wider than the cell's 0.02 so that index scores and
    attention logits spread at 64 wide."""
    from chipbench import weights

    model = dm.DeepseekV32ForCausalLM(**CFG)
    model.eval()
    specs = {k: (s, kind, 0.2 if kind == "normal" and not k.endswith("router_bias") else scale)
             for k, (s, kind, scale) in ref.leaf_specs(CFG).items()}
    vals = weights.make(specs, 5, jnp.float32)
    state = model.state_dict()
    assert set(state) == set(vals)
    for name, t in state.items():
        assert tuple(t.shape) == tuple(vals[name].shape), name
        t._value = vals[name]
    return model, vals


def _engine(model, **kw):
    kw = {"max_seq_len": 256, "block_size": 8, "max_batch": 4, "prefill_buckets": (64, 128, 256),
          "decode_batch_buckets": (1, 2, 4), **kw}
    return InferenceEngine(model, **kw)


def _ids(seed, n, length):
    return np.random.RandomState(seed).randint(1, CFG["vocab_size"], (n, length)).astype(np.int32)


def _want(ref, vals, seq, **kw):
    pad = -len(seq) % 64  # the reference's indexer takes queries in blocks of 64
    return np.asarray(ref.forward(vals, np.asarray([list(seq) + [0] * pad]), CFG, **kw))[0, :len(seq)]


def _drain(sched, limit=600):
    for _ in range(limit):
        if sched.idle():
            return
        sched.step()
    raise AssertionError("the scheduler did not drain")


# (a) the model's full forward (no cache: expanded keys, the selector's mask)
def test_full_forward_matches_the_reference_and_selection_matters(ref, seeded):
    model, vals = seeded
    ids = _ids(0, 2, 64)
    with paddle.no_grad():
        got = model(paddle.to_tensor(ids)).numpy()
    want = np.asarray(ref.forward(vals, ids, CFG))
    np.testing.assert_allclose(got, want, **TOL)
    # the planted fault: the reference without selection is another function
    assert np.abs(np.asarray(ref.forward(vals, ids, CFG, select=False)) - want).max() > 100 * TOL["atol"]
    assert model.config["layer_kinds"] == ["attention", "attention+moe", "attention+moe"]
    assert model.config["cache_entry"] == {"layout": "latent", "width": 40, "index_width": 128}
    assert model.config["index_query_tile"] == dm.QUERY_TILE


# (b) bucketed prefill (through the cache, in query tiles), then decode over 8 chosen of up to 100 positions
@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "interpret"])
@pytest.mark.parametrize("tile", [128, 32], ids=["one_tile", "tiles_of_32"])
def test_prefill_then_decode_matches_the_reference(ref, seeded, interpret, tile, monkeypatch):
    model, vals = seeded
    monkeypatch.setattr(pk, "_INTERPRET", interpret)
    monkeypatch.setattr(dm, "QUERY_TILE", tile)
    eng = _engine(model)
    assert eng.cache_layout == "latent" and eng.pool.page_shape == (eng.pool.num_blocks, 8, 128)
    assert eng.pool.index_pages[0].shape == (eng.pool.num_blocks, 8, 128) and eng.index_topk == 8
    seq = _ids(1, 1, 100)[0].tolist()
    want = _want(ref, vals, seq)
    spans.clear()
    pages = eng.pool.alloc(eng.pool.blocks_for_tokens(len(seq)))
    np.testing.assert_allclose(eng.prefill(seq[:90], pages), want[89], **TOL)  # true_len 90 in the bucket of 128
    for t in range(90, len(seq)):
        np.testing.assert_allclose(eng.decode([seq[t]], [t], [t + 1], [pages])[0], want[t], **TOL)
    (pre,) = [r[6] for r in spans.records() if r[0] == "engine.prefill"]
    assert pre["index_positions_live"] == 90 * 91 // 2 and pre["sparse_queries"] == 82
    assert pre["index_positions_selected"] == 8 * 9 // 2 + 82 * 8
    dec = [r[6] for r in spans.records() if r[0] == "engine.decode"]
    assert [d["index_positions_live"] for d in dec] == list(range(91, 101))
    assert all(d["index_positions_selected"] == 8 and d["sparse_queries"] == 1 for d in dec)
    assert all(d["index_positions_scored"] == d["index_keys_read"] == d["index_positions_live"] for d in dec)
    eng.pool.free(pages)
    assert eng.pool.used() == 0


# (c) a prompt that enters in chunks beside a decode row in flight, through the scheduler
@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "interpret"])
def test_a_prompt_in_chunks_beside_decode_rows_matches_the_reference(ref, seeded, interpret, monkeypatch):
    model, vals = seeded
    monkeypatch.setattr(pk, "_INTERPRET", interpret)
    eng = _engine(model)
    monkeypatch.setattr(eng, "chunk_width", 32)  # three chunks for a prompt of 70
    sched = ContinuousBatchingScheduler(eng, prefix_cache=False)
    seen, chunks = {}, []
    decode, with_chunk = eng.decode, eng.decode_with_chunk

    def note(positions, page_rows, tokens, out):
        for i, row in enumerate(page_rows):
            seen.setdefault(row[0], {})[positions[i]] = (tokens[i], out[i])

    def rec_decode(tokens, positions, seq_lens, page_rows):
        out = decode(tokens=tokens, positions=positions, seq_lens=seq_lens, page_rows=page_rows)
        note(positions, page_rows, tokens, out)
        return out

    def rec_chunk(tokens, positions, seq_lens, page_rows, chunk_ids, chunk_start, chunk_pages):
        rows, last = with_chunk(tokens, positions, seq_lens, page_rows, chunk_ids, chunk_start, chunk_pages)
        note(positions, page_rows, tokens, rows)
        chunks.append((chunk_start, len(chunk_ids), last))
        return rows, last

    monkeypatch.setattr(eng, "decode", rec_decode)
    monkeypatch.setattr(eng, "decode_with_chunk", rec_chunk)
    first = Request(rid=0, prompt=_ids(3, 1, 45)[0].tolist(), max_new_tokens=12)
    sched.submit(first)
    sched.step()  # bucketed: nothing was in flight
    late = Request(rid=1, prompt=_ids(4, 1, 70)[0].tolist(), max_new_tokens=5)
    sched.submit(late)
    spans.clear()
    sched.step()
    (dec,) = [r[6] for r in spans.records() if r[0] == "engine.decode"]
    assert (dec["chunk_tokens"], dec["chunk_context"], dec["rows"]) == (32, 0, 1)
    # the row at position 47 (the first call dispatched two steps; 8 of 48 chosen) and the chunk's 32 queries
    # at 0..31 (8 in full, 24 with 8 chosen)
    assert dec["index_positions_live"] == 48 + 32 * 33 // 2 and dec["sparse_queries"] == 1 + 24
    assert dec["index_positions_selected"] == 8 + 36 + 24 * 8
    (step,) = [r[6] for r in spans.records() if r[0] == "sched.step"]
    assert all(step[k] == dec[k] for k in ("index_positions_live", "index_positions_selected", "sparse_queries"))
    keys = {first.pages[0]: first, late.pages[0]: late}
    _drain(sched)
    assert [(s, n) for s, n, _ in chunks] == [(0, 32), (32, 32), (64, 6)]
    np.testing.assert_allclose(chunks[2][2], _want(ref, vals, late.prompt + late.generated)[69], **TOL)
    assert set(seen) == set(keys)
    for key, steps in seen.items():
        seq = keys[key].prompt + keys[key].generated
        want = _want(ref, vals, seq)
        assert len(steps) == len(keys[key].generated) - 1
        for pos, (tok, logits) in steps.items():
            assert int(tok) == seq[pos]  # the host's token, or the step before's choice, read here
            np.testing.assert_allclose(logits, want[pos], **TOL)
    assert eng.pool.used() == 0


# (d) extend: several tokens a row over the cache, every position's logits
@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "interpret"])
def test_extend_matches_the_reference(ref, seeded, interpret, monkeypatch):
    model, vals = seeded
    monkeypatch.setattr(pk, "_INTERPRET", interpret)
    eng = _engine(model)
    seqs = [_ids(7, 1, 60)[0].tolist(), _ids(8, 1, 50)[0].tolist()]
    pages = [eng.pool.alloc(8), eng.pool.alloc(7)]
    for seq, pg in zip(seqs, pages):
        eng.prefill(seq[:40], pg)
    spans.clear()
    got = eng.extend([seqs[0][40:44], seqs[1][40:43]], [list(range(40, 44)), list(range(40, 43))], pages, 4)
    np.testing.assert_allclose(got[0], _want(ref, vals, seqs[0])[40:44], **TOL)
    np.testing.assert_allclose(got[1, :3], _want(ref, vals, seqs[1])[40:43], **TOL)
    (ext,) = [r[6] for r in spans.records() if r[0] == "engine.extend"]
    assert ext["index_positions_live"] == (41 + 42 + 43 + 44) + (41 + 42 + 43) and ext["sparse_queries"] == 7
    for pg in pages:
        eng.pool.free(pg)


# (e) a long prompt past the largest bucket enters as chunks even with nothing in flight
def test_a_prompt_past_the_largest_bucket_enters_in_chunks(ref, seeded):
    model, vals = seeded
    eng = _engine(model, prefill_buckets=(64,))
    sched = ContinuousBatchingScheduler(eng, prefix_cache=False)
    req = Request(rid=0, prompt=_ids(11, 1, 150)[0].tolist(), max_new_tokens=3)
    spans.clear()
    sched.submit(req)
    _drain(sched)
    assert req.chunks == 2 and not [r for r in spans.records() if r[0] == "engine.prefill"]
    want = _want(ref, vals, req.prompt + req.generated)
    assert req.generated == [int(want[t - 1].argmax()) for t in range(150, 153)]
    assert eng.pool.used() == 0


# (f) the kernels in interpret mode against plain jnp
@pytest.mark.parametrize("heads, q_len, counts, firsts", [
    (4, 1, [1, 1, 1], [37, 0, 120]), (4, 5, [5, 3], [20, 3]), (64, 20, [20, 7], [100, 0]),
    (4, 40, [40], [88])], ids=["rows", "extend", "heads_64_two_subtiles", "chunk"])
def test_dsa_index_kernel_interpret_matches_the_oracle(heads, q_len, counts, firsts, monkeypatch):
    monkeypatch.setattr(pk, "_INTERPRET", True)
    rng = np.random.RandomState(q_len)
    n, bs, d, m = 40, 8, 128, 18
    b = len(counts)
    pages = jnp.asarray(rng.randn(n, bs, d), jnp.float32)
    bt = np.zeros((b, m), np.int32)
    pos = np.zeros((b, q_len), np.int32)
    for r in range(b):
        need = -(-(firsts[r] + counts[r]) // bs)
        bt[r, :need] = rng.permutation(n - 1)[:need] + 1
        pos[r, :counts[r]] = firsts[r] + np.arange(counts[r])
    q = jnp.asarray(rng.randn(b, q_len, heads, d), jnp.float32)
    w = jnp.asarray(rng.randn(b, q_len, heads), jnp.float32)
    got = np.asarray(pk.dsa_index_scores(q, w, pages, bt, pos))
    want = np.asarray(pk.dsa_index_reference(q, w, pages, bt, pos))
    assert got.shape == (b, q_len, m * bs)
    np.testing.assert_array_equal(np.isinf(got), np.isinf(want))
    np.testing.assert_allclose(np.where(np.isinf(got), 0, got), np.where(np.isinf(want), 0, want), rtol=1e-5, atol=1e-4)
    for r in range(b):  # a query sees the positions up to its own and no other
        for j in range(counts[r]):
            assert np.isfinite(got[r, j, :pos[r, j] + 1]).all() and np.isinf(got[r, j, pos[r, j] + 1:]).all()


@pytest.mark.parametrize("heads, q_len, k", [(4, 1, 8), (4, 5, 20), (16, 3, 16)], ids=["rows_k8", "extend_k20", "k16"])
def test_mla_sparse_kernel_interpret_matches_the_oracle(heads, q_len, k, monkeypatch):
    monkeypatch.setattr(pk, "_INTERPRET", True)
    rng = np.random.RandomState(k)
    n, bs, e, w, m, b = 30, 8, 40, 128, 12, 2
    pages = jnp.asarray(rng.randn(n, bs, w), jnp.float32).at[..., e:].set(0.0)
    bt = np.stack([rng.permutation(n - 1)[:m] + 1 for _ in range(b)]).astype(np.int32)
    q = jnp.asarray(rng.randn(b, q_len, heads, e), jnp.float32)
    chosen = np.stack([[rng.permutation(m * bs)[:k] for _ in range(q_len)] for _ in range(b)]).astype(np.int32)
    counts = rng.randint(1, k + 1, (b, q_len)).astype(np.int32)
    rows = np.take_along_axis(np.asarray(pk.pool_rows(bt, bs))[:, None, :], chosen, axis=-1)
    got = np.asarray(pk.mla_sparse_attention(q, pages, rows, counts, 32, 0.2))
    want = np.asarray(pk.mla_sparse_reference(q, pages, rows, counts, 32, 0.2))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # by hand, one query: a softmax over its first `count` chosen entries
    ent = np.asarray(pages)[bt[0][chosen[0, 0] // bs], chosen[0, 0] % bs][:counts[0, 0], :e]
    logits = np.asarray(q)[0, 0] @ ent.T * 0.2
    p = np.exp(logits - logits.max(-1, keepdims=True))
    np.testing.assert_allclose(got[0, 0], (p / p.sum(-1, keepdims=True)) @ ent[:, :32], rtol=1e-4, atol=1e-5)


def test_all_positions_chosen_is_the_dense_kernel(monkeypatch):
    """With `topk` at or past every context the sparse path attends to what the
    dense latent kernel does; `dsa_attend` takes the dense kernel there (the
    table can hold no more), and takes it for a tile below `topk` at run time."""
    monkeypatch.setattr(pk, "_INTERPRET", True)
    rng = np.random.RandomState(3)
    n, bs, m = 20, 8, 6
    lat = jnp.asarray(rng.randn(n, bs, 128), jnp.float32).at[..., 40:].set(0.0)
    idx = jnp.asarray(rng.randn(n, bs, 128), jnp.float32)
    bt = np.asarray([[1, 2, 3, 4, 5, 0], [7, 8, 9, 0, 0, 0]], np.int32)
    pos = np.asarray([[20, 21, 22, 23], [3, 4, 5, 0]], np.int32)
    q = jnp.asarray(rng.randn(2, 4, 4, 40), jnp.float32)
    qi, wi = jnp.asarray(rng.randn(2, 4, 4, 128), jnp.float32), jnp.asarray(rng.randn(2, 4, 4), jnp.float32)
    dense = np.asarray(pk.mla_paged_attention(q, lat, bt, pos, 32, 0.2))
    kw = dict(value_width=32, scale=0.2)
    for topk in (48, 64, 30):  # the table's 48 positions: static dense, static dense, a run-time choice (23 < 30)
        got = np.asarray(dm.dsa_attend(q, qi, wi, lat, idx, bt, pos, topk=topk, **kw))
        np.testing.assert_allclose(got[0], dense[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got[1, :3], dense[1, :3], rtol=1e-5, atol=1e-6)
    sparse = np.asarray(dm.dsa_attend(q, qi, wi, lat, idx, bt, pos, topk=8, **kw))
    assert np.abs(sparse[0] - dense[0]).max() > 1e-3  # 8 of 21-24 positions: another answer
    np.testing.assert_allclose(sparse[1, :3], dense[1, :3], rtol=1e-5, atol=1e-6)  # contexts of 4-6: all chosen


# (g) the selected sets are the reference's, on float32 scores
def test_selected_sets_equal_the_references(ref, seeded):
    model, vals = seeded
    attn = model.model.layers[1].self_attn
    x = jnp.asarray(np.random.RandomState(2).randn(1, 64, 64), jnp.float32)
    w = [t.value for t in attn._leaves()]
    at = dict(positions=None, max_pos=64)
    *_, c_q = mla_moe.mla_project(x, *w[:5], **attn.dims, **at)
    q_idx, k_idx, w_idx = dm.index_project(x, c_q, *w[6:], **attn.index_dims, **at)
    mask = np.asarray(dm._selected_mask(q_idx, k_idx, w_idx, 8))[0]
    pre = "model.layers.1.self_attn."
    rw = {k[len(pre):]: v for k, v in vals.items() if k.startswith(pre)}
    with jax.default_matmul_precision("highest"):
        scores = ref.index_scores(x[0], c_q[0], rw, CFG)
        want = np.asarray(ref.selected(scores, 8))
    np.testing.assert_array_equal(mask, want)
    assert (mask.sum(-1) == np.minimum(8, np.arange(64) + 1)).all()
    # through the paged kernel's scores too: the same sets
    pool = BlockPool(10, 8, 1, 1, 40, layout="latent", index_width=128)
    view = pool.view(np.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], np.int32), np.asarray([64], np.int32))
    view.write(0, jnp.zeros((1, 64, 40)), k_idx)
    paged = pk.dsa_index_scores(q_idx, w_idx, view.index_pages[0], view.block_tables,
                                np.arange(64, dtype=np.int32)[None])
    chosen = np.asarray(pk.dsa_select(paged, 8))[0]
    rows = np.asarray(pk.dsa_select(paged, 8, carry=pk.pool_rows(view.block_tables, 8), frontier=jnp.asarray(64)))[0]
    for t in range(64):
        assert set(chosen[t, :min(8, t + 1)]) == set(np.flatnonzero(want[t]))
        assert [r - 8 for r in rows[t, :min(8, t + 1)]] == list(chosen[t, :min(8, t + 1)])  # pages 1.. in order


@pytest.mark.parametrize("frontier", [None, 100, 600, 1500], ids=["whole", "quarter", "half", "all"])
def test_selection_is_exact_whatever_width_it_sorts(frontier):
    """`dsa_select` against numpy's argsort, ties to the earlier position: over
    all 2048 positions, or the narrowest of 512 / 1024 / 2048 that holds the
    frontier; `carry` comes back at the chosen positions."""
    rng = np.random.RandomState(7)
    live = frontier or 2048
    scores = np.round(rng.randn(2, 3, 2048), 1).astype(np.float32)  # rounded: many ties
    scores[..., live:] = -np.inf
    carry = rng.permutation(5000)[:2 * 2048].reshape(2, 2048).astype(np.int32)
    got = np.asarray(pk.dsa_select(jnp.asarray(scores), 64, carry=jnp.asarray(carry),
                                   frontier=None if frontier is None else jnp.asarray(frontier)))
    want = np.argsort(-scores, axis=-1, kind="stable")[..., :64]
    np.testing.assert_array_equal(got, np.take_along_axis(carry[:, None, :], want, -1))
    np.testing.assert_array_equal(np.asarray(pk.dsa_select(jnp.asarray(scores), 64)), want)


# (h) group-limited routing against a loop in numpy; the bias moves the choice, not the weights
@pytest.mark.parametrize("n_group, topk_group, top_k", [(4, 2, 4), (8, 4, 8), (2, 1, 3)])
def test_grouped_route_topk_against_a_loop(n_group, topk_group, top_k):
    rng = np.random.RandomState(n_group)
    t, h, e = 24, 32, 32
    x = jnp.asarray(rng.randn(t, h), jnp.float32)
    router = jnp.asarray(rng.randn(h, e) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.randn(e) * 0.5, jnp.float32)
    chosen, weights = expert_share.route_topk(x, router, bias, top_k, 2.5, n_group=n_group, topk_group=topk_group)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(router, np.float64))))
    per, moved = e // n_group, 0
    for i in range(t):
        c = s[i] + np.asarray(bias, np.float64)
        score = [np.sort(c[g * per:(g + 1) * per])[-2:].sum() for g in range(n_group)]
        groups = np.argsort(score)[::-1][:topk_group]
        allowed = np.concatenate([np.arange(g * per, (g + 1) * per) for g in groups])
        want = allowed[np.argsort(c[allowed])[::-1][:top_k]]
        assert sorted(chosen[i]) == sorted(want)
        np.testing.assert_allclose(np.sort(weights[i]), np.sort(2.5 * s[i, want] / s[i, want].sum()), rtol=1e-5)
        moved += sorted(want) != sorted(np.argsort(s[i])[::-1][:top_k])
    assert moved > 0
    # without groups: today's path
    plain, _ = expert_share.route_topk(x, router, bias, top_k, 2.5)
    assert sorted(np.asarray(plain)[0]) == sorted(np.argsort(s[0] + np.asarray(bias))[::-1][:top_k])


# (i) the share: 16 shares' routed parts and the shared expert once make the uncut layer
def test_sixteen_shares_add_up_to_the_uncut_reference(ref):
    from chipbench import weights

    c = dict(CFG, n_routed_experts=32, n_group=8, topk_group=4, num_experts_per_tok=8, experts_held=[0, 32])
    w = weights.make(ref.layer_specs(c, 1), 9, jnp.float32)
    w = {k.split("mlp.")[1]: v for k, v in w.items() if ".mlp." in k}
    x = jnp.asarray(np.random.RandomState(1).randn(12, 64), jnp.float32)
    shared_leaves = [w[f"shared_experts.{n}_proj.weight"] for n in ("gate", "up", "down")]

    def share(first):
        held = [w[k][first:first + 2] for k in ("experts_gate", "experts_up", "experts_down")]
        return mla_moe.sparse_mlp(x, w["router"], *held, *shared_leaves, top_k=8, scale=2.5, first=first,
                                  b_corr=w["router_bias"], n_group=8, topk_group=4)

    with jax.default_matmul_precision("highest"):
        shared = mla_moe.gated_mlp(x, *shared_leaves)
        outs = [share(f) for f in range(0, 32, 2)]
        total = sum(o[0] - shared for o in outs) + shared
        want = ref.sparse_ffn(x, w, c)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-5)
    assert sum(int(o[1]) for o in outs) == 12 * 8  # every (token, expert) pair computed by exactly one share


# (j) YaRN's tables against the formula
def test_yarn_tables_follow_the_formula():
    inv = mla_moe.yarn_inv_freq(8, 10000.0, 40, 64, 32, 1)
    f = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    low = max(0, int(np.floor(8 * np.log(64 / (32 * 2 * np.pi)) / (2 * np.log(10000)))))
    high = min(7, int(np.ceil(8 * np.log(64 / (2 * np.pi)) / (2 * np.log(10000)))))
    ramp = np.clip((np.arange(4) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, f / 40 * ramp + f * (1 - ramp), rtol=1e-12)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 5, 8), jnp.float32)
    pos = np.asarray([[3, 9, 27, 81, 200]], np.int32)
    got = np.asarray(mla_moe.rope_half(x, pos, 10000.0, 256, (40, 64, 32, 1)))
    ang = pos[0][:, None] * inv[None, :]
    x1, x2 = np.asarray(x)[0, :, :4], np.asarray(x)[0, :, 4:]
    want = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang), x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)
    # without scaling: what it was
    np.testing.assert_array_equal(np.asarray(mla_moe.rope_half(x, pos, 10000.0, 256)),
                                  np.asarray(mla_moe.rope_half(x, pos, 10000.0, 256, None)))


# (k) the pool with an index array: geometry, bytes, refusals, a private copy
def test_index_pool_geometry_bytes_and_refusals():
    pool = BlockPool(9, 16, 5, 1, 576, dtype=jnp.bfloat16, layout="latent", index_width=128)
    assert pool.page_shape == (9, 16, 640) and pool.index_pages[0].shape == (9, 16, 128)
    assert pool.page_bytes() == 16 * 1536 * 5 and pool.pool_bytes() == 9 * 16 * 1536 * 5
    assert set(pool.device_state()) == {"k", "v", "index"} and len(pool.device_state()["index"]) == 5
    with pytest.raises(ValueError, match="latent pool's pages"):
        BlockPool(9, 16, 5, 2, 64, index_width=128)
    with pytest.raises(ValueError, match="whole lane tiles"):
        BlockPool(9, 16, 5, 1, 576, layout="latent", index_width=100)
    with pytest.raises(ValueError, match="index-key arrays"):
        pool.adopt_state({"k": pool.k_pages, "v": []})
    (page,) = pool.alloc(1)
    pool.index_pages = [a.at[page].set(i + 1.0) for i, a in enumerate(pool.index_pages)]
    pool.share([page])
    new = pool.make_private(page)
    assert new != page and all(float(a[new].min()) == i + 1.0 for i, a in enumerate(pool.index_pages))


# (l) the index array's three write paths, against a plain loop
@pytest.mark.parametrize("how", ["rows", "prefill", "chunk"])
def test_index_write_lands_where_a_loop_puts_it(how):
    n, bs, width, m = 12, 4, 6, 4
    rng = np.random.RandomState(0)
    lat0, idx0 = rng.randn(n, bs, 128).astype(np.float32), rng.randn(n, bs, 128).astype(np.float32)
    want = idx0.copy()
    state = {"k": [jnp.asarray(lat0)], "v": [], "index": [jnp.asarray(idx0)]}
    if how == "rows":
        tables = np.array([[3, 7, 0, 0], [5, 0, 0, 0], [0, 0, 0, 0]], np.int32)
        positions = np.array([[6], [2], [0]], np.int32)
        new, key = rng.randn(3, 1, width).astype(np.float32), rng.randn(3, 1, 128).astype(np.float32)
        view = PagedCacheView.from_state(state, tables, np.ones(3, np.int32), bs)
        view.write(0, jnp.asarray(new), jnp.asarray(key), positions=positions)
        for b in range(2):
            p = int(positions[b, 0])
            want[tables[b, p // bs], p % bs] = key[b, 0]
        skip = [0]
    elif how == "prefill":
        tables = np.array([[9, 2, 11, 0]], np.int32)
        new, key = rng.randn(1, 10, width).astype(np.float32), rng.randn(1, 10, 128).astype(np.float32)
        view = PagedCacheView.from_state(state, tables, np.asarray([10], np.int32), bs)
        view.write(0, jnp.asarray(new), jnp.asarray(key))
        padded = np.pad(key[0], ((0, 2), (0, 0)))
        for j, page in enumerate((9, 2, 11)):
            want[page] = padded[j * bs:(j + 1) * bs]
        skip = []
    else:
        table = np.array([[4, 6, 8, 10]], np.int32)
        new, key = rng.randn(1, 8, width).astype(np.float32), rng.randn(1, 8, 128).astype(np.float32)
        view = PagedCacheView.from_state(state, np.zeros((2, m), np.int32), np.ones(2, np.int32), bs,
                                         chunk_table=table)
        view.write_chunk(0, jnp.asarray(new), jnp.asarray(key), first_position=jnp.asarray(4))
        want[6], want[8] = key[0, :4], key[0, 4:]
        skip = []
    got = np.asarray(PagedCacheView.state_of(view)["index"][0])
    keep = [p for p in range(n) if p not in skip]
    np.testing.assert_array_equal(got[keep], want[keep])
    assert np.asarray(view.k_pages[0])[keep].shape == lat0[keep].shape and view.latent


# (m) a preemption between two chunks frees the index pages with the latent ones; the resume recomputes
def test_a_preemption_between_two_chunks_frees_both_arrays_pages(ref, seeded):
    model, vals = seeded
    eng = _engine(model, num_blocks=24)  # 23 pages of 8: not enough for a 100-token prompt beside 60 in flight
    eng.chunk_width = 32
    sched = ContinuousBatchingScheduler(eng, prefix_cache=False)
    first = Request(rid=0, prompt=_ids(21, 1, 60)[0].tolist(), max_new_tokens=30)
    sched.submit(first)
    sched.step()
    late = Request(rid=1, prompt=_ids(22, 1, 100)[0].tolist(), max_new_tokens=4)
    sched.submit(late)
    sched.step()   # admitted; its first chunk
    assert late.chunks == 1 and late.cursor == 32
    held = list(late.pages)
    victim = sched._preempt_one()
    assert victim is not None and eng.pool.used() == len(first.pages if victim else [])
    assert all(eng.pool.refcount(p) == 0 for p in held) and not any(eng.pool.is_indexed(p) for p in held)
    _drain(sched)
    assert sched.preempted_total >= 1 and eng.pool.used() == 0
    for req in (first, late):
        seq = req.prompt[:req.prompt_len] + req.prompt[req.prompt_len:] + list(req.generated)
        want = _want(ref, vals, seq)
        got = seq[req.prompt_len:]
        assert got == [int(want[t - 1].argmax()) for t in range(req.prompt_len, len(seq))]


def test_share_and_groups_are_validated():
    with pytest.raises(ValueError, match="outside the 16 routed experts"):
        dm.DeepseekV32ForCausalLM(**dict(CFG, experts_held=[14, 4]))
    with pytest.raises(ValueError, match="groups"):
        dm.DeepseekV32ForCausalLM(**dict(CFG, n_group=5))
