"""The latent-attention decoder (MLA + sandwich norm + gated routed experts)
against its plain reference, at a small size on seeded weights: the model's
forward, the four ways a prompt enters through the serving engine's latent
pool, the two attention paths against each other, the experts' share, the
latent pool's accounting and refusals, and the two kernels. The reference is
the benchmark's own file
(`chipbench/reference/openpangu-ultra-moe-718b-ep16-l5.py`), imported by path.

Tolerances. Program and reference both compute in float32 here (the leaves
are float32), so what separates them is the order of sums: `TOL` is a few
float32 roundings of logits of size 1. A planted fault (the rotary term
dropped, the two post-norms left out) moves logits by 1e-2 and more, four
hundred times `TOL`.
"""
import functools
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
from jax import numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.engine import InferenceEngine
from paddle_tpu.inference.kv_cache import BlockPool, PagedCacheView, export_pages, import_pages
from paddle_tpu.inference.scheduler import ContinuousBatchingScheduler, Request
from paddle_tpu.models import expert_share
from paddle_tpu.models import mla_moe
from paddle_tpu.models import pangu_ultra_moe as pm
from paddle_tpu.ops import pallas as pk
from paddle_tpu.profiler import utils as spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the tiny preset: 1 dense + 2 sparse layers, 16 experts, 4 held
CFG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=160, moe_intermediate_size=48, n_routed_experts=16, experts_held=[4, 4],
    num_experts_per_tok=4, n_shared_experts=1, routed_scaling_factor=2.5, rms_norm_eps=1e-5,
    rope_theta=25600000.0, initializer_range=0.02)
TOL = dict(rtol=2e-4, atol=2e-5)


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, ROOT)  # the reference imports chipbench.weights
    path = os.path.join(ROOT, "chipbench", "reference", "openpangu-ultra-moe-718b-ep16-l5.py")
    spec = importlib.util.spec_from_file_location("pangu_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def seeded(ref):
    """(model, its leaves as float32 arrays) on the benchmark's seeded weights."""
    from chipbench import weights

    model = pm.PanguUltraMoEForCausalLM(**CFG)
    model.eval()
    vals = weights.make(ref.leaf_specs(CFG), 5, jnp.float32)
    state = model.state_dict()
    assert set(state) == set(vals)
    for name, t in state.items():
        assert tuple(t.shape) == tuple(vals[name].shape), name
        t._value = vals[name]
    return model, vals


def _engine(model, **kw):
    return InferenceEngine(model, max_seq_len=64, block_size=8, max_batch=4,
                           prefill_buckets=(16, 32, 64), decode_batch_buckets=(1, 2, 4), **kw)


@pytest.fixture(scope="module")
def engine(seeded):
    return _engine(seeded[0])


def _ids(seed, n, length):
    return np.random.RandomState(seed).randint(1, CFG["vocab_size"], (n, length)).astype(np.int32)


def _want(ref, vals, seq, **faults):
    return np.asarray(ref.forward(vals, np.asarray([seq]), CFG, **faults))[0]


def _drain(sched, limit=400):
    for _ in range(limit):
        if sched.idle():
            return
        sched.step()
    raise AssertionError("the scheduler did not drain")


# (a) the model's full forward (the expanded path, no cache)
def test_full_forward_matches_the_reference(ref, seeded):
    model, vals = seeded
    ids = _ids(0, 2, 24)
    with paddle.no_grad():
        got = model(paddle.to_tensor(ids)).numpy()
    np.testing.assert_allclose(got, np.asarray(ref.forward(vals, ids, CFG)), **TOL)
    assert model.config["layer_kinds"] == ["attention", "attention+moe", "attention+moe"]
    assert model.config["cache_entry"] == {"layout": "latent", "width": 40, "value_width": 32}


# (b) bucketed prefill (expanded), then decode through the latent cache (absorbed)
@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "interpret"])
def test_prefill_then_decode_matches_the_reference(ref, seeded, interpret, monkeypatch):
    model, vals = seeded
    monkeypatch.setattr(pk, "_INTERPRET", interpret)
    eng = _engine(model)
    assert eng.cache_layout == "latent" and eng.pool.page_shape == (eng.pool.num_blocks, 8, 128)
    assert (eng.num_kv_layers, eng._has_moe, eng.chunk_width) == (3, True, 64)
    seq = _ids(1, 1, 26)[0].tolist()
    want = _want(ref, vals, seq)
    spans.clear()
    pages = eng.pool.alloc(eng.pool.blocks_for_tokens(len(seq)))
    np.testing.assert_allclose(eng.prefill(seq[:11], pages), want[10], **TOL)  # true_len 11 in the bucket of 16
    for t in range(11, len(seq)):
        np.testing.assert_allclose(eng.decode([seq[t]], [t], [t + 1], [pages])[0], want[t], **TOL)
    dec = [r[6] for r in spans.records() if r[0] == "engine.decode"]
    assert all(d["moe_layers"] == 2 and 0 <= d["moe_assignments"] <= 2 * 4 for d in dec)
    eng.pool.free(pages)
    assert eng.pool.used() == 0


# (c) a prompt that enters in chunks beside a decode row in flight, through the scheduler
@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "interpret"])
def test_a_prompt_in_chunks_beside_decode_rows_matches_the_reference(ref, seeded, interpret, monkeypatch):
    model, vals = seeded
    monkeypatch.setattr(pk, "_INTERPRET", interpret)
    eng = InferenceEngine(model, max_seq_len=64, block_size=8, max_batch=4, prefill_buckets=(16, 32, 64))
    monkeypatch.setattr(eng, "chunk_width", 16)  # two chunks for a prompt of 21
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
    first = Request(rid=0, prompt=_ids(3, 1, 9)[0].tolist(), max_new_tokens=12)
    sched.submit(first)
    sched.step()  # bucketed: nothing was in flight
    late = Request(rid=1, prompt=_ids(4, 1, 21)[0].tolist(), max_new_tokens=5)
    sched.submit(late)
    spans.clear()
    sched.step()
    (dec,) = [r[6] for r in spans.records() if r[0] == "engine.decode"]
    assert (dec["chunk_tokens"], dec["chunk_context"], dec["rows"]) == (16, 0, 1)
    assert dec["moe_layers"] == 2 and dec["moe_assignments"] <= 17 * 4 * 2
    keys = {first.pages[0]: first, late.pages[0]: late}
    _drain(sched)
    assert [(s, n) for s, n, _ in chunks] == [(0, 16), (16, 5)]
    # the chunk's last token gives the first answer's logits
    np.testing.assert_allclose(chunks[1][2], _want(ref, vals, late.prompt + late.generated)[20], **TOL)
    # every row's logits, whichever program computed them (beside a chunk or alone)
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
    seqs = [_ids(7, 1, 20)[0].tolist(), _ids(8, 1, 17)[0].tolist()]
    pages = [eng.pool.alloc(3), eng.pool.alloc(3)]
    for seq, pg in zip(seqs, pages):
        eng.prefill(seq[:10], pg)
    got = eng.extend([seqs[0][10:14], seqs[1][10:13]], [list(range(10, 14)), list(range(10, 13))], pages, 4)
    np.testing.assert_allclose(got[0], _want(ref, vals, seqs[0])[10:14], **TOL)
    np.testing.assert_allclose(got[1, :3], _want(ref, vals, seqs[1])[10:13], **TOL)
    for pg in pages:
        eng.pool.free(pg)


# (e) the two attention paths hold each other: absorbed over the cache against expanded
@pytest.mark.parametrize("group", [16, 2], ids=["heads_whole", "heads_in_groups"])
def test_absorbed_attention_equals_expanded(ref, seeded, group, monkeypatch):
    """One attention layer, one sequence of 24 tokens: the expanded path
    (keys and values a head, plain causal attention; with `_HEAD_GROUP` 2 its
    4 heads go through in two groups) against the absorbed path reading a
    latent pool it has just written (positions given, so every query goes
    through `mla_paged_attention`). Same mathematics, other order of sums:
    float32 roundings."""
    model, _ = seeded
    monkeypatch.setattr(mla_moe, "_HEAD_GROUP", group)
    attn = model.model.layers[1].self_attn
    x = paddle.to_tensor(np.random.RandomState(2).randn(1, 24, 64).astype(np.float32))
    with paddle.no_grad():
        expanded = attn(x).numpy()
        pool = BlockPool(8, 8, 3, 1, 40, layout="latent")
        view = pool.view(np.asarray([[1, 2, 3, 0]], np.int32), np.asarray([24], np.int32))
        absorbed = attn(x, cache=view, positions=np.arange(24, dtype=np.int32)[None]).numpy()
    np.testing.assert_allclose(absorbed, expanded, **TOL)
    written = np.asarray(view.k_pages[1])
    assert written.shape == (8, 8, 128) and not written[1:4, :, 40:].any() and written[1:4, :, :40].all()
    assert not np.asarray(view.k_pages[0]).any()  # the other layers' arrays untouched


# (f) the share: 16 / 4 = 4 shares' routed parts and the shared expert once make the uncut layer
def test_four_shares_add_up_to_the_uncut_layer(ref):
    from chipbench import weights

    whole = dict(CFG, experts_held=[0, 16])
    w = weights.make(ref.layer_specs(whole, 1), 3, jnp.float32)
    w = {k.split("mlp.")[1]: v for k, v in w.items() if ".mlp." in k}
    x = jnp.asarray(np.random.RandomState(0).randn(10, 64), jnp.float32)
    shared_leaves = [w[f"shared_experts.{n}_proj.weight"] for n in ("gate", "up", "down")]

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def program(first, count):
        held = [w[k][first:first + count] for k in ("experts_gate", "experts_up", "experts_down")]
        return pm.sparse_mlp(x, w["router"], *held, *shared_leaves, top_k=4, scale=2.5, first=first)

    with jax.default_matmul_precision("highest"):
        uncut, n_all, touched = program(0, 16)
        shared = pm.gated_mlp(x, *shared_leaves)
        parts = [program(first, 4) for first in (0, 4, 8, 12)]
        total = sum(p[0] - shared for p in parts) + shared
        want = ref.sparse_ffn(x, w, whole)
        held = {k: w[k][4:8] for k in ("experts_gate", "experts_up", "experts_down")}
        cut_ref = ref.sparse_ffn(x, dict(w, **held), dict(whole, experts_held=[4, 4]))
    assert int(n_all) == 10 * 4 and int(sum(p[1] for p in parts)) == 10 * 4
    assert int(touched) == int(sum(p[2] for p in parts))
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(want), **TOL)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **TOL)
    np.testing.assert_allclose(np.asarray(program(4, 4)[0]), np.asarray(cut_ref), **TOL)


# (g) planted faults: the comparison is tight enough to see a missing term
@pytest.mark.parametrize("fault", [{"rotary": False}, {"post_norms": False}], ids=["no_rotary_term", "no_post_norms"])
def test_a_missing_term_fails_the_comparison(ref, seeded, fault):
    model, vals = seeded
    ids = _ids(0, 1, 24)
    with paddle.no_grad():
        got = model(paddle.to_tensor(ids)).numpy()
    faulty = np.asarray(ref.forward(vals, ids, CFG, **fault))
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, faulty, **TOL)
    assert np.abs(got - faulty).max() > 100 * TOL["atol"]


# (h) the latent pool: geometry, accounting, what it refuses
def test_latent_pool_geometry_and_bytes():
    pool = BlockPool(9, 16, 5, 1, 576, dtype=jnp.bfloat16, layout="latent")
    assert pool.latent and pool.page_shape == (9, 16, 640) and pool.arrays_per_layer == 1
    assert [a.shape for a in pool.k_pages] == [(9, 16, 640)] * 5 and pool.v_pages == []
    assert pool.page_bytes() == 5 * 16 * 640 * 2 and pool.pool_bytes() == 9 * pool.page_bytes()
    state = pool.device_state()
    assert len(state["k"]) == 5 and state["v"] == []
    pool.adopt_state(state)
    with pytest.raises(ValueError, match="layer count"):
        pool.adopt(state["k"], state["k"])
    kv = BlockPool(9, 16, 5, 8, 128, dtype=jnp.bfloat16)
    assert not kv.latent and kv.page_shape == (9, 8, 16, 128) and kv.arrays_per_layer == 2


@pytest.mark.parametrize("what", ["int8", "kv_heads", "layout", "export", "import"])
def test_latent_pool_refuses_with_a_message(what):
    if what == "int8":
        with pytest.raises(ValueError, match="latent pool cannot store int8"):
            BlockPool(9, 16, 1, 1, 576, kv_dtype="int8", layout="latent")
    elif what == "kv_heads":
        with pytest.raises(ValueError, match="one vector a token"):
            BlockPool(9, 16, 1, 8, 576, layout="latent")
    elif what == "layout":
        with pytest.raises(ValueError, match="unsupported page layout"):
            BlockPool(9, 16, 1, 1, 576, layout="rows")
    else:
        pool = BlockPool(9, 16, 1, 1, 576, layout="latent")
        pages = pool.alloc(2)
        with pytest.raises(ValueError, match="latent pages"):
            export_pages(pool, pages) if what == "export" else import_pages(pool, pages, {"kv_dtype": None})


def test_pages_return_after_a_drain_and_a_shared_prefix_is_reused(ref, seeded, engine):
    """Generation through the scheduler over the latent pool: the prefix index
    is page-granular and the same, so a second request whose prompt extends
    the first's finds its full pages; every page goes back after the drain,
    and the ids are the greedy ids of the reference's logits."""
    model, vals = seeded
    engine.pool.reset()
    sched = ContinuousBatchingScheduler(engine)
    base = _ids(9, 1, 19)[0].tolist()
    a = Request(rid=0, prompt=base, max_new_tokens=6)
    sched.submit(a)
    _drain(sched)
    b = Request(rid=1, prompt=base + _ids(10, 1, 5)[0].tolist(), max_new_tokens=6)
    sched.submit(b)
    _drain(sched)
    assert b.cached_tokens == 16  # the two full pages of the shared prefix
    for req in (a, b):
        seq = req.prompt + req.generated
        want = _want(ref, vals, seq)
        assert req.generated == [int(want[t - 1].argmax()) for t in range(len(req.prompt), len(seq))]
    assert engine.pool.used() == 0 and engine.pool.retained() > 0


def test_a_private_copy_of_a_latent_page_copies_its_one_array():
    pool = BlockPool(6, 4, 2, 1, 8, layout="latent")
    (page,) = pool.alloc(1)
    pool.k_pages = [a.at[page].set(i + 1.0) for i, a in enumerate(pool.k_pages)]
    pool.share([page])
    new = pool.make_private(page)
    assert new != page and all(float(a[new].min()) == i + 1.0 for i, a in enumerate(pool.k_pages))


# (i) the latent write, against a plain loop
@pytest.mark.parametrize("how", ["rows", "prefill", "chunk"])
def test_latent_write_lands_where_a_loop_puts_it(how):
    n, bs, width, m = 12, 4, 6, 4
    rng = np.random.RandomState(0)
    pool0 = rng.randn(n, bs, 128).astype(np.float32)
    want = pool0.copy()
    if how == "rows":
        tables = np.array([[3, 7, 0, 0], [5, 0, 0, 0], [0, 0, 0, 0]], np.int32)
        positions = np.array([[6], [2], [0]], np.int32)
        new = rng.randn(3, 1, width).astype(np.float32)
        view = PagedCacheView([jnp.asarray(pool0)], [], tables, np.ones(3, np.int32), bs)
        view.write(0, jnp.asarray(new), positions=positions)
        for b in range(2):
            p = int(positions[b, 0])
            want[tables[b, p // bs], p % bs] = np.pad(new[b, 0], (0, 128 - width))
        skip = [0]
    elif how == "prefill":
        tables = np.array([[9, 2, 11, 0]], np.int32)
        new = rng.randn(1, 10, width).astype(np.float32)
        view = PagedCacheView([jnp.asarray(pool0)], [], tables, np.asarray([10], np.int32), bs)
        view.write(0, jnp.asarray(new))
        padded = np.pad(new[0], ((0, 2), (0, 128 - width)))
        for j, page in enumerate((9, 2, 11)):
            want[page] = padded[j * bs:(j + 1) * bs]
        skip = []
    else:
        table = np.array([[4, 6, 8, 10]], np.int32)
        new = rng.randn(1, 8, width).astype(np.float32)  # 6 real tokens from position 4, two pad slots
        view = PagedCacheView([jnp.asarray(pool0)], [], np.zeros((2, m), np.int32), np.ones(2, np.int32), bs,
                              chunk_table=table)
        view.write_chunk(0, jnp.asarray(new), first_position=jnp.asarray(4))
        padded = np.pad(new[0], ((0, 0), (0, 128 - width)))
        want[6], want[8] = padded[:4], padded[4:]
        skip = []
    got = np.asarray(view.k_pages[0])
    keep = [p for p in range(n) if p not in skip]
    np.testing.assert_array_equal(got[keep], want[keep])
    assert view.latent and view.v_pages == []


# (j) the latent paged kernel in interpret mode against its jnp oracle
@pytest.mark.parametrize("heads, q_len, counts, firsts", [
    (8, 1, [1, 1, 1], [0, 37, 300]),            # decode rows, a pad row at position 0
    (8, 40, [40, 17], [128, 0]),                # a chunk: several query tiles, the second row's count short
    (4, 4, [4, 2, 1], [10, 255, 0]),            # extend: pad slots behind the count
    (128, 3, [3], [200]),                       # the published head count: a tile is whole queries
], ids=["decode", "chunk", "extend", "heads128"])
def test_mla_paged_kernel_interpret_matches_the_oracle(heads, q_len, counts, firsts, monkeypatch):
    monkeypatch.setattr(pk, "_MLA_TILE_ROWS", 128)  # several query tiles and page blocks at a small size
    monkeypatch.setattr(pk, "_MLA_POSITIONS", 64)
    rng = np.random.RandomState(1)
    n, bs, entry, vw, m = 48, 8, 40, 32, 40
    pages = np.zeros((n, bs, 128), np.float32)
    pages[..., :entry] = rng.randn(n, bs, entry)
    b = len(counts)
    tables = np.stack([rng.permutation(np.arange(1, n))[:m] for _ in range(b)]).astype(np.int32)
    tables[:, -1] = 0  # the table's padding: the reserved page
    q = rng.randn(b, q_len, heads, entry).astype(np.float32)
    pos = np.zeros((b, q_len), np.int32)
    for i, (c, f) in enumerate(zip(counts, firsts)):
        pos[i, :c] = f + np.arange(c)
    want = pk.mla_paged_reference(jnp.asarray(q), jnp.asarray(pages), tables, pos, vw, 0.11)
    monkeypatch.setattr(pk, "_INTERPRET", True)
    got = pk.mla_paged_attention(jnp.asarray(q), jnp.asarray(pages), tables, pos, vw, 0.11)
    assert got.shape == (b, q_len, heads, vw)
    for i, c in enumerate(counts):  # pad slots hold nothing anyone reads
        np.testing.assert_allclose(np.asarray(got)[i, :c], np.asarray(want)[i, :c], rtol=2e-5, atol=2e-6)


def test_mla_live_blocks_stop_at_each_query_tiles_frontier(monkeypatch):
    """At 1024 query rows and 128 positions a grid step (the kernel's own
    sizes are larger: `pk._MLA_TILE_ROWS`, `pk._MLA_POSITIONS`), 128 heads: a
    tile is 8 queries. A chunk of 128 queries from position
    1024 in pages of 16 (blocks of 128 positions, a table of 544 pages = 68
    blocks): tile t ends at position 1024 + 8 t + 7, so tiles 0-15 read 9
    blocks each. A row of 20 real queries from 0 has 3 tiles, the rest
    padding at position 0: one block each."""
    monkeypatch.setattr(pk, "_MLA_TILE_ROWS", 1024)
    monkeypatch.setattr(pk, "_MLA_POSITIONS", 128)
    assert pk.mla_query_tile(128, 128) == 8 and pk.mla_query_tile(128, 1) == 1 and pk.mla_query_tile(4, 1) == 4
    live = pk.mla_live_blocks(np.array([1024, 0]), np.array([128, 20]), 128, 128, 16, 544)
    assert live.shape == (2, 16) and live[0].tolist() == [9] * 16 and live[1].tolist() == [1] * 16
    late = pk.mla_live_blocks(np.array([1000]), np.array([128]), 128, 128, 16, 544)
    assert late[0].tolist() == [8] * 3 + [9] * 13  # tile 2 ends at position 1023, the block's last
    one = pk.mla_live_blocks(np.array([4000, 0]), np.array([1, 1]), 1, 128, 16, 544)
    assert one.tolist() == [[32], [1]]
    monkeypatch.undo()  # the kernel's own sizes: 16 queries a tile, 512 positions a block, 17 blocks a table
    assert pk.mla_query_tile(128, 128) == 16 and pk.mla_page_blocks(16, 544) == (32, 17)
    own = pk.mla_live_blocks(np.array([1024]), np.array([128]), 128, 128, 16, 544)
    assert own.tolist() == [[3] * 8]


# (k) the gated grouped matmul
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_gated_moe_gmm_interpret_matches_a_loop_over_experts(dtype, monkeypatch):
    rng = np.random.RandomState(0)
    groups, k, n = 5, 32, 256
    ids = jnp.asarray(rng.randint(0, groups + 1, 50), jnp.int32)  # `groups` = not held
    dest, tile_group, live, sizes = pk.moe_group_layout(ids, groups)
    rows = pk.moe_padded_rows(50, groups)
    x = jnp.zeros((rows, k), dtype).at[dest].set(jnp.asarray(rng.randn(50, k), dtype), mode="drop")
    w_gate, w_up = (jnp.asarray(rng.randn(groups, k, n) * 0.2, dtype) for _ in range(2))
    monkeypatch.setattr(pk, "_INTERPRET", True)
    got = np.asarray(pk.moe_gmm(x, w_up, tile_group, live, activation="silu", w_gate=w_gate,
                                out_dtype=jnp.float32))
    oracle = np.asarray(pk.moe_gmm_reference(x, w_up, tile_group, live, "silu", jnp.float32, w_gate=w_gate))
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    for a in range(50):
        g = int(ids[a])
        if g == groups:
            continue
        xa = np.asarray(x[int(dest[a])], np.float32)
        gate = xa @ np.asarray(w_gate[g], np.float32)
        want = gate / (1.0 + np.exp(-gate)) * (xa @ np.asarray(w_up[g], np.float32))
        np.testing.assert_allclose(got[int(dest[a])], want, **tol)
        np.testing.assert_allclose(oracle[int(dest[a])], want, **tol)


def test_moe_gmm_names_the_activations_it_knows_and_checks_the_gate():
    x, w = jnp.zeros((16, 8)), jnp.zeros((2, 8, 128))
    tg, live = jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match=r"unknown activation 'gelu' \(known: \['relu2', 'silu'\] or None\)"):
        pk.moe_gmm(x, w, tg, live, activation="gelu")
    with pytest.raises(ValueError, match="w_gate .* must have w's shape"):
        pk.moe_gmm(x, w, tg, live, activation="silu", w_gate=jnp.zeros((2, 8, 64)))


# (l) a long prefill's expert layer goes through in blocks of tokens: the same numbers
def test_routed_experts_in_token_blocks_equal_the_whole(monkeypatch):
    rng = np.random.RandomState(3)
    t, h, f, count = 32, 16, 24, 4
    x = jnp.asarray(rng.randn(t, h), jnp.float32)
    router = jnp.asarray(rng.randn(h, 16), jnp.float32)
    e_gate, e_up = (jnp.asarray(rng.randn(count, h, f) * 0.3, jnp.float32) for _ in range(2))
    e_down = jnp.asarray(rng.randn(count, f, h) * 0.3, jnp.float32)
    chosen, weights = expert_share.route_topk(x, router, None, 4, 2.5)
    valid = jnp.asarray(rng.rand(t) > 0.2)
    args = (x, chosen, weights, e_up, e_down, 4, valid)
    whole = expert_share.routed_experts(*args, w_gate=e_gate, activation="silu")
    monkeypatch.setattr(expert_share, "MOE_TOKEN_BLOCK", 8)
    blocks = expert_share.routed_experts(*args, w_gate=e_gate, activation="silu")
    np.testing.assert_allclose(np.asarray(blocks[0]), np.asarray(whole[0]), rtol=1e-6, atol=1e-6)
    assert int(blocks[1]) == int(whole[1]) and int(blocks[2]) == int(whole[2])
    assert not np.asarray(whole[0])[~np.asarray(valid)].any()  # padding is computed nowhere


def test_share_and_kinds_are_validated(seeded):
    with pytest.raises(ValueError, match="experts_held"):
        pm.PanguUltraMoEForCausalLM(**dict(CFG, experts_held=[14, 4]))
    model, _ = seeded
    bad = type("M", (), {"config": dict(model.config, layer_kinds=["attention"])})()
    with pytest.raises(ValueError, match="layer kinds"):
        InferenceEngine(bad)
