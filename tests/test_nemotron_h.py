"""The hybrid decoder (Mamba-2 + attention + LatentMoE) against its plain
reference, at a small size on seeded weights: the model's forward, the
serving engine's two caches, the scheduler's slots, the experts' share, and
the grouped matmul. The reference is the benchmark's own file
(`chipbench/reference/nemotron3-super-120b-ep4-l11.py`), imported by path.
"""
import functools
import importlib.util
import os

import numpy as np
import pytest

import jax
from jax import numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.engine import InferenceEngine
from paddle_tpu.inference.kv_cache import BlockPool, PoolExhausted, StateSpec, export_pages
from paddle_tpu.inference.scheduler import ContinuousBatchingScheduler, Request, SpecDecodeConfig
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.ops import pallas as pk
from paddle_tpu.profiler import utils as spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dict(
    vocab_size=256, hidden_size=64, hybrid_override_pattern="MEM*E", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
    n_groups=2, conv_kernel=4, n_routed_experts=16, experts_held=[4, 8], num_experts_per_tok=4,
    moe_latent_size=32, moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
    routed_scaling_factor=5.0, layer_norm_epsilon=1e-5, initializer_range=0.02)
TOL = dict(rtol=2e-4, atol=2e-5)


@pytest.fixture(scope="module")
def ref():
    import sys

    sys.path.insert(0, ROOT)  # the reference imports chipbench.weights
    path = os.path.join(ROOT, "chipbench", "reference", "nemotron3-super-120b-ep4-l11.py")
    spec = importlib.util.spec_from_file_location("nemotron_h_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _seeded(ref, cfg, seed=5):
    """(model, its leaves as float32 arrays) on the benchmark's seeded weights."""
    from chipbench import weights

    model = nh.NemotronHForCausalLM(**cfg)
    model.eval()
    vals = weights.make(ref.leaf_specs(cfg), seed, jnp.float32)
    state = model.state_dict()
    assert set(state) == set(vals)
    for name, t in state.items():
        assert tuple(t.shape) == tuple(vals[name].shape), name
        t._value = vals[name]
    return model, vals


@pytest.fixture(scope="module")
def seeded(ref):
    return _seeded(ref, CFG)


@pytest.fixture(scope="module")
def engine(seeded):
    return InferenceEngine(seeded[0], max_seq_len=64, block_size=8, max_batch=4,
                           prefill_buckets=(16, 32), decode_batch_buckets=(1, 2, 4))


def _ids(seed, n, length):
    return np.random.RandomState(seed).randint(1, CFG["vocab_size"], (n, length)).astype(np.int32)


def _drain(sched, limit=400):
    for _ in range(limit):
        if sched.idle():
            return
        sched.step()
    raise AssertionError("the scheduler did not drain")


# (a) the model's full forward
def test_full_forward_matches_the_reference(ref, seeded):
    model, vals = seeded
    ids = _ids(0, 2, 24)
    with paddle.no_grad():
        got = model(paddle.to_tensor(ids)).numpy()
    np.testing.assert_allclose(got, np.asarray(ref.forward(vals, ids, CFG)), **TOL)


# (b) bucketed prefill then decode through the engine, kernels in interpret mode too
@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "interpret"])
def test_prefill_then_decode_matches_the_reference(ref, seeded, interpret, monkeypatch):
    model, vals = seeded
    monkeypatch.setattr(pk, "_INTERPRET", interpret)
    eng = InferenceEngine(model, max_seq_len=64, block_size=8, max_batch=4,
                          prefill_buckets=(16, 32), decode_batch_buckets=(1, 2, 4))
    seq = _ids(1, 1, 30)[0].tolist()
    want = np.asarray(ref.forward(vals, np.asarray([seq]), CFG))[0]
    spans.clear()
    pages = eng.pool.alloc(eng.pool.blocks_for_tokens(len(seq)))
    logits = eng.prefill(seq[:11], pages)  # true_len 11 in the bucket of 16
    np.testing.assert_allclose(logits, want[10], **TOL)
    assert eng.pool.state_slots_used() == 1
    for t in range(11, len(seq)):
        logits = eng.decode([seq[t]], [t], [t + 1], [pages])[0]
        np.testing.assert_allclose(logits, want[t], **TOL)
    calls = [r for r in spans.records() if r[0] in ("engine.prefill", "engine.decode")]
    assert all(r[6]["state_slots"] == 1 and r[6]["moe_layers"] == 2 for r in calls)
    # 8 of 16 experts held, 4 choices a token: every span counts what it computed
    assert all(0 <= r[6]["moe_assignments"] <= 2 * 4 * r[6].get("tokens", 1) for r in calls)
    assert all(r[6]["moe_experts_touched"] <= 2 * 8 for r in calls)
    eng.pool.free(pages)
    assert eng.pool.state_slots_used() == 0 and eng.pool.used() == 0


# (e) padded prefill leaves the state an unpadded one leaves
def test_padded_prefill_leaves_the_unpadded_state(seeded):
    model, _ = seeded
    prompt = _ids(2, 1, 16)[0].tolist()
    states = []
    for buckets in ((16,), (32,)):  # true_len 16: the bucket exactly, and half of one
        eng = InferenceEngine(model, max_seq_len=64, block_size=8, max_batch=2, prefill_buckets=buckets)
        pages = eng.pool.alloc(3)
        logits = eng.prefill(prompt, pages)
        slot = eng.pool.state_slot(pages[0])
        states.append((logits, [np.asarray(a[slot]) for a in eng.pool.ssm + eng.pool.conv]))
    np.testing.assert_allclose(states[0][0], states[1][0], **TOL)
    for a, b in zip(states[0][1], states[1][1]):
        assert np.abs(a).max() > 0
        np.testing.assert_allclose(a, b, **TOL)


# (c) a prompt entering in chunks beside rows in flight
C = 128


@pytest.fixture(scope="module")
def chunk_engine(seeded):
    """Contexts long enough for the engine's full chunk width and two chunks of one prompt."""
    eng = InferenceEngine(seeded[0], max_seq_len=256, block_size=8, max_batch=4,
                          prefill_buckets=(16,), decode_batch_buckets=(1, 2, 4))
    assert eng.chunk_width == C
    return eng


def _recording(engine, seen):
    """Wrap the engine's two step calls: every logit row they return, by the
    sequence's first page and the token's position."""
    decode, with_chunk = engine.decode, engine.decode_with_chunk

    def note(tokens, positions, page_rows, logits):
        for i, row in enumerate(page_rows):
            seen.setdefault(row[0], {})[positions[i]] = (tokens[i], logits[i])

    def rec_decode(tokens, positions, seq_lens, page_rows):
        out = decode(tokens=tokens, positions=positions, seq_lens=seq_lens, page_rows=page_rows)
        note(tokens, positions, page_rows, out)
        return out

    def rec_chunk(tokens, positions, seq_lens, page_rows, chunk_ids, chunk_start, chunk_pages):
        out, last = with_chunk(tokens, positions, seq_lens, page_rows, chunk_ids, chunk_start, chunk_pages)
        note(tokens, positions, page_rows, out)
        note([chunk_ids[-1]], [chunk_start + len(chunk_ids) - 1], [chunk_pages], [last])
        return out, last

    engine.decode, engine.decode_with_chunk = rec_decode, rec_chunk
    return decode, with_chunk


@pytest.mark.parametrize("beside", [1, 3], ids=["one_row", "full_bucket"])
def test_chunked_prompt_beside_rows_in_flight_matches_the_reference(ref, seeded, chunk_engine, beside):
    model, vals = seeded
    engine = chunk_engine
    engine.pool.reset()
    sched = ContinuousBatchingScheduler(engine)  # prefix_cache defaults to True
    seen = {}
    restore = _recording(engine, seen)
    try:
        rows = [Request(rid=10 + i, prompt=_ids(30 + i, 1, 5 + i)[0].tolist(), max_new_tokens=12 + 2 * i)
                for i in range(beside)]
        for r in rows:
            sched.submit(r)
        while any(r.cursor < len(r.prompt) for r in rows):
            sched.step()
        late = Request(rid=1, prompt=_ids(4, 1, C + 21)[0].tolist(), max_new_tokens=6)  # two chunks
        sched.submit(late)
        sched.step()
        assert late in sched.running and late.cursor == C and late.slot[1] == "chunked"
        assert engine.pool.state_slots_used() == beside + 1
        keys = {r.rid: r.pages[0] for r in rows + [late]}
        _drain(sched)
    finally:
        engine.decode, engine.decode_with_chunk = restore
    assert late.chunks == 2 and all(r.outcome == "completed" for r in rows + [late])
    for r in rows + [late]:
        seq = r.prompt + r.generated
        want = np.asarray(ref.forward(vals, np.asarray([seq]), CFG))[0]
        steps = seen[keys[r.rid]]
        if r is late:  # each chunk's last token, then every token through a decode row
            assert sorted(steps) == [C - 1] + list(range(len(r.prompt) - 1, len(seq) - 1))
        for pos, (tok, logits) in steps.items():
            assert int(tok) == seq[pos]  # the host's token, or the step before's choice, read here
            np.testing.assert_allclose(logits, want[pos], **TOL)
    assert engine.pool.used() == 0 and engine.pool.state_slots_used() == 0


def test_a_preemption_between_two_chunks_recomputes_from_the_zero_state(seeded, chunk_engine):
    engine = chunk_engine

    def serve(preempt):
        engine.pool.reset()
        sched = ContinuousBatchingScheduler(engine)
        row = Request(rid=0, prompt=_ids(41, 1, 6)[0].tolist(), max_new_tokens=40)
        sched.submit(row)
        sched.step()
        req = Request(rid=1, prompt=_ids(40, 1, C + 30)[0].tolist(), max_new_tokens=5)
        sched.submit(req)
        sched.step()
        assert req.cursor == C and req.chunks == 1
        if preempt:
            slot = engine.pool.state_slot(req.pages[0])
            # the first chunk left its state in the slot
            assert all(np.abs(np.asarray(a[slot])).max() > 0 for a in engine.pool.ssm)
            assert sched._preempt_one()  # the one still in its prompt goes first
            assert req.cursor == 0 and req.pages == [] and engine.pool.state_slots_used() == 1
            # admitted again: a first page, a slot on its first chunk, from position 0; the
            # preemption left nothing in flight, so the call dispatches that step and the next
            sched.step()
            assert (req.cursor, req.chunks) == (C + 30, 3) and engine.pool.state_slots_used() == 2
        _drain(sched)
        assert (req.preemptions, req.chunks) == ((1, 3) if preempt else (0, 2))
        assert engine.pool.used() == 0 and engine.pool.state_slots_used() == 0
        return req.prompt[req.prompt_len:] + req.generated, row.generated

    assert serve(preempt=True) == serve(preempt=False)


# (c') the chunk's recurrence in block form against the token-at-a-time scan
def _mixer_leaves(seed=0):
    heads, head_dim, groups, state, k, hidden = (CFG[n] for n in (
        "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel", "hidden_size"))
    inner = heads * head_dim
    conv_dim = inner + 2 * groups * state
    rng = np.random.RandomState(seed)
    w = [rng.randn(hidden, inner + conv_dim + heads) * 0.1, rng.randn(k, conv_dim) * 0.5,
         rng.randn(conv_dim) * 0.02, rng.randn(heads) * 2.0, rng.randn(heads), np.ones(heads),
         np.ones(inner), rng.randn(inner, hidden) * 0.1]
    dims = dict(heads=heads, head_dim=head_dim, groups=groups, state=state, eps=1e-5)
    return [jnp.asarray(v, jnp.float32) for v in w], dims, (heads, head_dim, state, k - 1, conv_dim)


def _block_mix(x, w, dims, **kw):
    """The mixer with the recurrence in block form, from the pieces the cache branch calls."""
    inner = dims["heads"] * dims["head_dim"]
    parts = nh.mamba2_in_proj(x, w[0], inner, inner + 2 * dims["groups"] * dims["state"])
    y, h, tail = nh.mamba2_core(*parts, *w[1:7], **dims, recurrence=nh.ssm_block, **kw)
    return jnp.dot(y, w[7]), h, tail


@pytest.mark.parametrize("start", ["zero_state", "live_state"])
@pytest.mark.parametrize("length", [1, 5, C - 1, C])
def test_block_form_matches_the_sequential_scan(length, start):
    w, dims, (heads, head_dim, state, rows, conv_dim) = _mixer_leaves()
    rng = np.random.RandomState(length)
    x = jnp.asarray(rng.randn(1, C, CFG["hidden_size"]), jnp.float32)
    kw = dict(valid_len=jnp.asarray([length]))  # the chunk's tail past `length` is padding
    if start == "live_state":
        kw.update(h0=jnp.asarray(rng.randn(1, heads, head_dim, state), jnp.float32),
                  conv0=jnp.asarray(rng.randn(1, rows, conv_dim), jnp.float32))
    want = nh.mamba2_mix(x, *w, **dims, **kw)
    got = _block_mix(x, w, dims, **kw)
    np.testing.assert_allclose(got[0][:, :length], want[0][:, :length], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[2], want[2])
    assert np.abs(np.asarray(want[1])).max() > 0


def test_two_blocks_in_a_row_match_one_scan_over_both():
    w, dims, _ = _mixer_leaves(1)
    x = jnp.asarray(np.random.RandomState(7).randn(1, C + 37, CFG["hidden_size"]), jnp.float32)
    want = nh.mamba2_mix(x, *w, **dims)
    _, h, tail = first = _block_mix(x[:, :C], w, dims)
    second = _block_mix(x[:, C:], w, dims, h0=h, conv0=tail)
    np.testing.assert_allclose(jnp.concatenate([first[0], second[0]], 1), want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(second[1], want[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(second[2], want[2], rtol=1e-5, atol=1e-5)  # projected in another shape


def test_segment_sums_are_masked_then_accumulated():
    a = -jnp.asarray(np.random.RandomState(0).rand(3, 6) * 50.0, jnp.float32)
    s = np.asarray(nh._segsum(a))
    for i in range(6):
        for j in range(6):
            if j > i:
                assert np.all(s[:, i, j] == -np.inf)
            else:
                np.testing.assert_allclose(s[:, i, j], np.asarray(a)[:, j + 1:i + 1].sum(-1), rtol=1e-6)
    assert np.all(s <= 0)


# (d) the share: four shares' routed parts and the shared expert once make the uncut layer
def test_four_shares_add_up_to_the_uncut_layer(ref):
    from chipbench import weights

    whole = dict(CFG, hybrid_override_pattern="E", experts_held=[0, 16])
    w = weights.make(ref.layer_specs(whole, 0), 3, jnp.float32)
    w = {k.split("mixer.")[1]: v for k, v in w.items() if ".mixer." in k}
    x = jnp.asarray(np.random.RandomState(0).randn(10, 64), jnp.float32)
    kw = dict(top_k=4, scale=5.0)
    leaves = ("gate.weight", "gate.e_score_correction_bias", "fc1_latent_proj.weight",
              "fc2_latent_proj.weight", "experts_up", "experts_down",
              "shared_experts.up_proj.weight", "shared_experts.down_proj.weight")

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def program(first, count):
        cut = dict(w, experts_up=w["experts_up"][first:first + count],
                   experts_down=w["experts_down"][first:first + count])
        return nh.latent_moe(x, *[cut[k] for k in leaves], first=first, **kw)

    with jax.default_matmul_precision("highest"):
        uncut, n_all, touched = program(0, 16)
        shared = nh._relu2(x @ w["shared_experts.up_proj.weight"]) @ w["shared_experts.down_proj.weight"]
        parts = [program(first, 4) for first in (0, 4, 8, 12)]
        total = sum(p[0] - shared for p in parts) + shared
        want = ref.moe(x, w, whole)
        cut_ref = ref.moe(x, dict(w, experts_up=w["experts_up"][4:12], experts_down=w["experts_down"][4:12]),
                          dict(whole, experts_held=[4, 8]))
    assert int(n_all) == 10 * 4 and int(sum(p[1] for p in parts)) == 10 * 4
    assert int(touched) == int(sum(p[2] for p in parts))
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(want), **TOL)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **TOL)
    np.testing.assert_allclose(np.asarray(program(4, 8)[0]), np.asarray(cut_ref), **TOL)


# (f) slots: bound with the request, released with it, the same output after a preemption
@pytest.mark.parametrize("how", ["finish", "expiry", "preemption"])
def test_slots_are_bound_and_released_with_the_request(seeded, engine, how):
    engine.pool.reset()
    now = [0.0]
    sched = ContinuousBatchingScheduler(engine, clock=lambda: now[0])
    prompts = [_ids(10 + i, 1, 7 + i)[0].tolist() for i in range(3)]
    want = engine.generate(prompts, max_new_tokens=8)
    assert engine.pool.state_slots_used() == 0
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=8,
                    deadline_s=5.0 if (how == "expiry" and i == 1) else None)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    for _ in range(4):
        sched.step()
    assert engine.pool.state_slots_used() == len(sched.running) == 3
    assert [r for r in spans.records() if r[0] == "sched.step"][-1][6]["state_slots"] == 3
    if how == "expiry":
        now[0] = 10.0
        sched.step()
        assert reqs[1].outcome == "expired" and engine.pool.state_slots_used() == 2
    elif how == "preemption":
        assert sched._preempt_one()
        assert engine.pool.state_slots_used() == 2
    _drain(sched)
    assert engine.pool.state_slots_used() == 0 and engine.pool.used() == 0
    for i, r in enumerate(reqs):
        if r.outcome == "completed":
            assert r.prompt[r.prompt_len:] + r.generated == want[i], (how, i)
    if how == "preemption":
        assert sched.preempted_total == 1 and all(r.outcome == "completed" for r in reqs)


def test_state_slots_run_out_loudly_and_pages_do_not_migrate():
    pool = BlockPool(8, 8, 1, 2, 16, state_layers=2, state_spec=StateSpec(4, 8, 16, 3, 96), state_slots=2)
    assert [a.shape for a in pool.ssm] == [(3, 4, 8, 16)] * 2 and pool.conv[0].shape == (3, 3, 96)
    assert pool.pool_bytes() == 8 * pool.page_bytes() + 2 * 3 * (4 * 4 * 8 * 16 + 4 * 3 * 96)
    pages = pool.alloc(3)
    assert pool.state_slot(0) == 0 and pool.state_slots_used() == 0  # the trash slot is nobody's
    assert pool.state_slot(pages[0]) == pool.state_slot(pages[0]) != 0
    pool.state_slot(pages[1])
    with pytest.raises(PoolExhausted, match="slots exhausted"):
        pool.state_slot(pages[2])
    with pytest.raises(ValueError, match="no sequence holds"):
        pool.state_slot(7)
    with pytest.raises(ValueError, match="recurrent-layer state"):
        export_pages(pool, pages)
    pool.free(pages[:1])
    assert pool.state_slots_used() == 1 and pool.state_slot(pages[2]) != 0


# (g) no prefix reuse, no speculation, no extend for a model with recurrent layers
def test_scheduler_skips_prefix_reuse_and_refuses_speculation(seeded, engine):
    engine.pool.reset()
    sched = ContinuousBatchingScheduler(engine, prefix_cache=True)
    assert sched.prefix_cache is False
    prompt = _ids(20, 1, 24)[0].tolist()
    outs = []
    for rid in range(2):  # the same prompt twice: the second may reuse nothing
        r = Request(rid=rid, prompt=list(prompt), max_new_tokens=4)
        sched.submit(r)
        _drain(sched)
        outs.append(r.generated)
        assert r.cached_tokens == 0
    assert outs[0] == outs[1] and engine.pool.prefix_index_size() == 0 and engine.pool.retained() == 0
    with pytest.raises(ValueError, match="recurrent-layer state"):
        ContinuousBatchingScheduler(engine, spec_decode=SpecDecodeConfig(draft_len=2))
    with pytest.raises(NotImplementedError, match="recurrent layers"):
        engine.extend([[1, 2]], [[0, 1]], [[1]], 2)
    assert engine.chunk_width > 0  # a chunk goes forward only: no snapshot, no refusal


def test_an_all_attention_model_keeps_its_pool_and_operands():
    """The engine's arrays and operands for a model without layer kinds are
    what they were: K/V pages a layer, no state arrays, no slot operand."""
    from paddle_tpu.models.llama import llama_tiny

    model = llama_tiny(num_key_value_heads=2)
    model.eval()
    eng = InferenceEngine(model, max_seq_len=32, block_size=8, max_batch=2)
    assert eng.layer_kinds == ("attention", "attention") and eng.head_dim == 16
    assert sorted(eng.pool.device_state()) == ["k", "v"] and len(eng.pool.k_pages) == 2
    assert not eng.pool.has_recurrent_state and eng._slot_avals(2) == () and eng._slots_of([[1]], 2) == ()
    assert ContinuousBatchingScheduler(eng).prefix_cache is True


# (h) the grouped matmul in interpret mode against a loop over the experts
@pytest.mark.parametrize("dtype, activation", [(jnp.float32, None), (jnp.float32, "relu2"),
                                               (jnp.bfloat16, "relu2")])
def test_moe_gmm_interpret_matches_a_loop_over_experts(dtype, activation, monkeypatch):
    rng = np.random.RandomState(0)
    groups, k, n, tokens, top = 6, 32, 256, 9, 3
    ids = np.stack([rng.permutation(8)[:top] for _ in range(tokens)]).astype(np.int32)  # 6, 7: absent
    ids[2] = [8, 8, 8]                      # a pad row: no expert at all
    ids[ids == 4] = 7                       # expert 4 is empty
    local = np.where(ids < groups, ids, groups).reshape(-1)
    x = jnp.asarray(rng.randn(tokens * top, k), dtype)
    w = jnp.asarray(rng.randn(groups, k, n) / np.sqrt(k), dtype)
    dest, tile_group, live, sizes = pk.moe_group_layout(local, groups)
    rows = pk.moe_padded_rows(tokens * top, groups)
    assert sizes.tolist() == [int((local == g).sum()) for g in range(groups)] and sizes[4] == 0
    assert int(live[0]) == int(sum(-(-s // pk.MOE_TILE_M) for s in sizes.tolist()))
    assert set(np.asarray(dest)[local == groups].tolist()) <= {rows}
    x_rows = jnp.zeros((rows, k), dtype).at[dest].set(x, mode="drop")
    monkeypatch.setattr(pk, "_INTERPRET", True)
    got = np.asarray(pk.moe_gmm(x_rows, w, tile_group, live, activation=activation), np.float32)
    want = np.asarray(pk.moe_gmm_reference(x_rows, w, tile_group, live, activation=activation), np.float32)
    xf, wf = np.asarray(x, np.float32), np.asarray(w, np.float32)
    tol = dict(rtol=1e-4, atol=1e-5) if dtype == jnp.float32 else dict(rtol=3e-2, atol=3e-2)
    for a, g in enumerate(local):
        if g == groups:
            continue
        loop = xf[a] @ wf[g]
        if activation == "relu2":
            loop = np.square(np.maximum(loop, 0.0))
        np.testing.assert_allclose(got[int(dest[a])], loop, **tol)
        np.testing.assert_allclose(got[int(dest[a])], want[int(dest[a])], **tol)


def test_pattern_and_share_are_validated():
    with pytest.raises(ValueError, match="unknown layer kind"):
        nh.layer_kinds("MXE")
    with pytest.raises(ValueError, match="outside the 16 routed experts"):
        nh.NemotronHForCausalLM(**dict(CFG, experts_held=[12, 8]))
    assert nh.NemotronHForCausalLM(**CFG).config["layer_kinds"] == ["mamba", "moe", "mamba", "attention", "moe"]
