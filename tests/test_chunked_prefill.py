"""A prompt that enters in chunks beside the decode rows
(`engine.decode_with_chunk`, the scheduler's one chunk a step): the ids it
generates are those of the bucketed prefill and of the one-token-a-step
stream, whatever the prompt's length against the page and the chunk, however
many rows decode beside it, after shared prefix pages, across a preemption
and when the pool runs dry under a chunk.

CPU, the jnp reference path of the paged kernel, a tiny llama. The stream is
the same scheduler over an engine whose `chunk_width` reads 0: no engine
reads so by itself, it is steered here, in the test. (A model with recurrent
layers enters in chunks too: `tests/test_nemotron_h.py`.)
"""
import numpy as np
import pytest

import paddle_tpu as paddle

PAGE = 8
MAX_NEW = 3


@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models.llama import llama_tiny

    paddle.seed(0)
    m = llama_tiny(num_key_value_heads=2)
    m.eval()
    return m


@pytest.fixture(scope="module")
def engine(tiny_model):
    from paddle_tpu.inference.engine import InferenceEngine

    eng = InferenceEngine(tiny_model, max_seq_len=512, block_size=PAGE, max_batch=8)
    assert eng.chunk_width == 128  # 16 whole pages
    return eng


def _scheduler(engine, **kw):
    from paddle_tpu.inference.scheduler import ContinuousBatchingScheduler

    return ContinuousBatchingScheduler(engine, **kw)


def _request(rid, prompt, max_new=MAX_NEW):
    from paddle_tpu.inference.scheduler import Request

    return Request(rid=rid, prompt=list(prompt), max_new_tokens=max_new)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 1024, (n,)).tolist()


def _run(sched, limit=4000):
    for _ in range(limit):
        if sched.idle():
            return
        sched.step()
    raise AssertionError("the scheduler did not drain")


def _rows_in_flight(sched, k):
    """k requests that decode, and keep decoding, beside what comes next."""
    rows = [_request(100 + i, _prompt(5 + i, 1000 + i), max_new=400) for i in range(k)]
    for r in rows:
        sched.submit(r)
    while any(r.cursor < len(r.prompt) for r in rows):
        sched.step()
    assert len(sched.running) == k
    return rows


def _alone(engine, prompt):
    """Nothing in flight: the bucketed prefill."""
    engine.pool.reset()
    sched = _scheduler(engine, prefix_cache=False)
    req = _request(0, prompt)
    sched.submit(req)
    _run(sched)
    assert req.chunks == 0 and engine.pool.used() == 0
    return req.generated


def _beside_rows(engine, prompt, k, monkeypatch=None):
    """`k` rows decode; then the prompt arrives. With `monkeypatch` the
    engine plans no chunk and the prompt streams a token a step."""
    engine.pool.reset()
    if monkeypatch is not None:
        monkeypatch.setattr(engine, "chunk_width", 0)
    sched = _scheduler(engine, prefix_cache=False)
    rows = _rows_in_flight(sched, k)
    req = _request(0, prompt)
    sched.submit(req)
    steps = 0
    while not req.done:
        sched.step()
        steps += 1
    for r in rows:
        assert sched.cancel(r.rid)
    assert sched.idle() and engine.pool.used() == 0  # pool_pages_held_after_drain
    return req, steps


C = 128
LENGTHS = [1, PAGE - 1, PAGE, C - 1, C, C + 1, 3 * C + 5]


@pytest.fixture(scope="module")
def expected(engine):
    """Each length's ids by the bucketed prefill, computed once."""
    return {n: _alone(engine, _prompt(n, n)) for n in LENGTHS}


@pytest.mark.parametrize("rows", [1, 3, 7], ids=["rows1", "rows3", "full_bucket"])
@pytest.mark.parametrize("n", LENGTHS)
def test_chunked_ids_equal_the_bucketed_prefills(engine, expected, n, rows):
    req, steps = _beside_rows(engine, _prompt(n, n), rows)
    assert req.generated == expected[n]
    # one chunk a step, the first token in the step that carries the last; the
    # rows' next step was in flight when the prompt came, so its first chunk
    # rides the step after that one: one call more than the steps it takes
    assert req.chunks == -(-n // C) and steps == req.chunks + MAX_NEW
    assert req.slot[1] == "chunked"


@pytest.mark.parametrize("n", [1, PAGE, C + 1])
def test_streamed_ids_equal_the_chunked(engine, expected, n, monkeypatch):
    req, steps = _beside_rows(engine, _prompt(n, n), 1, monkeypatch)
    assert req.generated == expected[n]
    assert req.chunks == 0 and steps == n + MAX_NEW and req.slot[1] == "streamed"


def test_only_the_oldest_prompt_rides_a_step_and_the_others_hold_no_row(engine):
    from paddle_tpu.profiler import utils as spans

    engine.pool.reset()
    sched = _scheduler(engine, prefix_cache=False)
    _rows_in_flight(sched, 2)
    a, b = _request(0, _prompt(C + 9, 7)), _request(1, _prompt(20, 8))
    sched.submit(a)
    sched.submit(b)
    # two rows decode; `a` joins them once its last chunk is in, and only then `b` rides
    for want_a, want_b, chunk, rows in [(C, 0, C, 2), (C + 9, 0, 9, 2), (C + 9, 20, 20, 3)]:
        spans.clear()
        sched.step()
        assert (a.cursor, b.cursor) == (want_a, want_b)
        (dec,) = [r for r in spans.records() if r[0] == "engine.decode"]
        assert (dec[6]["chunk_tokens"], dec[6]["rows"]) == (chunk, rows)
    # each call dispatched the step above and read the one before it: the last
    # step's tokens (a's second, b's first) are computed and not read yet
    assert (len(a.generated), a.unread) == (1, 1) and (len(b.generated), b.unread) == (0, 1)
    for r in list(sched.running):
        sched.cancel(r.rid)
    assert engine.pool.used() == 0


def test_a_chunk_after_shared_prefix_pages(engine, tiny_model):
    engine.pool.reset()
    sched = _scheduler(engine)  # prefix cache on
    head = _prompt(3 * PAGE + 2, 21)
    first = _request(0, head + _prompt(5, 22))
    sched.submit(first)
    _run(sched)
    _rows_in_flight(sched, 1)
    tail = _prompt(C + 3, 23)
    req = _request(1, head + tail)
    sched.submit(req)
    while not req.done:
        sched.step()
    # three whole pages came from the first request's; the rest entered in two
    # chunks, from the page's edge where the shared pages end
    assert req.cached_tokens == 3 * PAGE and req.chunks == 2
    for r in list(sched.running):
        sched.cancel(r.rid)
    assert engine.pool.used() == 0
    assert req.generated == _alone(engine, head + tail)


def test_a_preemption_between_two_chunks_and_its_resume(engine):
    prompt = _prompt(C + 40, 31)
    want = _alone(engine, prompt)
    engine.pool.reset()
    sched = _scheduler(engine, prefix_cache=False)
    rows = _rows_in_flight(sched, 2)
    req = _request(0, prompt)
    sched.submit(req)
    sched.step()
    assert req.cursor == C and req.chunks == 1
    assert sched._preempt_one()  # the one still in its prompt goes first
    assert req.preemptions == 1 and req.cursor == 0 and req.pages == [] and sched.waiting == [req]
    while not req.done:
        sched.step()
    assert req.generated == want and req.chunks == 3
    for r in rows:
        sched.cancel(r.rid)
    assert engine.pool.used() == 0


def test_the_pool_runs_dry_while_a_chunk_grows_pages(tiny_model):
    from paddle_tpu.inference.engine import InferenceEngine

    # 18 pages: the long request's 17 and one more; the row beside it holds 2 to 5
    eng = InferenceEngine(tiny_model, max_seq_len=256, block_size=PAGE, max_batch=2, num_blocks=19)
    prompt = _prompt(C + 2, 41)
    sched = _scheduler(eng, prefix_cache=False)
    row = _request(9, _prompt(8, 42), max_new=30)
    sched.submit(row)
    sched.step()
    req = _request(0, prompt, max_new=4)
    sched.submit(req)
    _run(sched)
    assert sched.preempted_total >= 1 and req.preemptions >= 1
    assert eng.pool.used() == 0
    got, got_row = req.prompt[req.prompt_len:] + req.generated, row.prompt[row.prompt_len:] + row.generated
    solo = _scheduler(eng, prefix_cache=False)
    again, again_row = _request(0, prompt, max_new=4), _request(9, _prompt(8, 42), max_new=30)
    for r in (again, again_row):
        solo.submit(r)
        _run(solo)
    assert (got, got_row) == (again.generated, again_row.generated)


def test_engine_refuses_a_chunk_off_a_pages_edge_or_too_wide(engine):
    engine.pool.reset()
    pages = engine.pool.alloc(20, owner=0)
    try:
        with pytest.raises(ValueError, match="page's edge"):
            engine.decode_with_chunk([], [], [], [], [5, 6], 3, pages)
        with pytest.raises(ValueError, match="1..128 tokens"):
            engine.decode_with_chunk([], [], [], [], [5] * 129, 0, pages)
    finally:
        engine.pool.free(pages, owner=0, retain=False)


@pytest.mark.parametrize("build, chunk_programs", [
    ("llama_4_slots", [4]), ("llama_1_slot", []), ("hybrid_4_slots", [4])],
    ids=["four_slots", "a_lone_slot", "recurrent_state"])
def test_readying_the_decode_buckets_readies_the_chunk_program(tiny_model, monkeypatch, build, chunk_programs):
    """Who readies the largest decode bucket readies the one chunk program
    (the harness's set-up asks for the decode buckets and nothing else), a
    pool with recurrent state like any other; an engine with a lone slot,
    where no prompt ever rides beside a row, readies none. Nothing is
    compiled here."""
    from paddle_tpu.inference.engine import InferenceEngine, clear_shared_executables

    if build == "hybrid_4_slots":
        from paddle_tpu.models.nemotron_h import NemotronHForCausalLM

        paddle.seed(0)
        model = NemotronHForCausalLM()
        model.eval()
    else:
        model = tiny_model
    eng = InferenceEngine(model, max_seq_len=64, block_size=PAGE,
                          max_batch=1 if build == "llama_1_slot" else 4)
    assert eng.chunk_width == 64  # whole pages, at most the table: whatever the layers keep
    clear_shared_executables()
    made = []
    monkeypatch.setattr(eng, "_compile_decode", lambda b: made.append(("decode", b)) or object())
    monkeypatch.setattr(eng, "_compile_chunk", lambda b: made.append(("chunk", b)) or object())
    for b in eng.decode_batch_buckets:
        eng._get_compiled("decode", b)
    clear_shared_executables()  # the stand-ins are no programs
    assert [b for kind, b in made if kind == "chunk"] == chunk_programs
    assert [b for kind, b in made if kind == "decode"] == list(eng.decode_batch_buckets)
