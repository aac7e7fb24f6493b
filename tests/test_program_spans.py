"""The program's one span primitive (`paddle_tpu.profiler.RecordEvent`):
the ring, and the spans of the scheduler, the engine and `to_static`.

CPU, a tiny engine and a tiny `to_static` step. What a span costs is kept
here as a test with a loose limit; the measured figure is in PERF.md.
"""
import contextlib
import gc
import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import RecordEvent
from paddle_tpu.profiler import utils as spans


@pytest.fixture(autouse=True)
def _clean_ring():
    spans.clear()
    yield
    spans.clear()


@pytest.fixture(scope="module")
def tiny_engine():
    from paddle_tpu.inference.engine import InferenceEngine
    from paddle_tpu.models.llama import llama_tiny

    paddle.seed(0)
    model = llama_tiny(num_key_value_heads=2)
    model.eval()
    return InferenceEngine(model, max_seq_len=64, block_size=8, max_batch=4)


def _scheduler(engine, **kw):
    from paddle_tpu.inference.scheduler import ContinuousBatchingScheduler

    return ContinuousBatchingScheduler(engine, clock=time.perf_counter, **kw)


def _request(rid, n_prompt=5, max_new=4):
    from paddle_tpu.inference.scheduler import Request

    return Request(rid=rid, prompt=[3 + (rid + i) % 50 for i in range(n_prompt)],
                   max_new_tokens=max_new)


def _tiny_step():
    lin = paddle.nn.Linear(4, 2)
    opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=lin.parameters())

    @paddle.jit.to_static
    def train_step(x, y):
        loss = ((lin(x) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.ones((8, 4), "float32"))
    y = paddle.to_tensor(np.zeros((8, 2), "float32"))
    return train_step, x, y


@contextlib.contextmanager
def _no_collections():
    """No automatic collection inside: a collection of generation 1 or 2 is a
    `host.gc` record, so a test that counts the ring's records holds them
    off (`gc.collect()` still runs, and is recorded)."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def _by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r[0], []).append(r)
    return out


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def test_ring_nests_by_parent():
    with _no_collections(), RecordEvent("outer", ident=7, args={"n": 1}) as outer:
        spans.clear()
        with RecordEvent("inner"):
            pass
        with RecordEvent("second"):
            pass
        outer.args["n"] = 2  # args may be filled until the span ends
    by = {r[0]: r for r in spans.records()}
    name, t0, t1, sid, parent, ident, args = by["outer"][:7]
    assert parent == 0 and ident == 7 and args == {"n": 2}
    assert by["inner"][4] == sid and by["second"][4] == sid
    assert t0 <= by["inner"][1] <= by["inner"][2] <= by["second"][1] <= by["second"][2] <= t1
    # children end first: the ring is in order of ending (a collection since is a record too)
    assert [r[0] for r in spans.records() if r[0] != "host.gc"] == ["inner", "second", "outer"]
    # lo/hi keep what lies inside
    assert [r[0] for r in spans.records(lo=by["inner"][1], hi=by["second"][2])] == ["inner", "second"]


def test_ring_is_bounded_and_counts_evictions():
    extra = 10
    with _no_collections():
        spans.clear()
        for _ in range(spans.RING_LEN + extra):
            spans.record_span("x", 0.0, 1.0)
        n, lost = len(spans.records()), spans.evicted()
    assert n == spans.RING_LEN
    assert lost == extra
    spans.clear()
    assert spans.records() == [] and spans.evicted() == 0


def test_ring_keeps_count_under_threads():
    """More threads than cores, a short switch interval: no append and no
    eviction is lost, ids stay unique, and a span's parent is a span of its
    own thread."""
    import sys
    import threading

    n_threads = 16
    n_each = spans.RING_LEN // (2 * n_threads) + 1000   # 2 records a turn: past the ring's end
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def work():
        for _ in range(n_each):
            with RecordEvent("outer"):
                with RecordEvent("inner"):
                    pass

    try:
        with _no_collections():
            spans.clear()
            threads = [threading.Thread(target=work) for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            recs, lost = spans.records(), spans.evicted()
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    total = 2 * n_threads * n_each
    assert total > spans.RING_LEN
    assert len(recs) + lost == total
    assert len({r[3] for r in recs}) == len(recs)
    outer = {r[3]: r[8] for r in recs if r[0] == "outer"}
    for r in recs:
        if r[0] == "inner" and r[4] in outer:
            assert outer[r[4]] == r[8]   # same thread
        if r[0] == "outer":
            assert r[4] == 0


def test_ring_records_nothing_with_telemetry_off():
    paddle.set_flags({"PADDLE_TPU_TELEMETRY": False})
    try:
        with RecordEvent("dark"):
            pass
        spans.record_span("dark.after", 0.0, 1.0)
        assert spans.records() == []
    finally:
        paddle.set_flags({"PADDLE_TPU_TELEMETRY": True})
    with _no_collections():
        spans.clear()
        with RecordEvent("lit"):
            pass
        assert [r[0] for r in spans.records()] == ["lit"]


def test_span_cost_with_no_profiler_session():
    """Two clock reads, one ring append, a session check: a few microseconds
    at the most on a loaded CPU worker (measured figure: PERF.md)."""
    n = 20000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with RecordEvent("cost"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 25e-6, f"{best * 1e9:.0f} ns a span"


# ---------------------------------------------------------------------------
# scheduler and engine
# ---------------------------------------------------------------------------

def test_scheduler_step_yields_named_phases_that_add_up(tiny_engine):
    sched = _scheduler(tiny_engine)
    sched.submit(_request(0))
    sched.step()          # admission by a bucketed prefill, then a decode
    sched.submit(_request(1))
    spans.clear()
    # a busy step: request 1's prompt rides request 0's decode, one chunk, in the
    # step this call dispatches; the tokens it returns are the step before's
    produced = sched.step()
    recs = spans.records()
    by = _by_name(recs)
    (step,) = by["sched.step"]
    assert step[6] == {"produced": produced, "running": len(sched.running),
                       "waiting": len(sched.waiting), "prompt_tokens": 5, "chunk_tokens": 5,
                       "ahead": 1, "rows_dropped": 0}
    # nothing expires here: a sweep that finds nothing is no phase of the step.
    # The next step is planned and dispatched, THEN the one in flight is waited for
    phases = ["sched.admit", "sched.grow", "sched.rows", "engine.decode",
              "engine.decode.fetch", "sched.emit", "sched.publish"]
    children = [r for r in recs if r[4] == step[3]]
    assert [r[0] for r in children] == phases  # in order of ending = order of running
    for c in children:
        assert step[1] <= c[1] <= c[2] <= step[2]
    for a, b in zip(children, children[1:]):
        assert a[2] <= b[1]  # phases do not overlap
    self_time = (step[2] - step[1]) - sum(c[2] - c[1] for c in children)
    assert 0.0 <= self_time < 0.5 * (step[2] - step[1])
    while not sched.idle():
        sched.step()


def test_phases_that_do_nothing_are_skipped_and_expiry_is_named(tiny_engine):
    sched = _scheduler(tiny_engine)
    sched.submit(_request(0, max_new=6))
    sched.step()
    spans.clear()
    sched.step()          # nobody waits, nothing expires
    names = [r[0] for r in spans.records()]
    assert "sched.admit" not in names and "sched.expire" not in names
    assert {"sched.step", "sched.grow", "sched.rows", "sched.emit", "sched.publish"} <= set(names)
    late = _request(1)
    late.deadline_s = 0.0
    sched.submit(late)
    spans.clear()
    sched.step()
    (expire,) = [r for r in spans.records() if r[0] == "sched.expire"]
    assert expire[6] == {"expired": 1} and late.outcome == "expired"
    while not sched.idle():
        sched.step()


def test_bucketed_prefill_is_a_child_of_admit(tiny_engine):
    sched = _scheduler(tiny_engine)
    sched.submit(_request(0, n_prompt=6))
    sched.step()
    by = _by_name(spans.records())
    (admit,) = by["sched.admit"]
    (prefill,) = by["engine.prefill"]
    assert prefill[4] == admit[3]
    assert prefill[6]["tokens"] == 6 and prefill[6]["bucket"] >= 6
    kids = [r[0] for r in spans.records() if r[4] == prefill[3]]
    assert kids == ["engine.prefill.inputs", "engine.prefill.dispatch", "engine.prefill.fetch"]
    while not sched.idle():
        sched.step()


def test_request_queue_plus_prompt_is_ttft(tiny_engine):
    sched = _scheduler(tiny_engine)
    reqs = [_request(i, n_prompt=4 + i) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    while not sched.idle():
        sched.step()
    by = _by_name(spans.records())
    queue = {r[5]: r for r in by["request.queue"]}
    prompt = {r[5]: r for r in by["request.prompt"]}
    assert set(queue) == set(prompt) == {0, 1, 2}
    for r in reqs:
        q, p = queue[r.rid], prompt[r.rid]
        assert q[1] == r.submitted_time and q[2] == p[1] and p[2] == r.first_token_time
        assert (q[2] - q[1]) + (p[2] - p[1]) == pytest.approx(r.ttft(), abs=1e-9)
        assert p[6]["prompt_len"] == r.prompt_len and p[6]["cached"] == 0
    # the first found an idle scheduler: one bucketed prefill; the others took a
    # slot beside it and entered in chunks, one chunk each (5 and 6 tokens)
    assert (prompt[0][6]["mode"], prompt[0][6]["chunks"]) == ("bucketed", 0)
    assert [(prompt[i][6]["mode"], prompt[i][6]["chunks"]) for i in (1, 2)] == [("chunked", 1)] * 2


def test_engine_decode_carries_rows_bucket_context(tiny_engine):
    engine = tiny_engine
    pages = [engine.pool.alloc(1, owner=i) for i in range(3)]
    try:
        out = engine.decode(tokens=[5, 6, 7], positions=[0, 1, 2], seq_lens=[1, 2, 3], page_rows=pages)
    finally:
        for i, p in enumerate(pages):
            engine.pool.free(p, owner=i, retain=False)
    recs = spans.records()
    (dec,) = [r for r in recs if r[0] == "engine.decode"]
    # a 64-position table is one page block of the paged kernel: 4 rows, 4 live
    assert dec[6] == {"rows": 3, "bucket": 4, "context": 6, "chunk_tokens": 0, "chunk_width": 64,
                      "page_blocks_live": 4, "page_blocks_grid": 4}
    kids = [r for r in recs if r[4] == dec[3] and r[0].startswith("engine.decode.")]
    # the call dispatches and does not wait: the fetch is where the result is read
    assert [r[0] for r in kids] == ["engine.decode.inputs", "engine.decode.dispatch"]
    assert sum(r[2] - r[1] for r in kids) <= dec[2] - dec[1]
    assert not [r for r in recs if r[0] == "engine.decode.fetch"]
    assert out.ids().shape == (3,) and out.shape == (3, engine.vocab_size)  # ids, then the logits: a fetch each
    fetches = [r for r in spans.records() if r[0] == "engine.decode.fetch"]
    assert len(fetches) == 2 and all(r[4] == 0 and r[1] >= dec[2] for r in fetches)


@pytest.mark.parametrize("n_prompt, chunks", [(9, [9]), (128, [128]), (150, [128, 22])],
                         ids=["one_short_chunk", "one_full_chunk", "two_chunks"])
def test_a_chunk_step_is_an_engine_decode_span_that_counts_its_chunk(tiny_engine, n_prompt, chunks):
    """A prompt that arrives beside a decode row rides the steps in chunks of
    128 (512 positions at block 8: 4 page blocks a row). Each such step is
    ONE `engine.decode` span, named and shaped as a plain step's, in the
    largest bucket, with the chunk's tokens, its first position
    (`chunk_context`: what its sequence cached before it) and frontier counted; the step's
    `sched.step` counts them too, and the request's `request.prompt` says how
    it entered and in how many steps."""
    from paddle_tpu.inference.engine import InferenceEngine

    wide = InferenceEngine(tiny_engine._model, max_seq_len=512, block_size=8, max_batch=4)
    sched = _scheduler(wide, prefix_cache=False)
    sched.submit(_request(0, n_prompt=3, max_new=8))
    sched.step()                  # bucketed, then two decodes dispatched (one read): context 5 after them
    late = _request(1, n_prompt=n_prompt, max_new=2)
    sched.submit(late)
    start = 0
    for i, take in enumerate(chunks):
        spans.clear()
        sched.step()
        recs = spans.records()
        (dec,) = [r for r in recs if r[0] == "engine.decode"]
        row_context = 6 + i       # the one decode row, a token a step
        frontier_blocks = (start + take - 1) // 128 + 1
        assert dec[6] == {"rows": 1, "bucket": 4, "chunk_tokens": take, "chunk_width": 128,
                          "context": row_context + start + take, "chunk_context": start,
                          "page_blocks_live": 4 + frontier_blocks, "page_blocks_grid": 5 * 4}
        kids = [r[0] for r in recs if r[4] == dec[3] and r[0].startswith("engine.decode.")]
        assert kids == ["engine.decode.inputs", "engine.decode.dispatch"]
        (step,) = [r for r in recs if r[0] == "sched.step"]
        assert (step[6]["chunk_tokens"], step[6]["prompt_tokens"], step[6]["ahead"]) == (take, take, 1)
        assert dec[4] == step[3]  # a child of the step, where `engine.decode` always was
        (fetch,) = [r for r in recs if r[0] == "engine.decode.fetch"]
        assert fetch[4] == step[3] and fetch[1] >= dec[2]  # the wait for the step BEFORE, after this one went
        start += take
    spans.clear()
    sched.step()                  # both decode: a plain step says so; the last chunk's step is read in it
    (prompt,) = [r for r in spans.records() if r[0] == "request.prompt"]
    assert prompt[5] == 1 and prompt[6] == {"mode": "chunked", "prompt_len": n_prompt, "cached": 0,
                                            "chunks": len(chunks)}
    (dec,) = [r for r in spans.records() if r[0] == "engine.decode"]
    (step,) = [r for r in spans.records() if r[0] == "sched.step"]
    assert (dec[6]["rows"], dec[6]["chunk_tokens"], dec[6]["chunk_width"]) == (2, 0, 128)
    assert (step[6]["chunk_tokens"], step[6]["prompt_tokens"]) == (0, 0)
    while not sched.idle():
        sched.step()
    assert wide.pool.used() == 0


def test_a_bucketed_prefill_and_a_streamed_row_count_as_prompt_tokens_not_chunks(tiny_engine, monkeypatch):
    sched = _scheduler(tiny_engine, prefix_cache=False)
    sched.submit(_request(0, n_prompt=6, max_new=8))
    sched.step()
    (step,) = [r for r in spans.records() if r[0] == "sched.step"]
    assert (step[6]["prompt_tokens"], step[6]["chunk_tokens"]) == (6, 0)
    monkeypatch.setattr(tiny_engine, "chunk_width", 0)   # as a pool with recurrent state reads
    sched.submit(_request(1, n_prompt=3, max_new=2))
    for _ in range(3):
        spans.clear()
        sched.step()
        (step,) = [r for r in spans.records() if r[0] == "sched.step"]
        assert (step[6]["prompt_tokens"], step[6]["chunk_tokens"]) == (1, 0)
    sched.step()  # reads the step that carried the prompt's last token
    (prompt,) = [r for r in spans.records() if r[0] == "request.prompt"]
    assert (prompt[6]["mode"], prompt[6]["chunks"]) == ("streamed", 0)
    while not sched.idle():
        sched.step()


def test_engine_counts_the_page_blocks_the_paged_kernel_reads(tiny_engine):
    """512 positions at block 8 are 64 table columns = 4 page blocks of 16
    pages (128 positions); a row's live blocks reach its frontier, a pad
    row's (and an extend row's pad slots, position 0) the first block."""
    from paddle_tpu.inference.engine import InferenceEngine

    wide = InferenceEngine(tiny_engine._model, max_seq_len=512, block_size=8, max_batch=4)
    lens = [1, 129, 300]
    pages = [wide.pool.alloc(-(-n // 8), owner=i) for i, n in enumerate(lens)]
    try:
        wide.decode(tokens=[5, 6, 7], positions=[n - 1 for n in lens], seq_lens=lens,
                    page_rows=pages)
        wide.extend([[5, 6], [7, 8, 9]], [[126, 127], [127, 128, 129]], pages[1:], q_len=4)
    finally:
        for i, p in enumerate(pages):
            wide.pool.free(p, owner=i, retain=False)
    recs = spans.records()
    (dec,) = [r for r in recs if r[0] == "engine.decode"]
    (ext,) = [r for r in recs if r[0] == "engine.extend"]
    assert (dec[6]["page_blocks_live"], dec[6]["page_blocks_grid"]) == (1 + 2 + 3 + 1, 16)
    assert (ext[6]["page_blocks_live"], ext[6]["page_blocks_grid"]) == (1 + 2, 8)


def test_engine_compile_is_a_span_of_the_call_that_paid(tiny_engine):
    from paddle_tpu.inference.engine import InferenceEngine

    fresh = InferenceEngine(tiny_engine._model, max_seq_len=32, block_size=8, max_batch=2)
    page = fresh.pool.alloc(1, owner=0)
    fresh.decode(tokens=[5], positions=[0], seq_lens=[1], page_rows=[page])
    fresh.decode(tokens=[5], positions=[1], seq_lens=[2], page_rows=[page])
    fresh.pool.free(page, owner=0, retain=False)
    recs = spans.records()
    first, second = [r for r in recs if r[0] == "engine.decode"]
    (comp,) = [r for r in recs if r[0] == "engine.compile"]
    assert comp[4] == first[3]
    assert comp[6]["kind"] == "decode" and comp[6]["size"] == 1
    assert comp[6]["outcome"] in ("compile", "shared")
    assert not [r for r in recs if r[4] == second[3] and r[0] == "engine.compile"]


# ---------------------------------------------------------------------------
# when a step's outputs came back (`read_at`), and the host's own pauses (`host.gc`)
# ---------------------------------------------------------------------------

def _serve(engine, n_requests=3, max_new=6, between=None):
    """Requests through a scheduler to idle; `between(i)` runs after call i."""
    sched = _scheduler(engine)
    for i in range(n_requests):
        sched.submit(_request(i, max_new=max_new))
    i = 0
    while not sched.idle():
        sched.step()
        if between is not None:
            between(i)
        i += 1
    return sched


def test_read_at_is_stamped_once_after_the_dispatch_of_the_next_step(tiny_engine):
    _serve(tiny_engine)
    recs = spans.records()
    steps = sorted((r for r in recs if r[0] == "engine.decode"), key=lambda r: r[1])
    assert len(steps) > 4 and all("read_at" in r[6] for r in steps)
    for r in steps:
        assert r[6]["read_at"] > r[2]  # the span ends at the dispatch; the outputs come later
    ahead = [(prev, cur) for prev, cur in zip(steps, steps[1:]) if cur[2] < prev[6]["read_at"]]
    # one step ahead: step j + 1 was on its way before step j was read
    assert len(ahead) >= len(steps) - 3
    for prev, cur in ahead:
        (fetch,) = [f for f in recs if f[0] == "engine.decode.fetch" and cur[2] <= f[1] <= f[2] <= prev[6]["read_at"]]
    # a later copy of the logits leaves the first read's stamp alone
    page = tiny_engine.pool.alloc(1, owner=0)
    try:
        out = tiny_engine.decode(tokens=[5], positions=[0], seq_lens=[1], page_rows=[page])
    finally:
        tiny_engine.pool.free(page, owner=0, retain=False)
    (dec,) = [r for r in spans.records() if r[0] == "engine.decode" and r[1] > steps[-1][2]]
    out.ids()
    first = dec[6]["read_at"]
    assert np.asarray(out).shape == (1, tiny_engine.vocab_size)
    assert dec[6]["read_at"] == first and first > dec[2]


def test_a_result_never_read_has_no_read_at(tiny_engine):
    for n in range(1, tiny_engine.max_batch + 1):  # the harness's warm calls
        tiny_engine.decode(tokens=[1] * n, positions=[0] * n, seq_lens=[1] * n, page_rows=[[] for _ in range(n)])
    calls = [r for r in spans.records() if r[0] == "engine.decode"]
    assert len(calls) == tiny_engine.max_batch and not [r for r in calls if "read_at" in r[6]]


def test_a_forced_collection_in_a_served_loop_is_one_host_gc_record_inside_its_step_period(tiny_engine):
    with _no_collections():
        _serve(tiny_engine, max_new=8, between=lambda i: gc.collect() if i == 3 else None)
    recs = spans.records()
    (pause,) = [r for r in recs if r[0] == "host.gc"]
    assert pause[6]["generation"] == 2 and {"collected", "uncollectable"} <= set(pause[6])
    assert pause[4] == 0 and pause[2] > pause[1]
    # it lies between two reads of steps dispatched ahead: inside the period a step reader counts
    steps = sorted((r for r in recs if r[0] == "engine.decode"), key=lambda r: r[1])
    (held,) = [(prev, cur) for prev, cur in zip(steps, steps[1:])
               if prev[6]["read_at"] <= pause[1] and pause[2] <= cur[6]["read_at"]]
    assert held[1][2] < held[0][6]["read_at"]


def test_with_the_ring_off_neither_read_at_nor_host_gc_is_in_the_ring(tiny_engine):
    paddle.set_flags({"PADDLE_TPU_TELEMETRY": False})
    try:
        with _no_collections():
            _serve(tiny_engine, n_requests=2, between=lambda i: gc.collect() if i == 1 else None)
        assert spans.records() == []
    finally:
        paddle.set_flags({"PADDLE_TPU_TELEMETRY": True})


def test_a_short_generation_0_collection_leaves_no_record_and_a_long_one_does(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(spans, "_clock", lambda: now[0])

    def collection(generation, seconds):
        info = {"generation": generation, "collected": 3, "uncollectable": 0}
        spans._on_gc("start", info)
        now[0] += seconds
        spans._on_gc("stop", info)
        now[0] += 1.0

    collection(0, 0.0002)
    assert spans.records() == []
    collection(0, spans.GC_RECORD_S)
    collection(1, 0.0001)
    got = [(r[0], r[2] - r[1], r[6]["generation"]) for r in spans.records()]
    assert got == [("host.gc", pytest.approx(spans.GC_RECORD_S), 0), ("host.gc", pytest.approx(0.0001), 1)]
    # and a real one: a generation-0 collection of a few objects is far under a millisecond
    spans.clear()
    monkeypatch.undo()
    with _no_collections():
        t0 = time.perf_counter()
        gc.collect(0)
        took = time.perf_counter() - t0
    if took < spans.GC_RECORD_S:
        assert not [r for r in spans.records() if r[0] == "host.gc"]


# ---------------------------------------------------------------------------
# to_static
# ---------------------------------------------------------------------------

def test_to_static_call_numbers_steps_and_names_phases():
    step, x, y = _tiny_step()
    for _ in range(4):
        step(x, y)
    recs = spans.records()
    calls = [r for r in recs if r[0] == "to_static.call"]
    assert [c[6]["step"] for c in calls] == [1, 2, 3, 4]
    assert {c[6]["fn"] for c in calls} == {"train_step"}

    def kids(c):
        return [r[0] for r in recs if r[4] == c[3]]

    # call 1 is the eager recording pass; call 2 builds the program; after
    # that neither is seen again
    assert kids(calls[0]) == ["to_static.guard", "to_static.record"]
    assert kids(calls[1]) == ["to_static.guard", "to_static.gather", "to_static.dispatch",
                              "to_static.compile", "to_static.gather", "to_static.dispatch",
                              "to_static.writeback"]
    (comp,) = [r for r in recs if r[0] == "to_static.compile"]
    assert comp[6]["outcome"] == "compile"
    # gather and dispatch twice each: the arguments, then the key's split (the
    # call's first work for the device); the state's values, then the program
    steady = ["to_static.guard", "to_static.gather", "to_static.dispatch",
              "to_static.gather", "to_static.dispatch", "to_static.writeback"]
    assert kids(calls[2]) == steady and kids(calls[3]) == steady


# ---------------------------------------------------------------------------
# the profiler's own trace, and Profiler's export
# ---------------------------------------------------------------------------

def test_jax_profiler_capture_holds_the_programs_spans(tmp_path, tiny_engine):
    import jax
    from jax.profiler import ProfileData

    step, x, y = _tiny_step()
    for _ in range(2):
        step(x, y)
    sched = _scheduler(tiny_engine)
    sched.submit(_request(0))
    jax.profiler.start_trace(str(tmp_path))
    try:
        step(x, y)
        while not sched.idle():
            sched.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events if ev.name.startswith("paddle_tpu:"))
    assert {"paddle_tpu:sched.step", "paddle_tpu:sched.admit", "paddle_tpu:engine.decode",
            "paddle_tpu:engine.decode.fetch", "paddle_tpu:to_static.call",
            "paddle_tpu:to_static.dispatch"} <= names


def test_a_capture_holds_the_hosts_collections(tmp_path):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    assert "paddle_tpu:host.gc" in names
    assert [r[6]["generation"] for r in spans.records() if r[0] == "host.gc"][-1] == 2


def test_profiler_host_events_are_the_rings_records():
    from paddle_tpu.profiler import Profiler, ProfilerTarget

    with RecordEvent("before"):
        pass
    with Profiler(targets=[ProfilerTarget.CPU]) as prof:
        with RecordEvent("inside", args={"k": 1}):
            pass
    with RecordEvent("after"):
        pass
    names = [e.name for e in prof.profiler_result.host_events]
    assert "inside" in names and "before" not in names and "after" not in names
    trace = prof.profiler_result.to_chrome_trace()
    (ev,) = [e for e in trace["traceEvents"] if e["name"] == "inside"]
    assert ev["args"] == {"k": 1} and ev["dur"] >= 0
    # one store: what Profiler reported is what the ring holds
    ring = {r[0]: r for r in spans.records()}
    assert ev["ts"] == pytest.approx(ring["inside"][1] * 1e6, abs=1.0)


# ---------------------------------------------------------------------------
# a model that selects cached tokens: what its selector scored and chose
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def selecting_engine():
    from paddle_tpu.inference.engine import InferenceEngine
    from paddle_tpu.models.deepseek_v32 import DeepseekV32ForCausalLM

    paddle.seed(0)
    model = DeepseekV32ForCausalLM(index_topk=8)
    model.eval()
    return InferenceEngine(model, max_seq_len=64, block_size=8, max_batch=4)


INDEX_KEYS = ("index_positions_live", "index_positions_selected", "sparse_queries")


@pytest.mark.parametrize("call, want", [
    # one row at position 20: 21 positions scored, 8 chosen; a row at 3: all 4 chosen, no selection runs for it alone
    ("decode", {"index_positions_live": 21 + 4, "index_positions_selected": 8 + 4, "sparse_queries": 1,
                "index_positions_scored": 25, "sparse_positions_attended": 12, "index_keys_read": 25}),
    ("decode_low", {"index_positions_live": 4 + 6, "index_positions_selected": 4 + 6, "sparse_queries": 0}),
    # 12 prompt tokens: contexts 1..12, the first 8 whole, the last 4 cut to 8
    ("prefill", {"index_positions_live": 78, "index_positions_selected": 36 + 32, "sparse_queries": 4,
                 "index_positions_scored": 78, "sparse_positions_attended": 68, "index_keys_read": 12}),
    # two rows of 3 and 2 queries from positions 10 and 2
    ("extend", {"index_positions_live": (11 + 12 + 13) + (3 + 4), "index_positions_selected": 24 + 7,
                "sparse_queries": 3, "index_positions_scored": 43, "sparse_positions_attended": 31,
                "index_keys_read": 13 + 4}),
], ids=["decode", "decode_below_topk", "prefill", "extend"])
def test_engine_spans_count_what_the_selector_scored_and_chose(selecting_engine, call, want):
    engine = selecting_engine
    pages = [engine.pool.alloc(3, owner=i) for i in range(2)]
    try:
        if call == "decode":
            engine.decode(tokens=[5, 6], positions=[20, 3], seq_lens=[21, 4], page_rows=pages)
        elif call == "decode_low":
            engine.decode(tokens=[5, 6], positions=[3, 5], seq_lens=[4, 6], page_rows=pages)
        elif call == "prefill":
            engine.prefill(list(range(3, 15)), pages[0])
        else:
            engine.extend([[5, 6, 7], [8, 9]], [[10, 11, 12], [2, 3]], pages, 4)
    finally:
        for i, p in enumerate(pages):
            engine.pool.free(p, owner=i, retain=False)
    (span,) = [r[6] for r in spans.records() if r[0] == "engine." + call.split("_")[0]]
    assert {k: v for k, v in span.items() if k.startswith(("index_", "sparse_"))} == want


def test_sched_step_sums_the_selectors_counters_and_a_dense_decoders_spans_carry_none(selecting_engine, tiny_engine):
    sched = _scheduler(selecting_engine, prefix_cache=False)
    sched.submit(_request(0, n_prompt=30, max_new=3))
    while not sched.idle():
        sched.step()
    recs = spans.records()
    steps = [r[6] for r in recs if r[0] == "sched.step"]
    calls = [r[6] for r in recs if r[0] in ("engine.decode", "engine.prefill")]
    assert steps and all(set(INDEX_KEYS) <= set(s) for s in steps)
    for key in INDEX_KEYS:
        assert sum(s[key] for s in steps) == sum(c[key] for c in calls) > 0
    assert selecting_engine.pool.used() == 0
    spans.clear()
    dense = _scheduler(tiny_engine)
    dense.submit(_request(1))
    while not dense.idle():
        dense.step()
    assert not [r for r in spans.records() if r[6] and any(k.startswith(("index_", "sparse_")) for k in r[6])]
