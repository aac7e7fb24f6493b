"""One step ahead: the scheduler dispatches step j + 1 before it reads step
j's tokens. The program chooses the token on the device, ids only come back,
and a row's input may be the id the step before chose, still on the device.

What is held here, over the tiny llama, the hybrid (recurrent state and
experts), the latent (MLA and experts) and the sparse-latent (a token
selector) models where a case applies: the served tokens are those of the
synchronous order (every step planned after the last one is read, which is
what the scheduler did before) and of a full-forward oracle; the ring shows
the next dispatch ending before the wait for the last step; a row ended by
`eos_id` in the step before is dropped and nothing else moves; each event
that cannot happen beside a step in flight reads it out first and says so;
the expert counters land on the span of the step that computed them; a
result nobody reads leaves nothing behind.

CPU, the paged kernels' jnp reference path, chunks of 16 tokens so that a
prompt of 40 takes three.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.engine import InferenceEngine, RowToken
from paddle_tpu.inference.scheduler import (
    ContinuousBatchingScheduler, Request, SpecDecodeConfig, _Abandon)
from paddle_tpu.profiler import utils as spans

MODELS = ["llama", "hybrid", "latent", "sparse_latent"]
MOE = ["hybrid", "latent", "sparse_latent"]
CHUNK = 16


def _build(kind):
    paddle.seed(0)
    if kind == "llama":
        from paddle_tpu.models.llama import llama_tiny

        return llama_tiny(num_key_value_heads=2)
    if kind == "hybrid":
        from paddle_tpu.models.nemotron_h import NemotronHForCausalLM

        return NemotronHForCausalLM()
    if kind == "latent":
        from paddle_tpu.models.pangu_ultra_moe import PanguUltraMoEForCausalLM

        return PanguUltraMoEForCausalLM()
    from paddle_tpu.models.deepseek_v32 import DeepseekV32ForCausalLM

    return DeepseekV32ForCausalLM()


_ENGINES = {}


def _engine(model):
    """One decode bucket, one prefill bucket: three programs to compile a model."""
    eng = InferenceEngine(model, max_seq_len=96, block_size=8, max_batch=4,
                          prefill_buckets=(32,), decode_batch_buckets=(4,))
    eng.chunk_width = CHUNK  # before its chunk program compiles
    return eng


@pytest.fixture
def served(request):
    """(model, engine) of the kind the test is parametrised with, built once a
    module; the pool empty."""
    kind = request.param
    if kind not in _ENGINES:
        model = _build(kind)
        model.eval()
        _ENGINES[kind] = (model, _engine(model))
    model, eng = _ENGINES[kind]
    eng.pool.reset()
    return model, eng


def _prompt(model, n, seed):
    return np.random.RandomState(seed).randint(1, model.config["vocab_size"], (n,)).tolist()


def _assert_greedy(model, r, n=None):
    """What the request produced (`n` tokens, where given) is the greedy
    continuation of its prompt: one full forward over the whole sequence, no
    cache, every produced token the first maximum of the position before it."""
    prompt, out = r.prompt[:r.prompt_len], _produced(r)
    assert out and (n is None or len(out) == n)
    with paddle.no_grad():
        lg = model(paddle.to_tensor(np.asarray([prompt + out[:-1]], np.int64))).numpy()[0]
    assert out == lg[len(prompt) - 1:].argmax(-1).tolist()


def _synchronous(sched):
    """The order the scheduler kept before: no step is planned while another
    is in flight, so every row's token is the host's."""
    plan = sched._plan

    def never_ahead():
        if sched._planning:
            raise _Abandon("first")
        return plan()

    sched._plan = never_ahead
    return sched


def _drain(sched, limit=600):
    for _ in range(limit):
        if sched.idle():
            return
        sched.step()
    raise AssertionError("the scheduler did not drain")


def _produced(r):
    return r.prompt[r.prompt_len:] + r.generated


# prompts that enter bucketed (the first, the engine idle), in one chunk, in three and in two
MIX = [(5, 7), (12, 6), (40, 9), (20, 8)]


def _serve_mix(model, eng, synchronous=False, **kw):
    eng.pool.reset()
    sched = ContinuousBatchingScheduler(eng, **kw)
    if synchronous:
        _synchronous(sched)
    reqs = [Request(rid=i, prompt=_prompt(model, n, 10 + i), max_new_tokens=new) for i, (n, new) in enumerate(MIX)]
    sched.submit(reqs[0])
    sched.step()
    for r in reqs[1:]:
        sched.submit(r)
    _drain(sched)
    assert eng.pool.used() == 0 and sched._flight is None
    return sched, reqs


def _steps(recs):
    """The `sched.step` spans that ran a program."""
    return [r for r in recs if r[0] == "sched.step" and r[6] and ("ahead" in r[6] or "sync" in r[6])]


# (a) the tokens
@pytest.mark.parametrize("served", MODELS, indirect=True)
def test_tokens_equal_the_synchronous_orders_and_the_oracles(served):
    model, eng = served
    _, ahead = _serve_mix(model, eng)
    _, sync = _serve_mix(model, eng, synchronous=True)
    assert [(r.slot[1], r.chunks) for r in ahead] == [("bucketed", 0), ("chunked", 1), ("chunked", 3), ("chunked", 2)]
    for a, s, (n, new) in zip(ahead, sync, MIX):
        assert a.outcome == s.outcome == "completed" and a.unread == 0
        assert a.generated == s.generated
        _assert_greedy(model, a, new)


# (b) the order of the host's work
@pytest.mark.parametrize("served", MODELS, indirect=True)
def test_the_next_step_is_dispatched_before_the_last_one_is_read(served):
    model, eng = served
    spans.clear()
    _serve_mix(model, eng)
    recs = spans.records()
    steps = _steps(recs)
    ahead = [s for s in steps if s[6].get("ahead") == 1]
    # all but the first step, and the step after each row's known end, go ahead
    assert len(ahead) >= len(steps) - 4 and steps[0][6]["sync"] == "prefill"
    kinds = set()
    for later in ahead:
        # the call that dispatched this step read the step before it afterwards
        call = steps[steps.index(later) - 1]
        inside = [r for r in recs if r[4] == call[3]]
        decodes = [r for r in inside if r[0] == "engine.decode"]
        (fetch,) = [r for r in inside if r[0] == "engine.decode.fetch"]
        dispatch = [r for r in recs if r[0] == "engine.decode.dispatch" and r[4] == decodes[-1][3]]
        assert dispatch[0][2] <= fetch[1] <= fetch[2]
        kinds.add(decodes[-1][6]["chunk_tokens"] > 0)
    assert kinds == {True, False}  # plain steps and chunk steps alike
    assert all(s[6]["rows_dropped"] == 0 for s in steps)


# (c) a row that ends by eos_id while the next step holds it
@pytest.mark.parametrize("served", MODELS, indirect=True)
def test_a_row_ended_by_eos_is_dropped_and_its_neighbours_keep_their_tokens(served):
    model, eng = served
    _, free = _serve_mix(model, eng)
    # an end token that one request meets mid-run and no other meets at all
    others = lambda i: {t for j, r in enumerate(free) if j != i for t in r.generated}
    i, eos = next((i, t) for i, r in enumerate(free) for t in r.generated[1:-2] if t not in others(i))
    spans.clear()
    sched, reqs = _serve_mix(model, eng, eos_id=eos)
    cut = free[i].generated.index(eos) + 1
    assert reqs[i].generated == free[i].generated[:cut] and reqs[i].outcome == "completed"
    for j, r in enumerate(reqs):
        if j != i:
            assert r.generated == free[j].generated
    assert sum(s[6]["rows_dropped"] for s in _steps(spans.records())) == 1
    assert all(r.unread == 0 for r in reqs)
    if eng.pool.has_recurrent_state:
        assert eng.pool.state_slots_used() == 0


def test_a_step_whose_every_row_ended_by_eos_is_not_waited_for():
    if "llama" not in _ENGINES:
        model = _build("llama")
        model.eval()
        _ENGINES["llama"] = (model, _engine(model))
    model, eng = _ENGINES["llama"]
    eng.pool.reset()
    prompt = _prompt(model, 6, 3)
    free = Request(rid=0, prompt=prompt, max_new_tokens=8)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(free)
    _drain(sched)
    want = free.generated
    eos = next(t for t in want[2:] if t not in want[:2])
    sched = ContinuousBatchingScheduler(eng, eos_id=eos)
    req = Request(rid=0, prompt=prompt, max_new_tokens=8)
    sched.submit(req)
    spans.clear()
    _drain(sched)  # `idle()` behind the loop: nothing in flight, though a step was dispatched past the end
    assert req.generated == want[:want.index(eos) + 1]
    assert sched._flight is None and req.unread == 0 and eng.pool.used() == 0
    assert _steps(spans.records())[-1][6]["rows_dropped"] == 1


# (d) what reads the step in flight out first
def _two_in_flight(model, eng, **kw):
    eng.pool.reset()
    sched = ContinuousBatchingScheduler(eng, **kw)
    a = Request(rid=0, prompt=_prompt(model, 6, 40), max_new_tokens=14)
    b = Request(rid=1, prompt=_prompt(model, 9, 41), max_new_tokens=14)
    sched.submit(a)
    sched.step()
    sched.submit(b)
    for _ in range(3):
        sched.step()
    assert sched._flight is not None and (a.unread, b.unread) == (1, 1)
    return sched, a, b


def _next_sync(sched):
    spans.clear()
    sched.step()
    (step,) = _steps(spans.records())
    return step[6].get("sync")


@pytest.mark.parametrize("served", ["llama", "hybrid"], indirect=True)
def test_cancel_reads_the_step_in_flight_out_first(served):
    model, eng = served
    sched, a, b = _two_in_flight(model, eng)
    had = len(b.generated)
    assert sched.cancel(b.rid)
    assert sched._flight is None and b.outcome == "cancelled" and len(b.generated) == had + 1 and b.pages == []
    carried = sched._carried
    assert carried == 2  # the tokens of the step read out: the next call returns them too
    spans.clear()
    produced = sched.step()
    (step,) = _steps(spans.records())
    assert step[6]["sync"] == "cancel" and produced == carried + 1
    _drain(sched)
    assert eng.pool.used() == 0
    _assert_greedy(model, a, 14)
    _assert_greedy(model, b)  # as far as it got
    if eng.pool.has_recurrent_state:
        assert eng.pool.state_slots_used() == 0


@pytest.mark.parametrize("served", ["llama", "hybrid"], indirect=True)
def test_expiry_of_a_running_request_reads_the_step_in_flight_out_first(served):
    model, eng = served
    t = [0.0]
    sched, a, b = _two_in_flight(model, eng, clock=lambda: t[0])
    b.deadline_s = 5.0
    t[0] = 10.0
    spans.clear()
    sched.step()  # the sweep finds b in the step in flight: read out, expired, the next step behind it
    (step,) = _steps(spans.records())
    assert b.outcome == "expired" and b.pages == [] and b.unread == 0
    assert step[6].get("ahead") == 1  # the step this call READ had gone ahead
    assert sched._flight is not None and sched._flight.how == {"sync": "expire"}
    assert _next_sync(sched) == "expire"
    _drain(sched)
    assert eng.pool.used() == 0
    _assert_greedy(model, a, 14)


@pytest.mark.parametrize("served", ["llama", "hybrid"], indirect=True)
def test_a_dry_pool_preempts_with_nothing_in_flight(served):
    model, _ = served
    # 5 usable pages: each request peaks at 4 (15 prompt + 12 new), so growth must preempt
    eng = InferenceEngine(model, max_seq_len=48, block_size=8, max_batch=2, num_blocks=6,
                          decode_batch_buckets=(2,), prefill_buckets=(16,))
    sched = ContinuousBatchingScheduler(eng)
    reqs = [Request(rid=i, prompt=_prompt(model, 15, 50 + i), max_new_tokens=12) for i in range(2)]
    for r in reqs:
        sched.submit(r)
    spans.clear()
    _drain(sched)
    assert sched.preempted_total >= 1 and eng.pool.used() == 0
    for r in reqs:
        _assert_greedy(model, r, 12)
    assert "preempt" in [s[6].get("sync") for s in _steps(spans.records())]


@pytest.mark.parametrize("served", ["llama"], indirect=True)
def test_evacuation_and_adoption_read_the_step_in_flight_out_first(served):
    model, eng = served
    src, a, b = _two_in_flight(model, eng)
    dst = ContinuousBatchingScheduler(eng)
    c = Request(rid=2, prompt=_prompt(model, 7, 42), max_new_tokens=10)
    dst.submit(c)
    dst.step()
    assert dst._flight is not None
    # a's pages stay where they are (one pool): it moves as the fleet moves a request, pages resident
    src.sync("handoff")
    src.running.remove(a)
    a._registered_pages, a._chain_digest = 0, b""
    dst.adopt_running(a)
    assert dst._flight is None and a.unread == 0
    assert _next_sync(dst) == "handoff"
    # b leaves by evacuation: folded for a resume, its pages freed
    (moved,) = src.evacuate()
    assert moved is b and src._flight is None and b.pages == [] and b.unread == 0 and src.idle()
    dst.submit(b)
    _drain(dst)
    for r, n in ((a, 14), (b, 14), (c, 10)):
        _assert_greedy(model, r, n)
    assert eng.pool.used() == 0


@pytest.mark.parametrize("served", ["llama"], indirect=True)
def test_speculation_and_drain_run_with_nothing_in_flight(served):
    model, eng = served
    eng.pool.reset()
    motif = _prompt(model, 5, 60)
    sched = ContinuousBatchingScheduler(eng, spec_decode=SpecDecodeConfig(draft_len=3, ngram=2))
    r = Request(rid=0, prompt=motif * 4, max_new_tokens=12)
    sched.submit(r)
    spans.clear()
    _drain(sched)
    steps = _steps(spans.records())
    assert steps and all(s[6] == {**s[6], "sync": "spec"} and "ahead" not in s[6] for s in steps)
    assert sched._flight is None
    _assert_greedy(model, r, 12)

    sched, a, b = _two_in_flight(model, eng)
    sched.drain()
    assert sched._flight is None and sched.draining and (a.unread, b.unread) == (0, 0)
    assert _next_sync(sched) == "drain"
    _drain(sched)
    _assert_greedy(model, a, 14)
    _assert_greedy(model, b, 14)


# (e) the expert counters arrive with the ids, a call later
@pytest.mark.parametrize("served", MOE, indirect=True)
def test_expert_counters_land_on_the_span_of_the_step_that_computed_them(served):
    model, eng = served

    def calls(synchronous):
        spans.clear()
        _serve_mix(model, eng, synchronous=synchronous)
        return [r[6] for r in spans.records() if r[0] == "engine.decode"]

    ahead, sync = calls(False), calls(True)
    assert len(ahead) == len(sync) > 8
    keys = ("rows", "chunk_tokens", "context", "moe_assignments", "moe_experts_touched", "moe_layers")
    for a, s in zip(ahead, sync):
        assert [a[k] for k in keys] == [s[k] for k in keys]
    assert len({a["moe_assignments"] for a in ahead}) > 2  # they differ a step: a shifted one would show


# (f) a result nobody reads
@pytest.mark.parametrize("served", MODELS, indirect=True)
def test_a_result_nobody_reads_leaves_nothing_behind(served):
    model, eng = served
    spans.clear()
    for n in range(1, eng.max_batch + 1):  # the harness's warm calls: trash pages, nothing read
        out = eng.decode(tokens=[1] * n, positions=[0] * n, seq_lens=[1] * n, page_rows=[[] for _ in range(n)])
    names = [r[0] for r in spans.records()]
    assert "engine.decode.fetch" not in names and names.count("engine.decode") == eng.max_batch
    # a token of a step that is no longer the last one is read where it is used
    stale = out.token(0)
    eng.decode(tokens=[1], positions=[0], seq_lens=[1], page_rows=[[]])
    assert isinstance(stale, RowToken) and int(stale) == int(out.ids()[0])
    prompt = _prompt(model, 9, 70)
    eng.pool.reset()
    sched = ContinuousBatchingScheduler(eng)
    req = Request(rid=0, prompt=prompt, max_new_tokens=6)
    sched.submit(req)
    _drain(sched)
    assert eng.pool.used() == 0
    _assert_greedy(model, req, 6)
    if eng.pool.has_recurrent_state:
        assert eng.pool.state_slots_used() == 0
