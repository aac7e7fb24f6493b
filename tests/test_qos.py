"""Overload protection & multi-tenant QoS (round 19).

The ISSUE acceptance bars pinned here:

* an overload replay at >= 2x decode capacity with mixed tenants and
  priorities loses and duplicates ZERO tokens, sheds only from the lowest
  eligible class (or an over-quota tenant), and keeps high-priority p99
  TPOT within tolerance of an uncontended baseline;
* the brownout ladder is reversible and EXACT — it un-winds to rung 0,
  surviving greedy requests are byte-identical to the no-brownout oracle,
  and a step-2-capped request's output is an exact prefix of its uncapped
  chain;
* priority preemption rides the pool-dry preempt-resume machinery, so the
  evicted victim's final output is byte-identical to its oracle;
* cancellation and TTL expiry mid-prefill-stream free pages the same step
  and close the trace chain (no orphaned spans), including under FaultPlan
  chaos;
* a dead fleet still expires its held requests (the TTL sweep runs from
  submit(), not only step()).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import fault_injection as fi
from paddle_tpu.inference.engine import InferenceEngine
from paddle_tpu.inference.fleet import ReplicaFleet, ReplicaStatus
from paddle_tpu.inference.qos import (
    BROWNOUT_STEPS,
    BrownoutConfig,
    BrownoutController,
    QoSConfig,
    QoSPolicy,
    TenantConfig,
    TokenBucket,
    jain_fairness,
    tenant_report,
)
from paddle_tpu.inference.scheduler import (
    ContinuousBatchingScheduler,
    Request,
    SpecDecodeConfig,
)
from paddle_tpu.telemetry import metrics as tm
from paddle_tpu.telemetry import request_trace as rt


@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models.llama import llama_tiny

    paddle.seed(0)
    m = llama_tiny(num_key_value_heads=2)
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    fi.clear_plan()


@pytest.fixture()
def traced():
    paddle.set_flags({"FLAGS_request_trace": True,
                      "FLAGS_request_trace_sample": 1.0})
    rt.reset()
    yield rt.recorder()
    paddle.set_flags({"FLAGS_request_trace": False})
    rt.reset()


def _engine(model, **kw):
    opts = dict(max_seq_len=64, block_size=8, max_batch=4)
    opts.update(kw)
    return InferenceEngine(model, **opts)


def _greedy_oracle(model, prompt, n):
    cur = list(prompt)
    for _ in range(n):
        with paddle.no_grad():
            lg = model(paddle.to_tensor(np.asarray([cur], np.int64))).numpy()[0, -1]
        cur.append(int(lg.argmax()))
    return cur[len(prompt):]


def _produced(req):
    """Full client-visible output (folds a preemption resume back out)."""
    return req.prompt[req.prompt_len:] + list(req.generated)


def _counter_val(name, **labels):
    fam = tm.default_registry().get(name)
    return fam.labels(**labels).value if fam is not None else 0.0


# ---------------------------------------------------------------------------
# policy units (no model)
# ---------------------------------------------------------------------------

def test_token_bucket_refill_take_and_retry_hint():
    b = TokenBucket(rate=10.0, burst=20.0, now=0.0)
    assert b.try_take(20, now=0.0)          # full burst drains to zero
    assert not b.try_take(1, now=0.0)
    assert b.retry_after(5) == pytest.approx(0.5)
    assert not b.try_take(5, now=0.25)      # only 2.5 refilled
    assert b.try_take(5, now=0.5)           # 5 available exactly
    b2 = TokenBucket(rate=1.0, burst=4.0, now=0.0)
    b2.refill(100.0)
    assert b2.tokens == 4.0                 # refill caps at burst


def test_rate_gate_clamps_oversized_cost_to_burst():
    """A single request costing more than the burst drains the bucket to
    empty instead of being permanently inadmissible."""
    pol = QoSPolicy(QoSConfig(tenants={
        "t": TenantConfig(rate_tokens_per_s=10.0, burst_tokens=20.0)}))
    big = Request(rid=0, prompt=list(range(100)), max_new_tokens=8, tenant="t")
    ok, retry = pol.rate_gate(big, now=0.0)
    assert ok and retry is None
    ok, retry = pol.rate_gate(big, now=0.0)  # bucket now empty
    assert not ok and retry == pytest.approx(2.0)  # 20 clamped tokens @ 10/s


def test_select_strict_priority_then_weighted_fair():
    pol = QoSPolicy(QoSConfig(tenants={
        "a": TenantConfig(weight=2.0), "b": TenantConfig(weight=1.0)}))

    def mk(rid, tenant, priority):
        return Request(rid=rid, prompt=[1] * 4, max_new_tokens=4,
                       tenant=tenant, priority=priority)

    # the lone priority-0 request outranks everything regardless of debt
    waiting = [mk(0, "a", 1), mk(1, "b", 1), mk(2, "b", 0)]
    assert pol.select(waiting) == 2
    # weighted-fair within a class: weight-2 tenant drains ~2x the tokens
    waiting = ([mk(10 + i, "a", 1) for i in range(12)]
               + [mk(30 + i, "b", 1) for i in range(12)])
    took = {"a": 0, "b": 0}
    for _ in range(9):
        i = pol.select(waiting)
        r = waiting.pop(i)
        pol.charge(r)
        took[r.tenant] += 1
    assert took["a"] == 6 and took["b"] == 3


def test_select_single_tenant_reduces_to_fifo():
    """Pre-QoS traffic (one tenant, one class) must dequeue in exactly the
    old FIFO order — preempt-requeue-at-front semantics depend on it."""
    pol = QoSPolicy()
    waiting = [Request(rid=i, prompt=[1], max_new_tokens=2) for i in range(5)]
    for _ in range(5):
        assert pol.select(waiting) == 0
        pol.charge(waiting.pop(0))


def test_idle_tenant_reenters_at_debt_floor():
    """Idle time must not bank credit: a tenant returning after a long
    absence starts at the floor, it does not burst ahead on stale debt."""
    pol = QoSPolicy()

    def mk(rid, tenant):
        return Request(rid=rid, prompt=[1] * 10, max_new_tokens=10, tenant=tenant)

    for i in range(50):  # tenant "busy" accumulates real debt
        pol.charge(mk(i, "busy"))
    waiting = [mk(100, "busy"), mk(101, "fresh"), mk(102, "busy"), mk(103, "fresh")]
    picks = []
    for _ in range(4):
        i = pol.select(waiting)
        r = waiting.pop(i)
        pol.charge(r)
        picks.append(r.tenant)
    # floor lift: strict alternation, not fresh-drains-everything-first
    assert picks == ["busy", "fresh", "busy", "fresh"]


def test_queue_full_victim_rules():
    pol = QoSPolicy(QoSConfig(max_waiting=2))

    def mk(rid, priority, t):
        r = Request(rid=rid, prompt=[1], max_new_tokens=2, priority=priority)
        r.submitted_time = t
        return r

    waiting = [mk(0, 2, 0.0), mk(1, 2, 1.0)]
    assert pol.queue_full(2) and not pol.queue_full(1)
    # equal class: the newcomer sheds (queued requests have waited longer)
    newcomer = mk(2, 2, 2.0)
    assert pol.queue_full_victim(waiting, newcomer) is newcomer
    # strictly outranking newcomer displaces the LATEST lowest-class entry
    high = mk(3, 0, 2.0)
    assert pol.queue_full_victim(waiting, high) is waiting[1]


def test_brownout_ladder_hysteresis_and_degradations():
    cfg = BrownoutConfig(enter_pressure=0.8, exit_pressure=0.5,
                         cooldown_s=1.0, capped_max_new=4, low_priority=2)
    bc = BrownoutController(cfg)
    assert BROWNOUT_STEPS[bc.step] == "normal"
    assert bc.update(0.9, now=0.0) == [("escalate", 1)]
    assert not bc.spec_allowed()
    assert bc.max_new_cap(2) is None          # cap only arms at rung 2
    assert bc.update(0.95, now=0.1) == [("escalate", 2)]
    assert bc.max_new_cap(2) == 4 and bc.max_new_cap(1) is None
    assert not bc.sheds(2)                    # shed only arms at rung 3
    assert bc.update(0.9, now=0.2) == [("escalate", 3)]
    assert bc.sheds(2) and not bc.sheds(0)
    assert bc.update(0.9, now=5.0) == []      # hot: rung 3 is the top
    assert bc.update(0.4, now=5.5) == [("recover", 2)]
    # recovery needs pressure <= exit AND the cooldown since last change
    assert bc.update(0.4, now=6.0) == []      # cooldown not elapsed
    assert bc.update(0.6, now=7.0) == []      # between thresholds: hold
    assert bc.update(0.4, now=7.1) == [("recover", 1)]
    assert bc.update(0.4, now=7.5) == []      # cooldown again
    assert bc.update(0.4, now=8.2) == [("recover", 0)]
    assert bc.spec_allowed() and bc.transitions == 6


def test_jain_fairness_index():
    assert jain_fairness([5.0, 5.0, 5.0]) == 1.0
    assert jain_fairness([9.0, 0.0001, 0.0001]) == pytest.approx(1 / 3, abs=1e-3)
    assert jain_fairness([]) is None
    assert jain_fairness([0.0, 0.0]) is None


def test_deadline_unmeetable_math():
    pol = QoSPolicy()
    r = Request(rid=0, prompt=[1] * 4, max_new_tokens=10, deadline_s=1.0)
    assert not pol.deadline_unmeetable(r, None, 1)          # ewma cold
    assert pol.deadline_unmeetable(r, 0.5, 1)               # 5s floor > 1s
    assert not pol.deadline_unmeetable(r, 0.5, 8)           # spec emit bound
    assert not pol.deadline_unmeetable(
        Request(rid=1, prompt=[1], max_new_tokens=10), 0.5, 1)  # no TTL
    pol2 = QoSPolicy(QoSConfig(deadline_shed=False))
    assert not pol2.deadline_unmeetable(r, 0.5, 1)


def test_config_validation():
    with pytest.raises(ValueError):
        TenantConfig(weight=0.0)
    with pytest.raises(ValueError):
        TenantConfig(rate_tokens_per_s=-1.0)
    with pytest.raises(ValueError):
        BrownoutConfig(enter_pressure=0.5, exit_pressure=0.6)
    with pytest.raises(ValueError):
        BrownoutConfig(enter_pressure=1.5)


# ---------------------------------------------------------------------------
# scheduler admission gates (fake clock, no decode needed)
# ---------------------------------------------------------------------------

def _gated_sched(model, qos, **kw):
    """A scheduler with admission paused (drain) so submit-time gates can
    be tested without any decode running."""
    eng = _engine(model, **kw.pop("engine", {}))
    t = [0.0]
    sched = ContinuousBatchingScheduler(eng, clock=lambda: t[0], qos=qos, **kw)
    sched.drain()
    return sched, t


def test_validation_rejects_name_field_and_bound(tiny_model):
    eng = _engine(tiny_model)
    sched = ContinuousBatchingScheduler(eng)
    before = _counter_val("paddle_tpu_serving_requests_total",
                          event="rejected", reason="context_overflow")
    with pytest.raises(ValueError) as exc:
        sched.submit(Request(rid=7, prompt=list(range(60)), max_new_tokens=10))
    msg = str(exc.value)
    # the message names the offending fields AND the violated bound
    for part in ("request 7", "prompt_len 60", "max_new_tokens 10",
                 "70", "exceeds max_seq_len 64"):
        assert part in msg
    assert _counter_val("paddle_tpu_serving_requests_total",
                        event="rejected", reason="context_overflow") == before + 1

    small = _engine(tiny_model, num_blocks=4)   # 3 usable pages = 24 tokens
    sched2 = ContinuousBatchingScheduler(small)
    before = _counter_val("paddle_tpu_serving_requests_total",
                          event="rejected", reason="pool_capacity")
    with pytest.raises(ValueError) as exc:
        sched2.submit(Request(rid=8, prompt=list(range(20)), max_new_tokens=12))
    msg = str(exc.value)
    for part in ("request 8", "32", "4", "pages", "usable"):
        assert part in msg
    assert _counter_val("paddle_tpu_serving_requests_total",
                        event="rejected", reason="pool_capacity") == before + 1


def test_rate_limit_shed_with_retry_hint(tiny_model):
    qos = QoSPolicy(QoSConfig(tenants={
        "free": TenantConfig(rate_tokens_per_s=10.0, burst_tokens=12.0)}))
    sched, t = _gated_sched(tiny_model, qos)
    before = _counter_val("paddle_tpu_serving_requests_total",
                          event="shed", reason="rate_limit")
    r0 = Request(rid=0, prompt=[1] * 4, max_new_tokens=8, tenant="free")
    sched.submit(r0)                       # cost 12 drains the burst
    assert r0 in sched.waiting
    r1 = Request(rid=1, prompt=[2] * 4, max_new_tokens=8, tenant="free")
    sched.submit(r1)
    assert r1.outcome == "shed" and r1.shed_reason == "rate_limit"
    assert r1.retry_after_s == pytest.approx(1.2)   # 12 tokens @ 10/s
    assert r1 in sched.finished and r1 not in sched.waiting
    assert sched.shed_total == 1 and qos.shed_counts == {"rate_limit": 1}
    assert _counter_val("paddle_tpu_serving_requests_total",
                        event="shed", reason="rate_limit") == before + 1
    t[0] = 1.3                             # bucket refilled past the cost
    r2 = Request(rid=2, prompt=[3] * 4, max_new_tokens=8, tenant="free")
    sched.submit(r2)
    assert r2 in sched.waiting


def test_bounded_queue_overflow_and_priority_displacement(tiny_model):
    qos = QoSPolicy(QoSConfig(max_waiting=2))
    sched, t = _gated_sched(tiny_model, qos)
    r0 = Request(rid=0, prompt=[1] * 4, max_new_tokens=4, priority=2)
    t[0] = 0.1
    sched.submit(r0)
    r1 = Request(rid=1, prompt=[2] * 4, max_new_tokens=4, priority=2)
    t[0] = 0.2
    sched.submit(r1)
    # equal class at a full line: the NEWCOMER sheds
    r2 = Request(rid=2, prompt=[3] * 4, max_new_tokens=4, priority=2)
    t[0] = 0.3
    sched.submit(r2)
    assert r2.outcome == "shed" and r2.shed_reason == "queue_full"
    assert sched.waiting == [r0, r1]
    # a strictly-outranking newcomer displaces the latest lowest-class entry
    r3 = Request(rid=3, prompt=[4] * 4, max_new_tokens=4, priority=0)
    t[0] = 0.4
    sched.submit(r3)
    assert r1.outcome == "shed" and r1.shed_reason == "queue_full"
    assert sched.waiting == [r0, r3] and r3.outcome is None
    assert sched.shed_total == 2


def test_queue_wait_bound_sheds_stale_work(tiny_model):
    qos = QoSPolicy(QoSConfig(max_queue_wait_s=1.0))
    sched, t = _gated_sched(tiny_model, qos)
    r0 = Request(rid=0, prompt=[1] * 4, max_new_tokens=4)
    sched.submit(r0)
    t[0] = 0.5
    sched.step()
    assert r0 in sched.waiting             # within the bound
    t[0] = 1.6
    sched.step()
    assert r0.outcome == "shed" and r0.shed_reason == "queue_wait"
    assert sched.waiting == []


def test_deadline_unmeetable_shed_at_submit(tiny_model):
    sched, t = _gated_sched(tiny_model, QoSPolicy())
    sched.ewma_step_s = 0.5                # warm drain estimate: 0.5 s/step
    r0 = Request(rid=0, prompt=[1] * 4, max_new_tokens=10, deadline_s=1.0)
    sched.submit(r0)                       # needs >= 5 s, TTL is 1 s
    assert r0.outcome == "shed" and r0.shed_reason == "deadline_unmeetable"
    assert r0.retry_after_s is None        # provably unmeetable: no hint
    r1 = Request(rid=1, prompt=[1] * 4, max_new_tokens=10, deadline_s=30.0)
    sched.submit(r1)
    assert r1 in sched.waiting


# ---------------------------------------------------------------------------
# priority preemption (exact-output bar)
# ---------------------------------------------------------------------------

def test_priority_preemption_exact_output(tiny_model):
    eng = _engine(tiny_model, max_batch=2)
    sched = ContinuousBatchingScheduler(eng, qos=QoSPolicy())
    rng = np.random.RandomState(3)
    low = [Request(rid=i, prompt=rng.randint(0, 1024, (6,)).tolist(),
                   max_new_tokens=16, priority=2) for i in range(2)]
    for r in low:
        sched.submit(r)
    for _ in range(3):
        sched.step()
    assert len(sched.running) == 2
    before = _counter_val("paddle_tpu_serving_requests_total",
                          event="preempted", reason="priority")
    high = Request(rid=9, prompt=rng.randint(0, 1024, (5,)).tolist(),
                   max_new_tokens=8, priority=0)
    sched.submit(high)
    sched.step()                           # slots full -> evict one low
    assert high in sched.running
    assert _counter_val("paddle_tpu_serving_requests_total",
                        event="preempted", reason="priority") == before + 1
    while not sched.idle():
        sched.step()
    victims = [r for r in low if r.preemptions > 0]
    assert len(victims) == 1 and sched.preempted_total == 1
    # the exact-output bar: EVERY request (the resumed victim included)
    # matches its full-forward greedy oracle byte for byte
    for r in low + [high]:
        assert r.outcome == "completed"
        assert _produced(r) == _greedy_oracle(
            tiny_model, r.prompt[:r.prompt_len], r.max_new_tokens), r.rid
    assert eng.pool.used() == 0


def test_pool_dry_preemption_order_unchanged_without_qos(tiny_model):
    """Equal-priority traffic through a QoS scheduler preempts the exact
    victim the pre-QoS order would have picked (youngest, still-streaming
    first) — pinned so the QoS layer cannot silently reorder recovery."""
    eng = _engine(tiny_model, num_blocks=8)
    sched = ContinuousBatchingScheduler(eng, qos=QoSPolicy())
    rng = np.random.RandomState(5)
    reqs = [Request(rid=i, prompt=rng.randint(0, 1024, (8,)).tolist(),
                    max_new_tokens=12) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    while not sched.idle():
        sched.step()
    for r in reqs:
        assert _produced(r) == _greedy_oracle(
            tiny_model, r.prompt[:r.prompt_len], r.max_new_tokens), r.rid


# ---------------------------------------------------------------------------
# brownout ladder through the scheduler (reversible + exact)
# ---------------------------------------------------------------------------

def test_brownout_escalates_degrades_and_unwinds_exactly(tiny_model, traced):
    qos = QoSPolicy(QoSConfig(brownout=BrownoutConfig(
        enter_pressure=0.8, exit_pressure=0.5, cooldown_s=1.0,
        capped_max_new=4, low_priority=2)))
    eng = _engine(tiny_model)
    t = [0.0]
    sched = ContinuousBatchingScheduler(
        eng, clock=lambda: t[0], qos=qos,
        spec_decode=SpecDecodeConfig(draft_len=3, ngram=2),
    )
    # spec-friendly repetitive prompt: would draft aggressively at rung 0
    survivor = Request(rid=0, prompt=[5, 6, 5, 6, 5, 6, 5, 6],
                       max_new_tokens=10, priority=0)
    sched.submit(survivor)
    qos.note_slo_burn(1.0)                 # force pressure to 1.0
    t[0] = 1.0
    sched.step()                           # rung 1: spec off
    assert qos.brownout.step == 1 and sched.spec is not None  # restored
    t[0] = 2.0
    sched.step()                           # rung 2: cap arms
    capped = Request(rid=1, prompt=[7] * 6, max_new_tokens=12, priority=2)
    sched.submit(capped)
    t[0] = 3.0
    sched.step()                           # rung 3 + capped admission
    assert qos.brownout.step == 3
    assert capped.max_new_tokens == 4 and capped.qos_orig_max_new == 12
    shed = Request(rid=2, prompt=[8] * 4, max_new_tokens=4, priority=2)
    sched.submit(shed)                     # rung 3 refuses low-class work
    assert shed.outcome == "shed" and shed.shed_reason == "brownout"
    assert shed.retry_after_s == pytest.approx(1.0)  # the recovery cooldown
    vip = Request(rid=3, prompt=[9] * 4, max_new_tokens=4, priority=0)
    sched.submit(vip)                      # high class still admitted
    assert vip in sched.waiting

    # recovery: pressure off, cooldown elapsing -> one rung per step
    qos.note_slo_burn(0.0)
    steps_at = []
    while not sched.idle() or qos.brownout.step > 0:
        t[0] += 2.0
        sched.step()
        steps_at.append(qos.brownout.step)
        assert len(steps_at) < 60, "ladder failed to unwind"
    assert qos.brownout.step == 0          # fully un-wound
    assert steps_at[:3] == [2, 1, 0]       # one rung per cooled reading
    fam = tm.default_registry().get("paddle_tpu_qos_brownout_step")
    assert fam is not None and fam.value == 0.0
    trans = tm.default_registry().get("paddle_tpu_qos_brownout_transitions_total")
    assert trans.labels(direction="escalate", to="shed_low").value >= 1
    assert trans.labels(direction="recover", to="normal").value >= 1

    # EXACTNESS: the high-priority survivor is byte-identical to the
    # no-brownout oracle (spec-off changes pacing, never tokens); the
    # capped request's 4 tokens are an exact prefix of its uncapped chain
    assert survivor.drafted == 0           # spec really was off
    assert _produced(survivor) == _greedy_oracle(tiny_model, survivor.prompt[:8], 10)
    assert _produced(vip) == _greedy_oracle(tiny_model, [9] * 4, 4)
    got = _produced(capped)
    assert len(got) == 4
    assert got == _greedy_oracle(tiny_model, [7] * 6, 12)[:4]
    # every brownout transition left a qos-lane trace event
    qos_events = [r for r in rt.recorder().records()
                  if r["lane"] == "qos" and r["name"] == "brownout"]
    assert len(qos_events) == qos.brownout.transitions
    assert {e["attrs"]["rung"] for e in qos_events} >= {"spec_off", "normal"}


# ---------------------------------------------------------------------------
# the overload replay acceptance bar
# ---------------------------------------------------------------------------

def test_overload_replay_zero_loss_fair_sheds_bounded_p99(tiny_model):
    """>= 2x capacity, mixed tenants and priorities: nothing lost, nothing
    duplicated, sheds only from the lowest class present or the over-quota
    tenant, and the priority-0 class's p99 TPOT stays within tolerance of
    an uncontended run of the same requests."""
    rng = np.random.RandomState(11)
    specs = []                             # (rid, tenant, priority, prompt)
    for rid, tenant, priority in (
        [(i, "gold", 0) for i in range(4)]
        + [(10 + i, "silver", 1) for i in range(6)]
        + [(20 + i, "bronze", 2) for i in range(6)]
        + [(30 + i, "free", 2) for i in range(4)]
    ):
        specs.append((rid, tenant, priority,
                      rng.randint(0, 1024, (int(rng.randint(4, 10)),)).tolist()))

    def build(only_tenant=None):
        return [Request(rid=rid, prompt=list(p), max_new_tokens=6,
                        tenant=t, priority=pr)
                for rid, t, pr, p in specs
                if only_tenant is None or t == only_tenant]

    # uncontended baseline: the gold class alone on a fresh engine
    base = ContinuousBatchingScheduler(_engine(tiny_model))
    base_gold = build("gold")
    for r in base_gold:
        base.submit(r)
    while not base.idle():
        base.step()
    base_tpots = sorted(r.tpot() for r in base_gold if r.tpot() is not None)

    cfg = QoSConfig(
        tenants={
            "gold": TenantConfig(weight=4.0),
            "silver": TenantConfig(weight=2.0),
            "bronze": TenantConfig(weight=1.0),
            "free": TenantConfig(weight=1.0, rate_tokens_per_s=10.0,
                                 burst_tokens=24.0),
        },
        # no max_waiting: sheds can then ONLY come from the rate limit or
        # the brownout ladder (both lowest-eligible by construction)
        brownout=BrownoutConfig(enter_pressure=0.95, exit_pressure=0.5,
                                cooldown_s=0.05, capped_max_new=4,
                                low_priority=2),
    )
    qos = QoSPolicy(cfg)
    eng = _engine(tiny_model)
    sched = ContinuousBatchingScheduler(eng, qos=qos)
    reqs = build()                         # 20 requests, 4 decode slots
    gold = [r for r in reqs if r.tenant == "gold"]
    order = list(reqs)
    rng.shuffle(order)
    for r in order:
        sched.submit(r)
    steps = 0
    while not sched.idle():
        sched.step()
        steps += 1
        assert steps < 2000
    assert eng.pool.used() == 0

    # --- zero loss / zero duplication: every request terminal exactly once
    assert len(sched.finished) == len(reqs)
    assert sorted(r.rid for r in sched.finished) == sorted(r.rid for r in reqs)
    for r in reqs:
        assert r.outcome in ("completed", "shed"), (r.rid, r.outcome)
        if r.outcome == "completed":
            got = _produced(r)
            want = _greedy_oracle(tiny_model, r.prompt[:r.prompt_len],
                                  len(got))
            assert got == want, r.rid      # exact prefix, no dup/lost tokens
            assert len(got) in (r.max_new_tokens, r.qos_orig_max_new or r.max_new_tokens)

    # --- every shed is from the lowest class present or the over-quota tenant
    sheds = [r for r in reqs if r.outcome == "shed"]
    for r in sheds:
        if r.shed_reason == "rate_limit":
            assert r.tenant == "free"
        else:
            assert r.priority == 2, (r.rid, r.shed_reason)
    assert all(r.outcome == "completed" for r in gold)
    assert sched.shed_total == len(sheds)
    assert sum(qos.shed_counts.values()) == len(sheds)

    # --- fairness + per-tenant report over the drained replay
    rep = tenant_report(sched.finished, cfg)
    assert set(rep["tenants"]) == {"gold", "silver", "bronze", "free"}
    assert rep["tenants"]["gold"]["completed"] == 4
    if rep["fairness_index"] is not None:
        assert 0.0 < rep["fairness_index"] <= 1.0

    # --- the p99-TPOT bar: contended gold within tolerance of uncontended.
    # Both runs decode gold in (at most) full batches of 4 on this engine;
    # the generous envelope absorbs CI wall-clock noise, while still
    # failing if priority admission stops protecting the gold class.
    over_tpots = sorted(r.tpot() for r in gold if r.tpot() is not None)
    if base_tpots and over_tpots:
        assert over_tpots[-1] <= 5.0 * base_tpots[-1] + 0.05


# ---------------------------------------------------------------------------
# cancellation / TTL mid-prefill-stream (trace + page hygiene, chaos)
# ---------------------------------------------------------------------------

def test_cancel_mid_prefill_stream_frees_pages_and_closes_trace(
        tiny_model, traced):
    eng = _engine(tiny_model, max_seq_len=256)
    sched = ContinuousBatchingScheduler(eng)
    anchor = Request(rid=0, prompt=[1, 2, 3, 4], max_new_tokens=12)
    sched.submit(anchor)
    sched.step()                           # anchor running: B enters in chunks of 128
    streamer = Request(rid=1, prompt=list(range(10, 160)), max_new_tokens=4)
    sched.submit(streamer)
    sched.step()
    assert streamer in sched.running
    assert 0 < streamer.cursor < len(streamer.prompt)   # genuinely mid-prompt
    used_before = eng.pool.used()
    assert sched.cancel(1)
    # pages freed the SAME step, not at the next harvest
    assert eng.pool.used() < used_before
    assert streamer.pages == [] and streamer.outcome == "cancelled"
    while not sched.idle():
        sched.step()
    finishes = {r["rid"]: r["attrs"]["outcome"]
                for r in rt.recorder().records()
                if r["type"] == "event" and r["name"] == "finish"}
    assert finishes == {0: "completed", 1: "cancelled"}
    assert rt.recorder().open_spans() == []
    assert eng.pool.used() == 0


def test_ttl_expiry_mid_prefill_stream(tiny_model, traced):
    eng = _engine(tiny_model, max_seq_len=256)
    t = [0.0]
    sched = ContinuousBatchingScheduler(eng, clock=lambda: t[0])
    anchor = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=8)
    sched.submit(anchor)
    sched.step()
    doomed = Request(rid=1, prompt=list(range(100, 250)), max_new_tokens=4,
                     deadline_s=0.5)       # two chunks of 128: one step leaves it mid-prompt
    sched.submit(doomed)
    sched.step()
    assert doomed in sched.running and doomed.cursor < len(doomed.prompt)
    t[0] = 1.0                             # past the TTL mid-stream
    used_before = eng.pool.used()
    sched.step()                           # expiry sweep runs first
    assert doomed.outcome == "expired" and doomed.pages == []
    assert eng.pool.used() < used_before
    while not sched.idle():
        sched.step()
    finishes = {r["rid"]: r["attrs"]["outcome"]
                for r in rt.recorder().records()
                if r["type"] == "event" and r["name"] == "finish"}
    assert finishes == {0: "completed", 1: "expired"}
    assert rt.recorder().open_spans() == []
    assert eng.pool.used() == 0


def test_no_orphaned_spans_under_fleet_chaos(tiny_model, traced):
    """FaultPlan kills a replica while work (including a mid-stream TTL
    request) is in flight: every request still reaches exactly one terminal
    outcome and the trace chain closes — zero orphaned spans."""
    engines = [_engine(tiny_model, max_batch=2) for _ in range(2)]
    fleet = ReplicaFleet(engines)
    rng = np.random.RandomState(7)
    reqs = [Request(rid=i, prompt=rng.randint(0, 1024, (6,)).tolist(),
                    max_new_tokens=6) for i in range(4)]
    reqs.append(Request(rid=4, prompt=list(range(200, 240)),
                        max_new_tokens=4, deadline_s=0.15))
    for r in reqs:
        fleet.submit(r)
    fleet.step()
    fi.install_plan(fi.FaultPlan().add("fleet.replica_step.1", "fail", times=2))
    steps = 0
    while not fleet.idle():
        fleet.step()
        steps += 1
        assert steps < 500
    fi.clear_plan()
    outcomes = {r.rid: r.outcome for r in reqs}
    assert all(o in ("completed", "expired") for o in outcomes.values())
    assert len(fleet.finished) == len(reqs)          # exactly-once terminal
    assert rt.recorder().open_spans() == []
    assert all(e.pool.used() == 0 for e in engines)


# ---------------------------------------------------------------------------
# fleet: held-queue TTL on submit (the dead-fleet fix) + bounded holds
# ---------------------------------------------------------------------------

def test_dead_fleet_expires_held_requests_on_submit(tiny_model):
    eng = _engine(tiny_model)
    t = [0.0]
    fleet = ReplicaFleet([eng], clock=lambda: t[0])
    fleet.replicas[0].status = ReplicaStatus.DOWN
    before = _counter_val("paddle_tpu_serving_requests_total",
                          event="expired", reason="")
    doomed = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4, deadline_s=1.0)
    fleet.submit(doomed)
    assert doomed in fleet._pending
    t[0] = 2.0
    # the fleet is DEAD — step() would raise NoHealthyReplica and callers
    # stop stepping; the sweep MUST run from submit() or `doomed` is held
    # past its TTL forever
    other = Request(rid=1, prompt=[4, 5], max_new_tokens=4)
    fleet.submit(other)
    assert doomed not in fleet._pending
    assert doomed.outcome == "expired" and doomed in fleet.finished
    assert _counter_val("paddle_tpu_serving_requests_total",
                        event="expired", reason="") == before + 1
    assert other in fleet._pending


def test_dead_fleet_held_queue_is_bounded(tiny_model):
    eng = _engine(tiny_model)
    t = [0.0]
    qos = QoSPolicy(QoSConfig(max_waiting=2))
    fleet = ReplicaFleet([eng], clock=lambda: t[0], qos=qos)
    fleet.replicas[0].status = ReplicaStatus.DOWN
    low = [Request(rid=i, prompt=[1] * 3, max_new_tokens=4, priority=2)
           for i in range(2)]
    for r in low:
        t[0] += 0.1
        fleet.submit(r)
    assert len(fleet._pending) == 2
    # equal class: the newcomer sheds; the line never grows past the bound
    extra = Request(rid=5, prompt=[2] * 3, max_new_tokens=4, priority=2)
    t[0] += 0.1
    fleet.submit(extra)
    assert extra.outcome == "shed" and extra.shed_reason == "queue_full"
    assert len(fleet._pending) == 2
    # an outranking newcomer displaces the latest low-class hold
    vip = Request(rid=6, prompt=[3] * 3, max_new_tokens=4, priority=0)
    t[0] += 0.1
    fleet.submit(vip)
    assert low[1].outcome == "shed" and vip in fleet._pending
    assert len(fleet._pending) == 2
    assert fleet.shed_total == 2
    # zero-loss accounting still balances: all 4 submits are either held
    # or terminally shed into fleet.finished
    assert len(fleet._pending) + len(fleet.finished) == 4


def test_fleet_shares_one_policy_across_replicas(tiny_model):
    """The rate bucket is FLEET-wide: a tenant cannot multiply its quota
    by the replica count."""
    engines = [_engine(tiny_model) for _ in range(2)]
    t = [0.0]
    qos = QoSPolicy(QoSConfig(tenants={
        "free": TenantConfig(rate_tokens_per_s=10.0, burst_tokens=12.0)}))
    fleet = ReplicaFleet(engines, clock=lambda: t[0], qos=qos)
    for rep in fleet.replicas:
        assert rep.sched.qos is qos
        rep.sched.drain()                  # hold work in the queues
    r0 = Request(rid=0, prompt=[1] * 4, max_new_tokens=8, tenant="free")
    fleet.submit(r0)                       # drains the shared bucket
    r1 = Request(rid=1, prompt=[2] * 4, max_new_tokens=8, tenant="free")
    fleet.submit(r1)                       # whichever replica: same bucket
    assert r1.outcome == "shed" and r1.shed_reason == "rate_limit"
    assert fleet.shed_total == 1


def test_degraded_fleet_floors_brownout_pressure(tiny_model):
    """Round 21: a tiered fleet knocked off its disaggregated rung (decode
    tier dead -> monolithic) marks the shared QoS policy degraded, which
    FLOORS the pressure reading at degraded_pressure_floor — the brownout
    ladder escalates on an otherwise idle half-fleet instead of waiting
    for its queues to back up. Recovery (revive -> re-split) clears it."""
    qos = QoSPolicy(QoSConfig(brownout=BrownoutConfig(
        enter_pressure=0.8, exit_pressure=0.5, cooldown_s=0.0,
        degraded_pressure_floor=0.9)))
    assert qos.pressure(0.0, 0.0) == 0.0        # floor off while split
    fi.install_plan(fi.FaultPlan().add("fleet.replica_step.1", "fail",
                                       times=None))
    fleet = ReplicaFleet([_engine(tiny_model), _engine(tiny_model)],
                         tiers=["prefill", "decode"], breaker_threshold=1,
                         qos=qos)
    try:
        out = fleet.generate([[1, 2, 3, 4]], max_new_tokens=4)
    finally:
        fi.clear_plan()
    assert out == [_greedy_oracle(tiny_model, [1, 2, 3, 4], 4)]
    assert fleet.mode() == "monolithic"
    assert qos.degraded
    assert qos.pressure(0.0, 0.0) == 0.9        # floored while degraded
    # the prefill replica's ticks fed the floored reading into the ladder
    assert qos.brownout.step >= 1
    fleet.revive(1)
    assert fleet.mode() == "disaggregated"
    assert not qos.degraded                     # re-split clears the floor
    assert qos.pressure(0.0, 0.0) == 0.0
    with pytest.raises(ValueError):
        BrownoutConfig(degraded_pressure_floor=1.5)


# ---------------------------------------------------------------------------
# predictor wiring
# ---------------------------------------------------------------------------

def test_llm_predictor_qos_wiring(tiny_model, tmp_path):
    import paddle_tpu.inference as inf

    prefix = str(tmp_path / "llm")
    inf.save_llm(tiny_model, prefix)
    cfg = inf.Config(prefix)
    cfg.enable_llm_engine(
        max_new_tokens=4, max_seq_len=32, block_size=8, max_batch=2,
        prefill_buckets=(16,), decode_batch_buckets=(2,),
        qos=QoSConfig(max_waiting=16),
    )
    pred = inf.create_predictor(cfg)
    # QoS always runs through a fleet backend, even at one replica, so the
    # policy state (buckets/debt/ladder) is shared and observable
    assert pred.fleet() is not None and len(pred.fleet().replicas) == 1
    assert isinstance(pred.qos(), QoSPolicy)
    assert pred.fleet().qos is pred.qos()
    rng = np.random.RandomState(9)
    ids = rng.randint(0, 1024, (1, 10)).astype(np.int64)
    (out,) = pred.run([ids, np.array([10])])
    m2 = inf.load_llm(prefix)
    assert list(out[0]) == _greedy_oracle(m2, list(ids[0]), 4)
    assert pred.qos().brownout.step == 0
