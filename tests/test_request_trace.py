"""Request-scoped tracing & SLO attribution (round 16).

The ISSUE-14 acceptance bars pinned here:

- on a seeded replay through the ContinuousBatchingScheduler, every
  request's breakdown components sum to within 5% of its MEASURED wall
  time (Request's own submitted/finish timestamps, not the trace's);
- a 2-replica fleet with one mid-run swap + one FaultPlan kill leaves
  cause-labeled preempt spans (evacuation) and swap-drain windows, with
  the same 5% sum bar;
- chaos never orphans an open span: pool-dry preemption, evacuation, TTL
  expiry, and cancellation all leave a well-formed terminal event.
"""
import json
import subprocess
import sys
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import fault_injection as fi
from paddle_tpu.inference.engine import InferenceEngine
from paddle_tpu.inference.fleet import ReplicaFleet, fleet_replay
from paddle_tpu.inference.scheduler import (
    ContinuousBatchingScheduler,
    Request,
    replay,
)
from paddle_tpu.telemetry import request_trace as rt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models.llama import llama_tiny

    paddle.seed(0)
    m = llama_tiny(num_key_value_heads=2)
    m.eval()
    return m


@pytest.fixture()
def traced():
    """Tracing on at full sampling around one test, recorder clean."""
    paddle.set_flags({"FLAGS_request_trace": True,
                      "FLAGS_request_trace_sample": 1.0})
    rt.reset()
    yield rt.recorder()
    paddle.set_flags({"FLAGS_request_trace": False})
    rt.reset()


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    fi.clear_plan()


def _engine(model, **kw):
    opts = dict(max_seq_len=64, block_size=8, max_batch=4)
    opts.update(kw)
    return InferenceEngine(model, **opts)


def _mk_requests(n, seed=7, max_new=6, **kw):
    rng = np.random.RandomState(seed)
    return [
        Request(rid=i, prompt=rng.randint(0, 1024, (int(rng.randint(4, 12)),)).tolist(),
                max_new_tokens=max_new, arrival_time=0.001 * i, **kw)
        for i in range(n)
    ]


def _assert_sum_bar(scheduler_or_fleet, analysis, tol=0.05):
    """The acceptance bar: per request, trace components sum to within
    `tol` of the MEASURED wall (Request.submitted_time -> finish_time)."""
    finished = {r.rid: r for r in scheduler_or_fleet.finished}
    checked = 0
    for rid, q in analysis["requests"].items():
        req = finished.get(rid)
        if req is None or req.finish_time is None or req.submitted_time is None:
            continue
        measured = req.finish_time - req.submitted_time
        if measured <= 0:
            continue
        comp_sum = sum(q["components"].values())
        assert abs(comp_sum - measured) / measured < tol, (
            rid, comp_sum, measured, q["components"])
        checked += 1
    assert checked > 0
    return checked


# ---------------------------------------------------------------------------
# scheduler lifecycle
# ---------------------------------------------------------------------------

def test_replay_breakdown_sums_to_measured_wall(tiny_model, traced):
    """Seeded replay: every request gets contiguous queue/prefill/decode
    spans, a terminal event, and components summing to its measured wall."""
    eng = _engine(tiny_model)
    sched = ContinuousBatchingScheduler(eng)
    replay(sched, _mk_requests(8))
    bd = rt.slo_breakdown()
    assert bd["n_traced"] == 8
    assert bd["open_spans"] == 0
    assert bd["dropped_records"] == 0
    assert bd["consistency"]["max_abs_err_frac"] <= 0.05
    assert bd["outcomes"] == {"completed": 8}
    _assert_sum_bar(sched, rt.analyze())
    # TTFT side decomposes into queue_wait + prefill (+preempt)
    assert set(bd["ttft_p99_components_ms"]) == {"queue_wait", "prefill", "preempt"}
    assert bd["ttft_ms"]["p99"] is not None
    # blame table ranks components by tail share, shares sum to ~1
    shares = [b["share_of_p99_ttft"] for b in bd["ttft_p99_blame"]]
    assert abs(sum(shares) - 1.0) < 0.05
    assert shares == sorted(shares, reverse=True)


def test_pool_dry_preemption_spans_with_recompute_counts(tiny_model, traced):
    """Chaos bar 2: pool-dry preemption leaves a cause-labeled preempt span
    and the resume prefill records the recompute token count (the folded
    generated prefix rebuilt from scratch)."""
    eng = InferenceEngine(tiny_model, max_seq_len=48, block_size=8, max_batch=2,
                          num_blocks=6, decode_batch_buckets=(2,),
                          prefill_buckets=(16, 32))
    rng = np.random.RandomState(6)
    sched = ContinuousBatchingScheduler(eng)
    # short prompts + long generations: both requests are DECODING when the
    # pool dries (combined context grows past 5 usable pages), so the
    # victim folds already-generated tokens into its prompt — a nonzero
    # recompute count on resume
    sched.submit(Request(rid=0, prompt=rng.randint(0, 1024, (8,)).tolist(),
                         max_new_tokens=24))
    sched.submit(Request(rid=1, prompt=rng.randint(0, 1024, (8,)).tolist(),
                         max_new_tokens=12))
    while not sched.idle():
        sched.step()
    assert sched.preempted_total >= 1
    recs = rt.recorder().records()
    preempt = [r for r in recs if r["type"] == "span" and r["name"] == "preempt"]
    assert preempt and all(r["attrs"]["cause"] == "pool_dry" for r in preempt)
    # the resume prefill carries recompute_tokens == the folded prefix
    resumes = [r for r in recs if r["type"] == "span" and r["name"] == "prefill"
               and r["attrs"].get("recompute_tokens", 0) > 0]
    assert resumes
    victims = {r.rid for r in sched.finished if r.preemptions > 0}
    assert {r["rid"] for r in resumes} <= victims and victims
    for r in resumes:
        req = next(q for q in sched.finished if q.rid == r["rid"])
        assert r["attrs"]["recompute_tokens"] <= len(req.prompt) - req.prompt_len
    # post-resume tokens flip BACK to the decode phase: the resume prefill
    # must not swallow the rest of the generation (a victim whose
    # first_token_time predates the preemption used to stay in "prefill"
    # until its terminal close, blaming decode slowness on prefill)
    for rid in {r["rid"] for r in resumes}:
        spans = sorted(
            (r for r in recs if r["type"] == "span"
             and r["lane"] == "request" and r["rid"] == rid),
            key=lambda r: r["t1"])
        assert spans[-1]["name"] == "decode", [s["name"] for s in spans]
        resume_end = max(r["t1"] for r in resumes if r["rid"] == rid)
        assert any(s["name"] == "decode" and s["t0"] >= resume_end
                   for s in spans)
    assert rt.recorder().open_spans() == []
    bd = rt.slo_breakdown()
    assert bd["causes"].get("pool_dry", 0) >= 1
    assert bd["preemptions"] >= 1
    assert bd["components_mean_ms"]["preempt"] > 0
    _assert_sum_bar(sched, rt.analyze())


def test_ttl_expiry_and_cancel_leave_terminal_events(tiny_model, traced):
    """Chaos bar 3: TTL expiry and client cancellation each close the trace
    with a terminal outcome — no orphaned open spans, pages freed."""
    eng = _engine(tiny_model)
    sched = ContinuousBatchingScheduler(eng)
    doomed = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4, deadline_s=0.0)
    live = Request(rid=1, prompt=[4, 5, 6], max_new_tokens=2)
    victim = Request(rid=2, prompt=[7, 8, 9], max_new_tokens=32)
    for r in (doomed, live, victim):
        sched.submit(r)
    sched.step()           # expiry sweep fires first
    sched.cancel(2)
    while not sched.idle():
        sched.step()
    outcomes = {r.rid: r.outcome for r in sched.finished}
    assert outcomes[0] == "expired" and outcomes[2] == "cancelled"
    finishes = {r["rid"]: r["attrs"]["outcome"]
                for r in rt.recorder().records()
                if r["type"] == "event" and r["name"] == "finish"}
    assert finishes == {0: "expired", 1: "completed", 2: "cancelled"}
    assert rt.recorder().open_spans() == []
    assert eng.pool.used() == 0


def test_kv_pool_and_engine_attribution(tiny_model, traced):
    """Page alloc/free carry the owning request id (per-request page
    accounting + pool-occupancy-over-time), and every engine dispatch logs
    bucket hit vs compile with the signature."""
    eng = _engine(tiny_model)
    sched = ContinuousBatchingScheduler(eng)
    replay(sched, _mk_requests(4))
    a = rt.analyze()
    for q in a["requests"].values():
        assert q["pages_allocated"] >= 1
        # everything freed back: terminal paths release all pages
        assert q["pages_freed"] == q["pages_allocated"]
    assert a["kv_pool"]["peak_used_pages"] >= 1
    assert a["kv_pool"]["peak_used_pages"] <= eng.pool.num_blocks - 1
    eng_stats = a["engine"]
    assert eng_stats["bucket_hits"] == eng.bucket_stats["hits"]
    assert eng_stats["bucket_compiles"] == eng.bucket_stats["compiles"]
    assert eng_stats["compile_s_total"] > 0
    kinds = {(r["attrs"]["kind"], r["attrs"]["event"])
             for r in rt.recorder().records() if r["lane"] == "engine"}
    assert ("decode", "compile") in kinds or ("decode", "hit") in kinds


# ---------------------------------------------------------------------------
# fleet chaos: the ISSUE acceptance scenario
# ---------------------------------------------------------------------------

def test_fleet_swap_and_kill_trace_completeness(tiny_model, traced):
    """THE acceptance scenario: 2-replica fleet, one mid-run weight swap +
    one FaultPlan replica kill. Every request's components sum to within 5%
    of its measured wall, evacuated requests carry cause-labeled spans,
    swap-drain windows land in the fleet lane, zero orphaned spans."""
    fleet = ReplicaFleet([_engine(tiny_model), _engine(tiny_model)])
    weights = {k: v.numpy() for k, v in tiny_model.state_dict().items()}
    events = [
        (3, lambda: fleet.request_swap(weights)),
        # after 4 of 12: replica 1 still holds work (a prompt enters in one
        # chunk now, and by 6 it has none left to evacuate)
        (4, lambda: fi.install_plan(
            fi.FaultPlan().add("fleet.replica_step.1", "fail", times=2))),
    ]
    stats = fleet_replay(fleet, _mk_requests(12, seed=13), events=events)
    assert stats["lost"] == 0 and stats["duplicated"] == 0
    assert stats["evacuated"] >= 1 and stats["swaps_completed"] == 1

    recs = rt.recorder().records()
    evac = [r for r in recs if r["type"] == "span"
            and r["attrs"].get("cause") == "evacuation"]
    assert evac, "evacuated requests must carry cause-labeled spans"
    drains = [r for r in recs if r["lane"] == "fleet"
              and r["type"] == "span" and r["name"] == "swap_drain"]
    assert drains and all(r["attrs"]["replica"] is not None for r in drains)
    downs = [r for r in recs if r["lane"] == "fleet"
             and r["type"] == "event" and r["name"] == "replica_down"]
    assert [r["attrs"]["replica"] for r in downs] == [1]
    routes = [r for r in recs if r["type"] == "event" and r["name"] == "route"]
    assert {r["attrs"]["reason"] for r in routes} >= {"least_loaded", "evacuated"}
    assert all(r["attrs"]["replica"] is not None for r in routes)

    assert rt.recorder().open_spans() == []
    bd = rt.slo_breakdown()
    assert bd["n_traced"] == 12
    assert bd["consistency"]["max_abs_err_frac"] <= 0.05
    assert bd["causes"].get("evacuation", 0) >= 1
    assert bd["swap_windows"] >= 1
    _assert_sum_bar(fleet, rt.analyze())


# ---------------------------------------------------------------------------
# sampling + zero-cost-off
# ---------------------------------------------------------------------------

def test_tracing_off_is_inert(tiny_model):
    paddle.set_flags({"FLAGS_request_trace": False})
    rt.reset()
    sched = ContinuousBatchingScheduler(_engine(tiny_model))
    reqs = _mk_requests(3)
    replay(sched, reqs)
    assert rt.recorder().records() == []
    assert all(r.trace is None for r in reqs)
    assert rt.slo_breakdown()["n_traced"] == 0


def test_sampling_is_deterministic_and_partial(tiny_model, traced):
    paddle.set_flags({"FLAGS_request_trace_sample": 0.0})
    assert not any(rt.sampled(i) for i in range(64))
    paddle.set_flags({"FLAGS_request_trace_sample": 0.5})
    picks = [rt.sampled(i) for i in range(256)]
    assert picks == [rt.sampled(i) for i in range(256)]  # deterministic
    assert 0 < sum(picks) < 256  # actually partial
    # a partially-sampled replay traces exactly the sampled rids
    sched = ContinuousBatchingScheduler(_engine(tiny_model))
    reqs = _mk_requests(8)
    replay(sched, reqs)
    traced_rids = {r.rid for r in reqs if r.trace is not None}
    assert traced_rids == {i for i in range(8) if picks[i]}
    bd = rt.slo_breakdown()
    assert bd["n_traced"] == len(traced_rids)


def test_ring_bound_counts_evictions(tiny_model):
    paddle.set_flags({"FLAGS_request_trace": True,
                      "FLAGS_request_trace_sample": 1.0})
    small = rt.set_recorder(rt.RequestTraceRecorder(capacity=16))
    try:
        sched = ContinuousBatchingScheduler(_engine(tiny_model))
        replay(sched, _mk_requests(6))
        assert small.dropped > 0
        assert len(small.records()) == 16
        # the breakdown still renders; truncation is visible, not silent —
        # a request whose leading (queue) spans were evicted is COUNTED,
        # because its consistency ratio still reads ~1.0 (wall and
        # component sum shrink together when the head of the trace is lost)
        bd = rt.slo_breakdown()
        assert bd["dropped_records"] == small.dropped
        assert bd["truncated_requests"] >= 1
        ana = rt.analyze()
        assert any(q["truncated"] for q in ana["requests"].values())
    finally:
        paddle.set_flags({"FLAGS_request_trace": False})
        rt.set_recorder(rt.RequestTraceRecorder())


# ---------------------------------------------------------------------------
# exports: chrome lanes, jsonl round-trip, report CLI, perf_report
# ---------------------------------------------------------------------------

def test_chrome_export_one_lane_per_request(tiny_model, traced):
    sched = ContinuousBatchingScheduler(_engine(tiny_model))
    replay(sched, _mk_requests(3))
    tr = rt.to_chrome_trace()
    assert tr["metadata"]["request_lanes"] is True
    assert tr["metadata"]["clock_sync"]["unix_ns"] > 0
    req_pids = {e["pid"] for e in tr["traceEvents"]
                if e.get("ph") == "X" and e["pid"] >= rt.REQUEST_PID_BASE}
    assert req_pids == {rt.REQUEST_PID_BASE + i for i in range(3)}
    names = {e["name"] for e in tr["traceEvents"] if e.get("ph") == "X"}
    assert {"queue", "prefill", "decode"} <= names
    # lanes are labeled
    labels = {e["args"]["name"] for e in tr["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "process_name"}
    assert "request 0" in labels


def test_jsonl_round_trip_and_report_cli(tiny_model, traced, tmp_path):
    sched = ContinuousBatchingScheduler(_engine(tiny_model))
    replay(sched, _mk_requests(4))
    path = str(tmp_path / "events.jsonl")
    rt.dump_json_lines(path)
    back = rt.load_json_lines(path)
    assert len(back) == len(rt.recorder().records())
    bd_file = rt.slo_breakdown(back)
    bd_live = rt.slo_breakdown()
    assert bd_file["n_traced"] == bd_live["n_traced"] == 4
    assert bd_file["ttft_p99_components_ms"] == bd_live["ttft_p99_components_ms"]
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.telemetry.request_trace",
         "report", path, "--slo-ttft-ms", "0.001", "--slo-target", "0.99"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr
    assert "p99 TTFT blame table" in r.stdout
    assert "consistency" in r.stdout and "INCONSISTENT" not in r.stdout
    assert "burn rate" in r.stdout  # every request violates a 1 µs SLO
    r2 = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.telemetry.request_trace",
         "report", path, "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r2.returncode == 0, r2.stderr
    parsed = json.loads(r2.stdout)
    assert parsed["n_traced"] == 4 and parsed["open_spans"] == 0


def test_perf_report_carries_serving_section(tiny_model, traced):
    from paddle_tpu.profiler import perf_attribution as pa

    rep = pa.perf_report()
    pa.validate_report(rep)
    assert rep["serving"]["available"] is False  # nothing traced yet
    sched = ContinuousBatchingScheduler(_engine(tiny_model))
    replay(sched, _mk_requests(3))
    rep = pa.perf_report()
    pa.validate_report(rep)
    assert rep["serving"]["available"] is True
    assert rep["serving"]["n_traced"] == 3
    assert rep["serving"]["consistency"]["max_abs_err_frac"] <= 0.05


def test_trace_carries_prefix_and_spec_attribution(tiny_model, traced):
    """Round-17 satellite: cached_tokens (prefix hits) and drafted/accepted
    counts ride each request's trace, surface in slo_breakdown (where the
    TTFT/TPOT wins come from), and validate_report accepts the extended
    serving section."""
    from paddle_tpu.inference.scheduler import SpecDecodeConfig

    rng = np.random.RandomState(55)
    prefix = rng.randint(0, 1024, (17,)).tolist()
    motif = rng.randint(0, 64, (4,)).tolist()
    eng = _engine(tiny_model)
    sched = ContinuousBatchingScheduler(
        eng, prefix_cache=True, spec_decode=SpecDecodeConfig(draft_len=3))
    prompts = [prefix + motif * 2, prefix + rng.randint(0, 1024, (3,)).tolist()]
    for i, p in enumerate(prompts):
        r = Request(rid=i, prompt=list(p), max_new_tokens=8)
        sched.submit(r)
        while not sched.idle():
            sched.step()
    bd = rt.slo_breakdown()
    assert bd["open_spans"] == 0
    assert bd["cached_tokens"] >= 16          # request 1 shared the prefix
    assert bd["prefix_hit_requests"] >= 1
    assert bd["spec"]["drafted_tokens"] > 0
    assert bd["spec"]["accepted_tokens"] >= 0
    if bd["spec"]["accepted_tokens"]:
        assert bd["spec"]["accept_rate"] == pytest.approx(
            bd["spec"]["accepted_tokens"] / bd["spec"]["drafted_tokens"], abs=1e-3)
    # the prefill span carries the per-admission cached_tokens attr
    cached_attrs = [r["attrs"].get("cached_tokens") for r in rt.recorder().records()
                    if r["type"] == "span" and r["name"] == "prefill"]
    assert any(c for c in cached_attrs if c)
    # pool share events are attributed to the sharing request
    assert bd["pages_shared"] >= 2
    # and the perf_report schema carries it end to end
    from paddle_tpu.profiler.perf_attribution import perf_report, validate_report

    rep = validate_report(perf_report())
    assert rep["serving"]["available"] and rep["serving"]["cached_tokens"] >= 16
    # a serving section claiming traced requests but missing the round-17
    # attribution fields is a schema regression
    broken = json.loads(json.dumps(rep))
    del broken["serving"]["spec"]
    with pytest.raises(ValueError, match="spec"):
        validate_report(broken)
