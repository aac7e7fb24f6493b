"""Round 22: unified incident timeline + chaos-coverage-gated auto-triage.

Covers the recorder (bounded ring, counted evictions, dual clocks,
flag gating), the exports (JSON-lines with header, chrome-trace instant
lane, clock-sync derivation and trace_merge alignment), the chaos
observability coverage matcher, the triage ranking contract (injected
cause first on a seeded replay), the report CLI (events file and
crash-dump modes), the live /timeline.json + /compile_cache.json debug
endpoints, the crash-artifact embeds (guardian FlightRecorder + watchdog
flush_diagnostics, both NaN-lenient), and the metrics-inventory CI check.
"""
import json
import math
import os
import subprocess
import sys

import pytest

import paddle_tpu as paddle
from paddle_tpu.telemetry import timeline as tl
from paddle_tpu.distributed.resilience import fault_injection as fi

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _timeline_on():
    """Every test here runs with the flag on and a fresh ring; restore the
    default-off state after (other tests rely on emit being a no-op)."""
    paddle.set_flags({"FLAGS_incident_timeline": True})
    tl.reset()
    fi.clear_plan()
    yield
    fi.clear_plan()
    tl.reset()
    paddle.set_flags({"FLAGS_incident_timeline": False})


# ---------------------------------------------------------------------------
# recorder semantics
# ---------------------------------------------------------------------------

def test_emit_record_shape_and_both_clocks():
    tl.emit("fleet", "mode", severity="warn", labels={"site": "s"},
            mode="monolithic", was="disaggregated")
    (r,) = tl.recorder().records()
    assert set(r) == {"t_wall", "t_perf", "rank", "source", "kind",
                      "severity", "labels", "payload"}
    assert r["source"] == "fleet" and r["kind"] == "mode"
    assert r["severity"] == "warn" and r["labels"] == {"site": "s"}
    assert r["payload"] == {"mode": "monolithic", "was": "disaggregated"}
    # both clocks, plausible values
    assert r["t_wall"] > 1e9 and 0 < r["t_perf"] < 1e9


def test_flag_off_is_a_noop_and_cache_resyncs():
    paddle.set_flags({"FLAGS_incident_timeline": False})
    tl.emit("x", "y", severity="fatal")
    assert tl.recorder().records() == []
    assert not tl.enabled()
    paddle.set_flags({"FLAGS_incident_timeline": True})  # watcher resyncs
    assert tl.enabled()
    tl.emit("x", "y")
    assert len(tl.recorder().records()) == 1


def test_ring_bounds_and_counted_evictions():
    rec = tl.TimelineRecorder(capacity=16)
    for i in range(40):
        rec.emit("s", "k", payload={"i": i})
    assert len(rec.records()) == 16
    assert rec.dropped == 24  # appended - retained, never silent
    assert rec.records()[0]["payload"]["i"] == 24  # oldest evicted first
    rec.reset()
    assert rec.dropped == 0 and rec.records() == []


def test_bad_severity_coerces_to_info():
    tl.emit("s", "k", severity="catastrophic")
    assert tl.recorder().records()[0]["severity"] == "info"


def test_tail_is_nan_lenient():
    tl.emit("guardian", "anomaly", severity="error", loss=float("nan"),
            grad_norm=float("inf"))
    (r,) = tl.tail(10)
    assert r["payload"]["loss"] == "nan"
    assert r["payload"]["grad_norm"] == "inf"
    json.dumps(r, allow_nan=False)  # the whole tail survives strict dumps
    # json_safe=False returns the raw floats
    (raw,) = tl.tail(10, json_safe=False)
    assert math.isnan(raw["payload"]["loss"])


def test_clock_sync_pair_from_oldest_record():
    tl.emit("a", "b")
    tl.emit("c", "d")
    r0 = tl.recorder().records()[0]
    cs = tl.recorder().clock_sync()
    assert cs == {"perf_ns": int(r0["t_perf"] * 1e9),
                  "unix_ns": int(r0["t_wall"] * 1e9)}
    assert tl.TimelineRecorder(capacity=16).clock_sync() is None


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------

def test_json_lines_round_trip_with_header(tmp_path):
    tl.emit("fleet", "replica.down", severity="error",
            labels={"site": "fleet.replica_step.1"}, replica=1)
    tl.emit("scheduler", "request.finish", rid=3, outcome="completed")
    p = tl.dump_json_lines(str(tmp_path / "ev.jsonl"))
    header, recs = tl.load_json_lines(p, with_header=True)
    assert header["stream"] == "incident_timeline"
    assert header["dropped"] == 0 and header["clock_sync"] is not None
    assert [r["kind"] for r in recs] == ["replica.down", "request.finish"]
    assert tl.load_json_lines(p) == recs  # records-only default


def test_chrome_trace_instant_lane():
    tl.emit("qos", "shed", severity="warn", rid=1)
    tl.emit("fleet", "no_healthy_replica", severity="fatal", held=2)
    ct = tl.to_chrome_trace()
    evs = [e for e in ct["traceEvents"] if e["ph"] == "i"]
    assert all(e["pid"] == tl.TIMELINE_LANE_PID for e in evs)
    assert evs[0]["name"] == "qos.shed" and evs[0]["s"] == "p"
    assert evs[1]["s"] == "g"  # fatal marks globally in the viewer
    assert ct["metadata"]["timeline_lane"] is True
    assert ct["metadata"]["clock_sync"]["perf_ns"] > 0


def test_trace_merge_timeline_lane_clock_alignment(tmp_path):
    """The derived (perf_ns, unix_ns) pair puts incident instants at the
    same wall-clock position as a synced rank trace's spans: an event
    emitted between two known perf_counter stamps lands between their
    wall-clock mappings in the merged view."""
    import time

    from paddle_tpu.profiler import trace_merge as tm

    p0 = time.perf_counter_ns()
    tl.emit("fleet", "mode", mode="monolithic")
    p1 = time.perf_counter_ns()
    # a synced rank trace whose clock pair is THIS process's real clocks
    cs = {"rank": 0, "perf_ns": time.perf_counter_ns(),
          "unix_ns": time.time_ns()}
    rank_trace = {
        "traceEvents": [
            {"ph": "X", "name": "step", "pid": 0, "tid": 0,
             "ts": p0 / 1e3, "dur": (p1 - p0) / 1e3},
        ],
        "metadata": {"rank": 0, "clock_sync": cs},
    }
    tl_path = str(tmp_path / "incidents.json")
    tl.dump_chrome_trace(tl_path)
    merged = tm.merge_traces([rank_trace])
    merged = tm.merge_timeline_lane(merged, tl_path)
    assert merged["metadata"]["timeline_lane"] is True
    assert merged["metadata"]["timeline_event_count"] == 1
    step = next(e for e in merged["traceEvents"] if e.get("name") == "step")
    inst = next(e for e in merged["traceEvents"] if e.get("ph") == "i")
    # both lanes are on the same wall clock now; the emit happened inside
    # the rank span's window (allow the sub-ms skew of two clock captures)
    assert step["ts"] - 1e3 <= inst["ts"] <= step["ts"] + step["dur"] + 1e3


def test_trace_merge_cli_timeline_flag(tmp_path):
    from paddle_tpu.profiler import trace_merge as tm

    tl.emit("compile", "compile.miss", origin="engine", name="b128")
    rank = str(tmp_path / "rank0.json")
    with open(rank, "w") as f:
        json.dump({"traceEvents": [], "metadata": {"rank": 0}}, f)
    inc = str(tmp_path / "incidents.json")
    tl.dump_chrome_trace(inc)
    out = str(tmp_path / "merged.json")
    assert tm.main([rank, "-o", out, "--timeline", inc]) == 0
    with open(out) as f:
        merged = json.load(f)
    assert merged["metadata"]["timeline_event_count"] == 1


# ---------------------------------------------------------------------------
# chaos observability coverage
# ---------------------------------------------------------------------------

def _inject(site, action="fail"):
    fi.install_plan(fi.FaultPlan().add(site, action, times=1))
    try:
        fi.fault_point(site)
    except fi.FaultInjected:
        pass
    fi.clear_plan()


def test_injection_emits_site_action_seed():
    fi.install_plan(fi.FaultPlan(seed=77).add("demo.site", "fail", times=1))
    with pytest.raises(fi.FaultInjected):
        fi.fault_point("demo.site")
    (r,) = tl.recorder().records()
    assert r["source"] == tl.INJECTION_SOURCE
    assert r["kind"] == tl.INJECTION_KIND and r["severity"] == "error"
    assert r["labels"]["site"] == "demo.site"
    assert r["labels"]["action"] == "fail"
    assert r["payload"]["seed"] == 77


def test_coverage_matches_same_site_within_deadline():
    _inject("a.site")
    tl.emit("fleet", "handled", severity="warn", labels={"site": "a.site"})
    cov = tl.chaos_coverage()
    assert cov["injected"] == 1 and cov["observed"] == 1
    assert cov["unobserved_faults"] == 0 and cov["orphans"] == []
    assert cov["matched"] == {"a.site": 1}


def test_coverage_orphan_when_site_never_observed():
    _inject("dark.site")
    tl.emit("fleet", "handled", labels={"site": "other.site"})
    cov = tl.chaos_coverage()
    assert cov["unobserved_faults"] == 1
    assert cov["orphans"][0]["site"] == "dark.site"
    assert cov["orphans"][0]["action"] == "fail"


def test_coverage_deadline_and_ordering():
    # an observation BEFORE the injection, or past the deadline, never
    # matches — causality runs injection -> consequence on t_perf
    tl.emit("fleet", "early", labels={"site": "t.site"})
    _inject("t.site")
    recs = tl.recorder().records()
    assert tl.chaos_coverage(recs)["unobserved_faults"] == 1
    late = dict(recs[0])
    late["source"], late["kind"] = "fleet", "late"
    late["t_perf"] = recs[-1]["t_perf"] + 10.0
    assert tl.chaos_coverage(recs + [late])["unobserved_faults"] == 1
    assert tl.chaos_coverage(
        recs + [late], deadline_s=60.0)["unobserved_faults"] == 0


def test_coverage_another_injection_is_not_an_observation():
    _inject("x.site")
    _inject("x.site")
    assert tl.chaos_coverage()["unobserved_faults"] == 2


# ---------------------------------------------------------------------------
# triage
# ---------------------------------------------------------------------------

def test_triage_ranks_injected_cause_first_on_seeded_replay():
    """The acceptance contract: severity-weighted earliest-first ranking
    puts the fault.injected group above every downstream consequence."""
    tl.emit("scheduler", "request.finish", rid=0, outcome="completed")
    _inject("fleet.replica_step.1")
    tl.emit("fleet", "replica.failure", severity="error",
            labels={"site": "fleet.replica_step.1"}, replica=1)
    tl.emit("fleet", "replica.down", severity="error",
            labels={"site": "fleet.replica_step.1"}, replica=1)
    tl.emit("fleet", "mode", severity="warn", mode="monolithic")
    t = tl.triage()
    assert t["n_events"] == 5
    top = t["blame"][0]
    assert (top["source"], top["kind"]) == ("resilience", "fault.injected")
    assert top["rank"] == 1
    # downstream error-severity consequences follow, warn/info after
    sevs = [g["severity"] for g in t["blame"]]
    assert sevs == sorted(sevs, key=lambda s: -tl.SEVERITIES.index(s))
    assert t["chaos_coverage"]["unobserved_faults"] == 0
    assert t["severity_counts"]["error"] == 3


def test_triage_fatal_outranks_earlier_error():
    _inject("a.site")  # error, earliest
    tl.emit("watchdog", "escalation", severity="fatal", op="all_reduce")
    t = tl.triage()
    assert t["blame"][0]["kind"] == "escalation"
    assert t["blame"][1]["kind"] == "fault.injected"


def test_triage_window_bounds_and_clock_choice():
    tl.emit("a", "one")
    tl.emit("a", "two")
    recs = tl.recorder().records()
    w = (recs[1]["t_wall"] - 1e-7, recs[1]["t_wall"] + 1e-7)
    t = tl.triage(window=w)
    assert t["n_events"] == 1 and t["blame"][0]["kind"] == "two"
    t = tl.triage(window=(recs[0]["t_perf"] - 1e-7, recs[0]["t_perf"] + 1e-7),
                  clock="perf")
    assert t["n_events"] == 1 and t["blame"][0]["kind"] == "one"


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------

def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.telemetry.timeline", *args],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120,
    )


@pytest.mark.slow
def test_report_cli_events_file(tmp_path):
    _inject("cli.site")
    tl.emit("fleet", "handled", severity="warn", labels={"site": "cli.site"})
    p = tl.dump_json_lines(str(tmp_path / "ev.jsonl"))
    r = _run_cli("report", p, "--json")
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert doc["blame"][0]["kind"] == "fault.injected"
    assert doc["chaos_coverage"]["unobserved_faults"] == 0
    assert doc["dropped_events"] == 0
    # human format leads with the ranked table
    r = _run_cli("report", p)
    assert "ranked blame table" in r.stdout
    assert "chaos coverage: 1/1" in r.stdout


@pytest.mark.slow
def test_report_cli_crash_dump_mode(tmp_path):
    from paddle_tpu.framework.guardian import FlightRecorder

    _inject("dump.site")
    rec = FlightRecorder(capacity=8, name="t22", crash_dir=str(tmp_path))
    rec.record_step(1, loss=1.0)
    path = rec.dump(reason="test")
    r = _run_cli("report", "--crash-dump", path, "--json")
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert doc["blame"][0]["kind"] == "fault.injected"
    # exactly one of events/--crash-dump
    assert _run_cli("report").returncode != 0
    assert _run_cli("report", path, "--crash-dump", path).returncode != 0


# ---------------------------------------------------------------------------
# crash artifacts (satellite: guardian dump + watchdog flush embed the tail)
# ---------------------------------------------------------------------------

def test_flight_recorder_dump_embeds_nan_lenient_tail(tmp_path):
    from paddle_tpu.framework.guardian import FlightRecorder

    tl.emit("guardian", "anomaly", severity="error", loss=float("nan"))
    rec = FlightRecorder(capacity=8, name="t22b", crash_dir=str(tmp_path))
    rec.record_step(1, loss=float("nan"))
    path = rec.dump(reason="nan")
    with open(path) as f:
        dump = json.load(f)  # the dump itself must be valid JSON
    assert dump["timeline"][0]["payload"]["loss"] == "nan"
    assert dump["timeline_dropped"] == 0


def test_watchdog_flush_diagnostics_writes_tail(capsys):
    from paddle_tpu.distributed import comm_watchdog as wd

    tl.emit("watchdog", "soft_deadline", severity="warn", op="all_gather")
    wd.flush_diagnostics()
    err = capsys.readouterr().err
    assert "incident timeline tail" in err
    assert "soft_deadline" in err


def test_watchdog_escalation_ladder_emits(monkeypatch):
    from paddle_tpu.distributed import comm_watchdog as wd

    task = wd.CommTask(0, "all_reduce", {}, 0.0)
    monkeypatch.setattr(wd.CommTaskManager.instance(), "_abort_handler",
                        lambda t: None)
    wd.CommTaskManager.instance()._warn(task)
    wd._default_handler(task, task.describe())
    kinds = [(r["source"], r["kind"], r["severity"])
             for r in tl.recorder().records()]
    assert ("watchdog", "soft_deadline", "warn") in kinds
    assert ("watchdog", "escalation", "fatal") in kinds


# ---------------------------------------------------------------------------
# live debug endpoints (satellite 1)
# ---------------------------------------------------------------------------

def test_timeline_and_compile_cache_endpoints_live_refresh():
    import urllib.request

    from paddle_tpu import telemetry
    from paddle_tpu.compile_cache import ledger

    ledger.reset()
    tl.emit("fleet", "mode", mode="disaggregated")
    srv = telemetry.start_metrics_server(port=0)
    try:
        def get(path):
            return json.loads(urllib.request.urlopen(
                srv.url + path, timeout=10).read().decode())

        doc = get("/timeline.json")
        assert doc["enabled"] is True and doc["dropped"] == 0
        assert doc["clock_sync"]["perf_ns"] > 0
        assert [e["kind"] for e in doc["events"]] == ["mode"]
        # live: a new event and a new ledger record appear on re-scrape
        # without restarting anything
        tl.emit("qos", "shed", severity="warn", rid=9)
        ledger.record("engine", "b128", "miss", seconds=0.5)
        doc = get("/timeline.json")
        assert [e["kind"] for e in doc["events"]] == ["mode", "shed",
                                                      "compile.miss"]
        doc = get("/timeline.json?n=1")
        assert len(doc["events"]) == 1  # bounded tail
        cc = get("/compile_cache.json")
        assert [e["outcome"] for e in cc["events"]] == ["miss"]
        assert cc["summary"]["events"] == 1
    finally:
        srv.stop()
        ledger.reset()


# ---------------------------------------------------------------------------
# producer spot-checks: ledger + retry wire in with site labels
# ---------------------------------------------------------------------------

def test_ledger_emits_independent_of_metrics_gate(monkeypatch):
    from paddle_tpu import telemetry as tm
    from paddle_tpu.compile_cache import ledger

    ledger.reset()
    monkeypatch.setattr(tm, "enabled", lambda: False)
    ledger.record("engine", "b64", "shared", seconds=0.2)
    ledger.record("engine", "b64", "hit")  # per-dispatch: never an event
    kinds = [r["kind"] for r in tl.recorder().records()]
    assert kinds == ["compile.shared"]


def test_retry_giveup_observes_injected_site():
    from paddle_tpu.distributed.resilience import retry as rt

    fi.install_plan(fi.FaultPlan().add("net.op", "fail", times=5))
    pol = rt.RetryPolicy(max_attempts=2, base_s=0.0, sleep=lambda _s: None)
    with pytest.raises(rt.RetryError):
        pol.call(lambda: fi.fault_point("net.op"), site="net.op")
    fi.clear_plan()
    cov = tl.chaos_coverage()
    assert cov["injected"] == 2  # both attempts claimed a spec
    assert cov["unobserved_faults"] == 0  # retry + giveup events match


# ---------------------------------------------------------------------------
# metrics inventory (satellite 4)
# ---------------------------------------------------------------------------

def test_metrics_inventory_in_sync():
    """CI gate: every registered family is documented in the README
    catalog (and no stale entries linger)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_inventory as mi
    finally:
        sys.path.pop(0)
    fams = mi.scan_families()
    assert len(fams) > 80  # the scanner actually found the tree
    assert "paddle_tpu_faults_injected_total" in fams
    assert mi.check(fams) == []


def test_metrics_inventory_detects_missing_family(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_inventory as mi
    finally:
        sys.path.pop(0)
    fams = dict(mi.scan_families())
    fams["paddle_tpu_not_yet_documented_total"] = {
        "kind": "counter", "help": "x", "where": "nowhere.py"}
    problems = mi.check(fams)
    assert len(problems) == 1
    assert "paddle_tpu_not_yet_documented_total" in problems[0]
    # and the other polarity: a stale README entry is also flagged
    fams.pop("paddle_tpu_not_yet_documented_total")
    fams.pop("paddle_tpu_faults_injected_total")
    problems = mi.check(fams)
    assert any("stale" in p for p in problems)
