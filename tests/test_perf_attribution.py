"""Performance attribution layer: XLA cost/memory records on every compile
path, live-HBM census + watermark, roofline math, perf_report schema,
CostModel.profile_measure, MemoryView, and the multi-rank trace merge."""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import static, telemetry
from paddle_tpu.cost_model import CostModel
from paddle_tpu.profiler import perf_attribution as pa
from paddle_tpu.profiler import trace_merge as tm


@pytest.fixture(autouse=True)
def _telemetry_on():
    was = telemetry.enabled()
    telemetry.enable()
    yield
    (telemetry.enable if was else telemetry.disable)()


def _train_objects():
    paddle.seed(0)
    net = paddle.nn.Linear(8, 8)
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
    x = paddle.to_tensor(np.ones((4, 8), "float32"))
    return net, opt, x


# ---------------------------------------------------------------------------
# the acceptance loop: 3-step to_static train -> populated records
# ---------------------------------------------------------------------------


def test_to_static_3step_loop_populates_records():
    pa.reset()
    net, opt, x = _train_objects()

    @paddle.jit.to_static
    def train_step(x):
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for _ in range(3):
        loss = train_step(x)
    assert np.isfinite(float(loss.numpy()))

    recs = pa.program_records("to_static", name="train_step")
    assert recs, "to_static compile did not record into the attribution layer"
    r = recs[-1]
    # no zeros-by-default placeholders: a fwd+bwd+AdamW program has real
    # FLOPs, real HBM traffic, and a real memory footprint on CPU too
    assert r["flops"] > 0
    assert r["bytes_accessed"] > 0
    assert r["peak_memory_bytes"] > 0
    assert r["memory"]["argument_bytes"] > 0
    assert r["compile_seconds"] > 0
    assert r["available"] is True

    report = pa.validate_report(pa.perf_report())
    assert report["live_arrays"]["count"] > 0
    assert report["live_arrays"]["bytes"] > 0
    # the compiled-step boundary probe sampled the watermark (throttled:
    # at least the first step's sample landed)
    wm = report["hbm_watermark"]
    assert wm["samples"] >= 1
    assert wm["peak_hbm_bytes"] > 0


def test_perf_report_json_round_trips():
    pa.reset()
    net, opt, x = _train_objects()

    @paddle.jit.to_static
    def step_fn(x):
        loss = net(x).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step_fn(x)
    step_fn(x)
    rep = pa.perf_report()
    back = json.loads(json.dumps(rep))
    pa.validate_report(back)
    assert back["programs"] and back["programs"][-1]["origin"] == "to_static"
    with pytest.raises(ValueError):
        pa.validate_report({k: v for k, v in back.items() if k != "programs"})


def test_disabled_telemetry_records_nothing():
    pa.reset()
    net, opt, x = _train_objects()

    @paddle.jit.to_static
    def quiet_step(x):
        loss = net(x).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    telemetry.disable()
    try:
        quiet_step(x)
        quiet_step(x)
        assert pa.program_records() == []
        assert pa.watermark()["samples"] == 0
        assert pa.sample_watermark() is None
    finally:
        telemetry.enable()


# ---------------------------------------------------------------------------
# static Executor + fused-optimizer compile paths
# ---------------------------------------------------------------------------


def _param_program():
    """A static program whose matmul reads a PARAMETER (replay input, not a
    foldable constant), so cost analysis sees real FLOPs."""
    main = static.Program()
    with static.program_guard(main, static.Program()):
        x = static.data("x", [4, 8], "float32")
        net = paddle.nn.Linear(8, 8)
        out = (net(x) ** 2).mean()
    return main, out


def test_static_executor_records_cost_and_memory():
    pa.reset()
    main, out = _param_program()
    exe = static.Executor()
    xv = np.ones((4, 8), "float32")
    exe.run(main, feed={"x": xv}, fetch_list=[out])
    recs = pa.program_records("static_executor")
    assert recs and recs[-1]["flops"] > 0 and recs[-1]["bytes_accessed"] > 0
    n = len(pa.program_records())
    # cache hit: same shapes -> no second compile, no second record
    exe.run(main, feed={"x": xv}, fetch_list=[out])
    assert len(pa.program_records()) == n
    hist = telemetry.default_registry().get("paddle_tpu_executor_compile_seconds")
    assert hist is not None and hist.count >= 1


def test_fused_bucket_kernel_records():
    pa.reset()
    paddle.set_flags({"FLAGS_fused_optimizer": True})
    try:
        net, _, x = _train_objects()
        opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
    finally:
        paddle.set_flags({"FLAGS_fused_optimizer": False})
    recs = pa.program_records("fused_optimizer")
    assert recs, "bucket build did not record the kernel"
    assert recs[-1]["name"].startswith("bucket[")
    assert recs[-1]["n_elems"] > 0
    assert recs[-1]["bytes_accessed"] > 0


def test_cost_model_profile_measure_returns_real_numbers():
    pa.reset()
    main = static.Program()
    with static.program_guard(main, static.Program()):
        net = paddle.nn.Linear(16, 16)
        out = (net(paddle.ones([4, 16])) ** 2).sum()
        assert out is not None
    cost = CostModel().profile_measure(main_program=main)
    assert cost["time"] > 0
    assert cost["flops"] > 0
    assert cost["bytes_accessed"] > 0
    assert cost["peak_memory_bytes"] > 0
    assert cost["compile_seconds"] > 0


# ---------------------------------------------------------------------------
# census / watermark / MemoryView
# ---------------------------------------------------------------------------


def test_census_by_dtype_and_annotated_module():
    net = paddle.nn.Linear(32, 32)
    pa.annotate_module("encoder", net)
    census = pa.live_array_census()
    assert census["count"] > 0 and census["bytes"] > 0
    assert any(k.startswith("float32") for k in census["by_dtype"])
    enc = census["by_module"]["encoder"]
    # weight 32x32 f32 + bias 32 f32
    assert enc["count"] == 2
    assert enc["bytes"] == 32 * 32 * 4 + 32 * 4
    # annotation is weak: dropping the layer drops the census entry
    del net
    assert "encoder" not in pa.live_array_census()["by_module"]


def test_watermark_monotone_and_tagged():
    pa.reset()
    keep = paddle.to_tensor(np.zeros((64, 64), "float32"))
    wm1 = pa.sample_watermark(tag="t1", force=True)
    assert wm1["peak_hbm_bytes"] >= 64 * 64 * 4
    keep2 = paddle.to_tensor(np.zeros((128, 128), "float32"))
    # un-forced samples inside the throttle window return the LAST snapshot
    assert pa.sample_watermark(tag="throttled")["samples"] == 1
    wm2 = pa.sample_watermark(tag="t2", force=True)
    assert wm2["peak_hbm_bytes"] >= wm1["peak_hbm_bytes"]
    assert wm2["samples"] == 2
    del keep, keep2


def test_memory_view_table_renders_census():
    from paddle_tpu.profiler.profiler_statistic import _build_memory_table

    census = {
        "count": 3,
        "bytes": 3 * 1024,
        "by_dtype": {"float32": {"count": 2, "bytes": 2048},
                     "int32": {"count": 1, "bytes": 1024}},
        "by_module": {"embed": {"count": 1, "bytes": 1024}},
    }
    table = _build_memory_table(
        census, watermark={"peak_hbm_bytes": 4096, "peak_tag": "step"}
    )
    assert "Memory Summary" in table
    assert "float32" in table and "int32" in table and "embed" in table
    assert "TOTAL" in table and "High-water mark" in table
    # the enum routes the table through Profiler.summary
    from paddle_tpu.profiler import Profiler, SummaryView
    from paddle_tpu.profiler.profiler_statistic import StatisticData

    prof = Profiler.__new__(Profiler)
    prof.profiler_result = StatisticData([], memory_census=census)
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        prof.summary(views=SummaryView.MemoryView)
    assert "Memory Summary" in buf.getvalue()


def test_flight_recorder_dump_carries_hbm_and_perf(tmp_path):
    pa.reset()
    keep = paddle.to_tensor(np.zeros((32, 32), "float32"))
    pa.sample_watermark(tag="test", force=True)
    rec = paddle.FlightRecorder(capacity=4, name="perf", crash_dir=str(tmp_path))
    rec.record_step(1, loss=1.0)
    path = rec.dump(reason="test")
    payload = json.loads(open(path).read())
    assert payload["peak_hbm_bytes"] >= 32 * 32 * 4
    assert "programs" in payload["perf_report"]
    assert "hbm_watermark" in payload["perf_report"]
    del keep


def test_guardian_step_records_peak_hbm():
    pa.reset()
    net, opt, x = _train_objects()
    guardian = paddle.TrainingGuardian(opt, policy="raise")
    loss = (net(x) ** 2).mean()
    loss.backward()
    assert guardian.step(loss) == "ok"
    steps = [r for r in guardian.recorder.records() if r["kind"] == "step"]
    assert steps and steps[-1]["peak_hbm_bytes"] > 0


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------


_FAKE_TABLE = {"faketpu": {"flops_per_s": 100.0, "bytes_per_s": 10.0}}


def test_roofline_math_against_pinned_table():
    r = pa.roofline(50.0, 5.0, 1.0, platform="faketpu", peak_table=_FAKE_TABLE)
    assert r["mfu"] == pytest.approx(0.5)
    assert r["hbm_util"] == pytest.approx(0.5)
    assert r["bound"] == "compute"  # ties resolve to compute
    assert r["platform"] == "faketpu"

    r = pa.roofline(10.0, 9.0, 2.0, platform="faketpu", peak_table=_FAKE_TABLE)
    assert r["achieved_flops_per_s"] == pytest.approx(5.0)
    assert r["mfu"] == pytest.approx(0.05)
    assert r["hbm_util"] == pytest.approx(0.45)
    assert r["bound"] == "memory"

    assert pa.peak_for("FakeTPU", _FAKE_TABLE)[0] == "faketpu"  # case only
    with pytest.raises(ValueError):
        pa.roofline(1.0, 1.0, 0.0, platform="faketpu", peak_table=_FAKE_TABLE)


@pytest.mark.parametrize("kind, row", [
    ("TPU v5 lite", "tpu v5e"),   # what jax.devices()[0].device_kind says on a v5e
    ("TPU v5e", "tpu v5e"),
    ("cpu", None),                # this sandbox: no published peak, no guess
    ("TPU v5 lite pod", None),    # no substring matching
])
def test_peak_table_resolves_device_kind_or_raises(kind, row):
    """A v5e is priced as a v5e (197 TFLOP/s bf16, 0.82 TB/s), and a device
    the table does not know raises instead of borrowing another row."""
    if row is None:
        with pytest.raises(pa.UnknownDeviceError, match="no published peak"):
            pa.peak_for(kind)
        with pytest.raises(pa.UnknownDeviceError):
            pa.roofline(1e9, 1e8, 0.01, platform=kind)
        return
    key, peak = pa.peak_for(kind)
    assert key == row
    assert peak == {"flops_per_s": 197e12, "bytes_per_s": 0.82e12}
    r = pa.roofline(197e12, 0.41e12, 2.0, platform=kind)
    assert r["mfu"] == pytest.approx(0.5) and r["hbm_util"] == pytest.approx(0.25)


def test_peak_table_has_no_cpu_row_and_default_lookup_raises_here():
    assert "cpu" not in pa.DEFAULT_PEAK_TABLE
    assert pa.platform_name() == "cpu"  # the test mesh
    with pytest.raises(pa.UnknownDeviceError):
        pa.peak_for()


# ---------------------------------------------------------------------------
# multi-rank trace merge
# ---------------------------------------------------------------------------


def _rank_trace(rank, perf_ns, unix_ns, events):
    return {
        "traceEvents": [
            {"name": n, "cat": cat, "ph": "X", "ts": ts, "dur": dur,
             "pid": 0, "tid": 1, "args": args or {}}
            for (n, cat, ts, dur, args) in events
        ],
        "metadata": {
            "rank": rank,
            "clock_sync": {"rank": rank, "world_size": 2,
                           "perf_ns": perf_ns, "unix_ns": unix_ns},
        },
    }


def test_trace_merge_aligns_ranks_and_preserves_order():
    # rank 0's perf epoch is 1 ms before the wall instant; rank 1's is 3 ms
    # before — so rank 1's raw ts are 2 ms "behind" rank 0's for the same
    # wall moment, and the merge must shift them forward
    t0 = _rank_trace(0, perf_ns=1_000_000, unix_ns=2_000_000, events=[
        ("fwd", "Forward", 10.0, 5.0, None),
        ("all_reduce", "Communication", 20.0, 8.0, {"bytes": 64, "group": "pg_0"}),
    ])
    t1 = _rank_trace(1, perf_ns=3_000_000, unix_ns=2_000_000, events=[
        ("all_reduce", "Communication", 25.0, 6.0, {"bytes": 64, "group": "pg_0"}),
        ("fwd", "Forward", 14.0, 5.0, None),
    ])
    merged = tm.merge_traces([t0, t1])
    assert merged["metadata"]["alignment"] == "clock_sync"
    assert merged["metadata"]["merged_ranks"] == [0, 1]

    real = [e for e in merged["traceEvents"] if e.get("ph") != "M"]
    # one lane per rank, every event stamped with its rank
    assert {e["pid"] for e in real} == {0, 1}
    assert all(e["args"]["rank"] == e["pid"] for e in real)
    # rank lanes are labeled
    names = [e for e in merged["traceEvents"] if e.get("ph") == "M" and e["name"] == "process_name"]
    assert {m["args"]["name"] for m in names} == {"rank 0", "rank 1"}

    # clock math: offsets are (unix-perf)/1e3 -> rank0 +1000us, rank1
    # -1000us; wall starts: rank1 fwd 14-1000=-986 (the origin), rank0 fwd
    # 10+1000=1010 -> merged ts 1010-(-986)=1996
    by = {(e["pid"], e["name"]): e["ts"] for e in real}
    assert by[(1, "fwd")] == pytest.approx(0.0)
    assert by[(0, "fwd")] == pytest.approx(1996.0)
    # merged stream is time-sorted across ranks
    order = [(e["pid"], e["name"]) for e in real]
    assert order == [(1, "fwd"), (1, "all_reduce"), (0, "fwd"), (0, "all_reduce")]

    # the merged events feed the DistributedView summary
    from paddle_tpu.profiler.profiler_statistic import _build_distributed_table

    table = _build_distributed_table(tm.to_statistic_data(merged))
    assert "all_reduce" in table and "pg_0" in table
    assert "128" in table  # 2 ranks x 64 bytes aggregated


def test_trace_merge_best_effort_without_clock_sync():
    t0 = {"traceEvents": [{"name": "a", "cat": "Forward", "ph": "X",
                           "ts": 100.0, "dur": 1.0, "pid": 0, "tid": 0}]}
    t1 = {"traceEvents": [{"name": "b", "cat": "Forward", "ph": "X",
                           "ts": 900.0, "dur": 1.0, "pid": 0, "tid": 0}]}
    merged = tm.merge_traces([t0, t1])
    assert merged["metadata"]["alignment"] == "best_effort"
    real = [e for e in merged["traceEvents"] if e.get("ph") != "M"]
    # each unsynced trace is pinned to the merged origin
    assert [e["ts"] for e in real] == [0.0, 0.0]
    assert {e["pid"] for e in real} == {0, 1}
    with pytest.raises(ValueError):
        tm.merge_traces([t0, t1], ranks=[3, 3])


def test_trace_merge_cli_round_trip(tmp_path):
    t0 = _rank_trace(0, 0, 0, [("fwd", "Forward", 1.0, 2.0, None)])
    t1 = _rank_trace(1, 0, 0, [("fwd", "Forward", 3.0, 2.0, None)])
    p0, p1 = tmp_path / "r0.json", tmp_path / "r1.json"
    p0.write_text(json.dumps(t0))
    p1.write_text(json.dumps(t1))
    out = tmp_path / "merged.json"
    rc = tm.main([str(p0), str(p1), "-o", str(out), "--summary"])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert merged["metadata"]["merged_ranks"] == [0, 1]
    real = [e for e in merged["traceEvents"] if e.get("ph") != "M"]
    assert len(real) == 2 and {e["pid"] for e in real} == {0, 1}


def test_note_rendezvous_round_trips_into_export_metadata():
    was = tm.clock_sync()
    try:
        cs = tm.note_rendezvous(3, 8)
        assert cs["rank"] == 3 and cs["world_size"] == 8
        assert cs["perf_ns"] > 0 and cs["unix_ns"] > 0
        from paddle_tpu.profiler.profiler_statistic import StatisticData

        trace = StatisticData([]).to_chrome_trace()
        assert trace["metadata"]["rank"] == 3
        assert trace["metadata"]["clock_sync"]["perf_ns"] == cs["perf_ns"]
    finally:
        tm._clock_sync[0] = was

def test_trace_merge_requests_interleaves_request_lanes(tmp_path):
    """Round 16: `--requests timeline.json` interleaves per-request lanes
    (telemetry.request_trace chrome export) with the rank lanes — request
    pids preserved (not flattened onto a rank), clock-aligned through the
    same clock_sync machinery."""
    from paddle_tpu.telemetry import request_trace as rt

    t0 = _rank_trace(0, perf_ns=1_000_000, unix_ns=2_000_000, events=[
        ("all_reduce", "Communication", 1.0, 2.0, None),
    ])
    # a request timeline whose clock maps onto the same wall clock: span at
    # clock 1500us with (perf 1000us <-> unix 2000us) sync -> wall 2500us;
    # the rank event (ts 1us, same pair) is wall 1001us = the merged origin,
    # so the request span lands 1499us after it
    req = {
        "traceEvents": [
            {"ph": "M", "name": "process_name", "pid": rt.REQUEST_PID_BASE,
             "tid": 0, "args": {"name": "request 0"}},
            {"ph": "X", "name": "decode", "cat": "serving_request",
             "pid": rt.REQUEST_PID_BASE, "tid": 0, "ts": 1500.0, "dur": 500.0,
             "args": {"rid": 0}},
            # a global engine-lane event rides along but is NOT a request
            # lane — request_lane_count must not include it
            {"ph": "X", "name": "dispatch", "cat": "serving_engine",
             "pid": 90001, "tid": 0, "ts": 1500.0, "dur": 10.0, "args": {}},
        ],
        "metadata": {"request_lanes": True,
                     "clock_sync": {"perf_ns": 1_000_000, "unix_ns": 2_000_000}},
    }
    p0, pr = tmp_path / "r0.json", tmp_path / "req.json"
    p0.write_text(json.dumps(t0))
    pr.write_text(json.dumps(req))
    out = tmp_path / "merged.json"
    rc = tm.main([str(p0), "-o", str(out), "--requests", str(pr)])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert merged["metadata"]["request_lanes"] is True
    assert merged["metadata"]["request_lane_count"] == 1
    real = [e for e in merged["traceEvents"] if e.get("ph") != "M"]
    by_pid = {e["pid"]: e for e in real}
    assert set(by_pid) == {0, 90001, rt.REQUEST_PID_BASE}
    # both lanes share the wall clock: rank event pins the origin, the
    # request span lands 1499us later (2500us wall - 1001us origin)
    assert abs(by_pid[0]["ts"] - 0.0) < 1e-6
    assert abs(by_pid[rt.REQUEST_PID_BASE]["ts"] - 1499.0) < 1e-6
    # the real thing round-trips too: a live recorder's export merges clean
    rec = rt.RequestTraceRecorder(capacity=64)
    rec.add_span("request", "queue", 0.001, 0.002, rid=7)
    rec.add_event("request", "finish", 0.002, rid=7, attrs={"outcome": "completed"})
    live = tmp_path / "live.json"
    live.write_text(json.dumps(rt.to_chrome_trace(rec)))
    rc = tm.main([str(p0), "-o", str(out), "--requests", str(live)])
    assert rc == 0
    merged = json.loads(out.read_text())
    pids = {e["pid"] for e in merged["traceEvents"] if e.get("ph") != "M"}
    assert rt.REQUEST_PID_BASE + 7 in pids and 0 in pids
