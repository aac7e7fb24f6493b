"""The un-forfeitable bench capture (fast tier-1 lane, NOT `slow`).

r05's driver capture was lost entirely (the r05 capture: rc=124,
parsed=null) because bench.py printed its single JSON line only after ALL
configs completed. These tests pin the round-6 contract: under an
artificially tiny `BENCH_DEADLINE_S` the run still exits 0, every stdout
line is a complete parsable JSON snapshot, and the last line lists every
config as measured or EXPLICITLY skipped — the driver can never again read
`parsed: null` from a timed-out run.
"""
import json
import os
import subprocess
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench.py")
CONFIGS = {"seq128", "passes", "seq4096", "llama3_shape", "resnet50",
           "ppocr_e2e", "serving", "fleet", "qos", "input_stream",
           "moe_longcontext"}


def _run_bench(deadline_s):
    env = dict(os.environ)
    env["BENCH_DEADLINE_S"] = str(deadline_s)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("BENCH_CHILD", None)
    return subprocess.run(
        [sys.executable, BENCH], env=env, capture_output=True, text=True,
        timeout=240,
    )


def test_tiny_deadline_yields_explicit_skips():
    r = _run_bench(0.1)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.strip()]
    assert lines, "bench printed nothing"

    # EVERY line is a complete snapshot (the driver may catch any of them)
    snaps = [json.loads(l) for l in lines]
    for s in snaps:
        assert set(s) >= {"metric", "value", "unit", "vs_baseline", "detail"}
        assert set(s["detail"]["configs"]) == CONFIGS

    last = snaps[-1]
    for k, status in last["detail"]["configs"].items():
        assert status == "skipped:deadline", (k, status)
    # the headline's skip is recorded in the detail too, not silently null
    assert last["detail"]["seq128"] == {"skipped": "deadline"}
    assert last["value"] is None
    # snapshot-and-extend: one line per resolved config plus the terminal one
    assert len(lines) >= len(CONFIGS)


def test_measured_config_carries_attribution():
    """Round-8 contract: every MEASURED config's record carries a
    `attribution` block — XLA cost/memory numbers + roofline — or an
    explicit `attribution: unavailable` marker; silence is not an option.
    Runs the real bench pipeline on a seconds-scale shrunken ERNIE (the
    dims override is recorded in the result)."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        BENCH_DEADLINE_S="200",
        BENCH_SKIP_VISION="1", BENCH_SKIP_4096="1", BENCH_SKIP_LLAMA="1",
        BENCH_SKIP_SERVING="1",  # the serving replay has its own tier-1 test
        # shrink the headline model to tier-1 scale; dims land in the record
        BENCH_STEPS="10", BENCH_BATCH="2", BENCH_SEQ="16",
        BENCH_VOCAB="256", BENCH_HIDDEN="64", BENCH_LAYERS="2",
        BENCH_FFN="128", BENCH_HEADS="4",
        # shrink the co-measured peak + the don't-even-start estimates
        BENCH_PEAK_N="256", BENCH_EST_SEQ128="5", BENCH_EST_PEAK="1",
        PADDLE_TPU_TELEMETRY="1",
    )
    env.pop("BENCH_CHILD", None)
    r = subprocess.run(
        [sys.executable, BENCH], env=env, capture_output=True, text=True,
        timeout=220,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["detail"]["configs"]["seq128"] == "measured", last["detail"]["configs"]
    assert last["detail"]["dims_override"]["hidden"] == 64

    # round-15 contract: the passes probe is measured (in a child of its
    # own, like every config) and carries the gated fusion-coverage fields
    assert last["detail"]["configs"]["passes"] == "measured", last["detail"]["configs"]
    pblock = last["detail"]["passes"]
    assert pblock["matches"]["fuse_attention"] >= 2
    assert pblock["matches"]["fuse_norm_matmul"] >= 1
    assert pblock["outputs_identical"] is True
    assert pblock["pipeline_ms"] > 0
    assert pblock["n_ops_after"] < pblock["n_ops_recorded"]

    attr = last["detail"]["attribution"]
    if attr.get("attribution") == "unavailable":
        # explicit marker: allowed only on platforms without cost analysis,
        # and it must say why
        assert attr.get("why") or attr.get("error")
    else:
        # well-formed block: real counts (CPU supports cost analysis, so
        # this is the branch this runner takes) — but NO utilization: the
        # peak table holds published TPU peaks only, and a CPU run must not
        # print a number under a device metric's name
        assert attr["flops"] > 0
        assert attr["hbm_bytes"] > 0
        assert attr["program_memory_bytes"] > 0
        assert attr["peak_hbm_bytes"] > 0
        assert attr["compile_seconds"] > 0
        assert "mfu" not in attr and "hbm_util" not in attr
        assert attr["roofline"].startswith("unavailable: no published peak")
    # every child's record names the device it ran on; the headline's rides
    # the detail, the pass probe's its own block
    for dev in (last["detail"]["device"], pblock["device"]):
        assert (dev["platform"], dev["kind"]) == ("cpu", "cpu") and dev["count"] >= 1


def test_sigterm_still_emits_terminal_snapshot():
    """Round-9 contract: the driver's timeout sends SIGTERM — bench must
    answer with a complete terminal JSON line as its LAST output (pending
    configs become explicit `skipped:sigterm`), so the driver's short
    stdout tail always contains a parsable record."""
    import select
    import signal

    env = dict(os.environ)
    env["BENCH_DEADLINE_S"] = "3000"  # deadline far away: SIGTERM is the exit
    env["JAX_PLATFORMS"] = "cpu"
    # shrink the headline so the first compile is short: SIGTERM delivery
    # waits out whatever C-level XLA call is in flight, so a full-size
    # headline compile adds ~10s of pure latency to this test
    env.update(
        BENCH_STEPS="10", BENCH_BATCH="2", BENCH_SEQ="16",
        BENCH_VOCAB="256", BENCH_HIDDEN="64", BENCH_LAYERS="2",
        BENCH_FFN="128", BENCH_HEADS="4",
        BENCH_PEAK_N="256", BENCH_EST_SEQ128="5", BENCH_EST_PEAK="1",
    )
    env.pop("BENCH_CHILD", None)
    p = subprocess.Popen(
        [sys.executable, BENCH], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    try:
        # signal as soon as the FIRST snapshot line lands (headline just
        # resolved, the other configs are pending — each needs a child
        # spawn, so they cannot all resolve in the signal-delivery gap) or
        # after 3s mid-headline, whichever comes first; a fixed sleep alone
        # races bench finishing entirely on a fast host
        select.select([p.stdout], [], [], 3.0)
        p.send_signal(signal.SIGTERM)
        out, err = p.communicate(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()
            p.communicate()
    assert p.returncode == 0, err[-2000:]
    lines = [l for l in out.strip().splitlines() if l.strip()]
    assert lines, "SIGTERM produced no terminal snapshot"
    last = json.loads(lines[-1])
    assert set(last["detail"]["configs"]) == CONFIGS
    for k, status in last["detail"]["configs"].items():
        assert status != "pending", (k, status)
    assert any(s.startswith("skipped:sigterm")
               for s in last["detail"]["configs"].values())


def test_input_stream_child_prefetch_wins_and_is_attributed():
    """Round-12 acceptance: the input-bound config's prefetch-ON step time
    beats prefetch-OFF on the same seeded stream, and the difference is
    attributed to the pipeline's own input_wait_s measurements (the field
    the guardian records per step). Runs the real child builder at
    seconds scale (knobs recorded in input_dims)."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", BENCH_CHILD="input_stream",
        BENCH_INPUT_SAMPLES="512", BENCH_INPUT_BATCH="16",
        BENCH_INPUT_FEATURES="256", BENCH_INPUT_HIDDEN="512",
        BENCH_INPUT_CLASSES="32", BENCH_INPUT_READER_WORK="60000",
        BENCH_INPUT_STEPS="15", PADDLE_TPU_TELEMETRY="1",
    )
    r = subprocess.run(
        [sys.executable, BENCH], env=env, capture_output=True, text=True,
        timeout=220,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["input_dims"]["reader_work"] == 60000  # shrink is recorded
    # the headline comparison: overlap must win on the same stream
    assert res["ms_per_step"] < res["prefetch_off"]["ms_per_step"], res
    assert res["final_loss"] == res["prefetch_off"]["final_loss"]
    # and the win must be explained by the pipeline's own wait metric:
    # hidden wait accounts for (most of) the step-time delta
    wa = res["wait_attribution"]
    assert wa["step_delta_ms"] > 0
    assert wa["explained_fraction"] is not None
    assert 0.5 <= wa["explained_fraction"] <= 2.0, wa
    assert res["p99_input_wait_ms"] >= 0
    assert res["samples_per_sec"] > res["prefetch_off"]["samples_per_sec"]
    assert res["verdict"]["verdict"] in (
        "starved", "input_limited", "compute"
    )
    # attribution block rides the record like every measured config
    attr = res["attribution"]
    assert attr.get("flops") or attr.get("attribution") == "unavailable"


def test_moe_longcontext_child_reports_drops():
    """ROADMAP-5, round 20: the MoE + long-context child runs COMPILED
    (to_static over the sep×ep mesh) and its record carries real
    attribution (FLOPs/HBM — never the unavailable marker), the post-step
    drop counters and the fuse_moe match count."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", BENCH_CHILD="moe_longcontext",
        BENCH_MOE_SEQ="64", BENCH_MOE_DMODEL="32", BENCH_MOE_HEADS="4",
        BENCH_MOE_KV_HEADS="2", BENCH_MOE_EXPERTS="4", BENCH_MOE_FFN="64",
        BENCH_MOE_STEPS="3", PADDLE_TPU_TELEMETRY="1",
    )
    r = subprocess.run(
        [sys.executable, BENCH], env=env, capture_output=True, text=True,
        timeout=280,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["seq"] == 64 and res["experts"] == 4  # shrink recorded
    assert res["heads"] == "4q/2kv"  # GQA shape in the record
    assert res["compiled"] is True
    assert res["tokens_per_sec"] > 0
    assert res["sep_ep_dims"]["sep"] == 1 and res["sep_ep_dims"]["ep"] == 1
    drops = res["moe_drops"]
    assert drops["routed_per_step"] == 2 * 64 * 2  # 2 layers x T x top_k
    assert 0 <= drops["dropped_per_step"] <= drops["routed_per_step"]
    assert drops["per_layer"]["moe0"]["routed"] == 128
    # the compiled config carries MEASURED attribution — regressing back
    # to the explicit unavailable marker is a perf_gate hard failure now
    attr = res["attribution"]
    assert "attribution" not in attr, attr
    assert attr["program"] == "moe_longcontext_step"
    assert attr["flops"] > 0 and attr["hbm_bytes"] > 0
    # counts, not a utilization: this runner is a CPU (no published peak)
    assert "mfu" not in attr and attr["roofline"].startswith("unavailable")
    assert res["device"]["platform"] == "cpu"
    # the fusion probe: both layers' dispatch->expert->combine chains match
    assert res["matches"]["fuse_moe"] == 2


def test_moe_longcontext_eager_escape_hatch():
    """BENCH_MOE_EAGER=1 restores the eager step: the record says so
    (compiled false, explicit unavailable attribution naming the hatch)
    and the drop counters still flow through the same post-step read."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", BENCH_CHILD="moe_longcontext",
        BENCH_MOE_EAGER="1",
        BENCH_MOE_SEQ="64", BENCH_MOE_DMODEL="32", BENCH_MOE_HEADS="4",
        BENCH_MOE_KV_HEADS="2", BENCH_MOE_EXPERTS="4", BENCH_MOE_FFN="64",
        BENCH_MOE_STEPS="3", PADDLE_TPU_TELEMETRY="1",
    )
    r = subprocess.run(
        [sys.executable, BENCH], env=env, capture_output=True, text=True,
        timeout=280,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["compiled"] is False
    assert res["attribution"]["attribution"] == "unavailable"
    assert "BENCH_MOE_EAGER" in res["attribution"]["why"]
    assert res["moe_drops"]["routed_per_step"] == 2 * 64 * 2


def test_deadline_skip_reason_survives_env_skips():
    env = dict(os.environ)
    env.update(
        BENCH_DEADLINE_S="0.1", JAX_PLATFORMS="cpu",
        BENCH_SKIP_VISION="1", BENCH_SKIP_4096="1", BENCH_SKIP_LLAMA="1",
    )
    env.pop("BENCH_CHILD", None)
    r = subprocess.run(
        [sys.executable, BENCH], env=env, capture_output=True, text=True,
        timeout=240,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    cfg = last["detail"]["configs"]
    # env skips and deadline skips stay distinguishable in the record
    assert cfg["resnet50"] == "skipped:env"
    assert cfg["ppocr_e2e"] == "skipped:env"
    assert cfg["seq4096"] == "skipped:env"
    assert cfg["llama3_shape"] == "skipped:env"
    assert cfg["seq128"] == "skipped:deadline"


def test_qos_child_overload_replay_record():
    """Round-19 acceptance at tier-1 scale: the QoS child runs the
    >= 2x-capacity mixed-tenant burst for real and the record carries the
    gated fields (fairness_index, p99_tpot_gold_ms, gold_p99_vs_uncontended,
    qos_dims) plus the zero-loss/shed accounting the gate reads."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", BENCH_CHILD="qos",
        BENCH_QOS_VOCAB="512", BENCH_QOS_HIDDEN="64", BENCH_QOS_FFN="128",
        BENCH_QOS_HEADS="4", BENCH_QOS_KV_HEADS="2", BENCH_QOS_MAX_SEQ="64",
        BENCH_QOS_REQUESTS="24", BENCH_QOS_SUBMIT_PROBE="300",
        PADDLE_TPU_TELEMETRY="1",
    )
    r = subprocess.run(
        [sys.executable, BENCH], env=env, capture_output=True, text=True,
        timeout=220,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["qos_dims"]["hidden"] == 64          # shrink is recorded
    assert res["overload_factor"] >= 2.0            # the acceptance floor
    # zero-loss: every offered request is terminal exactly once
    assert res["completed"] + res["shed"] == res["n_requests"]
    assert res["shed"] == sum(res["sheds_by_reason"].values())
    # gated fields present and sane
    assert res["fairness_index"] is None or 0.0 < res["fairness_index"] <= 1.0
    assert res["p99_tpot_gold_ms"] is None or res["p99_tpot_gold_ms"] > 0
    assert "gold_p99_vs_uncontended" in res
    assert set(res["per_tenant_p99_tpot_ms"]) >= {"gold", "bronze"}
    # the round-19 BASELINE number: per-submit QoS overhead is measured
    assert isinstance(res["submit_overhead_us"], float)
    attr = res["attribution"]
    assert attr.get("flops") or attr.get("attribution") == "unavailable"


def test_parent_never_imports_jax():
    """One process per chip: the parent only sequences children (the peak
    probe, the headline and the pass probe included), so `jax` must not be
    loaded in it when it spawns a child, nor when it exits. A spy on
    subprocess.Popen records `'jax' in sys.modules` at every spawn."""
    driver = (
        "import runpy, subprocess, sys\n"
        "real = subprocess.Popen\n"
        "class Spy(real):\n"
        "    def __init__(self, *a, **k):\n"
        "        print('SPAWN jax_loaded=%s' % ('jax' in sys.modules),\n"
        "              file=sys.stderr, flush=True)\n"
        "        super().__init__(*a, **k)\n"
        "subprocess.Popen = Spy\n"
        f"runpy.run_path({BENCH!r}, run_name='__main__')\n"
        "print('EXIT jax_loaded=%s' % ('jax' in sys.modules), file=sys.stderr)\n"
    )
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", BENCH_DEADLINE_S="60",
        BENCH_SKIP_VISION="1", BENCH_SKIP_4096="1", BENCH_SKIP_LLAMA="1",
        # only the peak child and the pass probe fit this budget: every
        # other estimate stays at its full-size default
        BENCH_PEAK_N="256", BENCH_EST_PEAK="1", BENCH_EST_PASSES="1",
    )
    env.pop("BENCH_CHILD", None)
    r = subprocess.run(
        [sys.executable, "-c", driver], env=env, capture_output=True,
        text=True, timeout=200,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    marks = [l for l in r.stderr.splitlines() if "jax_loaded=" in l]
    spawns = [l for l in marks if l.startswith("SPAWN")]
    assert len(spawns) >= 2, r.stderr[-2000:]  # peak, then the pass probe
    assert marks[-1].startswith("EXIT")
    assert all(l.endswith("jax_loaded=False") for l in marks), marks
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["detail"]["configs"]["passes"] == "measured"
    assert last["detail"]["all_peaks_tflops"]  # the peak child reported
