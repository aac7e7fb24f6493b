"""Compilation-lifecycle observability: the compile-event LEDGER (every
lower()/compile() across the four entry points emits origin/outcome events
with paddle_tpu_compile_* telemetry; hits are counter-only), the cold-start
report over it, and the engine's in-process sharing of bucket executables.
The cache that outlives a process is JAX's: tests/test_persistent_cache.py.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import compile_cache as cc
from paddle_tpu import telemetry as tm
from paddle_tpu.inference.engine import clear_shared_executables

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def telemetry_on():
    was = tm.enabled()
    tm.enable()
    yield
    if not was:
        tm.disable()


@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models.llama import llama_tiny

    paddle.seed(0)
    m = llama_tiny(num_key_value_heads=2)
    m.eval()
    return m


def _tiny_engine(model, **kw):
    from paddle_tpu.inference.engine import InferenceEngine

    kw.setdefault("max_seq_len", 32)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 2)
    kw.setdefault("decode_batch_buckets", (2,))
    return InferenceEngine(model, **kw)


# ---------------------------------------------------------------------------
# fingerprints + topology keys
# ---------------------------------------------------------------------------

def test_fingerprint_stability_and_aval_signature():
    assert cc.fingerprint_text("abc") == cc.fingerprint_text("abc")
    assert cc.fingerprint_text("abc") != cc.fingerprint_text("abd")
    s1 = cc.aval_signature([jax.ShapeDtypeStruct((2, 3), jnp.float32)])
    s2 = cc.aval_signature([jax.ShapeDtypeStruct((2, 3), jnp.bfloat16)])
    s3 = cc.aval_signature([jax.ShapeDtypeStruct((3, 2), jnp.float32)])
    assert len({s1, s2, s3}) == 3  # dtype and shape both participate


def test_entry_key_separates_disjoint_same_shape_submeshes():
    """The fleet-sharing bugfix: two replicas on DISJOINT same-shape
    submeshes compile executables pinned to different devices — their cache
    keys must differ or replica B runs on replica A's devices."""
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device test mesh")
    m1 = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "tp"))
    m2 = Mesh(np.array(devs[4:8]).reshape(2, 2), ("dp", "tp"))
    meta1, meta2 = cc.topology_meta(m1), cc.topology_meta(m2)
    assert meta1["mesh_shape"] == meta2["mesh_shape"]
    assert meta1["mesh_devices"] != meta2["mesh_devices"]
    assert cc.entry_key("f" * 32, meta1) != cc.entry_key("f" * 32, meta2)


# ---------------------------------------------------------------------------
# ledger
# ---------------------------------------------------------------------------

def test_ledger_events_and_hit_counter_only(telemetry_on):
    cc.reset()
    before = cc.summary()
    serial0 = cc.ledger.last_serial()
    cc.record("serving", "prefill_8", "miss", seconds=0.25, fingerprint="ab")
    cc.record("serving", "prefill_8", "hit")
    cc.record("serving", "prefill_8", "hit")
    cc.record("serving", "prefill_8", "shared", seconds=0.01)
    evs = cc.events(since_serial=serial0)
    # hits are counter-only: per-dispatch events would flood the bounded
    # store out of its rare compile-path events
    assert [e["outcome"] for e in evs] == ["miss", "shared"]
    assert evs[0]["seconds"] == 0.25 and evs[0]["fingerprint"] == "ab"
    after = cc.summary()
    assert after["hits"] - before["hits"] == 3
    assert after["misses"] - before["misses"] == 1
    assert after["available"]


def test_ledger_disabled_records_nothing():
    was = tm.enabled()
    tm.disable()
    try:
        serial0 = cc.ledger.last_serial()
        assert cc.record("serving", "x", "miss", seconds=1.0) is None
        assert cc.events(since_serial=serial0) == []
    finally:
        if was:
            tm.enable()


def test_ledger_dump_roundtrip(tmp_path, telemetry_on):
    cc.reset()
    cc.record("to_static", "step", "miss", seconds=0.5)
    p = cc.ledger.dump_json(str(tmp_path / "ledger.json"))
    doc = cc.ledger.load_dump(p)
    assert doc["version"] == 1
    assert any(e["origin"] == "to_static" for e in doc["events"])
    assert doc["summary"]["available"]


# ---------------------------------------------------------------------------
# engine: in-process sharing
# ---------------------------------------------------------------------------

def test_engine_inprocess_sharing_outcome_shared(tiny_model, telemetry_on):
    clear_shared_executables()
    cc.reset()
    a = _tiny_engine(tiny_model)
    a.prewarm()
    n = a.bucket_stats["compiles"]
    b = _tiny_engine(tiny_model)
    b.prewarm()
    assert b.bucket_stats.get("compiles", 0) == 0
    assert b.bucket_stats.get("shared", 0) == n
    shared_evs = cc.events(outcome="shared")
    assert len([e for e in shared_evs if e["origin"] == "serving"]) == n
    # and the shared executable really answers
    ids_a = a.generate([[1, 2, 3]], max_new_tokens=3)
    ids_b = b.generate([[1, 2, 3]], max_new_tokens=3)
    assert ids_a == ids_b


def test_fleet_prewarm_compiles_once(tiny_model, telemetry_on):
    """Satellite 1: a same-signature replica fleet compiles each bucket
    ONCE — replica 0 pays the misses, the rest adopt via the shared
    registry."""
    from paddle_tpu.inference.fleet import ReplicaFleet

    clear_shared_executables()
    cc.reset()
    engines = [_tiny_engine(tiny_model) for _ in range(2)]
    fl = ReplicaFleet(engines)
    stats = fl.prewarm()
    assert stats[0]["compiles"] >= 2 and stats[0].get("shared", 0) == 0
    assert stats[1].get("compiles", 0) == 0
    assert stats[1].get("shared", 0) == stats[0]["compiles"]


# ---------------------------------------------------------------------------
# the other entry points: to_static, static Executor, fused optimizer
# ---------------------------------------------------------------------------

def test_to_static_ledger_miss_then_hit(telemetry_on):
    from paddle_tpu import nn

    paddle.seed(11)
    m = nn.Linear(4, 2)
    f = paddle.jit.to_static(lambda x: m(x) * 2)
    x = paddle.ones([2, 4])
    serial0 = cc.ledger.last_serial()
    eager = f(x).numpy()  # first call is the eager recording run
    out1 = f(x).numpy()  # the compile is on call 2
    (ev,) = [e for e in cc.events(since_serial=serial0)
             if e["origin"] == "to_static"]
    assert ev["outcome"] == "miss" and ev["seconds"] > 0
    assert ev["signature"].startswith("n_state=")
    # the entry's own program serves call 3: no second event
    out2 = f(x).numpy()
    assert [e for e in cc.events(since_serial=serial0)
            if e["origin"] == "to_static"] == [ev]
    np.testing.assert_array_equal(out1, out2)
    np.testing.assert_allclose(eager, out1, rtol=1e-6)


def test_static_executor_ledger_miss_then_hit(telemetry_on):
    from paddle_tpu import static

    main = static.Program()
    with static.program_guard(main, static.Program()):
        x = static.data("x", [2, 4], "float32")
        y = paddle.matmul(x, paddle.ones([4, 2])) + 1.0
    exe = static.Executor()
    feed = np.arange(8, dtype="float32").reshape(2, 4)

    serial0 = cc.ledger.last_serial()
    hits0 = cc.summary()["hits"]
    (out1,) = exe.run(main, feed={"x": feed}, fetch_list=[y])
    (ev,) = [e for e in cc.events(since_serial=serial0)
             if e["origin"] == "static_executor"]
    assert ev["outcome"] == "miss" and ev["seconds"] > 0
    assert ev["signature"] == "1feeds"
    # same program + feed shapes: the replay's own cache serves it, which
    # the ledger counts as a hit and files no event for
    (out2,) = exe.run(main, feed={"x": feed}, fetch_list=[y])
    assert [e for e in cc.events(since_serial=serial0)
            if e["origin"] == "static_executor"] == [ev]
    assert cc.summary()["hits"] == hits0 + 1
    np.testing.assert_array_equal(out1, out2)
    np.testing.assert_array_equal(out1, feed @ np.ones((4, 2), "float32") + 1.0)


def test_fused_optimizer_ledger_event(telemetry_on):
    from paddle_tpu import nn

    paddle.set_flags({"FLAGS_fused_optimizer": True})
    try:
        paddle.seed(3)
        m = nn.Linear(8, 8)
        opt = paddle.optimizer.AdamW(0.01, parameters=m.parameters())
        serial0 = cc.ledger.last_serial()
        loss = (m(paddle.ones([2, 8])) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    finally:
        paddle.set_flags({"FLAGS_fused_optimizer": False})
    evs = [e for e in cc.events(since_serial=serial0)
           if e["origin"] == "fused_optimizer"]
    assert evs and all(e["outcome"] == "miss" for e in evs)
    assert all(e["fingerprint"] for e in evs)


# ---------------------------------------------------------------------------
# report surfaces: perf_report section, cold-start decomposition, CLIs
# ---------------------------------------------------------------------------

def test_perf_report_compilation_section(telemetry_on):
    from paddle_tpu.profiler import perf_attribution as pa

    cc.record("serving", "prefill_8", "miss", seconds=0.1)
    rep = pa.perf_report()
    pa.validate_report(rep)
    comp = rep["compilation"]
    assert comp["available"]
    assert "serving" in comp["by_origin"]
    # a malformed section fails validation
    bad = dict(rep)
    bad["compilation"] = {"available": True}  # missing the rollup keys
    with pytest.raises(ValueError, match="compilation section"):
        pa.validate_report(bad)


def test_cold_start_report_decomposition(telemetry_on):
    """Components are contiguous by construction, so they sum to the wall
    (consistency == 1.0 on a synthetic airtight timeline)."""
    cc.reset()
    t0 = 100.0
    cc.ledger.mark("engine_load_start", t0)
    cc.ledger.span("engine_init", t0, t0 + 0.5)
    cc.ledger.span("prewarm", t0 + 0.5, t0 + 3.0)
    cc.record("serving", "prefill_8", "miss", seconds=1.0)
    cc.ledger._events[-1]["t_end"] = t0 + 1.8  # land inside the prewarm span
    cc.record("serving", "decode_2", "shared", seconds=0.2)
    cc.ledger._events[-1]["t_end"] = t0 + 2.0
    cc.ledger.mark("first_token", t0 + 3.4)
    rep = cc.cold_start_report()
    assert rep["available"]
    assert abs(rep["wall_s"] - 3.4) < 1e-6
    comps = rep["components"]
    assert abs(sum(comps.values()) - rep["wall_s"]) <= 0.05 * rep["wall_s"]
    assert abs(rep["consistency"] - 1.0) <= 0.05
    assert comps["engine_init_s"] == pytest.approx(0.5)
    assert comps["prewarm_compile_s"] == pytest.approx(1.0)
    assert comps["prewarm_host_s"] == pytest.approx(1.5)
    assert rep["outcomes"] == {"miss": 1, "shared": 1}
    # no timeline -> explicitly unavailable, never a crash
    cc.reset_timeline()
    assert not cc.cold_start_report()["available"]


def test_report_cli_subprocess(tmp_path, telemetry_on):
    cc.reset()
    t0 = 10.0
    cc.ledger.mark("engine_load_start", t0)
    cc.ledger.span("engine_init", t0, t0 + 0.2)
    cc.ledger.mark("first_token", t0 + 1.0)
    dump = cc.ledger.dump_json(str(tmp_path / "dump.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.compile_cache", "report",
         "-i", dump, "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    rep = json.loads(r.stdout)
    assert rep["available"] and abs(rep["wall_s"] - 1.0) < 1e-6
    # unreadable dump -> exit 2 with a message, not a traceback
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.compile_cache", "report",
         "-i", str(tmp_path / "nope.json")],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert r.returncode == 2 and "unreadable" in r.stderr
