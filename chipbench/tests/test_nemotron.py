"""The hybrid configuration's share of the yardstick, on the CPU: its FLOPs
and bytes against hand counts, the readers of the expert layer's and the
state cache's counters on a ring written by hand, the plain reference's
share summed over four, and the tiny cell through the serving loop."""
import contextlib
import io
import json
import os
import types

import numpy as np
import pytest

from chipbench import flops_nemotron_h as fl
from chipbench import peaks, run, weights, xplane
from chipbench.layer_metrics import _program_spans as ps

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "nemotron3-super-120b-ep4-l11"


def real_cfg():
    with open(os.path.join(ROOT, "chipbench", "configs", CELL + ".json")) as f:
        return json.load(f)


def tiny_cfg():
    with open(os.path.join(DATA, "configs", "tiny-nemotron.json")) as f:
        return json.load(f)


def reader(name):
    return run.load_module("layer_metrics", name).read


def test_parameter_counts_are_the_hand_counts():
    c = real_cfg()
    assert fl.mamba_layer_params(c) == 4096 * 18560 + 4 * 10240 + 10240 + 3 * 128 + 8192 + 8192 * 4096 + 4096
    assert round(fl.mamba_layer_params(c) / 1e6, 2) == 109.64
    assert round(fl.attention_layer_params(c) / 1e6, 2) == 35.66
    assert round(fl.moe_layer_params_outside_experts(c) / 1e6, 2) == 54.53
    assert round(fl.expert_params(c) / 1e6, 3) == 5.505
    assert round(fl.held_params(c) / 1e9, 3) == 4.648
    # the reference's leaves are the same count, leaf by leaf
    ref = run.load_module("reference", CELL)
    assert sum(int(np.prod(s[0])) for s in ref.leaf_specs(c).values()) == fl.held_params(c)
    # the published model, by the same functions: 120.67 B
    whole = 40 * fl.mamba_layer_params(c) + 8 * fl.attention_layer_params(c) + 40 * (
        fl.moe_layer_params_outside_experts(c) + 512 * fl.expert_params(c)) + 2 * 131072 * 4096 + 4096
    assert round(whole / 1e9, 2) == 120.67


def test_flops_and_bytes_by_hand():
    c = real_cfg()
    assert fl.expert_bytes(c) == 2 * 1024 * 2688 * 2 == 11010048
    assert fl.expert_flops_per_assignment(c) == 4 * 1024 * 2688
    assert fl.scan_flops_per_token(c) == 5 * 128 * 64 * 128 + 2 * 4 * 10240
    dense = (5 * (2 * (4096 * 18560 + 8192 * 4096) + fl.scan_flops_per_token(c))
             + 2 * (2 * 4096 * 4096 + 2 * 4096 * 256)
             + 5 * 2 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376) + 2 * 4096 * 32768)
    assert fl.dense_flops_per_token(c) == dense
    peak = peaks.peak_for("TPU v5 lite")
    # 640 experts touched: bytes bound; few experts and many pairs: compute bound
    assert fl.moe_gmm_least_seconds(3520, 640, c, peak) == 640 * 11010048 / 819e9
    assert fl.moe_gmm_least_seconds(10 ** 6, 5, c, peak) == 10 ** 6 * 4 * 1024 * 2688 / 197e12


class Ring:
    def __init__(self, recs):
        self.recs = list(recs)

    def records(self):
        return list(self.recs)

    def evicted(self):
        return 0


def hand_ring(with_counters=True):
    """Two scheduler steps of a hybrid server inside the window (a decode of
    100 rows, then a prefill of 40 tokens and a decode of 101 rows), and one
    decode before it."""
    moe = lambda a, t: {"moe_assignments": a, "moe_experts_touched": t, "moe_layers": 5} if with_counters else {}
    slots = lambda n: {"state_slots": n} if with_counters else {}
    return [
        ("engine.decode", 90.0, 90.5, 1, 0, None, {"rows": 7, "bucket": 8, **moe(999, 99), **slots(7)}),
        ("engine.decode", 100.1, 100.2, 3, 2, None, {"rows": 100, "bucket": 128, **moe(2750, 630), **slots(100)}),
        ("sched.step", 100.0, 100.3, 2, 0, None, {"produced": 100, "running": 100, "waiting": 0, **slots(100)}),
        ("engine.prefill", 100.4, 100.5, 5, 4, None, {"tokens": 40, "bucket": 64, **moe(1100, 600), **slots(101)}),
        ("engine.decode", 100.5, 100.6, 6, 4, None, {"rows": 101, "bucket": 128, **moe(2805, 640), **slots(101)}),
        ("sched.step", 100.4, 100.7, 4, 0, None, {"produced": 102, "running": 101, "waiting": 3, **slots(101)}),
    ]


def serve_ctx(ir=None):
    events = [("decode", 100.2, 100, 0), ("prefill", 100.5, 40, 40), ("decode", 100.6, 101, 0),
              ("decode", 90.5, 7, 0)]
    return types.SimpleNamespace(
        ir=ir, peak=peaks.peak_for("TPU v5 lite"), cfg=real_cfg(),
        mix={"loop": "open", "engine": {"max_batch": 128}}, events=events,
        spans=types.SimpleNamespace(records=[("window", 100.0, 101.0)]),
        facts={"t_start": 100.0, "t_end": 101.0, "window_s": 1.0, "open_loop": True})


def test_readers_on_a_hand_written_ring(monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: Ring(hand_ring()))
    ctx = serve_ctx()
    pairs, toks = 2750 + 1100 + 2805, 100 + 40 + 101
    assert reader("moe_assignments_per_token")(ctx) == pytest.approx(pairs / (toks * 5))
    assert reader("moe_experts_touched_pct")(ctx) == pytest.approx(100.0 * (630 + 640) / (2 * 5 * 128))
    assert reader("ssm_state_slots_used_peak_pct")(ctx) == pytest.approx(100.0 * 101 / 128)
    need = fl.dense_flops_per_token(ctx.cfg) * toks + 4 * 1024 * 2688 * pairs
    assert reader("hybrid_serve_mfu_pct")(ctx) == pytest.approx(100.0 * need / 197e12)
    # the roofline: 2 ms of `moe_gmm` on the device inside the traced stretch
    ir = {"devices": {"/device:TPU:0": [("moe_gmm.3", "custom-call", 0.2e9, 1.2e6),
                                         ("moe_gmm.4", "custom-call", 0.6e9, 0.8e6),
                                         ("fusion.1", "fusion:kLoop", 0.7e9, 5e6)]},
          "spans": [("window", 0.0, 1e9)]}
    least = (630 + 600 + 640) * 11010048 / 819e9
    assert reader("moe_gmm_roofline")(serve_ctx(ir)) == pytest.approx(100.0 * least / 2e-3)


@pytest.mark.parametrize("ring", [None, Ring(hand_ring(with_counters=False))], ids=["no_ring", "no_counters"])
def test_readers_give_none_where_the_program_has_no_such_counter(monkeypatch, ring):
    """The parent's program: no ring, or spans without the new arguments."""
    monkeypatch.setattr(ps, "ring", lambda: ring)
    ir = {"devices": {"/device:TPU:0": [("fusion.1", "fusion:kLoop", 0.7e9, 5e6)]}, "spans": [("window", 0.0, 1e9)]}
    for name in ("moe_assignments_per_token", "moe_experts_touched_pct", "ssm_state_slots_used_peak_pct",
                 "hybrid_serve_mfu_pct", "moe_gmm_roofline"):
        assert reader(name)(serve_ctx(ir)) is None, name


def test_reference_shares_add_up_to_the_uncut_forward():
    """The reference against itself: with every expert held it is the uncut
    model; four shares' routed parts add up to the uncut layer's."""
    import jax
    import jax.numpy as jnp

    ref = run.load_module("reference", CELL)
    c = dict(tiny_cfg(), hybrid_override_pattern="E", num_hidden_layers=1, experts_held=[0, 16])
    w = weights.make(ref.layer_specs(c, 0), 9, jnp.float32)
    w = {k.split("mixer.")[1]: v for k, v in w.items() if ".mixer." in k}
    x = jnp.asarray(np.random.RandomState(1).randn(12, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_part(x, w, c)
        parts = [ref.routed_part(x, dict(w, experts_up=w["experts_up"][f:f + 4], experts_down=w["experts_down"][f:f + 4]),
                                 dict(c, experts_held=[f, 4])) for f in (0, 4, 8, 12)]
    assert float(jnp.abs(whole).max()) > 0
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), rtol=1e-5, atol=1e-6)
    # the weights of the chosen add up to the scaling factor, held or not
    np.testing.assert_allclose(np.asarray(ref.route(x, w, c).sum(-1)), 5.0, rtol=1e-5)
    assert int((np.asarray(ref.route(x, w, c)) > 0).sum(-1).max()) == 4


def test_tiny_hybrid_cell_through_the_serving_loop():
    """The whole path at a tiny size: builder, seeded weights, engine and
    scheduler over both caches, the reference and the int8 control after the
    window; the program's ring then holds the counters the readers read (a
    traced run needs a device plane: `run.py` reduces one on the chip only)."""
    from paddle_tpu.profiler import utils

    utils.clear()
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        run.main(["--workload", "tiny-nemotron.tiny-open", "--seed", str(2 ** 31 + 27), "--seconds", "1.5",
                  "--trace", "0", "--control", "1",
                  "--benchmark", os.path.join(DATA, "BENCHMARK-nemotron.json")],
                 allow_cpu=True, data_root=DATA)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms", "setup_s"}  # the real cell's
    assert line["compared"]["pool_pages_held_after_drain"]["value"] == 0.0
    recs = utils.records()
    decodes = [r[6] for r in recs if r[0] == "engine.decode"]
    assert decodes and all(d["moe_layers"] == 2 and 0 <= d["moe_assignments"] <= 2 * 4 * d["rows"]
                           and d["moe_experts_touched"] <= 2 * 8 for d in decodes)
    assert max(r[6]["state_slots"] for r in recs if r[0] == "sched.step") >= 1
    assert [r[6]["state_slots"] for r in recs if r[0] == "sched.step"][-1] == 0   # drained
