"""The latent-attention configuration's share of the yardstick, on the CPU:
its FLOPs and bytes against hand counts, the four new readers on a ring and a
trace written by hand (and None on a program without the spans), the plain
reference's shares summed over four, the mix's parameters, and the tiny cell
through the serving loop."""
import contextlib
import io
import json
import os
import types

import numpy as np
import pytest

from chipbench import flops_mla_moe as fl
from chipbench import peaks, run, traffic, weights
from chipbench.layer_metrics import _program_spans as ps

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "openpangu-ultra-moe-718b-ep16-l5"
CELL = CONFIG + ".longdoc-open"
NEW_READERS = ("mla_paged_attn_roofline", "gated_moe_gmm_roofline", "mla_moe_serve_mfu_pct", "chunk_step_ms_p50")


def real_cfg():
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny_cfg():
    with open(os.path.join(DATA, "configs", "tiny-pangu.json")) as f:
        return json.load(f)


def reader(name):
    return run.load_module("layer_metrics", name).read


def test_parameter_counts_are_the_hand_counts():
    c = real_cfg()
    mla = 7680 * 1536 + 1536 * 24576 + 7680 * 576 + 512 * 32768 + 16384 * 7680
    assert fl.mla_matmul_params(c) == mla and round(mla / 1e6, 2) == 196.58
    assert fl.expert_params(c) == 3 * 7680 * 2048 and round(fl.expert_params(c) / 1e6, 2) == 47.19
    assert round(fl.dense_mlp_params(c) / 1e6, 2) == 424.67
    assert round(fl.sparse_mlp_matmul_params_outside_experts(c) / 1e6, 2) == 49.15
    assert round(fl.held_params(c) / 1e9, 3) == 4.919       # 9.84 GB in bfloat16
    # the reference's leaves are the same count, leaf by leaf
    ref = run.load_module("reference", CONFIG)
    assert sum(int(np.prod(s[0])) for s in ref.leaf_specs(c).values()) == fl.held_params(c)
    # the published model, by the same functions
    pub = dict(c, num_hidden_layers=61, first_k_dense_replace=3, experts_held=[0, 256], vocab_size=153600)
    assert round(fl.held_params(pub) / 1e9) == 719


def test_the_configuration_file_holds_the_catalogs_numbers():
    c = real_cfg()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "openPangu-Ultra-MoE-718B")
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k) != v}
    assert differs == set(c["reduced"]) - {"experts_held"} and set(c["reduced_why"]) == set(c["reduced"])
    assert all(k in c for k in ("published", "deployment", "assumed", "precision"))


def test_flops_and_bytes_by_hand():
    c = real_cfg()
    peak = peaks.peak_for("TPU v5 lite")
    assert fl.entry_width(c) == 576
    assert fl.absorbed_pair_flops(c) == 2 * 128 * (576 + 512) == 278528
    assert fl.expanded_pair_flops(c) == 2 * 128 * (192 + 128)
    assert fl.expert_bytes(c) == 3 * 7680 * 2048 * 2
    assert fl.layer_flops_per_token(c) == 2 * (5 * fl.mla_matmul_params(c) + 3 * 7680 * 18432
                                               + 4 * (7680 * 256 + 3 * 7680 * 2048))
    # rows alone: 3 rows whose contexts add up to 5000
    assert fl.call_pairs({"context": 5000, "chunk_tokens": 0}) == (5000, 5000)
    # rows (context 700) beside a chunk of 128 tokens from position 2048
    call = {"context": 700 + 2048 + 128, "chunk_tokens": 128, "chunk_context": 2048}
    assert fl.call_pairs(call) == (700 + 128 * 2048 + 128 * 129 / 2, 700 + 2048 + 128)
    # that call: 262,144 + 8,956 pairs x 278.5 kFLOP x 5 layers = 1.9 ms; 2,876 entries x 1,152 B x 5 = 20 us
    least = fl.mla_paged_attn_least_seconds([call], c, peak)
    assert least == pytest.approx(5 * (700 + 128 * 2048 + 8256) * 278528 / 197e12)
    # decode rows alone are bound by their bytes: 1,152 B against 278.5 kFLOP a pair is the ridge within 1%
    rows = fl.mla_paged_attn_least_seconds([{"context": 40000, "chunk_tokens": 0}], c, peak)
    assert rows == pytest.approx(max(5 * 40000 * 1152 / 819e9, 5 * 40000 * 278528 / 197e12))
    assert fl.gated_moe_gmm_least_seconds(270, 64, c, peak) == 64 * 3 * 7680 * 2048 * 2 / 819e9
    assert fl.gated_moe_gmm_least_seconds(10 ** 6, 4, c, peak) == 10 ** 6 * 6 * 7680 * 2048 / 197e12


class Ring:
    def __init__(self, recs):
        self.recs = list(recs)

    def records(self):
        return list(self.recs)

    def evicted(self):
        return 0


def hand_ring(with_new_args=True):
    """Inside the window: a chunk step (5 rows and 128 tokens of a prompt from
    position 1024), a plain step of 6 rows, a bucketed prefill of 1500 tokens;
    one step before the window."""
    moe = lambda a, t: {"moe_assignments": a, "moe_experts_touched": t, "moe_layers": 4} if with_new_args else {}
    chunk = {"chunk_context": 1024} if with_new_args else {}
    return [
        ("engine.decode", 90.0, 90.5, 1, 0, None, {"rows": 7, "bucket": 8, "context": 900, "chunk_tokens": 0, **moe(9, 9)}),
        ("engine.decode", 100.10, 100.13, 3, 2, None,
         {"rows": 5, "bucket": 16, "context": 9000 + 1024 + 128, "chunk_tokens": 128, "chunk_width": 128, **chunk, **moe(270, 61)}),
        ("sched.step", 100.0, 100.2, 2, 0, None, {"produced": 5, "prompt_tokens": 128, "chunk_tokens": 128}),
        ("engine.decode", 100.30, 100.31, 5, 4, None,
         {"rows": 6, "bucket": 8, "context": 11000, "chunk_tokens": 0, "chunk_width": 128, **moe(12, 11)}),
        ("sched.step", 100.3, 100.4, 4, 0, None, {"produced": 6, "prompt_tokens": 0, "chunk_tokens": 0}),
        ("engine.prefill", 100.5, 100.7, 7, 6, None, {"tokens": 1500, "bucket": 2048, **moe(2900, 64)}),
        ("sched.step", 100.5, 100.8, 6, 0, None, {"produced": 1, "prompt_tokens": 1500, "chunk_tokens": 0}),
    ]


def serve_ctx(ir=None, cfg=None):
    return types.SimpleNamespace(
        ir=ir, peak=peaks.peak_for("TPU v5 lite"), cfg=cfg or real_cfg(),
        mix={"loop": "open", "engine": {"max_batch": 16}}, events=[],
        spans=types.SimpleNamespace(records=[("window", 100.0, 101.0)]),
        facts={"t_start": 100.0, "t_end": 101.0, "window_s": 1.0, "open_loop": True})


def hand_ir():
    """A synthetic reduced trace: 4 ms of `mla_paged_attn`, 6 ms of `moe_gmm`."""
    return {"devices": {"/device:TPU:0": [("mla_paged_attn.7", "custom-call", 0.10e9, 2.5e6),
                                           ("mla_paged_attn.8", "custom-call", 0.30e9, 1.5e6),
                                           ("moe_gmm.3", "custom-call", 0.2e9, 4e6),
                                           ("moe_gmm.4", "custom-call", 0.6e9, 2e6),
                                           ("fusion.1", "fusion:kOutput", 0.7e9, 5e6)]},
            "spans": [("window", 0.0, 1e9)]}


def test_new_readers_on_a_hand_written_ring_and_trace(monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: Ring(hand_ring()))
    ctx = serve_ctx(hand_ir())
    c = ctx.cfg
    assert reader("chunk_step_ms_p50")(ctx) == pytest.approx(30.0)
    pairs = (9000 + 128 * 1024 + 128 * 129 / 2) + 11000
    cached = (9000 + 1024 + 128) + 11000
    least = max(5 * cached * 1152 / 819e9, 5 * pairs * 278528 / 197e12)
    assert reader("mla_paged_attn_roofline")(ctx) == pytest.approx(100.0 * least / 4e-3)
    assert reader("gated_moe_gmm_roofline")(ctx) == pytest.approx(100.0 * (61 + 11 + 64) * fl.expert_bytes(c) / 819e9 / 6e-3)
    tokens, heads = (5 + 128) + 6 + 1500, (5 + 1) + 6 + 1
    need = (tokens * fl.layer_flops_per_token(c) + heads * 2 * 7680 * 19200
            + 5 * pairs * 278528 + 5 * 1500 * 1501 / 2 * 2 * 128 * 320
            + (270 + 12 + 2900) * 6 * 7680 * 2048)
    assert reader("mla_moe_serve_mfu_pct")(ctx) == pytest.approx(100.0 * need / 197e12)
    # the accepted readers this cell is appended to read the same ring
    assert reader("prompt_chunked_token_pct")(ctx) == pytest.approx(100.0 * 128 / 1628)
    assert reader("moe_experts_touched_pct")(ctx) == pytest.approx(100.0 * (61 + 11) / (2 * 4 * 16))
    assert reader("decode_bucket_fill_pct")(ctx) == pytest.approx(100.0 * 11 / 24)


@pytest.mark.parametrize("ring", [None, Ring(hand_ring(with_new_args=False))], ids=["no_ring", "parents_spans"])
def test_new_readers_give_none_on_a_program_without_the_spans(monkeypatch, ring):
    """The parent's program: no ring, or `engine.decode` spans without
    `chunk_context` and the expert counters, and no such kernel in the trace."""
    monkeypatch.setattr(ps, "ring", lambda: ring)
    ir = {"devices": {"/device:TPU:0": [("paged_attn.1", "custom-call", 0.7e9, 5e6)]}, "spans": [("window", 0.0, 1e9)]}
    for name in NEW_READERS[:3]:
        assert reader(name)(serve_ctx(ir)) is None, name
    if ring is None:
        assert reader("chunk_step_ms_p50")(serve_ctx(ir)) is None
    # another configuration's cell (the hybrid's keys): the two by-config readers stay silent
    monkeypatch.setattr(ps, "ring", lambda: Ring(hand_ring()))
    hybrid = {"hidden_size": 4096, "experts_held": [0, 128]}
    assert reader("mla_moe_serve_mfu_pct")(serve_ctx(hand_ir(), hybrid)) is None
    assert reader("gated_moe_gmm_roofline")(serve_ctx(hand_ir(), hybrid)) is None


def test_reference_shares_add_up_to_the_uncut_layer():
    """The reference against itself: four shares' routed parts add up to the
    uncut layer's; the weights of the chosen add up to the scaling factor."""
    import jax
    import jax.numpy as jnp

    ref = run.load_module("reference", CONFIG)
    c = dict(tiny_cfg(), experts_held=[0, 16])
    w = weights.make(ref.layer_specs(c, 1), 9, jnp.float32)
    w = {k.split("mlp.")[1]: v for k, v in w.items() if ".mlp." in k}
    x = jnp.asarray(np.random.RandomState(1).randn(12, 64), jnp.float32)

    def share(f, n):
        return ref.routed_part(x, dict(w, **{k: w[k][f:f + n] for k in ("experts_gate", "experts_up", "experts_down")}),
                               c, held=[f, n])

    with jax.default_matmul_precision("highest"):
        whole = share(0, 16)
        parts = [share(f, 4) for f in (0, 4, 8, 12)]
    assert float(jnp.abs(whole).max()) > 0
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.route(x, w, c).sum(-1)), 2.5, rtol=1e-5)
    assert int((np.asarray(ref.route(x, w, c)) > 0).sum(-1).max()) == 4


def test_the_mix_is_what_the_issue_names():
    mix = traffic.load("longdoc-open")
    assert (mix["loop"], mix["ramp_s"], mix["order_seed"], mix["sampling"], mix["shared_prefixes"],
            mix["trace_seconds"], mix["reference_requests"]) == ("open", 30.0, 31, "greedy", False, 3.0, 6)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 3072, "sigma": 0.6, "min": 1024, "max": 8192}
    assert mix["output_len"] == {"dist": "lognormal", "median": 160, "sigma": 0.6, "min": 32, "max": 512}
    assert mix["engine"] == {"max_batch": 16, "max_seq_len": 8704, "block_size": 16, "num_blocks": 8705,
                             "prefill_buckets": [1024, 2048, 4096, 8192], "decode_batch_buckets": [1, 2, 4, 8, 16]}
    plan = traffic.requests(mix, 2 ** 31 + 5, 40.0, 19200)
    lens = np.asarray([len(p) for _, p, _ in plan])
    assert lens.min() >= 1024 and lens.max() <= 8192 and max(n for _, _, n in plan) <= 512
    assert all(1 <= t < 19200 for _, p, _ in plan[:3] for t in p)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    reported = {m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"serve_tokens_per_s", "ttft_p95_ms", "setup_s"}
    for name in NEW_READERS:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]


def test_tiny_latent_cell_through_the_serving_loop():
    """The whole path at a tiny size: builder, seeded weights, engine and
    scheduler over the latent pool (bucketed prefills and prompts in chunks
    beside decode rows), the reference and the int8 control after the window;
    the program's ring then holds what the new readers read (a traced run
    needs a device plane: `run.py` reduces one on the chip only)."""
    from paddle_tpu.profiler import utils

    utils.clear()
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        run.main(["--workload", "tiny-pangu.tiny-longdoc", "--seed", str(2 ** 31 + 31), "--seconds", "1.5",
                  "--trace", "0", "--control", "1",
                  "--benchmark", os.path.join(DATA, "BENCHMARK-pangu.json")],
                 allow_cpu=True, data_root=DATA)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, (line, err.getvalue()[-2000:])
    assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms", "setup_s"}  # the real cell's
    assert line["compared"]["pool_pages_held_after_drain"]["value"] == 0.0
    recs = utils.records()
    decodes = [r[6] for r in recs if r[0] == "engine.decode"]
    chunked = [d for d in decodes if d["chunk_tokens"]]
    assert chunked and all("chunk_context" in d and d["moe_layers"] == 2 for d in chunked)
    assert any(r[0] == "engine.prefill" for r in recs)
    assert all(d["page_blocks_live"] <= d["page_blocks_grid"] for d in decodes)
