"""The sparse-attention configuration's share of the yardstick, on the CPU: its
parameter, FLOP and byte counts against hand counts, the configuration file
against the catalog, YaRN's frequencies against the formula, the four new
readers on a ring and a trace written by hand (and None on a program without
the counters), the plain reference's grouped routing and its shares, the mix's
parameters, and the tiny cell through the serving loop with its planted fault:
the reference with the selection switched off must fail the cell's limits."""
import contextlib
import io
import json
import math
import os
import types

import numpy as np
import pytest

from chipbench import flops_dsa_moe as fl
from chipbench import flops_mla_moe as flm
from chipbench import peaks, run, traffic, weights
from chipbench.layer_metrics import _program_spans as ps

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "deepseek-v32-ep16-l5"
CELL = CONFIG + ".longctx-open"
NEW_READERS = ("dsa_index_roofline", "dsa_sparse_attn_roofline", "dsa_selected_pct", "dsa_moe_serve_mfu_pct")


def real_cfg():
    with open(os.path.join(ROOT, "chipbench", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny_cfg():
    with open(os.path.join(DATA, "configs", "tiny-deepseek.json")) as f:
        return json.load(f)


def reader(name):
    return run.load_module("layer_metrics", name).read


@pytest.fixture(scope="module")
def ref():
    return run.load_module("reference", CONFIG)


def test_parameter_counts_are_the_hand_counts(ref):
    c = real_cfg()
    mla = 7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 + 16384 * 7168
    assert flm.mla_matmul_params(c) == mla and round(mla / 1e6, 2) == 187.11
    assert fl.indexer_matmul_params(c) == 1536 * 8192 + 7168 * 128 + 7168 * 64
    assert round(fl.indexer_matmul_params(c) / 1e6, 2) == 13.96
    assert round(flm.expert_params(c) / 1e6, 2) == 44.04 and round(flm.dense_mlp_params(c) / 1e6, 2) == 396.36
    assert round(flm.sparse_mlp_matmul_params_outside_experts(c) / 1e6, 2) == 45.88
    assert round(fl.held_params(c) / 1e9, 3) == 4.636 and round(2 * fl.held_params(c) / 1e9, 2) == 9.27  # GB in bfloat16
    # the reference's leaves are the same count, leaf by leaf
    assert sum(int(np.prod(s[0])) for s in ref.leaf_specs(c).values()) == fl.held_params(c)
    # the published model, by the same functions (its drafting layer not counted)
    pub = dict(c, num_hidden_layers=61, first_k_dense_replace=3, experts_held=[0, 256], vocab_size=129280)
    assert round(fl.held_params(pub) / 1e9) == 672


def test_the_configuration_file_holds_the_catalogs_numbers():
    c = real_cfg()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V3.2")
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k) != v}
    assert differs == set(c["reduced"]) - {"experts_held"} and set(c["reduced_why"]) == set(c["reduced"])
    assert all(k in c for k in ("published", "deployment", "assumed", "precision"))
    assert (c["index_topk"], c["index_n_heads"], c["index_head_dim"], c["n_group"], c["topk_group"]) == (2048, 64, 128, 8, 4)


def test_yarn_frequencies_follow_the_formula(ref):
    """The reference's and the program's inverse frequencies against the
    formula written out: theta 10000 over 64 columns, factor 40 over 4096."""
    from paddle_tpu.models.mla_moe import yarn_inv_freq, yarn_softmax_factor

    c = real_cfg()
    f = [10000.0 ** (-2 * i / 64) for i in range(32)]
    low = max(0, math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000))))
    high = min(63, math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000))))
    assert (low, high) == (10, 23)
    want = []
    for i in range(32):
        ramp = min(1.0, max(0.0, (i - low) / (high - low)))
        want.append(f[i] / 40 * ramp + f[i] * (1 - ramp))
    np.testing.assert_allclose(ref.yarn_inv_freq(c), want, rtol=1e-12)
    np.testing.assert_allclose(yarn_inv_freq(64, 10000, 40, 4096, 32, 1), want, rtol=1e-12)
    assert want[0] == 1.0 and want[31] == pytest.approx(f[31] / 40)
    m = 0.1 * math.log(40) + 1
    assert ref.softmax_scale(c) == pytest.approx(192 ** -0.5 * m * m)
    assert yarn_softmax_factor(40, 1) == pytest.approx(m * m) and round(m, 4) == 1.3689


def test_flops_and_bytes_by_hand():
    c = real_cfg()
    peak = peaks.peak_for("TPU v5 lite")
    assert fl.index_pair_flops(c) == 2 * 64 * 128 and fl.index_key_bytes(c) == 256
    assert fl.sparse_pair_flops(c) == 2 * 128 * (576 + 512) and fl.entry_bytes(c) == 1280
    assert fl.layer_flops_per_token(c) == 2 * (5 * (flm.mla_matmul_params(c) + fl.indexer_matmul_params(c))
                                               + 3 * 7168 * 18432 + 4 * (7168 * 256 + 3 * 7168 * 2048))
    # a chunk of 128 queries at 8k: 17 GFLOP a layer bound the index scores; a decode row's are bound by its keys
    chunk_live = 128 * 8064 + 128 * 129 // 2
    assert fl.dsa_index_least_seconds(chunk_live, 8192, c, peak) == pytest.approx(5 * chunk_live * 16384 / 197e12)
    assert fl.dsa_index_least_seconds(8192, 8192, c, peak) == pytest.approx(5 * 8192 * 256 / 819e9)
    # 128 x 2048 chosen pairs: 73 GFLOP and 335 MB a layer, the bytes the larger by a little
    sel = 128 * 2048
    assert fl.dsa_sparse_attn_least_seconds(sel, c, peak) == pytest.approx(5 * sel * 1280 / 819e9)
    assert 5 * sel * 278528 / 197e12 < 5 * sel * 1280 / 819e9 < 1.2 * 5 * sel * 278528 / 197e12


class Ring:
    def __init__(self, recs):
        self.recs = list(recs)

    def records(self):
        return list(self.recs)

    def evicted(self):
        return 0


def hand_ring(with_new_args=True):
    """Inside the window: a chunk step (3 rows at 5000, 6000, 7000 and 128
    tokens of a prompt from position 4096), a chunk step below index_topk (no
    rows, 128 tokens from 1024), a plain step of 2 rows; one step before the
    window."""
    moe = {"moe_assignments": 270, "moe_experts_touched": 50, "moe_layers": 4}

    def index(live, selected, sparse, scored=0, attended=0, keys=0):
        if not with_new_args:
            return {}
        out = {"index_positions_live": live, "index_positions_selected": selected, "sparse_queries": sparse}
        if scored:
            out.update(index_positions_scored=scored, sparse_positions_attended=attended, index_keys_read=keys)
        return out

    rows_live = 5000 + 6000 + 7000
    chunk_live = 128 * 4096 + 128 * 129 // 2
    low_live = 128 * 1024 + 128 * 129 // 2
    return [
        ("engine.decode", 90.0, 90.5, 1, 0, None, {"rows": 7, "bucket": 8, "context": 900, "chunk_tokens": 0, **moe}),
        ("engine.decode", 100.10, 100.14, 3, 2, None,
         {"rows": 3, "bucket": 8, "context": rows_live + 4096 + 128, "chunk_tokens": 128, "chunk_context": 4096, **moe,
          **index(rows_live + chunk_live, 131 * 2048, 131, rows_live + chunk_live, 131 * 2048, rows_live + 4224)}),
        ("sched.step", 100.0, 100.2, 2, 0, None, {"produced": 3, "prompt_tokens": 128, "chunk_tokens": 128}),
        ("engine.decode", 100.30, 100.32, 5, 4, None,
         {"rows": 0, "bucket": 8, "context": 1024 + 128, "chunk_tokens": 128, "chunk_context": 1024, **moe,
          **index(low_live, low_live, 0)}),
        ("sched.step", 100.3, 100.4, 4, 0, None, {"produced": 0, "prompt_tokens": 128, "chunk_tokens": 128}),
        ("engine.decode", 100.50, 100.51, 7, 6, None,
         {"rows": 2, "bucket": 2, "context": 9000, "chunk_tokens": 0, **moe,
          **index(9000, 4096, 2, 9000, 4096, 9000)}),
        ("sched.step", 100.5, 100.6, 6, 0, None, {"produced": 2, "prompt_tokens": 0, "chunk_tokens": 0}),
    ]


def serve_ctx(ir=None, cfg=None):
    return types.SimpleNamespace(
        ir=ir, peak=peaks.peak_for("TPU v5 lite"), cfg=cfg or real_cfg(),
        mix={"loop": "open", "engine": {"max_batch": 8}}, events=[],
        spans=types.SimpleNamespace(records=[("window", 100.0, 101.0)]),
        facts={"t_start": 100.0, "t_end": 101.0, "window_s": 1.0, "open_loop": True})


def hand_ir():
    """A synthetic reduced trace: 2 ms of `dsa_index`, 8 ms of `mla_sparse_paged_attn`."""
    return {"devices": {"/device:TPU:0": [("dsa_index.7", "custom-call", 0.10e9, 1.5e6),
                                           ("dsa_index.8", "custom-call", 0.30e9, 0.5e6),
                                           ("mla_sparse_paged_attn.3", "custom-call", 0.2e9, 5e6),
                                           ("mla_sparse_paged_attn.4", "custom-call", 0.6e9, 3e6),
                                           ("mla_paged_attn.1", "custom-call", 0.65e9, 1e6),
                                           ("fusion.1", "fusion:kOutput", 0.7e9, 5e6)]},
            "spans": [("window", 0.0, 1e9)]}


def test_new_readers_on_a_hand_written_ring_and_trace(monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: Ring(hand_ring()))
    ctx = serve_ctx(hand_ir())
    c = ctx.cfg
    rows_live, chunk_live, low_live = 18000, 128 * 4096 + 8256, 128 * 1024 + 8256
    scored = rows_live + chunk_live + 9000
    keys = rows_live + 4224 + 9000
    least = max(5 * scored * 16384 / 197e12, 5 * keys * 256 / 819e9)
    assert reader("dsa_index_roofline")(ctx) == pytest.approx(100.0 * least / 2e-3)
    attended = 131 * 2048 + 4096
    assert reader("dsa_sparse_attn_roofline")(ctx) == pytest.approx(100.0 * 5 * attended * 1280 / 819e9 / 8e-3)
    live = scored + low_live
    assert reader("dsa_selected_pct")(ctx) == pytest.approx(100.0 * (attended + low_live) / live)
    tokens, heads = (3 + 128) + 128 + 2, (3 + 1) + 1 + 2
    need = (tokens * fl.layer_flops_per_token(c) + heads * 2 * 7168 * 16160
            + 5 * (scored * 16384 + (attended + low_live) * 278528) + 3 * 270 * 6 * 7168 * 2048)
    assert reader("dsa_moe_serve_mfu_pct")(ctx) == pytest.approx(100.0 * need / 197e12)
    # the accepted readers this cell is appended to read the same ring
    assert reader("prompt_chunked_token_pct")(ctx) == pytest.approx(100.0)
    assert reader("chunk_step_ms_p50")(ctx) == pytest.approx(30.0)
    assert reader("moe_experts_touched_pct")(ctx) == pytest.approx(100.0 * 150 / (3 * 4 * 16))
    assert reader("decode_bucket_fill_pct")(ctx) == pytest.approx(100.0 * 5 / 18)


@pytest.mark.parametrize("ring", [None, Ring(hand_ring(with_new_args=False))], ids=["no_ring", "parents_spans"])
def test_new_readers_give_none_on_a_program_without_the_counters(monkeypatch, ring):
    """The parent's program: no ring, or engine spans without the selector's
    counters, and no such kernel in the trace; and another configuration's
    cell, whose keys lack `index_topk`."""
    monkeypatch.setattr(ps, "ring", lambda: ring)
    ir = {"devices": {"/device:TPU:0": [("mla_paged_attn.1", "custom-call", 0.7e9, 5e6)]}, "spans": [("window", 0.0, 1e9)]}
    for name in NEW_READERS:
        assert reader(name)(serve_ctx(ir)) is None, name
    monkeypatch.setattr(ps, "ring", lambda: Ring(hand_ring()))
    pangu = {k: v for k, v in real_cfg().items() if not k.startswith("index_")}
    for name in ("dsa_index_roofline", "dsa_sparse_attn_roofline", "dsa_moe_serve_mfu_pct"):
        assert reader(name)(serve_ctx(hand_ir(), pangu)) is None, name


def test_reference_routes_inside_the_best_groups_and_its_shares_add_up(ref):
    """The reference against a loop in numpy and against itself: the choice
    stays inside the 2 best of 4 groups (a group's score the sum of its two
    largest biased scores), the bias moves the choice and not the weights,
    and four shares' routed parts add up to the uncut layer's."""
    import jax
    import jax.numpy as jnp

    c = dict(tiny_cfg(), experts_held=[0, 16])
    w = weights.make(ref.layer_specs(c, 1), 9, jnp.float32)
    w = {k.split("mlp.")[1]: v for k, v in w.items() if ".mlp." in k}
    w["router_bias"] = w["router_bias"] * 5.0  # wide enough to change the choice at this size
    x = jnp.asarray(np.random.RandomState(1).randn(12, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.route(x, w, c))
        unbiased = np.asarray(ref.route(x, dict(w, router_bias=jnp.zeros(16)), c))
        s = np.asarray(jax.nn.sigmoid(x @ w["router"]))
    bias = np.asarray(w["router_bias"])
    for t in range(12):
        cc = s[t] + bias
        groups = sorted(range(4), key=lambda g: -np.sort(cc[4 * g:4 * g + 4])[-2:].sum())[:2]
        allowed = [e for g in groups for e in range(4 * g, 4 * g + 4)]
        chosen = sorted(allowed, key=lambda e: -cc[e])[:4]
        assert sorted(np.flatnonzero(got[t])) == sorted(chosen)
        np.testing.assert_allclose(got[t, chosen], 2.5 * s[t, chosen] / s[t, chosen].sum(), rtol=1e-5)
    assert (np.flatnonzero(got[0]) != np.flatnonzero(unbiased[0])).any() or (got != unbiased).any()

    def share(f, n):
        return ref.routed_part(x, dict(w, **{k: w[k][f:f + n] for k in ("experts_gate", "experts_up", "experts_down")}),
                               c, held=[f, n])

    with jax.default_matmul_precision("highest"):
        whole = share(0, 16)
        parts = [share(f, 4) for f in (0, 4, 8, 12)]
    assert float(jnp.abs(whole).max()) > 0
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), rtol=1e-5, atol=1e-6)


def test_reference_selects_exactly_topk_positions(ref):
    """`selected` on hand-made scores: min(topk, t + 1) positions a query, the
    largest, never one past the query's own."""
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    raw = rng.randn(40, 40).astype(np.float32)
    scores = np.where(np.arange(40)[None, :] <= np.arange(40)[:, None], raw, -np.inf)
    mask = np.asarray(ref.selected(jnp.asarray(scores), 8))
    for t in range(40):
        want = set(np.argsort(-scores[t, :t + 1])[:8])
        assert set(np.flatnonzero(mask[t])) == want and len(want) == min(8, t + 1)


def test_the_mix_is_what_the_issue_names():
    mix = traffic.load("longctx-open")
    assert (mix["loop"], mix["ramp_s"], mix["order_seed"], mix["sampling"], mix["shared_prefixes"],
            mix["trace_seconds"], mix["reference_requests"]) == ("open", 30.0, 34, "greedy", False, 3.0, 4)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 8192, "sigma": 0.4, "min": 4096, "max": 16384}
    assert mix["output_len"] == {"dist": "lognormal", "median": 320, "sigma": 0.6, "min": 64, "max": 1024}
    assert mix["engine"] == {"max_batch": 8, "max_seq_len": 17408, "block_size": 16, "num_blocks": 8705,
                             "prefill_buckets": [4096, 8192], "decode_batch_buckets": [1, 2, 4, 8]}
    assert mix["order_seed"] not in {traffic.load(n)["order_seed"] for n in ("chat-open", "longdoc-open", "reason-open")}
    plan = traffic.requests(mix, 2 ** 31 + 5, 40.0, 16160)
    lens = np.asarray([len(p) for _, p, _ in plan])
    assert lens.min() >= 4096 and lens.max() <= 16384 and max(n for _, _, n in plan) <= 1024
    assert all(1 <= t < 16160 for _, p, _ in plan[:2] for t in p)
    assert all(int(l) + n <= 17408 for l, (_, _, n) in zip(lens, plan))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and bench["workloads"][-1] is cell
    config = bench["configs"][-1]
    assert config["name"] == cell["config"] and config["file"] == "chipbench/configs/deepseek-v32-ep16-l5.json"
    for text in (cell["why"], config["why"], config["source"]):  # the driver refuses a longer line before any run
        assert 1 <= len(text) <= 200 and text.isprintable()
    reported = {m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert {"serve_tokens_per_s", "setup_s"} <= reported <= {"serve_tokens_per_s", "ttft_p95_ms", "setup_s"}
    for name in NEW_READERS:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"]  # a later cell of this family may be appended


def test_tiny_sparse_cell_through_the_serving_loop_and_its_planted_fault():
    """The whole path at a tiny size (index_topk 16 against contexts of 40-190):
    builder, seeded weights, engine and scheduler over the latent pool and its
    index array (bucketed prefills and prompts in chunks beside decode rows),
    the reference, the int8 control and the planted fault after the window.
    The served tokens pass the cell's limits against the reference and FAIL
    them against the reference with the selection switched off."""
    from paddle_tpu.profiler import utils

    utils.clear()
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        run.main(["--workload", "tiny-deepseek.tiny-longctx", "--seed", str(2 ** 31 + 34), "--seconds", "1.5",
                  "--trace", "0", "--control", "1",
                  "--benchmark", os.path.join(DATA, "BENCHMARK-deepseek.json")],
                 allow_cpu=True, data_root=DATA)
    lines = [json.loads(ln) for ln in out.getvalue().strip().splitlines() if ln.startswith("{")]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, (line, err.getvalue()[-2000:])
    assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms", "setup_s"}
    assert line["compared"]["pool_pages_held_after_drain"]["value"] == 0.0
    (fault,) = [ln for ln in lines if ln.get("chipbench") == "fault control"]
    limits = traffic_limits()
    assert fault["fault"] == "no_select"
    assert fault["gap_max"] > limits["served_logit_gap_max"] and fault["gap_mean"] > limits["served_logit_gap_mean"]
    assert fault["gap_mean"] > 4 * line["compared"]["served_logit_gap_mean"]["value"]
    recs = utils.records()
    decodes = [r[6] for r in recs if r[0] == "engine.decode"]
    chunked = [d for d in decodes if d["chunk_tokens"]]
    assert chunked and all("index_positions_live" in d and d["moe_layers"] == 2 for d in decodes)
    assert any(d.get("sparse_queries") for d in decodes)
    assert all(d["index_positions_selected"] <= d["index_positions_live"] for d in decodes)
    steps = [r[6] for r in recs if r[0] == "sched.step" and r[6]]
    assert sum(s["index_positions_live"] for s in steps) == sum(  # the engine calls inside a step (not the warm-up's)
        r[6]["index_positions_live"] for r in recs if r[0] in ("engine.decode", "engine.prefill") and r[4])


def traffic_limits():
    with open(os.path.join(DATA, "traffic", "tiny-longctx.json")) as f:
        return json.load(f)["limits"]
