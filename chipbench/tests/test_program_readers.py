"""The readers of the program's own spans (layer_metrics/_program_spans.py
and the eleven metrics on it), on reduced traces recorded on the chip with
PR 25 (data/mistral_doc_named_ir.json.gz, data/ernie_named_ir.json.gz: the
kernels carry their names) and span records written by hand."""
import os
import types

import pytest

from chipbench import peaks, run, xplane
from chipbench.layer_metrics import _program_spans as ps

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
HOST_T0 = 5000.0  # where the hand-written host clock puts the window's start


class Ring:
    """What the readers need of paddle_tpu.profiler.utils."""

    def __init__(self, recs, evicted=0):
        self.recs, self.n = list(recs), evicted

    def records(self):
        return list(self.recs)

    def evicted(self):
        return self.n


def to_host(ir, ns):
    return HOST_T0 + (ns - xplane.window_of(ir)[0]) / 1e9


def reader(name):
    return run.load_module("layer_metrics", name).read


def serve_ctx(ir, skew_s=0.0):
    """A context as loops/serve.py leaves it, the host clock laid on the
    trace through `window` (its end `skew_s` off)."""
    lo, hi = xplane.window_of(ir)
    win = ("window", HOST_T0, to_host(ir, hi) + skew_s)
    cfg = {"hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8,
           "num_hidden_layers": 16}
    return types.SimpleNamespace(
        ir=ir, peak=peaks.peak_for("TPU v5 lite"), cfg=cfg,
        mix={"loop": "closed", "engine": {"num_blocks": 4097, "block_size": 16}},
        spans=types.SimpleNamespace(records=[win]), events=[],
        facts={"t_start": HOST_T0 - 30.0, "t_end": win[2], "open_loop": True})


def hand_written(ir):
    """Program spans for a recorded serving trace: a `sched.step` over each
    harness `sched_step` span, an `engine.decode` over each `engine_decode`
    whose last fifth is the fetch, ids and parents as the ring gives them."""
    recs, nid = [], 0
    steps = [(s, s + d) for n, s, d in ir["spans"] if n == "sched_step"]
    decodes = [(s, s + d) for n, s, d in ir["spans"] if n == "engine_decode"]
    for s0, s1 in steps:
        nid += 1
        step_id = nid
        for d0, d1 in decodes:
            if s0 <= d0 and d1 <= s1:
                nid += 1
                dec_id = nid
                cut = d0 + 0.8 * (d1 - d0)
                mid = d0 + 0.1 * (d1 - d0)
                for name, a, b in (("engine.decode.inputs", d0, mid), ("engine.decode.dispatch", mid, cut),
                                   ("engine.decode.fetch", cut, d1)):
                    nid += 1
                    recs.append((name, to_host(ir, a), to_host(ir, b), nid, dec_id, None, None))
                recs.append(("engine.decode", to_host(ir, d0), to_host(ir, d1), dec_id, step_id, None,
                             {"rows": 1, "bucket": 2, "context": 2048}))
        recs.append(("sched.step", to_host(ir, s0), to_host(ir, s1), step_id, 0, None,
                     {"produced": 1, "running": 1, "waiting": 0}))
    return recs


def by_hand_split(ir, recs):
    """The idle split the slow way: every idle nanosecond range between
    span edges goes to fetch, else to a step, else outside."""
    fetch = [(r[1], r[2]) for r in recs if r[0].endswith(".fetch")]
    step = [(r[1], r[2]) for r in recs if r[0] == "sched.step"]
    out = {"fetch": 0.0, "host": 0.0, "outside": 0.0}
    for g0, g1 in xplane.idle_gaps(ir):
        h0, h1 = to_host(ir, g0), to_host(ir, g1)
        edges = sorted({h0, h1, *[t for iv in fetch + step for t in iv if h0 < t < h1]})
        for a, b in zip(edges, edges[1:]):
            m = 0.5 * (a + b)
            key = ("fetch" if any(s <= m < e for s, e in fetch)
                   else "host" if any(s <= m < e for s, e in step) else "outside")
            out[key] += b - a
    return out


@pytest.fixture(scope="module")
def doc_ir():
    return xplane.load_ir(os.path.join(DATA, "mistral_doc_named_ir.json.gz"))


@pytest.fixture(scope="module")
def ernie_ir():
    return xplane.load_ir(os.path.join(DATA, "ernie_named_ir.json.gz"))


def test_three_idle_shares_add_up_to_the_devices_idle_share(doc_ir, monkeypatch):
    recs = hand_written(doc_ir)
    assert len([r for r in recs if r[0] == "sched.step"]) > 3
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs))
    ctx = serve_ctx(doc_ir)
    split = ps.idle_split(ctx)
    want = by_hand_split(doc_ir, recs)
    for k in ("fetch", "host", "outside"):
        assert split[k] == pytest.approx(want[k], abs=2e-6)
    idle_pct = reader("serve_device_idle_pct")(ctx)
    fetch_pct = reader("serve_idle_fetch_pct")(ctx)
    host_pct = reader("serve_idle_host_pct")(ctx)
    outside_pct = 100.0 * split["outside"] / split["window"]
    assert fetch_pct > 0 and host_pct > 0
    assert fetch_pct + host_pct + outside_pct == pytest.approx(idle_pct, abs=1e-6)


def test_a_ring_that_does_not_cover_the_window_gives_none(doc_ir, monkeypatch):
    recs = hand_written(doc_ir)
    ctx = serve_ctx(doc_ir)
    # records fell off the far end and the oldest left ended inside the window
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs[len(recs) // 2:], evicted=7))
    for name in ("serve_idle_fetch_pct", "serve_idle_host_pct", "engine_host_ms_per_step",
                 "decode_bucket_fill_pct", "paged_attn_kernel_roofline", "request_queue_p50_ms"):
        assert reader(name)(ctx) is None, name
    # evictions that ended before the stretch a reader needs do no harm
    old = ("sched.step", HOST_T0 - 100.0, HOST_T0 - 99.0, 10 ** 6, 0, None, None)
    monkeypatch.setattr(ps, "ring", lambda: Ring([old] + recs, evicted=7))
    assert reader("serve_idle_host_pct")(ctx) is not None
    # a program with no ring at all: nothing, and no error
    monkeypatch.setattr(ps, "ring", lambda: None)
    for name in ("serve_idle_fetch_pct", "engine_host_ms_per_step", "request_prompt_p95_ms",
                 "paged_attn_kernel_roofline", "to_static_host_ms_per_step"):
        assert reader(name)(ctx) is None, name


def test_window_offsets_that_disagree_give_none(doc_ir, monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: Ring(hand_written(doc_ir)))
    assert reader("serve_idle_host_pct")(serve_ctx(doc_ir, skew_s=0.1e-3)) is not None
    skewed = serve_ctx(doc_ir, skew_s=0.5e-3)   # the two ends 0.5 ms apart: no common clock
    assert ps.host_to_trace_ns(skewed) is None
    assert reader("serve_idle_fetch_pct")(skewed) is None
    assert reader("serve_idle_host_pct")(skewed) is None


def test_engine_readers_on_hand_written_spans(doc_ir, monkeypatch):
    recs = hand_written(doc_ir)
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs))
    ctx = serve_ctx(doc_ir)
    calls = [r for r in recs if r[0] == "engine.decode"]
    want = sum(0.8 * (r[2] - r[1]) for r in calls) / len(calls) * 1e3   # inputs + dispatch: four fifths
    assert reader("engine_host_ms_per_step")(ctx) == pytest.approx(want, rel=1e-6)
    assert reader("decode_bucket_fill_pct")(ctx) == pytest.approx(50.0)


def test_request_readers_keep_the_windows_requests(doc_ir, monkeypatch):
    ctx = serve_ctx(doc_ir)
    lo, hi = ctx.facts["t_start"], ctx.facts["t_end"]
    recs = []
    for rid, (submit, queue_s, prompt_s) in enumerate([
            (lo - 1.0, 0.5, 9.0),     # submitted before the window: not counted
            (lo + 1.0, 0.010, 1.0), (lo + 2.0, 0.030, 2.0), (lo + 3.0, 0.020, 40.0),   # ends after it: counted
            (hi + 0.5, 0.7, 7.0)]):   # submitted after it: not counted
        recs.append(("request.queue", submit, submit + queue_s, 100 + rid, 0, rid, None))
        recs.append(("request.prompt", submit + queue_s, submit + queue_s + prompt_s, 200 + rid, 0, rid,
                     {"mode": "streamed", "prompt_len": 64, "cached": 0}))
    monkeypatch.setattr(ps, "ring", lambda: Ring(sorted(recs, key=lambda r: r[2])))
    assert reader("request_queue_p50_ms")(ctx) == pytest.approx(20.0)
    assert reader("request_prompt_p95_ms")(ctx) == pytest.approx((2.0 + 0.9 * 38.0) * 1e3)
    ctx.facts["open_loop"] = False
    assert reader("request_queue_p50_ms")(ctx) is None


def test_paged_kernel_by_name_equals_the_shape_keyed_reader(doc_ir, monkeypatch):
    recs = hand_written(doc_ir)
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs))
    ctx = serve_ctx(doc_ir)
    win = ctx.spans.records[0]
    # what the harness's wrapper would have noted for the same calls
    ctx.events = [("decode", r[2], 1, r[6]["context"]) for r in recs if r[0] == "engine.decode"]
    assert all(win[1] <= e[1] < win[2] for e in ctx.events)
    by_shape = reader("paged_attn_roofline")(ctx)
    by_name = reader("paged_attn_kernel_roofline")(ctx)
    assert 0 < by_shape < 100
    assert by_name == pytest.approx(by_shape, rel=1e-9)


def train_ctx(ir):
    lo, hi = xplane.window_of(ir)
    cfg = {"hidden_size": 768, "num_attention_heads": 12, "num_hidden_layers": 12}
    return types.SimpleNamespace(
        ir=ir, peak=peaks.peak_for("TPU v5 lite"), cfg=cfg, mix={"loop": "train"},
        spans=types.SimpleNamespace(records=[("window", HOST_T0, to_host(ir, hi))]),
        facts={"batch": 16, "heads": 12, "seq": 512})


def test_flash_kernels_by_name_reproduce_the_shape_keyed_share(ernie_ir):
    ctx = train_ctx(ernie_ir)
    both = reader("flash_attn_roofline")(ctx)
    took = {k: xplane.seconds_by(ernie_ir, ps.named("flash_" + k)) for k in ("fwd", "dq", "dkdv")}
    share = {k: reader(f"flash_{k}_roofline")(ctx) for k in took}
    assert all(0 < v < 100 for v in share.values())
    assert share["fwd"] > share["dq"] > share["dkdv"]   # the same FLOPs in more time each
    weighted = sum(share[k] * took[k] for k in took) / sum(took.values())
    assert weighted == pytest.approx(both, rel=1e-9)


def test_to_static_host_is_the_call_less_its_dispatch(ernie_ir, monkeypatch):
    ctx = train_ctx(ernie_ir)
    t = HOST_T0 + 0.001
    recs = []
    for i, (call_s, dispatch_s) in enumerate([(0.060, 0.055), (0.064, 0.061), (0.058, 0.050)]):
        cid = 10 * (i + 1)
        recs.append(("to_static.guard", t, t + 0.0002, cid + 1, cid, None, None))
        recs.append(("to_static.dispatch", t + 0.001, t + 0.001 + dispatch_s, cid + 2, cid, None, None))
        recs.append(("to_static.call", t, t + call_s, cid, 0, None, {"fn": "train_step", "step": i + 4}))
        t += call_s + 0.0001
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs))
    assert t < ctx.spans.records[0][2]
    assert reader("to_static_host_ms_per_step")(ctx) == pytest.approx(5.0)   # median of 5, 3, 8 ms
    assert reader("to_static_host_ms_per_step")(serve_ctx(ernie_ir)) is None   # not a training loop
