"""Helpers for the readers of the program's own spans (not a metric: no
entry names it).

The program keeps one ring of span records (`paddle_tpu.profiler.utils`):

    (name, t0, t1, id, parent, ident, args, ...)   t0, t1 on time.perf_counter

the clock of `harness.clock`. A reader takes the records inside a stretch of
that clock (the measured window, or the traced stretch) and, for the idle
shares, lays them against the device's ops through the harness's `window`
span, which is both in `ctx.spans.records` (host clock) and in
`ctx.ir["spans"]` (the trace's nanoseconds).

A program without the ring (a commit before it) gives None everywhere here,
and every reader then reports nothing.
"""
from chipbench import flops, xplane
from chipbench.layer_metrics._common import steps_in_trace, traced_host_window

CLOCKS_AGREE_NS = 0.2e6  # the two ends of `window` must give one offset to within this


def ring():
    """The module that holds the program's ring, or None."""
    try:
        from paddle_tpu.profiler import utils
    except Exception:  # noqa: BLE001 — no program, or one that cannot be imported: nothing to read
        return None
    return utils if hasattr(utils, "records") and hasattr(utils, "evicted") else None


def records(lo, hi):
    """The ring's records inside [lo, hi] on the host clock, oldest first;
    None where there is no ring, or the ring no longer reaches back to `lo`
    (records were evicted and the oldest left ended after `lo`)."""
    r = ring()
    if r is None or lo is None or hi is None:
        return None
    recs = r.records()
    if r.evicted() and (not recs or recs[0][2] > lo):
        return None
    return [x for x in recs if x[1] >= lo and x[2] <= hi]


def window_records(ctx):
    """Records inside the measured window (a serving loop's)."""
    return records(ctx.facts.get("t_start"), ctx.facts.get("t_end"))


def traced_records(ctx):
    """Records inside the traced stretch."""
    win = traced_host_window(ctx)
    return None if win is None else records(*win)


def child_seconds(recs, names):
    """{parent id: seconds} of the records named in `names`, by the span
    directly around them."""
    out = {}
    for x in recs:
        if x[0] in names:
            out[x[4]] = out.get(x[4], 0.0) + (x[2] - x[1])
    return out


def request_spans(ctx, name):
    """Seconds of the per-request spans `name` of the requests submitted
    inside the measured window (a request's `request.queue` starts at its
    submission; its spans may end after the window), or None."""
    lo, hi = ctx.facts.get("t_start"), ctx.facts.get("t_end")
    recs = records(lo, float("inf"))
    if recs is None or not ctx.facts.get("open_loop"):
        return None
    mine = {x[5] for x in recs if x[0] == "request.queue" and x[1] < hi}
    return [x[2] - x[1] for x in recs if x[0] == name and x[5] in mine]


def host_to_trace_ns(ctx):
    """A function from host-clock seconds to the trace's nanoseconds, or
    None where the trace has no `window` or its two ends disagree."""
    win = traced_host_window(ctx)
    if win is None or ctx.ir is None:
        return None
    try:
        lo, hi = xplane.window_of(ctx.ir)
    except ValueError:
        return None
    off0, off1 = lo - win[0] * 1e9, hi - win[1] * 1e9
    if abs(off0 - off1) > CLOCKS_AGREE_NS:
        return None
    off = 0.5 * (off0 + off1)
    return lambda t: t * 1e9 + off


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _intersect(a, b):
    """Where two sorted lists of disjoint intervals overlap."""
    i = j = 0
    out = []
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _overlap(a, b):
    return sum(e - s for s, e in _intersect(a, b))


def idle_split(ctx):
    """Where the device's idle time of the traced window falls, in seconds:
    {"fetch": host inside an `engine.*.fetch` span, "host": host inside
    `sched.step` but outside any fetch, "outside": the rest, "window": the
    window}. The three add up to the device's idle time. None where the
    spans cannot be laid on the trace."""
    recs = traced_records(ctx)
    to_ns = host_to_trace_ns(ctx)
    if recs is None or to_ns is None or not ctx.ir["devices"]:
        return None
    lo, hi = xplane.window_of(ctx.ir)
    gaps = [list(g) for g in xplane.idle_gaps(ctx.ir)]
    fetch = _merged((to_ns(x[1]), to_ns(x[2])) for x in recs
                    if x[0].startswith("engine.") and x[0].endswith(".fetch"))
    step = _merged((to_ns(x[1]), to_ns(x[2])) for x in recs if x[0] == "sched.step")
    if not step:
        return None
    idle = sum(e - s for s, e in gaps)
    in_fetch = _overlap(gaps, fetch)
    in_host = _overlap(gaps, step) - _overlap(gaps, _intersect(fetch, step))
    return {"fetch": in_fetch / 1e9, "host": in_host / 1e9,
            "outside": (idle - in_fetch - in_host) / 1e9, "window": (hi - lo) / 1e9}


def idle_share(ctx, part):
    """A part of idle_split (`fetch` or `host`) as a share of the traced
    window, in percent; None for a training loop or where nothing is read."""
    if ctx.ir is None or ctx.mix["loop"] == "train":
        return None
    split = idle_split(ctx)
    return None if split is None else 100.0 * split[part] / split["window"]


def named(prefix):
    """Device ops whose name starts with a kernel's stable name."""
    def match(name, cat):
        return name.startswith(prefix)

    return match


def flash_kernel_roofline(ctx, which):
    """One flash kernel (`fwd`, `dq`, `dkdv`) by its name in the trace
    (`flash_<which>`) against its own entry of flops.flash_attn_flops/bytes;
    steps counted as flash_attn_roofline counts them."""
    if ctx.ir is None or ctx.peak is None:
        return None
    steps = steps_in_trace(ctx.ir)
    mesh = ctx.mix.get("mesh") or {}
    f = ctx.facts
    batch = f["batch"] // int(mesh.get("dp", 1))
    heads = f["heads"] // int(mesh.get("mp", 1))
    d = ctx.cfg["hidden_size"] // ctx.cfg["num_attention_heads"]
    took = xplane.seconds_by(ctx.ir, named("flash_" + which))
    if took <= 0 or not steps:
        return None  # a program that does not name its kernels: no number
    need_f = flops.flash_attn_flops(batch, heads, f["seq"], d)[which]
    need_b = flops.flash_attn_bytes(batch, heads, f["seq"], d)[which]
    least = flops.roofline_seconds(need_f, need_b, ctx.peak)[0]
    return 100.0 * steps * ctx.cfg["num_hidden_layers"] * least / took
