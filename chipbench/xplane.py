"""Reduction from the profiler's trace to numbers. No program code.

`load()` turns an `.xplane.pb` into a small plain structure (the IR):

    {"devices": {plane name: [[op name, category, start_ns, dur_ns], ...]},
     "spans":   [[span name, start_ns, dur_ns], ...]}

`devices` holds the events of each device plane's "XLA Ops" line; `spans`
the host events whose name starts with "chipbench:" (the harness's own
`TraceAnnotation`s), prefix removed. Everything below works on the IR, so a
recorded IR (tests/data) checks the arithmetic without a chip.
"""
import glob
import gzip
import json
import os
import re

SPAN_PREFIX = "chipbench:"
OPS_LINE = "XLA Ops"


def find_trace_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices[plane.name] = [
                    [*short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name[len(SPAN_PREFIX):], int(ev.start_ns),
                                      int(ev.duration_ns)])
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


_HLO = re.compile(r"^%?(?P<op>[\w.\-]+) = (?P<out>.*?) (?P<code>[a-z][a-z\-]*)\(")
_SHAPE = re.compile(r"\b([a-z]+[0-9]+\[[0-9,]*\])")


def short_name(text: str):
    """(name, category) of a device event. The TPU's trace names an op by
    its whole HLO line; the name kept is the op's own (`fusion.712`), and
    the category says what it is, in words the readers match on:

        fusion:kOutput            a fusion rooted in a matmul (XLA's output fusion)
        fusion:kLoop / kInput / kCustom
        custom-call:tpu_custom_call:<operand shapes>   a Pallas kernel
        copy, all-reduce, ...     the opcode

    A Pallas kernel has no name of its own in the trace today (PERF.md
    lists the `name=` each `pallas_call` needs); its operand shapes and the
    number of its results are kept, so that a reader can tell kernels apart."""
    m = _HLO.match(text)
    if not m:
        return text[:80], ""
    code = m.group("code")
    cat = code
    if code == "fusion":
        k = re.search(r"kind=(\w+)", text)
        cat = "fusion:" + (k.group(1) if k else "")
    elif code == "custom-call":
        t = re.search(r'custom_call_target="([\w.$\-]+)"', text)
        target = t.group(1) if t else ""
        cat = "custom-call:" + target
        if target == "tpu_custom_call":
            args = text[m.end():].split("), custom_call_target")[0]
            n_out = len(_SHAPE.findall(m.group("out")))
            cat += f":out{n_out}:" + ",".join(_SHAPE.findall(args))
    return m.group("op"), cat


def save_ir(ir: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(ir, f)


def load_ir(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window_of(ir: dict):
    """(start_ns, end_ns) of the span named `window`: the traced stretch of
    the measured loop. Device events outside it (warm-up tail, drain) are
    not counted."""
    for name, start, dur in ir["spans"]:
        if name == "window":
            return start, start + dur
    raise ValueError("the trace holds no chipbench:window span")


def _clip(ops, lo, hi):
    out = []
    for name, cat, start, dur in ops:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, cat, s, e))
    return out


def busy_intervals(ops, lo, hi):
    """Union of the op intervals inside [lo, hi], as sorted (start, end)."""
    iv = sorted((s, e) for _, _, s, e in _clip(ops, lo, hi))
    merged = []
    for s, e in iv:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def busy_seconds(ir: dict) -> dict:
    """{"busy_s": mean over device planes, "window_s", "per_device": {...}}"""
    lo, hi = window_of(ir)
    per = {}
    for plane, ops in ir["devices"].items():
        per[plane] = sum(e - s for s, e in busy_intervals(ops, lo, hi)) / 1e9
    if not per:
        raise ValueError("the trace holds no device plane")
    return {"busy_s": sum(per.values()) / len(per), "window_s": (hi - lo) / 1e9,
            "per_device": per}


def idle_gaps(ir: dict, plane=None):
    """[(start_ns, end_ns)] in which no op ran on the device, inside the
    window; first device plane unless named."""
    lo, hi = window_of(ir)
    plane = plane or sorted(ir["devices"])[0]
    gaps, cur = [], lo
    for s, e in busy_intervals(ir["devices"][plane], lo, hi):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def gaps_by_span(ir: dict, plane=None) -> dict:
    """Idle seconds by what the host was doing: each idle gap is split over
    the innermost harness spans that overlap it (`window` itself takes what
    no inner span covers)."""
    inner = [(n, s, s + d) for n, s, d in ir["spans"] if n != "window"]
    out = {}
    for g0, g1 in idle_gaps(ir, plane):
        # walk the gap in pieces bounded by span edges
        edges = sorted({g0, g1, *[t for _, s, e in inner for t in (s, e) if g0 < t < g1]})
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            owner, width = "window", None
            for n, s, e in inner:
                if s <= mid < e and (width is None or e - s < width):
                    owner, width = n, e - s
            out[owner] = out.get(owner, 0.0) + (b - a) / 1e9
    return out


def seconds_by(ir: dict, match, plane=None) -> float:
    """Device seconds inside the window of the ops for which
    match(name, category) holds; first device plane unless named."""
    lo, hi = window_of(ir)
    plane = plane or sorted(ir["devices"])[0]
    return sum(e - s for n, c, s, e in _clip(ir["devices"][plane], lo, hi) if match(n, c)) / 1e9


def count_by(ir: dict, match, plane=None) -> int:
    lo, hi = window_of(ir)
    plane = plane or sorted(ir["devices"])[0]
    return sum(1 for n, c, s, e in _clip(ir["devices"][plane], lo, hi) if match(n, c))


def top_ops(ir: dict, n=10, plane=None):
    """[[category:name, seconds]] of the ops that took most device time."""
    lo, hi = window_of(ir)
    plane = plane or sorted(ir["devices"])[0]
    acc = {}
    for name, cat, s, e in _clip(ir["devices"][plane], lo, hi):
        key = f"{cat}:{name}" if cat else name
        acc[key] = acc.get(key, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def exposed_seconds(ir: dict, is_collective, plane=None) -> float:
    """Seconds inside the window in which a collective ran on the device
    and no other op did."""
    lo, hi = window_of(ir)
    plane = plane or sorted(ir["devices"])[0]
    ops = _clip(ir["devices"][plane], lo, hi)
    coll = [[s, e] for n, c, s, e in ops if is_collective(n, c)]
    other = busy_intervals([[n, c, s, e - s] for n, c, s, e in ops if not is_collective(n, c)], lo, hi)
    total = 0
    for s, e in busy_intervals([["", "", s, e - s] for s, e in coll], lo, hi):
        covered = sum(min(e, oe) - max(s, os_) for os_, oe in other if oe > s and os_ < e)
        total += (e - s) - covered
    return total / 1e9


COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def is_collective(name: str, cat: str) -> bool:
    return bool(COLLECTIVE.search(name) or COLLECTIVE.search(cat))
