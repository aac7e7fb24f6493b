"""The six readers of a served step's period (layer_metrics/_step_spans.py):
`serve_step_ms_p50`, `serve_chunk_step_ms_p50`, `serve_host_step_pct`,
`host_gc_ms_per_s`, `serve_stall_ms_max`, `serve_stall_gc_pct`, over a ring
written by hand with known periods and a planted 1.5 s collection."""
import types

import pytest

from chipbench import run
from chipbench.layer_metrics import _program_spans as ps

READERS = ("serve_step_ms_p50", "serve_chunk_step_ms_p50", "serve_host_step_pct",
           "host_gc_ms_per_s", "serve_stall_ms_max", "serve_stall_gc_pct")
T0 = 5000.0
MS = 1e-3
PLAIN, CHUNK = 10 * MS, 17 * MS   # a step's period on the device
STALL_AT, STALL = 40, 1.5        # before call STALL_AT the host pauses in a collection


class Ring:
    def __init__(self, recs, evicted=0):
        self.recs, self.n = list(recs), evicted

    def records(self):
        return list(self.recs)

    def evicted(self):
        return self.n


def reader(name):
    return run.load_module("layer_metrics", name).read


def served(n_calls=120):
    """The ring of a scheduler one step ahead: call j dispatches step j + 1
    (0.5-2 ms in), waits for step j (the fetch), then emits for 1 ms; 0.5 ms
    of the harness between calls. Every fifth step carries a chunk. A 1 ms
    generation-1 collection in call 10's emit, and a 1.5 s generation-2 one
    between calls STALL_AT - 1 and STALL_AT. Returns (records, window)."""
    recs, nid = [], iter(range(1, 10 ** 6))

    def add(name, t0, t1, parent=0, args=None):
        i = next(nid)
        recs.append((name, t0, t1, i, parent, None, args, "UserDefined", 1))
        return i

    def chunk(j):
        return 128 if j % 5 == 0 else 0

    # the first call: step 0 behind nothing, step 1 ahead of its read
    read_at = [T0 + 5 * MS]
    add("engine.decode", T0, T0 + MS, args={"chunk_tokens": chunk(0)})
    add("engine.decode", T0 + 2 * MS, T0 + 3 * MS, args={"chunk_tokens": chunk(1)})
    end = read_at[0]
    for j in range(1, n_calls + 1):
        start = end + 0.5 * MS
        if j == STALL_AT:
            add("host.gc", end + 0.2 * MS, end + 0.2 * MS + STALL,
                args={"generation": 2, "collected": 9, "uncollectable": 0})
            start += STALL
        period = CHUNK if chunk(j) else PLAIN
        read_at.append(max(read_at[-1] + period, start + 2.1 * MS))  # a fetch takes 0.1 ms at the least
        step = next(nid)
        add("engine.decode", start + 0.5 * MS, start + 2 * MS, step,
            {"chunk_tokens": chunk(j + 1)})  # read_at filled below
        add("engine.decode.fetch", start + 2 * MS, read_at[j], step)
        add("sched.emit", read_at[j], read_at[j] + MS, step)
        if j == 10:
            add("host.gc", read_at[j] + 0.2 * MS, read_at[j] + 1.2 * MS,
                args={"generation": 1, "collected": 0, "uncollectable": 0})
        end = read_at[j] + MS
        recs.append(("sched.step", start, end, step, 0, None, {"ahead": 1}, "UserDefined", 1))
    # step j + 1's span is in call j; its read is in call j + 1 (the last one is never read)
    decodes = [r for r in recs if r[0] == "engine.decode"]
    for r, t in zip(decodes, read_at):
        r[6]["read_at"] = t
    return recs, (T0, end + MS)


def ctx_of(window):
    return types.SimpleNamespace(facts={"t_start": window[0], "t_end": window[1], "open_loop": True},
                                 mix={"loop": "open"}, ir=None)


def test_the_readers_read_the_periods_the_ring_was_written_with(monkeypatch):
    recs, window = served()
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs))
    ctx = ctx_of(window)
    got = {name: reader(name)(ctx) for name in READERS}
    assert got["serve_step_ms_p50"] == pytest.approx(10.0)
    assert got["serve_chunk_step_ms_p50"] == pytest.approx(17.0)
    # the stall: the emit before it (1 ms), the harness (0.5), the collection (1.5 s), the call's 2.1 ms to its read
    assert got["serve_stall_ms_max"] == pytest.approx(1500.0 + 3.6, abs=1e-6)
    assert got["serve_stall_gc_pct"] == pytest.approx(100.0 * 1500.0 / 1503.6, abs=1e-6)
    assert got["serve_stall_gc_pct"] == pytest.approx(100.0, abs=0.5)
    seconds = window[1] - window[0]
    assert got["host_gc_ms_per_s"] == pytest.approx((1500.0 + 1.0) / seconds)
    # the host's 3 ms a call (dispatch 2, emit 1) against the periods the calls read: steps 1..120
    steps = [r for r in recs if r[0] == "engine.decode" and "read_at" in r[6]]
    assert len(steps) == 121
    total = steps[-1][6]["read_at"] - steps[0][6]["read_at"]
    assert got["serve_host_step_pct"] == pytest.approx(100.0 * 3 * MS * 120 / total, rel=1e-6)
    assert 0 < got["serve_host_step_pct"] < 100


def test_a_step_dispatched_behind_the_read_of_the_step_before_is_no_period(monkeypatch):
    recs, window = served(20)
    # step 7 went out only after step 6 was read: a sync step, e.g. behind a bucketed prefill
    decodes = sorted((r for r in recs if r[0] == "engine.decode"), key=lambda r: r[1])
    six, seven = decodes[6], decodes[7]
    late = (seven[0], six[6]["read_at"] + 0.1 * MS, six[6]["read_at"] + 0.2 * MS, *seven[3:])
    recs = [late if r is seven else r for r in recs]
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs))
    from chipbench.layer_metrics import _step_spans

    got = _step_spans.periods(recs)
    assert len(got) == len(decodes) - 1 - 1 - 1  # step 0 (no step before), step 7, the step never read
    assert all(cur is not late for cur, _, _ in got)


def test_a_program_without_read_at_or_a_ring_that_misses_the_window_gives_none(monkeypatch):
    recs, window = served(30)
    ctx = ctx_of(window)
    bare = [r if r[0] != "engine.decode" else (*r[:6], {"chunk_tokens": r[6]["chunk_tokens"]}, *r[7:])
            for r in recs]
    bare = [r for r in bare if r[0] != "host.gc"]  # a commit before PR 36: neither
    monkeypatch.setattr(ps, "ring", lambda: Ring(bare))
    for name in READERS:
        assert reader(name)(ctx) is None, name
    # records fell off the far end and the oldest left ended inside the window
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs[len(recs) // 2:], evicted=7))
    for name in READERS:
        assert reader(name)(ctx) is None, name
    monkeypatch.setattr(ps, "ring", lambda: None)
    for name in READERS:
        assert reader(name)(ctx) is None, name


def test_a_quiet_run_reads_no_collection_time_as_zero(monkeypatch):
    recs, window = served(30)
    quiet = [r for r in recs if r[0] != "host.gc"]
    monkeypatch.setattr(ps, "ring", lambda: Ring(quiet))
    ctx = ctx_of(window)
    assert reader("host_gc_ms_per_s")(ctx) == 0.0
    assert reader("serve_stall_gc_pct")(ctx) == 0.0
    assert reader("serve_stall_ms_max")(ctx) == pytest.approx(17.0)


def test_the_step_in_flight_at_the_close_read_in_the_drain_is_no_period(monkeypatch):
    recs, window = served(30)
    quiet = [r for r in recs if r[0] != "host.gc"]
    # the last step went out inside the window; the harness stops a capture (seconds) before the drain reads it
    last = max((r for r in quiet if r[0] == "engine.decode"), key=lambda r: r[1])
    assert "read_at" not in last[6] and last[2] < window[1]
    last[6]["read_at"] = window[1] + 10.0
    monkeypatch.setattr(ps, "ring", lambda: Ring(quiet))
    ctx = ctx_of(window)
    assert reader("serve_stall_ms_max")(ctx) == pytest.approx(17.0)
    assert reader("serve_step_ms_p50")(ctx) == pytest.approx(10.0)
    assert reader("serve_stall_gc_pct")(ctx) == 0.0
