"""serve_copy_ms_per_step on a trace and a ring written by hand: `copy` ops
and others inside and outside the traced window, three engine calls inside
the stretch; and the runs that give it nothing to read."""
import types

import pytest

from chipbench import run
from chipbench.layer_metrics import _program_spans as ps

T0, T1 = 5000.0, 5003.0            # the traced stretch on the host clock
LO, HI = 1_000_000, 4_000_000      # the same stretch in the trace's nanoseconds


class Ring:
    def __init__(self, recs):
        self.recs = list(recs)

    def records(self):
        return list(self.recs)

    def evicted(self):
        return 0


def ir():
    return {"devices": {
        "/device:TPU:0": [
            ["copy.1", "copy", LO - 500_000, 400_000],         # before the window
            ["copy.2", "copy", LO - 100_000, 300_000],         # straddles its start: 200 us inside
            ["copy.3", "copy", LO + 1_000_000, 410_000],
            ["fusion.7", "fusion:kOutput", LO + 1_500_000, 900_000],
            ["copy-start.4", "copy-start", LO + 1_600_000, 50_000],   # another opcode
            ["paged_attn.2", "custom-call:tpu_custom_call:out1:", LO + 2_400_000, 100_000],
            ["copy.5", "copy", LO + 2_500_000, 290_000],
            ["copy.6", "copy", HI + 10_000, 410_000],          # after it
        ],
        "/device:TPU:1": [["copy.9", "copy", LO + 10, 2_000_000]],   # not the first plane
    }, "spans": [["window", LO, HI - LO]]}


def ctx(trace=True):
    return types.SimpleNamespace(ir=ir() if trace else None,
                                 spans=types.SimpleNamespace(records=[("window", T0, T1)]))


CALLS = [
    ("engine.decode", T0 - 0.5, T0 - 0.46, 1, 0, None, {"rows": 3}),     # before the stretch
    ("engine.decode", T0 + 0.1, T0 + 0.14, 2, 0, None, {"rows": 3}),
    ("engine.decode.fetch", T0 + 0.13, T0 + 0.14, 3, 2, None, None),      # a child: no call
    ("sched.step", T0 + 0.1, T0 + 0.15, 4, 0, None, {"produced": 3}),
    ("engine.prefill", T0 + 1.0, T0 + 1.2, 5, 0, None, {"tokens": 100}),
    ("engine.extend", T0 + 2.0, T0 + 2.1, 6, 0, None, {"rows": 2}),
    ("engine.decode", T1 - 0.01, T1 + 0.03, 7, 0, None, {"rows": 3}),     # ends after it
]


@pytest.fixture
def read():
    return run.load_module("layer_metrics", "serve_copy_ms_per_step").read


def test_copy_time_over_the_traced_calls(read, monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: Ring(CALLS))
    assert read(ctx()) == pytest.approx((0.200 + 0.410 + 0.290) / 3)


def test_no_calls_or_no_trace_gives_none(read, monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: Ring([x for x in CALLS if not x[0].startswith("engine.")]))
    assert read(ctx()) is None      # a trainer: a trace and no engine call
    monkeypatch.setattr(ps, "ring", lambda: None)
    assert read(ctx()) is None      # a program without the ring
    monkeypatch.setattr(ps, "ring", lambda: Ring(CALLS))
    assert read(ctx(trace=False)) is None
    no_plane = ctx()
    no_plane.ir["devices"] = {}
    assert read(no_plane) is None
    # calls and a device that ran no copy: a reading, and it is 0
    no_copy = ctx()
    no_copy.ir["devices"] = {"/device:TPU:0": [["fusion.7", "fusion:kOutput", LO + 5, 900]]}
    assert read(no_copy) == 0.0
