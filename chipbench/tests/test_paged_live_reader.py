"""paged_attn_live_step_pct on a ring written by hand: spans of
`engine.decode` and `engine.extend` with known `page_blocks_live` /
`page_blocks_grid` inside and outside the traced stretch, and a program
whose spans do not carry the two counts (the parent of PR 26)."""
import types

import pytest

from chipbench import run
from chipbench.layer_metrics import _program_spans as ps

T0, T1 = 5000.0, 5003.0   # the traced stretch on the host clock


class Ring:
    def __init__(self, recs, evicted=0):
        self.recs, self.n = list(recs), evicted

    def records(self):
        return list(self.recs)

    def evicted(self):
        return self.n


def ctx():
    return types.SimpleNamespace(spans=types.SimpleNamespace(records=[("window", T0, T1)]))


def call(name, t, live=None, grid=None, nid=1):
    args = {"rows": 3, "bucket": 4, "context": 300}
    if grid is not None:
        args.update(page_blocks_live=live, page_blocks_grid=grid)
    return (name, t, t + 0.02, nid, 0, None, args)


@pytest.fixture
def read():
    return run.load_module("layer_metrics", "paged_attn_live_step_pct").read


def test_live_share_of_the_traced_calls(read, monkeypatch):
    recs = [
        call("engine.decode", T0 - 1.0, live=256, grid=256),   # before the stretch: not counted
        call("engine.decode", T0 + 0.1, live=40, grid=256),
        call("engine.decode", T0 + 0.2, live=9, grid=32),
        call("engine.extend", T0 + 0.3, live=6, grid=32),      # the same kernel at Q > 1
        ("engine.decode.inputs", T0 + 0.2, T0 + 0.201, 9, 1, None, None),
        ("sched.step", T0 + 0.1, T0 + 0.13, 10, 0, None, {"produced": 1, "running": 1, "waiting": 0}),
        call("engine.decode", T1 - 0.01, live=32, grid=32),    # ends after the stretch: not counted
    ]
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs))
    assert read(ctx()) == pytest.approx(100.0 * (40 + 9 + 6) / (256 + 32 + 32))


def test_spans_without_the_counts_give_none(read, monkeypatch):
    recs = [call("engine.decode", T0 + 0.1), call("engine.decode", T0 + 0.2),
            ("engine.prefill", T0 + 0.3, T0 + 0.5, 3, 0, None, {"tokens": 100, "bucket": 128})]
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs))
    assert read(ctx()) is None
    # a program with no ring, a ring that lost the stretch, a run with no traced stretch
    monkeypatch.setattr(ps, "ring", lambda: None)
    assert read(ctx()) is None
    monkeypatch.setattr(ps, "ring", lambda: Ring([call("engine.decode", T0 + 2.0, 1, 8)], evicted=5))
    assert read(ctx()) is None
    monkeypatch.setattr(ps, "ring", lambda: Ring([call("engine.decode", T0 + 2.0, 1, 8)]))
    assert read(ctx()) == pytest.approx(12.5)
    assert read(types.SimpleNamespace(spans=types.SimpleNamespace(records=[]))) is None
