"""prompt_chunked_token_pct on rings written by hand: steps that streamed,
steps that carried chunks, a bucketed prefill among them, steps outside the
window, and the runs that give it nothing to read."""
import types

import pytest

from chipbench import run
from chipbench.layer_metrics import _program_spans as ps

T0, T1 = 7000.0, 7040.0            # the measured window on the host clock


class Ring:
    def __init__(self, recs):
        self.recs = list(recs)

    def records(self):
        return list(self.recs)

    def evicted(self):
        return 0


def step(t, prompt, chunk, **more):
    return ("sched.step", t, t + 0.014, 1, 0, None,
            {"produced": 2, "running": 3, "waiting": 0, "prompt_tokens": prompt, "chunk_tokens": chunk, **more})


def ctx():
    return types.SimpleNamespace(facts={"t_start": T0, "t_end": T1})


@pytest.fixture
def read():
    return run.load_module("layer_metrics", "prompt_chunked_token_pct").read


def test_streamed_only_is_0_and_chunk_only_is_100(read, monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: Ring([step(T0 + i, 2, 0) for i in range(5)]))
    assert read(ctx()) == 0.0
    monkeypatch.setattr(ps, "ring", lambda: Ring([step(T0 + 1, 128, 128), step(T0 + 2, 0, 0),
                                                  step(T0 + 3, 37, 37)]))
    assert read(ctx()) == 100.0


def test_chunks_over_all_that_entered_inside_the_window(read, monkeypatch):
    recs = [
        step(T0 - 1.0, 500, 500),                   # before the window
        step(T0 + 1.0, 64, 0),                      # a bucketed prefill in its admission
        ("engine.prefill", T0 + 1.001, T0 + 1.01, 2, 1, None, {"tokens": 64, "bucket": 64}),
        step(T0 + 2.0, 128, 128),
        step(T0 + 2.1, 44 + 2, 44),                 # a last chunk beside two streamed rows
        ("engine.decode", T0 + 2.101, T0 + 2.11, 3, 1, None, {"rows": 3, "chunk_tokens": 44}),
        step(T0 + 3.0, 0, 0),                       # decode only
        step(T1 - 0.001, 300, 300),                 # ends after the window
    ]
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs))
    assert read(ctx()) == pytest.approx(100.0 * (128 + 44) / (64 + 128 + 46))


def test_nothing_to_read_gives_none(read, monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: Ring([step(T0 + 1, 0, 0), step(T0 + 2, 0, 0)]))
    assert read(ctx()) is None       # no prompt entered
    monkeypatch.setattr(ps, "ring", lambda: Ring([]))
    assert read(ctx()) is None       # no step at all
    monkeypatch.setattr(ps, "ring", lambda: None)
    assert read(ctx()) is None       # a program without the ring
    # a program whose steps do not count prompt tokens (a commit before the counter)
    old = ("sched.step", T0 + 1, T0 + 1.01, 1, 0, None, {"produced": 2, "running": 3, "waiting": 0})
    monkeypatch.setattr(ps, "ring", lambda: Ring([old]))
    assert read(ctx()) is None
    train = types.SimpleNamespace(facts={})      # a training loop: no window of its own
    monkeypatch.setattr(ps, "ring", lambda: Ring([step(T0 + 1, 5, 5)]))
    assert read(train) is None
