"""decode_ahead_step_pct on rings written by hand: steps dispatched ahead,
steps that were not and say why, steps that ran no program, steps outside the
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


def step(t, **how):
    return ("sched.step", t, t + 0.011, 1, 0, None,
            {"produced": 2, "running": 3, "waiting": 0, "prompt_tokens": 0, "chunk_tokens": 0, **how})


def ctx():
    return types.SimpleNamespace(facts={"t_start": T0, "t_end": T1})


@pytest.fixture
def read():
    return run.load_module("layer_metrics", "decode_ahead_step_pct").read


def test_all_ahead_is_100_and_all_synchronous_is_0(read, monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: Ring([step(T0 + i, ahead=1, rows_dropped=0) for i in range(5)]))
    assert read(ctx()) == 100.0
    monkeypatch.setattr(ps, "ring", lambda: Ring([step(T0 + 1, sync="first", rows_dropped=0),
                                                  step(T0 + 2, sync="spec")]))
    assert read(ctx()) == 0.0


def test_ahead_over_the_steps_that_ran_a_program_inside_the_window(read, monkeypatch):
    recs = [
        step(T0 - 1.0, sync="first", rows_dropped=0),       # before the window
        step(T0 + 1.0, sync="prefill", rows_dropped=0),     # the engine was idle: a bucketed prefill, then its step
        ("engine.prefill", T0 + 1.001, T0 + 1.01, 2, 1, None, {"tokens": 64, "bucket": 64}),
        step(T0 + 1.1, ahead=1, rows_dropped=0),
        step(T0 + 1.2, ahead=1, rows_dropped=1),            # a row ended by the end token a step before
        ("engine.decode", T0 + 1.201, T0 + 1.203, 3, 1, None, {"rows": 3, "chunk_tokens": 0}),
        step(T0 + 1.3),                                     # admission only: no program ran
        step(T0 + 2.0, sync="preempt", rows_dropped=0),
        step(T0 + 2.1, ahead=1, rows_dropped=0),
        step(T1 - 0.001, ahead=1, rows_dropped=0),          # ends after the window
    ]
    monkeypatch.setattr(ps, "ring", lambda: Ring(recs))
    assert read(ctx()) == pytest.approx(100.0 * 3 / 5)


def test_nothing_to_read_gives_none(read, monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: Ring([step(T0 + 1), step(T0 + 2)]))
    assert read(ctx()) is None       # no step ran a program, or the parent's program: its steps do not say
    monkeypatch.setattr(ps, "ring", lambda: Ring([]))
    assert read(ctx()) is None       # no step at all
    monkeypatch.setattr(ps, "ring", lambda: None)
    assert read(ctx()) is None       # a program without the ring
    train = types.SimpleNamespace(facts={})      # a training loop: no window of its own
    monkeypatch.setattr(ps, "ring", lambda: Ring([step(T0 + 1, ahead=1)]))
    assert read(train) is None
