"""`correct` has to be able to come out false. The control (the reference
one precision down) fails it, and so does each fault the timed path can
have, planted underneath a whole run: the harness's look for a chip is
skipped, the rest of the run is the real one."""
import json
import os

import numpy as np
import pytest

from chipbench import control, run
from test_loops import DATA, drive


def test_training_control_and_half_batch_fault_fail_at_test_size():
    lines = control.main(["--workload", "tiny-ernie.tiny-train", "--seeds", "3,4,5",
                          "--benchmark", os.path.join(DATA, "BENCHMARK.json")],
                         allow_cpu=True, data_root=DATA)
    for line in lines:
        assert line["control_bf16"]["correct"] is False
        assert line["fault_half_batch"]["correct"] is False


def test_fault_state_returned_unchanged(monkeypatch):
    import paddle_tpu as paddle

    monkeypatch.setattr(paddle.optimizer.AdamW, "step", lambda self: None)
    line, _ = drive("tiny-ernie.tiny-train")
    assert line["correct"] is False
    assert line["compared"]["grad_norm_gap"]["value"] == 1.0     # by the measure, no run needed
    assert line["compared"]["change_norm_gap"]["ok"] is False


def test_fault_half_of_the_batch_left_out(monkeypatch):
    from paddle_tpu.models import ErnieForMaskedLM

    forward = ErnieForMaskedLM.forward

    def half(self, input_ids, *a, labels=None, **kw):
        n = input_ids.shape[0] // 2
        return forward(self, input_ids[:n], *a, labels=labels[:n], **kw)

    monkeypatch.setattr(ErnieForMaskedLM, "forward", half)
    line, _ = drive("tiny-ernie.tiny-train")
    assert line["correct"] is False
    assert line["compared"]["grad_norm_gap"]["ok"] is False


def test_fault_exchange_between_chips_left_out(monkeypatch):
    """dp 2 x mp 2 with the data-parallel exchange gone: each replica keeps
    the gradient of its own half (planted by handing both replicas the same
    half, which is what a step without the all-reduce trains on)."""
    # the program sees rows 0..n/2 twice; the reference is given all rows
    from paddle_tpu.models import ErnieForMaskedLM

    forward = ErnieForMaskedLM.forward

    def own_half_only(self, input_ids, *a, labels=None, **kw):
        import paddle_tpu as paddle

        n = input_ids.shape[0] // 2
        ids2 = paddle.concat([input_ids[:n], input_ids[:n]], axis=0)
        lab2 = paddle.concat([labels[:n], labels[:n]], axis=0)
        return forward(self, ids2, *a, labels=lab2, **kw)

    monkeypatch.setattr(ErnieForMaskedLM, "forward", own_half_only)
    line, _ = drive("tiny-ernie.tiny-train-dp2mp2")
    assert line["correct"] is False


@pytest.mark.parametrize("workload", ["tiny-mistral.tiny-open", "tiny-mistral.tiny-closed"])
def test_fault_a_token_altered_where_it_is_produced(monkeypatch, workload):
    from paddle_tpu.inference.scheduler import ContinuousBatchingScheduler as S

    emit = S._emit_token
    calls = [0]

    def altered(self, req, logits, now):
        calls[0] += 1
        if calls[0] % 3 == 0:
            logits = np.array(logits, copy=True)
            logits[int(np.argmax(logits))] = -np.inf    # the second best is served
        return emit(self, req, logits, now)

    monkeypatch.setattr(S, "_emit_token", altered)
    line, _ = drive(workload)
    assert line["correct"] is False
    assert line["compared"]["served_logit_gap_max"]["ok"] is False


def test_served_control_int8_fails_at_test_size():
    """The int8 control's gap, read as a run reads it, is over the limit
    at a size a test can hold (a wider vocabulary than the loop tests', so
    that near ties exist)."""
    import jax.numpy as jnp

    with open(os.path.join(DATA, "configs", "tiny-mistral.json")) as f:
        c = json.load(f)
    c = dict(c, vocab_size=8192)
    ref = run.load_module("reference", c["reference"])
    rng = np.random.RandomState(0)
    seqs = [rng.randint(1, c["vocab_size"], 96).tolist() for _ in range(8)]
    logits, _ = ref.token_gaps(c, 21, seqs, [1] * 8, ("f32", "int8"), w_dtype=jnp.bfloat16)
    gap = ref.gaps(logits["f32"], logits["int8"].argmax(-1))
    with open(os.path.join(DATA, "traffic", "tiny-open.json")) as f:
        limit = json.load(f)["limits"]["served_logit_gap_max"]
    assert gap.max() > limit
