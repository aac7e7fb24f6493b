"""Each plain reference against the program's own model, on seeded weights
at a tiny size on the CPU: a wrong reference is found here, before chip
time is spent on it."""
import json
import os

import numpy as np
import pytest

from chipbench import run, weights

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def cfg(name):
    with open(os.path.join(DATA, "configs", name + ".json")) as f:
        return json.load(f)


def test_ernie_reference_matches_the_program_loss_and_gradients():
    import paddle_tpu as paddle

    c = cfg("tiny-ernie")
    ref = run.load_module("reference", c["reference"])
    model = run.load_module("builders", c["builder"]).build(c)
    vals = weights.make(ref.leaf_specs(c), 11, np.float32)
    for name, p in model.named_parameters():
        p._replace_value(vals[name])
        p.stop_gradient = False
    rng = np.random.RandomState(0)
    ids = rng.randint(1, c["vocab_size"], (4, 32)).astype(np.int64)
    labels = rng.randint(0, c["vocab_size"], (4, 32)).astype(np.int64)
    loss, _ = model(paddle.to_tensor(ids), labels=paddle.to_tensor(labels))
    loss.backward()

    import jax

    trained = {k: vals[k] for k in ref.trained_names(c)}
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(lambda p: ref.loss_fn(p, ids, labels, c))(trained)
    assert float(loss.numpy()) == pytest.approx(float(want), rel=1e-5)
    for name, p in model.named_parameters():
        if name.startswith("ernie.pooler."):
            assert p.grad is None or not np.asarray(p.grad.numpy()).any()
            continue
        np.testing.assert_allclose(np.asarray(p.grad.numpy()), np.asarray(grads[name]),
                                   rtol=2e-3, atol=2e-6, err_msg=name)


def test_ernie_reference_adamw_is_paddles():
    """Three reference steps equal three eager steps of the program's AdamW."""
    import paddle_tpu as paddle

    c = cfg("tiny-ernie")
    ref = run.load_module("reference", c["reference"])
    model = run.load_module("builders", c["builder"]).build(c)
    vals = weights.make(ref.leaf_specs(c), 12, np.float32)
    for name, p in model.named_parameters():
        p._replace_value(vals[name])
        p.stop_gradient = False
    hp = c["optimizer"]
    opt = paddle.optimizer.AdamW(hp["learning_rate"], parameters=model.parameters(),
                                 weight_decay=hp["weight_decay"])
    rng = np.random.RandomState(1)
    ids = rng.randint(1, c["vocab_size"], (4, 32)).astype(np.int64)
    labels = rng.randint(0, c["vocab_size"], (4, 32)).astype(np.int64)
    losses = []
    for _ in range(3):
        loss, _ = model(paddle.to_tensor(ids), labels=paddle.to_tensor(labels))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    out = ref.train_steps(vals, ids, labels, c, hp, steps=3, row_block=2)
    assert losses == pytest.approx(out["losses"], rel=1e-5)
    for name, p in model.named_parameters():
        if name in out["change_norms"]:
            moved = float(np.linalg.norm(np.asarray(p.numpy()) - np.asarray(vals[name])))
            assert moved == pytest.approx(out["change_norms"][name], rel=2e-2, abs=1e-7), name


def test_mistral_reference_matches_the_program_logits():
    import jax.numpy as jnp

    import paddle_tpu as paddle

    c = cfg("tiny-mistral")
    ref = run.load_module("reference", c["reference"])
    model = run.load_module("builders", c["builder"]).build(c)
    vals = weights.make(ref.leaf_specs(c), 13, jnp.float32)
    for name, t in model.state_dict().items():
        t._value = vals[name]
    rng = np.random.RandomState(2)
    seq = rng.randint(1, c["vocab_size"], 40).tolist()
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(np.asarray([seq], np.int64))).numpy())[0]
    logits, served = ref.token_gaps(c, 13, [seq], [1], ("f32",), w_dtype=jnp.float32)
    np.testing.assert_allclose(got[:-1], logits["f32"], rtol=2e-3, atol=2e-4)
    assert served.tolist() == seq[1:]
    gap = ref.gaps(logits["f32"], logits["f32"].argmax(-1))
    assert (gap == 0).all()


def test_weights_are_the_seeds_and_leafwise():
    import jax.numpy as jnp

    c = cfg("tiny-mistral")
    ref = run.load_module("reference", c["reference"])
    whole = weights.make(ref.leaf_specs(c), 2 ** 33 + 5, jnp.bfloat16)
    again = weights.make(ref.leaf_specs(c), 2 ** 33 + 5, jnp.bfloat16)
    other = weights.make(ref.leaf_specs(c), 5, jnp.bfloat16)
    layer1 = weights.make(ref.layer_specs(c, 1), 2 ** 33 + 5, jnp.bfloat16)
    k = "llama.layers.1.mlp.up_proj.weight"
    assert (np.asarray(whole[k]) == np.asarray(again[k])).all()
    assert (np.asarray(whole[k]) != np.asarray(other[k])).any()
    assert (np.asarray(whole[k]) == np.asarray(layer1[k])).all()   # a layer alone: the same
    assert abs(float(np.asarray(whole[k], np.float32).std()) - c["initializer_range"]) < 2e-3
