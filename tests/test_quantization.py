"""quantization: QAT fake-quant training, PTQ calibration + convert."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.quantization import (
    QAT,
    PTQ,
    AbsmaxObserver,
    FakeQuanterWithAbsMaxObserver,
    QuantConfig,
)
from paddle_tpu.quantization.quanted_layers import QuantedLinear
from paddle_tpu.quantization.quanters import fake_quant


def _model():
    return paddle.nn.Sequential(
        paddle.nn.Linear(8, 16),
        paddle.nn.ReLU(),
        paddle.nn.Linear(16, 4),
    )


def test_fake_quant_values_and_ste():
    x = paddle.to_tensor(np.array([0.11, -0.5, 0.27, 1.0], "float32"), stop_gradient=False)
    scale = paddle.to_tensor(np.asarray(1.0, "float32"))
    q = fake_quant(x, scale, bit_length=8)
    grid = 1.0 / 127
    np.testing.assert_allclose(q.numpy(), np.round(x.numpy() * 127) / 127, atol=1e-6)
    assert np.abs(q.numpy() - x.numpy()).max() <= grid / 2 + 1e-6
    # straight-through: gradient of sum(q) wrt x is all ones
    q.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), np.ones(4), atol=1e-6)


def test_qat_quantize_and_train():
    model = _model()
    q_config = QuantConfig(activation=FakeQuanterWithAbsMaxObserver(moving_rate=0.9), weight=FakeQuanterWithAbsMaxObserver())
    qat = QAT(q_config)
    qmodel = qat.quantize(model, inplace=False)
    # Linear layers wrapped, ReLU untouched
    kinds = [type(l).__name__ for l in qmodel.children()]
    assert kinds == ["QuantedLinear", "Relu", "QuantedLinear"]
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8).astype("float32"))
    out = qmodel(x)
    assert tuple(out.shape) == (4, 4)
    # trains end-to-end
    opt = paddle.optimizer.SGD(0.05, parameters=qmodel.parameters())
    l0 = None
    for _ in range(20):
        loss = (qmodel(x) ** 2).mean()
        if l0 is None:
            l0 = float(loss.numpy())
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert float(loss.numpy()) < l0
    # scales became positive during training
    ql = list(qmodel.children())[0]
    assert float(ql.weight_quanter.scales().numpy()) > 0


def test_qat_convert_bakes_weights():
    model = _model()
    q_config = QuantConfig(activation=None, weight=FakeQuanterWithAbsMaxObserver())
    qat = QAT(q_config)
    qmodel = qat.quantize(model, inplace=False)
    x = paddle.to_tensor(np.random.RandomState(1).randn(2, 8).astype("float32"))
    qmodel(x)  # populate scales
    deployed = qat.convert(qmodel, inplace=False)
    kinds = [type(l).__name__ for l in deployed.children()]
    assert kinds == ["Linear", "Relu", "Linear"]
    w = list(deployed.children())[0].weight.numpy()
    # baked weight sits on the int8 grid of its scale
    ql = list(qmodel.children())[0]
    scale = float(ql.weight_quanter.scales().numpy())
    grid = scale / 127
    np.testing.assert_allclose(w / grid, np.round(w / grid), atol=1e-3)


def test_ptq_calibrate_and_convert():
    model = _model()
    cfg = QuantConfig(activation=AbsmaxObserver(), weight=AbsmaxObserver())
    ptq = PTQ(cfg)
    qmodel = ptq.quantize(model, inplace=False)
    rng = np.random.RandomState(0)
    ref_out = None
    for _ in range(4):  # calibration batches: observers record, output unchanged
        x = paddle.to_tensor(rng.randn(4, 8).astype("float32"))
        out = qmodel(x)
    base = _model()
    base.set_state_dict({k: v for k, v in model.state_dict().items()})
    np.testing.assert_allclose(out.numpy(), base(x).numpy(), rtol=1e-5)
    ql = list(qmodel.children())[0]
    assert float(ql.weight_quanter.scales().numpy()) > 0
    deployed = ptq.convert(qmodel, inplace=False)
    w = list(deployed.children())[0].weight.numpy()
    scale = float(ql.weight_quanter.scales().numpy())
    np.testing.assert_allclose(w * 127 / scale, np.round(w * 127 / scale), atol=1e-3)
    # deployed output close to float model
    np.testing.assert_allclose(deployed(x).numpy(), base(x).numpy(), atol=0.2)


def test_type_and_name_configs():
    model = _model()
    cfg = QuantConfig()
    cfg.add_type_config(paddle.nn.Linear, weight=FakeQuanterWithAbsMaxObserver())
    qat = QAT(cfg)
    qmodel = qat.quantize(model)
    assert isinstance(list(qmodel.children())[0], QuantedLinear)

    cfg2 = QuantConfig()
    cfg2.add_name_config("2", weight=FakeQuanterWithAbsMaxObserver())
    qmodel2 = QAT(cfg2).quantize(_model())
    kinds = [type(l).__name__ for l in qmodel2.children()]
    assert kinds == ["Linear", "Relu", "QuantedLinear"]


def test_layer_config_survives_deepcopy():
    model = _model()
    first_linear = list(model.children())[0]
    cfg = QuantConfig()
    cfg.add_layer_config(first_linear, weight=FakeQuanterWithAbsMaxObserver())
    qmodel = QAT(cfg).quantize(model, inplace=False)  # deepcopy path
    kinds = [type(l).__name__ for l in qmodel.children()]
    assert kinds == ["QuantedLinear", "Relu", "Linear"]


# ---------------------------------------------------------------------------
# round 17: the observers' scale math, tested DIRECTLY (it was inert), and
# the contract that the int8 KV cache reuses it rather than forking it
# ---------------------------------------------------------------------------

def test_absmax_scale_math_direct():
    import jax.numpy as jnp

    from paddle_tpu.quantization.observers import (
        SCALE_FLOOR, absmax_scale, dequantize_absmax, quantize_absmax)

    x = np.array([[0.5, -2.0, 0.25], [0.1, 0.3, -0.2]], np.float32)
    # whole-tensor, per-axis, and keepdims forms
    assert float(absmax_scale(x)) == 2.0
    np.testing.assert_allclose(np.asarray(absmax_scale(x, axis=1)), [2.0, 0.3])
    assert absmax_scale(x, axis=0, keepdims=True).shape == (1, 3)
    # the floor: an all-zero block quantizes against SCALE_FLOOR, not 0
    assert float(absmax_scale(np.zeros(4, np.float32))) == np.float32(SCALE_FLOOR)
    # symmetric int8 grid round-trip: error bounded by half a grid step
    s = absmax_scale(x, axis=1)
    q = quantize_absmax(x, np.asarray(s)[:, None])
    assert q.dtype == jnp.int8 and int(np.abs(np.asarray(q)).max()) <= 127
    back = dequantize_absmax(q, np.asarray(s)[:, None])
    np.testing.assert_allclose(np.asarray(back), x,
                               atol=float(np.max(np.asarray(s))) / 127 / 2 + 1e-7)


def test_observer_layers_reuse_functional_math():
    """AbsmaxObserverLayer == running_absmax, AVGObserverLayer ==
    running_avg — the layer forwards and the functional helpers may never
    drift (the int8 KV pool quantizes with the helpers)."""
    from paddle_tpu.quantization.observers import (
        AbsmaxObserverLayer, AVGObserverLayer, running_absmax, running_avg)

    rng = np.random.RandomState(40)
    batches = [rng.randn(4, 8).astype(np.float32) * s for s in (0.5, 2.0, 1.0)]
    absmax_layer, avg_layer = AbsmaxObserverLayer(), AVGObserverLayer()
    ref_mx, ref_avg = np.float32(1e-9), np.float32(0.0)
    for i, b in enumerate(batches, start=1):
        absmax_layer(paddle.to_tensor(b))
        avg_layer(paddle.to_tensor(b))
        ref_mx = np.asarray(running_absmax(ref_mx, b))
        ref_avg = np.asarray(running_avg(ref_avg, b, i))
    np.testing.assert_allclose(absmax_layer.scales().numpy(), ref_mx, rtol=1e-6)
    np.testing.assert_allclose(avg_layer.scales().numpy(), ref_avg, rtol=1e-6)
    # and the running max really is max over per-batch absmaxes
    np.testing.assert_allclose(
        ref_mx, max(np.abs(b).max() for b in batches), rtol=1e-6)


def test_int8_kv_write_path_calls_observer_math(monkeypatch):
    """The KV cache's quantized write must flow through
    observers.absmax_scale — the reuse contract, pinned by interception."""
    import jax.numpy as jnp

    from paddle_tpu.inference.kv_cache import BlockPool
    from paddle_tpu.quantization import observers

    calls = []
    real = observers.absmax_scale

    def spy(x, axis=None, keepdims=False):
        calls.append(getattr(x, "shape", None))
        return real(x, axis=axis, keepdims=keepdims)

    monkeypatch.setattr(observers, "absmax_scale", spy)
    pool = BlockPool(num_blocks=4, block_size=4, num_layers=1, num_kv_heads=2,
                     head_dim=8, kv_dtype="int8")
    pages = pool.alloc(1)
    bt = np.asarray([pool.padded_table(pages, 1)], np.int32)
    view = pool.view(bt, np.array([3], np.int32))
    rng = np.random.RandomState(41)
    k = jnp.asarray(rng.randn(1, 3, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, 3, 2, 8), jnp.float32)
    view.write(0, k, v, np.arange(3, dtype=np.int32)[None])
    assert len(calls) == 2  # one absmax per written tensor (k and v)
    # and the stored values really sit on the observers' grid
    slot = np.asarray(view.k_pages[0][pages[0], :, 0])
    scale = np.asarray(view.k_scales[0][pages[0], :, 0])
    want = np.asarray(observers.quantize_absmax(k[0, 0], scale[:, None]))
    np.testing.assert_array_equal(slot, want)
