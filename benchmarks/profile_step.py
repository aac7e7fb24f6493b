"""Itemize the ERNIE train-step time on the real chip (VERDICT r2 Weak #1).

All timings are fetch-forced slopes (see BASELINE.md "Measurement
methodology") and all configurations run back-to-back in ONE process so
run-to-run drift can't skew comparisons.

Measures:
  A. measured bf16 matmul peak (denominator)
  B. full to_static train step (current production path)
  C. host dispatch-only cost of B (loop without the forcing fetch)
  D. handwritten pure-jax floor: same model via functional_call,
     jax.grad + hand-fused AdamW, donated buffers, ONE jit program
  E. fwd+bwd-only to_static slope
  F. B again at batch 128 (matmul-boundedness probe)

Run: python benchmarks/profile_step.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import ErnieForMaskedLM, ErnieModel
from paddle_tpu.jit.api import functional_call
from paddle_tpu.core.tensor import Tensor


def slope(fn, n1=8, n2=24):
    """fn(n) runs n steps ending in a host fetch; returns s/step."""
    fn(3)  # warm
    t1 = fn(n1)
    t2 = fn(n2)
    return (t2 - t1) / (n2 - n1)


def make_model(batch, seq):
    paddle.seed(0)
    model = ErnieForMaskedLM(
        ErnieModel(
            vocab_size=40000, hidden_size=768, num_hidden_layers=12,
            num_attention_heads=12, intermediate_size=3072,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        )
    )
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(), weight_decay=0.01)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 40000, (batch, seq)).astype(np.int64))
    labels = paddle.to_tensor(rng.randint(0, 40000, (batch, seq)).astype(np.int64))
    return model, opt, ids, labels


def timed_loop(step, ids, labels):
    def run(n):
        t0 = time.perf_counter()
        loss = None
        for _ in range(n):
            loss = step(ids, labels)
        float(loss.numpy() if hasattr(loss, "numpy") else loss)
        return time.perf_counter() - t0
    return run


def main():
    print(f"devices: {jax.devices()}")

    # ---- A. peak ----
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench import _measured_peak_flops
    peak = _measured_peak_flops()
    print(f"A. measured bf16 peak: {peak/1e12:.1f} TFLOP/s")

    batch, seq = 64, 128
    model, opt, ids, labels = make_model(batch, seq)
    n_params = sum(p.size for p in model.parameters())
    pos = model.ernie.embeddings.position_embeddings.weight.size
    tok = model.ernie.embeddings.token_type_embeddings.weight.size
    flops_per_tok = 6 * (n_params - pos - tok)
    step_flops = flops_per_tok * batch * seq
    print(f"   params {n_params/1e6:.1f}M, step flops {step_flops/1e12:.2f} TF, "
          f"matmul bound {step_flops/peak*1000:.1f} ms")

    # ---- B. full to_static step ----
    @paddle.jit.to_static
    def train_step(ids, labels):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            loss, _ = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    run_b = timed_loop(train_step, ids, labels)
    s_b = slope(run_b)
    print(f"B. full to_static step: {s_b*1000:.2f} ms/step  "
          f"(MFU {step_flops/s_b/peak:.3f})")

    # ---- C. host dispatch-only ----
    # warm already; loop WITHOUT fetch: dispatch is asynchronous, so this
    # times pure host-side per-step work (flatten, call, write-back)
    for _ in range(3):
        train_step(ids, labels)
    t0 = time.perf_counter()
    N = 30
    for _ in range(N):
        loss = train_step(ids, labels)
    t_disp = (time.perf_counter() - t0) / N
    float(loss.numpy())
    print(f"C. host dispatch-only: {t_disp*1000:.2f} ms/step")

    # ---- D. handwritten pure-jax floor ----
    model2, _opt2, ids2, labels2 = make_model(batch, seq)
    params = {k: v._value for k, v in model2.state_dict().items()}
    trainable = {k for k, v in model2.state_dict().items() if not v.stop_gradient}

    def loss_fn(tr, fixed, i, l):
        # no_grad: apply() runs ops directly (no eager jax.vjp), so the outer
        # jax.grad differentiates straight through, custom_vjp ops intact
        with paddle.no_grad():
            out = functional_call(model2, {**{k: Tensor(v) for k, v in tr.items()},
                                           **{k: Tensor(v) for k, v in fixed.items()}},
                                  Tensor(i), labels=Tensor(l))
        return out[0]._value if isinstance(out, tuple) else out._value

    tr0 = {k: v for k, v in params.items() if k in trainable}
    fixed0 = {k: v for k, v in params.items() if k not in trainable}
    m0 = {k: jnp.zeros_like(v) for k, v in tr0.items()}
    v0 = {k: jnp.zeros_like(v) for k, v in tr0.items()}

    b1, b2, eps, lr, wd = 0.9, 0.999, 1e-8, 1e-4, 0.01

    def adamw(p, g, m, v, t):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / (1 - b1 ** t)
        vh = v / (1 - b2 ** t)
        p = p * (1 - lr * wd) - lr * mh / (jnp.sqrt(vh) + eps)
        return p, m, v

    @jax.jit
    def amp_loss(tr, fixed, i, l):
        trb = {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v for k, v in tr.items()}
        fxb = {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v for k, v in fixed.items()}
        return loss_fn(trb, fxb, i, l)

    def pure_step(tr, m, v, fixed, i, l, t):
        loss, g = jax.value_and_grad(lambda tr_: amp_loss(tr_, fixed, i, l))(tr)
        new = {k: adamw(tr[k], g[k].astype(jnp.float32), m[k], v[k], t) for k in tr}
        return (loss,
                {k: new[k][0] for k in new},
                {k: new[k][1] for k in new},
                {k: new[k][2] for k in new})

    jstep = jax.jit(pure_step, donate_argnums=(0, 1, 2))
    iv, lv = ids2._value, labels2._value

    state = [tr0, m0, v0]
    def run_d(n):
        t0 = time.perf_counter()
        for s in range(n):
            loss, state[0], state[1], state[2] = jstep(
                state[0], state[1], state[2], fixed0, iv, lv, 1.0 + s)
        float(loss)
        return time.perf_counter() - t0
    s_d = slope(run_d)
    print(f"D. handwritten floor (donated, per-param adamw): {s_d*1000:.2f} ms/step  "
          f"(MFU {step_flops/s_d/peak:.3f})")

    # ---- E. fwd+bwd only ----
    model3, opt3, ids3, labels3 = make_model(batch, seq)

    @paddle.jit.to_static
    def fb_step(ids, labels):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            loss, _ = model3(ids, labels=labels)
        loss.backward()
        opt3.clear_grad()
        return loss

    run_e = timed_loop(fb_step, ids3, labels3)
    s_e = slope(run_e)
    print(f"E. fwd+bwd only to_static: {s_e*1000:.2f} ms/step")

    # ---- F. batch 128 full step ----
    import gc
    del model, opt, model2, _opt2, model3, opt3, state, tr0, fixed0, m0, v0, jstep
    del run_d, run_e
    gc.collect()
    model4, opt4, ids4, labels4 = make_model(128, seq)

    @paddle.jit.to_static
    def train_step4(ids, labels):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            loss, _ = model4(ids, labels=labels)
        loss.backward()
        opt4.step()
        opt4.clear_grad()
        return loss

    run_f = timed_loop(train_step4, ids4, labels4)
    s_f = slope(run_f, n1=6, n2=16)
    sf_flops = flops_per_tok * 128 * seq
    print(f"F. full step batch=128: {s_f*1000:.2f} ms/step  "
          f"(MFU {sf_flops/s_f/peak:.3f})")

    # re-run B to bracket drift within the run
    s_b2 = slope(run_b)
    print(f"B'. full step again (drift check): {s_b2*1000:.2f} ms/step")


if __name__ == "__main__":
    main()
