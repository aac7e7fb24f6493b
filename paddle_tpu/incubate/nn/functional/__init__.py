"""Fused ops (reference: python/paddle/incubate/nn/functional/ —
fused_rms_norm.py, swiglu.py, fused_transformer.py, fused_rotary_position_
embedding.py, fused_dropout_add.py).

TPU-native: "fused" here means (a) a Pallas kernel where the fusion is
genuinely profitable (rms_norm: one VMEM pass instead of two reductions) and
(b) jit-scoped jnp expressions elsewhere — XLA fuses elementwise chains into
the surrounding matmuls on its own, so the CUDA-style mega-kernels of the
reference collapse to composition.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ....core.apply import apply
from ....core.state import named_scope
from ....core.tensor import Tensor
from ....ops import pallas as _pk

_BLOCK_R = 256


# ---------------------------------------------------------------------------
# rms_norm — Pallas kernel
# ---------------------------------------------------------------------------

def _rms_norm_ref(x, w, b, eps):
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(ms + eps)
    out = out * w.astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rms_norm_pallas_2d(x, w, b, eps, has_bias):
    """Pallas forward + reference-impl backward: pallas_call has no built-in
    AD rule, so the vjp recomputes through _rms_norm_ref (same pattern as
    flash_attention_bshd in ops/pallas.py)."""
    return _rms_norm_pallas_fwd_impl(x, w, b, eps, has_bias, _pk._INTERPRET)


def _rms_norm_fwd_rule(x, w, b, eps, has_bias):
    return _rms_norm_pallas_fwd_impl(x, w, b, eps, has_bias, _pk._INTERPRET), (x, w, b)


def _rms_norm_bwd_rule(eps, has_bias, res, g):
    x, w, b = res
    _, vjp = jax.vjp(lambda a, ww, bb: _rms_norm_ref(a, ww, bb if has_bias else None, eps), x, w, b)
    return vjp(g)


_rms_norm_pallas_2d.defvjp(_rms_norm_fwd_rule, _rms_norm_bwd_rule)


@functools.partial(jax.jit, static_argnames=("eps", "has_bias", "interpret"))
def _rms_norm_pallas_fwd_impl(x, w, b, eps, has_bias, interpret=False):
    """Rows-normalize [R, D] in one VMEM pass (pallas_guide.md pattern:
    block rows, keep the row reduction in-register)."""
    from jax.experimental import pallas as pl

    r, d = x.shape

    def kernel(x_ref, w_ref, b_ref, o_ref):
        xb = x_ref[...].astype(jnp.float32)
        ms = jnp.mean(xb * xb, axis=-1, keepdims=True)
        out = xb * jax.lax.rsqrt(ms + eps) * w_ref[...].astype(jnp.float32)
        if has_bias:
            out = out + b_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)

    block_r = _BLOCK_R
    while r % block_r:
        block_r //= 2
        if block_r == 0:
            return _rms_norm_ref(x, w, b if has_bias else None, eps)
    bz = b if has_bias else jnp.zeros_like(w)
    return pl.pallas_call(
        kernel,
        grid=(r // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_r, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        interpret=interpret,
        name="rms_norm",
    )(x, w, bz)


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6, begin_norm_axis=-1, **kw):
    """paddle.incubate.nn.functional.fused_rms_norm parity."""

    def fn(xv, wv, *rest):
        bv = rest[0] if norm_bias is not None else None
        if begin_norm_axis not in (-1, xv.ndim - 1):
            raise NotImplementedError("fused_rms_norm normalizes the last axis")
        d = xv.shape[-1]
        lead = xv.shape[:-1]
        x2 = xv.reshape(-1, d)
        rows = x2.shape[0]
        use_pallas = _pk._on_tpu() and d % 128 == 0 and rows % 8 == 0
        if use_pallas:
            with jax.enable_x64(False):  # Mosaic rejects i64 index types
                bz = bv if bv is not None else jnp.zeros_like(wv)
                out = _rms_norm_pallas_2d(x2, wv, bz, float(epsilon), bv is not None)
        else:
            out = _rms_norm_ref(x2, wv, bv, float(epsilon))
        return out.reshape(*lead, d)

    args = [x, norm_weight] + ([norm_bias] if norm_bias is not None else [])
    return apply("fused_rms_norm", fn, *args)


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5, begin_norm_axis=-1, **kw):
    # the canonical layer norm lives in nn/functional/norm.py; begin_norm_axis
    # selects how many trailing axes normalize (reference semantics)
    from ....nn.functional.norm import layer_norm as _layer_norm

    ndim = len(x.shape)
    begin = begin_norm_axis % ndim
    if begin == ndim - 1:
        return _layer_norm(x, int(x.shape[-1]), norm_weight, norm_bias, epsilon)
    # multi-axis case: reference stores weight/bias flat over prod(trailing
    # dims) — flatten, normalize, restore
    shape = [int(d) for d in x.shape]
    lead, prod = shape[:begin], 1
    for d in shape[begin:]:
        prod *= d
    out = _layer_norm(x.reshape(lead + [prod]), prod, norm_weight, norm_bias, epsilon)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# activations / glu
# ---------------------------------------------------------------------------

def swiglu(x, y=None, name=None):
    """reference swiglu.py: silu(x) * y; with y=None, x splits in half."""
    if y is None:
        return apply("swiglu", lambda v: (lambda a, b: jax.nn.silu(a) * b)(*jnp.split(v, 2, axis=-1)), x)
    return apply("swiglu", lambda a, b: jax.nn.silu(a) * b, x, y)


def fused_bias_act(x, bias=None, act_method="gelu", **kw):
    acts = {
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "silu": jax.nn.silu,
        "swiglu": lambda v: (lambda a, b: jax.nn.silu(a) * b)(*jnp.split(v, 2, axis=-1)),
        "geglu": lambda v: (lambda a, b: jax.nn.gelu(a) * b)(*jnp.split(v, 2, axis=-1)),
    }
    act = acts[act_method]
    if bias is None:
        return apply(f"fused_bias_{act_method}", act, x)
    return apply(f"fused_bias_{act_method}", lambda v, b: act(v + b), x, bias)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train", seed=None, name=None):
    """reference fused_dropout_add.py: dropout(x) + y. Delegates to the
    canonical dropout (nn/functional/common.py) so mode semantics — incl.
    downscale_in_infer's (1-p) eval scaling — stay in one place; XLA fuses
    the add."""
    from ....nn.functional.common import dropout as _dropout

    return _dropout(x, p=p, training=training, mode=mode) + y


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    def fn(xv, wv, *rest):
        w = wv.T if transpose_weight else wv
        out = xv @ w
        if rest:
            out = out + rest[0]
        return out

    args = [x, weight] + ([bias] if bias is not None else [])
    return apply("fused_linear", fn, *args)


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False, activation="gelu"):
    acts = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "none": lambda v: v}
    act = acts[activation]

    def fn(xv, yv, bv):
        a = xv.T if trans_x else xv
        b = yv.T if trans_y else yv
        return act(a @ b + bv)

    return apply("fused_linear_activation", fn, x, y, bias)


# ---------------------------------------------------------------------------
# rotary embedding
# ---------------------------------------------------------------------------

def fused_rotary_position_embedding(
    q, k=None, v=None, sin=None, cos=None, position_ids=None, use_neox_rotary_style=True, name=None
):
    """reference fused_rotary_position_embedding.py. q/k/v: [B, S, H, D];
    sin/cos: [1, S, 1, D] (auto-built when not given)."""

    def build_sincos(s, d, dtype):
        inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        t = jnp.arange(s, dtype=jnp.float32)
        freqs = jnp.outer(t, inv)  # [S, D/2]
        emb = jnp.concatenate([freqs, freqs], axis=-1) if use_neox_rotary_style else jnp.repeat(freqs, 2, axis=-1)
        return jnp.sin(emb).astype(dtype)[None, :, None, :], jnp.cos(emb).astype(dtype)[None, :, None, :]

    def rotate(xv, sinv, cosv):
        if use_neox_rotary_style:
            half = xv.shape[-1] // 2
            x1, x2 = xv[..., :half], xv[..., half:]
            rot = jnp.concatenate([-x2, x1], axis=-1)
        else:
            x1 = xv[..., 0::2]
            x2 = xv[..., 1::2]
            rot = jnp.stack([-x2, x1], axis=-1).reshape(xv.shape)
        return xv * cosv + rot * sinv

    ref = next(t for t in (q, k, v) if t is not None)
    s_len, d = int(ref.shape[1]), int(ref.shape[-1])
    if sin is None or cos is None:
        sv, cv = build_sincos(s_len, d, jnp.float32)
    else:
        sv = sin._value if isinstance(sin, Tensor) else jnp.asarray(sin)
        cv = cos._value if isinstance(cos, Tensor) else jnp.asarray(cos)
    if position_ids is not None:
        pid = position_ids._value if isinstance(position_ids, Tensor) else jnp.asarray(position_ids)
        sv = jnp.take(sv[0, :, 0, :], pid, axis=0)[:, :, None, :]
        cv = jnp.take(cv[0, :, 0, :], pid, axis=0)[:, :, None, :]
    sv32, cv32 = sv.astype(jnp.float32), cv.astype(jnp.float32)

    def fn(xv):
        return rotate(xv.astype(jnp.float32), sv32, cv32).astype(xv.dtype)

    outs = [apply("fused_rope", fn, t) if t is not None else None for t in (q, k, v)]
    return tuple(outs)


# ---------------------------------------------------------------------------
# attention / ffn blocks
# ---------------------------------------------------------------------------

def fused_multi_head_attention(
    x,
    qkv_weight,
    linear_weight,
    pre_layer_norm=False,
    pre_ln_scale=None,
    pre_ln_bias=None,
    ln_scale=None,
    ln_bias=None,
    pre_ln_epsilon=1e-5,
    qkv_bias=None,
    linear_bias=None,
    cache_kv=None,
    attn_mask=None,
    dropout_rate=0.0,
    attn_dropout_rate=0.0,
    ln_epsilon=1e-5,
    training=True,
    num_heads=None,
    name=None,
):
    """reference fused_transformer.py fused_multi_head_attention:
    (pre-LN ->) qkv matmul -> attention -> out proj (-> post-LN), flash
    attention kernel when shapes allow. qkv_weight: [3, H, D, E]."""
    from ....nn.functional.attention import scaled_dot_product_attention

    if cache_kv is not None:
        raise NotImplementedError("fused_multi_head_attention: cache_kv (incremental decode) not yet supported")
    xin = x
    if pre_layer_norm:
        xin = fused_layer_norm(x, pre_ln_scale, pre_ln_bias, pre_ln_epsilon)

    def qkv_fn(xv, wv, *rest):
        b, s, e = xv.shape
        three, h, d, _ = wv.shape
        qkv = jnp.einsum("bse,thde->bsthd", xv, wv)
        if rest:
            qkv = qkv + rest[0][None, None]
        return qkv

    args = [xin, qkv_weight] + ([qkv_bias] if qkv_bias is not None else [])
    qkv = apply("fused_qkv", qkv_fn, *args)
    q = qkv[:, :, 0]
    k = qkv[:, :, 1]
    v = qkv[:, :, 2]
    ctx = scaled_dot_product_attention(q, k, v, attn_mask=attn_mask, dropout_p=attn_dropout_rate if training else 0.0)

    def proj_fn(cv, wv, *rest):
        b, s, h, d = cv.shape
        out = cv.reshape(b, s, h * d) @ wv
        if rest:
            out = out + rest[0]
        return out

    args = [ctx, linear_weight] + ([linear_bias] if linear_bias is not None else [])
    out = apply("fused_attn_proj", proj_fn, *args)
    if dropout_rate and training:
        from ....nn.functional.common import dropout as _dropout

        out = _dropout(out, p=dropout_rate, training=True)
    out = out + x  # residual (reference adds residual inside the fused op)
    if not pre_layer_norm:
        out = fused_layer_norm(out, ln_scale, ln_bias, ln_epsilon)
    return out


def fused_feedforward(
    x,
    linear1_weight,
    linear2_weight,
    linear1_bias=None,
    linear2_bias=None,
    ln1_scale=None,
    ln1_bias=None,
    ln2_scale=None,
    ln2_bias=None,
    dropout1_rate=0.5,
    dropout2_rate=0.5,
    activation="relu",
    ln1_epsilon=1e-5,
    ln2_epsilon=1e-5,
    pre_layer_norm=False,
    training=True,
    name=None,
):
    """reference fused_transformer.py fused_feedforward: (pre-LN ->) linear
    -> act -> dropout -> linear -> dropout -> residual (-> post-LN)."""
    from ....nn.functional.common import dropout as _dropout

    xin = x
    if pre_layer_norm:
        xin = fused_layer_norm(x, ln1_scale, ln1_bias, ln1_epsilon)
    h = fused_linear(xin, linear1_weight, linear1_bias)
    if activation != "none":
        h = fused_bias_act(h, None, act_method=activation)
    if dropout1_rate and training:
        h = _dropout(h, p=dropout1_rate, training=True)
    h = fused_linear(h, linear2_weight, linear2_bias)
    if dropout2_rate and training:
        h = _dropout(h, p=dropout2_rate, training=True)
    out = x + h
    if not pre_layer_norm:
        out = fused_layer_norm(out, ln2_scale, ln2_bias, ln2_epsilon)
    return out


# ---------------------------------------------------------------------------
# fused linear + softmax cross-entropy (the LM-head loss)
# ---------------------------------------------------------------------------

def _flce_fwd_impl(h, W, b, labels, ignore_index, transpose_weight):
    """h [N,H]; W [H,V] (or [V,H] with transpose_weight); b [V] or None.

    All big intermediates stay in h.dtype (bf16 under AMP) — the f32 work
    (logsumexp, label logit) runs through f32-accumulated reductions that XLA
    fuses into the logits' consumer, so no [N,V] f32 buffer is materialized
    (the unfused path materializes four of them on a 40k vocab)."""
    cdt = h.dtype
    Wc = W.astype(cdt)
    z = (h @ Wc.T) if transpose_weight else (h @ Wc)  # [N, V]
    if b is not None:
        z = z + b.astype(cdt)
    m = jnp.max(z, axis=-1).astype(jnp.float32)
    sumexp = jnp.sum(jnp.exp(z.astype(jnp.float32) - m[:, None]), axis=-1)
    lse = m + jnp.log(sumexp)

    valid = labels != ignore_index
    lab = jnp.where(valid, labels, 0)
    # label logit in f32 via a row-gathered dot (exact even when z is bf16)
    W_lab = (W[lab] if transpose_weight else W[:, lab].T).astype(jnp.float32)
    ll = jnp.sum(h.astype(jnp.float32) * W_lab, axis=-1)
    if b is not None:
        ll = ll + b.astype(jnp.float32)[lab]
    n_valid = jnp.maximum(jnp.sum(valid), 1)
    loss = jnp.sum(jnp.where(valid, lse - ll, 0.0)) / n_valid
    return loss, (z, lse, lab, valid, n_valid)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flce(h, W, b, labels, ignore_index, transpose_weight):
    loss, _ = _flce_fwd_impl(h, W, b, labels, ignore_index, transpose_weight)
    return loss


def _flce_fwd(h, W, b, labels, ignore_index, transpose_weight):
    loss, (z, lse, lab, valid, n_valid) = _flce_fwd_impl(
        h, W, b, labels, ignore_index, transpose_weight
    )
    return loss, (h, W, b, z, lse, lab, valid, n_valid)


def _flce_bwd(ignore_index, transpose_weight, res, g):
    h, W, b, z, lse, lab, valid, n_valid = res
    cdt = z.dtype
    scale = (g / n_valid.astype(jnp.float32)) * valid.astype(jnp.float32)  # [N]
    # dz = (softmax(z) - onehot(lab)) * scale as ONE elementwise chain from
    # the saved (possibly bf16) z. The one-hot is an iota compare, not a
    # scatter: a scatter forces dz to materialize as its own [N,V] buffer,
    # while this chain fuses straight into the dh/dW matmul operand reads
    # (profiled: the scatter form cost an extra [N,V] round-trip per step)
    col = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    onehot = (col == lab[:, None].astype(jnp.int32)).astype(jnp.float32)
    p_scaled = jnp.exp(z.astype(jnp.float32) - lse[:, None]) * scale[:, None]
    dz = (p_scaled - onehot * scale[:, None]).astype(cdt)
    Wc = W.astype(cdt)
    dh = (dz @ Wc if transpose_weight else dz @ Wc.T).astype(h.dtype)
    if transpose_weight:
        dW = jnp.dot(dz.T, h, preferred_element_type=jnp.float32)
    else:
        dW = jnp.dot(h.T, dz, preferred_element_type=jnp.float32)
    dW = dW.astype(W.dtype)
    db = jnp.sum(dz.astype(jnp.float32), axis=0).astype(b.dtype) if b is not None else None
    return dh, dW, db, None


_flce.defvjp(_flce_fwd, _flce_bwd)


def fused_linear_cross_entropy(
    x, weight, labels, bias=None, ignore_index=-100, transpose_weight=False, name=None
):
    """Fused LM-head: mean softmax cross-entropy of ``x @ weight (+ bias)``
    against int labels, without materializing f32 logits (and with the label
    logit computed in f32 regardless of compute dtype).

    Reference parity: the role of paddle's fused
    ``cross_entropy_with_softmax`` + fused_linear epilogue used by LLM heads
    (paddle/phi/kernels/fusion/, python/paddle/incubate/nn/functional/);
    redesigned as one XLA-fused custom-vjp op.

    x: [N, H] (or [..., H] — leading dims are flattened)
    weight: [H, V], or [V, H] with transpose_weight=True (tied embeddings)
    labels: int [N] (or [...]), entries equal to ignore_index are masked out
    Returns the scalar mean loss over non-ignored labels.
    """
    def fn(xv, wv, lv, *rest):
        bv = rest[0] if rest else None
        H = xv.shape[-1]
        xf = xv.reshape((-1, H))
        lf = lv.reshape((-1,))
        return _flce(xf, wv, bv, lf, ignore_index, transpose_weight)

    args = [x, weight, labels] + ([bias] if bias is not None else [])
    with named_scope("loss"):
        return apply("fused_linear_cross_entropy", fn, *args)


# ---------------------------------------------------------------------------
# decode-time fused attention with kv cache (LLM serving path)
# ---------------------------------------------------------------------------

def masked_multihead_attention(
    x,
    cache_kv=None,
    bias=None,
    src_mask=None,
    cum_offsets=None,
    sequence_lengths=None,
    rotary_tensor=None,
    beam_cache_offset=None,
    qkv_out_scale=None,
    out_shift=None,
    out_smooth=None,
    seq_len=1,
    rotary_emb_dims=0,
    use_neox_rotary_style=False,
    compute_dtype="default",
    out_scale=-1,
    quant_round_type=1,
    quant_max_bound=127.0,
    quant_min_bound=-127.0,
):
    """Single-step decode attention with kv-cache append (reference
    incubate/nn/functional/masked_multihead_attention.py; CUDA kernel
    phi/fusion/masked_multihead_attention). x is the current token's fused
    qkv [B, 3*H*D]; cache_kv [2, B, H, max_seq, D]; sequence_lengths [B]
    gives each sample's current cache fill. Returns (out [B, H*D],
    cache_kv_out). Quant paths (qkv_out_scale/out_shift/...) are CUDA int8
    serving tricks — not supported."""
    for unsupported in (qkv_out_scale, out_shift, out_smooth, beam_cache_offset, cum_offsets):
        if unsupported is not None:
            raise NotImplementedError("masked_multihead_attention: quant/beam paths not supported")
    from ....core.tensor import Tensor as _T

    x = x if isinstance(x, _T) else _T(jnp.asarray(x))
    cache = cache_kv if isinstance(cache_kv, _T) else _T(jnp.asarray(cache_kv))

    def fn(xv, ckv, *rest):
        r = list(rest)
        bias_v = r.pop(0) if bias is not None else None
        mask_v = r.pop(0) if src_mask is not None else None
        seqlen_v = r.pop(0) if sequence_lengths is not None else None
        rot_v = r.pop(0) if rotary_tensor is not None else None
        _, B, H, S, D = ckv.shape
        qkv = xv
        if bias_v is not None:
            qkv = qkv + bias_v
        qkv = qkv.reshape(B, 3, H, D)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # [B, H, D]
        pos = (
            seqlen_v.reshape(B).astype(jnp.int32)
            if seqlen_v is not None
            else jnp.zeros((B,), jnp.int32)
        )
        if rotary_emb_dims > 0 and rot_v is not None:
            # rotary_tensor [2, B, 1, max_seq, D]: cos/sin at each position
            cos = jnp.take_along_axis(
                rot_v[0, :, 0], pos[:, None, None], axis=1
            )  # [B, 1, D]
            sin = jnp.take_along_axis(rot_v[1, :, 0], pos[:, None, None], axis=1)

            def rope(t):
                if use_neox_rotary_style:
                    half = D // 2
                    t1, t2 = t[..., :half], t[..., half:]
                    rt = jnp.concatenate([-t2, t1], axis=-1)
                else:
                    t1 = t[..., 0::2]
                    t2 = t[..., 1::2]
                    rt = jnp.stack([-t2, t1], axis=-1).reshape(t.shape)
                return t * cos + rt * sin

            q, k = rope(q), rope(k)
        # append k/v at each sample's position
        bidx = jnp.arange(B)
        new_k = ckv[0].at[bidx, :, pos, :].set(k)
        new_v = ckv[1].at[bidx, :, pos, :].set(v)
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
        logits = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32), new_k.astype(jnp.float32)) * scale
        sidx = jnp.arange(S)[None, None, :]
        valid = sidx <= pos[:, None, None]
        logits = jnp.where(valid, logits, -1e30)
        if mask_v is not None:
            logits = logits + mask_v.reshape(B, 1, -1)[:, :, :S].astype(jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhs,bhsd->bhd", p.astype(new_v.dtype), new_v)
        return out.reshape(B, H * D), jnp.stack([new_k, new_v])

    args = [x, cache]
    for t in (bias, src_mask, sequence_lengths, rotary_tensor):
        if t is not None:
            args.append(t if isinstance(t, Tensor) else Tensor(jnp.asarray(t)))
    out, new_cache = apply("masked_multihead_attention", fn, *args, n_outputs=2)
    # reference semantics: cache updated in place
    cache._replace_value(new_cache._raw())
    return out, cache


def block_multihead_attention(
    qkv,
    key_cache,
    value_cache,
    seq_lens_encoder,
    seq_lens_decoder,
    seq_lens_this_time,
    padding_offsets,
    cum_offsets,
    cu_seqlens_q,
    cu_seqlens_k,
    block_tables,
    pre_key_cache=None,
    pre_value_cache=None,
    cache_k_quant_scales=None,
    cache_v_quant_scales=None,
    cache_k_dequant_scales=None,
    cache_v_dequant_scales=None,
    qkv_out_scale=None,
    qkv_bias=None,
    out_shift=None,
    out_smooth=None,
    max_enc_len_this_time=None,
    max_dec_len_this_time=None,
    rope_emb=None,
    mask=None,
    tgt_mask=None,
    max_seq_len=-1,
    block_size=64,
    use_neox_style=False,
    **quant_kwargs,
):
    """Paged-KV-cache attention (reference block_multihead_attention.py;
    CUDA kernel phi/fusion/block_multi_head_attention). Host-orchestrated
    TPU version: per sample, prefill (seq_lens_encoder > 0) runs causal
    self-attention over the packed tokens and writes k/v into the sample's
    cache pages via block_tables; decode (seq_lens_decoder > 0) appends one
    token into the current page and attends over the gathered pages.

    Supported serving paths (r3): cachekv-int8 (uint8 caches, dynamic
    per-(batch,head) scales computed at prefill and written back into the
    quant/dequant scale tensors, or static caller-provided scales; the
    +128-offset uint8 layout of the reference test oracle), rotary
    embedding via `rope_emb` [2, B|1, max_seq, 1, D/2] (cos, sin; non-neox
    interleaved pairs) or [..., D] (neox halves), additive prefill `mask`
    [B, 1, S, S] and decode `tgt_mask`. Still rejected: pre-cache and the
    int8-activation (qkv_out_scale/out_shift/out_smooth) epilogues.
    Returns (out, qkv, key_cache, value_cache); caches + dynamic scales
    updated in place."""
    use_dynamic_cachekv_quant = quant_kwargs.pop("use_dynamic_cachekv_quant", False)
    quant_max_bound = float(quant_kwargs.pop("quant_max_bound", 127.0) or 127.0)
    for unsupported in (pre_key_cache, pre_value_cache, qkv_out_scale, out_shift, out_smooth):
        if unsupported is not None:
            raise NotImplementedError(
                "block_multihead_attention: pre-cache / int8-activation"
                " epilogue paths not supported"
            )
    import numpy as np
    from ....core.tensor import Tensor as _T

    def _np(t):
        return np.asarray(t._raw() if isinstance(t, _T) else t)

    qkv_t = qkv if isinstance(qkv, _T) else _T(jnp.asarray(qkv))
    qv = qkv_t._raw()
    if qkv_bias is not None:
        qv = qv + (qkv_bias._raw() if isinstance(qkv_bias, _T) else jnp.asarray(qkv_bias))
    kc = key_cache._raw() if isinstance(key_cache, _T) else jnp.asarray(key_cache)
    vc = value_cache._raw() if isinstance(value_cache, _T) else jnp.asarray(value_cache)
    enc = _np(seq_lens_encoder).reshape(-1)
    dec = _np(seq_lens_decoder).reshape(-1)
    this = _np(seq_lens_this_time).reshape(-1)
    tables = _np(block_tables)
    B = enc.shape[0]
    nb_heads, bs, hd = kc.shape[1], kc.shape[2], kc.shape[3]
    H = nb_heads
    token_dim = qv.shape[-1] // 3
    D = token_dim // H

    quant = kc.dtype == jnp.uint8
    if quant:
        kqs = jnp.asarray(_np(cache_k_quant_scales), jnp.float32) if cache_k_quant_scales is not None else None
        vqs = jnp.asarray(_np(cache_v_quant_scales), jnp.float32) if cache_v_quant_scales is not None else None
        kdq = jnp.asarray(_np(cache_k_dequant_scales), jnp.float32) if cache_k_dequant_scales is not None else None
        vdq = jnp.asarray(_np(cache_v_dequant_scales), jnp.float32) if cache_v_dequant_scales is not None else None
        if kqs is None or vqs is None:
            raise ValueError("uint8 caches require cache_k/v_quant_scales")

        def _quantize(x, qs_ih):  # away-from-zero round, +128 uint8 offset
            q_ = jnp.sign(x.astype(jnp.float32)) * jnp.floor(
                jnp.abs(x.astype(jnp.float32)) * qs_ih[:, None] + 0.5
            )
            return jnp.clip(q_ + 128.0, 0.0, 255.0).astype(jnp.uint8)

        def _dequantize(x, dq_ih):
            return (x.astype(jnp.float32) - 128.0) * dq_ih[:, None]

    rope = None
    if rope_emb is not None:
        re_ = jnp.asarray(_np(rope_emb), jnp.float32)  # [2, B|1, S, 1, D/2 or D]
        rope = (re_[0], re_[1])

    def _apply_rope(x, positions):
        """x [n, H, D]; positions len-n ints."""
        cos, sin = rope
        bsel = 0 if cos.shape[0] == 1 else None  # broadcast batch
        c = cos[bsel if bsel is not None else i, np.asarray(positions), 0]  # [n, D/2|D]
        s = sin[bsel if bsel is not None else i, np.asarray(positions), 0]
        xf = x.astype(jnp.float32)
        if c.shape[-1] == D // 2:
            if use_neox_style:
                c2 = jnp.concatenate([c, c], -1)[:, None, :]
                s2 = jnp.concatenate([s, s], -1)[:, None, :]
                x1, x2 = xf[..., : D // 2], xf[..., D // 2:]
                rot = jnp.concatenate([-x2, x1], -1)
                return (xf * c2 + rot * s2).astype(x.dtype)
            xp = xf.reshape(x.shape[0], H, D // 2, 2)
            x0, x1 = xp[..., 0], xp[..., 1]
            c2, s2 = c[:, None, :], s[:, None, :]
            o0 = x0 * c2 - x1 * s2
            o1 = x1 * c2 + x0 * s2
            return jnp.stack([o0, o1], -1).reshape(x.shape).astype(x.dtype)
        c2, s2 = c[:, None, :], s[:, None, :]
        x1, x2 = xf[..., : D // 2], xf[..., D // 2:]
        rot = jnp.concatenate([-x2, x1], -1)
        return (xf * c2 + rot * s2).astype(x.dtype)

    mask_v = jnp.asarray(_np(mask), jnp.float32) if mask is not None else None
    tgt_v = jnp.asarray(_np(tgt_mask), jnp.float32) if tgt_mask is not None else None

    outs = []
    tok = 0
    scale = 1.0 / float(np.sqrt(D))
    for i in range(B):
        n = int(this[i])
        if n == 0:
            continue
        cur = qv[tok : tok + n].reshape(n, 3, H, D)
        q, k, v = cur[:, 0], cur[:, 1], cur[:, 2]  # [n, H, D]
        if enc[i] > 0:
            if rope is not None:
                pos_ids = list(range(n))
                q = _apply_rope(q, pos_ids)
                k = _apply_rope(k, pos_ids)
            # prefill: causal self-attention over this sample's n tokens
            lg = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
            if mask_v is not None:
                lg = lg + mask_v[i, 0, :n, :n][None]
            else:
                cm = jnp.tril(jnp.ones((n, n), bool))
                lg = jnp.where(cm[None], lg, -1e30)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(lg, -1).astype(v.dtype), v)
            if quant:
                if use_dynamic_cachekv_quant:
                    kmax = jnp.maximum(jnp.max(jnp.abs(k.astype(jnp.float32)), axis=(0, 2)), 1e-6)
                    vmax = jnp.maximum(jnp.max(jnp.abs(v.astype(jnp.float32)), axis=(0, 2)), 1e-6)
                    kqs = kqs.at[i].set(quant_max_bound / kmax)
                    vqs = vqs.at[i].set(quant_max_bound / vmax)
                    kdq = kdq.at[i].set(kmax / quant_max_bound) if kdq is not None else None
                    vdq = vdq.at[i].set(vmax / quant_max_bound) if vdq is not None else None
                kq = _quantize(jnp.moveaxis(k, 1, 0).reshape(H, -1), kqs[i]).reshape(H, n, D)
                vq = _quantize(jnp.moveaxis(v, 1, 0).reshape(H, -1), vqs[i]).reshape(H, n, D)
            for t_ in range(n):
                page = int(tables[i, t_ // bs])
                slot = t_ % bs
                kc = kc.at[page, :, slot, :].set(kq[:, t_] if quant else k[t_])
                vc = vc.at[page, :, slot, :].set(vq[:, t_] if quant else v[t_])
        else:
            # decode: append one token at position dec[i], attend over cache
            pos = int(dec[i])
            if rope is not None:
                q = _apply_rope(q, [pos])
                k = _apply_rope(k, [pos])
            page = int(tables[i, pos // bs])
            slot = pos % bs
            if quant:
                kc = kc.at[page, :, slot, :].set(
                    _quantize(k[0], kqs[i]))
                vc = vc.at[page, :, slot, :].set(
                    _quantize(v[0], vqs[i]))
            else:
                kc = kc.at[page, :, slot, :].set(k[0])
                vc = vc.at[page, :, slot, :].set(v[0])
            npages = pos // bs + 1
            pages = tables[i, :npages].astype(np.int64)
            ks = kc[jnp.asarray(pages)].transpose(1, 0, 2, 3).reshape(H, npages * bs, D)
            vs = vc[jnp.asarray(pages)].transpose(1, 0, 2, 3).reshape(H, npages * bs, D)
            ks, vs = ks[:, : pos + 1], vs[:, : pos + 1]
            if quant:
                kd = kdq[i] if kdq is not None else 1.0 / kqs[i]
                vd = vdq[i] if vdq is not None else 1.0 / vqs[i]
                ks = _dequantize(ks.reshape(H, -1), kd).reshape(H, pos + 1, D).astype(v.dtype)
                vs = _dequantize(vs.reshape(H, -1), vd).reshape(H, pos + 1, D).astype(v.dtype)
            lg = jnp.einsum("qhd,hkd->hqk", q.astype(jnp.float32), ks.astype(jnp.float32)) * scale
            if tgt_v is not None:
                lg = lg + tgt_v[i].reshape(-1)[: pos + 1][None, None, :]
            o = jnp.einsum("hqk,hkd->qhd", jax.nn.softmax(lg, -1).astype(vs.dtype), vs)
        outs.append(o.reshape(n, H * D))
        tok += n
    out = _T(jnp.concatenate(outs) if outs else jnp.zeros((0, token_dim), qv.dtype))
    if isinstance(key_cache, _T):
        key_cache._replace_value(kc)
        value_cache._replace_value(vc)
    if quant and use_dynamic_cachekv_quant:
        for t, vnew in (
            (cache_k_quant_scales, kqs), (cache_v_quant_scales, vqs),
            (cache_k_dequant_scales, kdq), (cache_v_dequant_scales, vdq),
        ):
            if isinstance(t, _T) and vnew is not None:
                t._replace_value(vnew)
    return out, qkv_t, key_cache, value_cache


def variable_length_memory_efficient_attention(
    query, key, value, seq_lens, kv_seq_lens, mask=None, scale=None,
    causal=False, pre_cache_length=0,
):
    """Variable-length batched attention (reference
    incubate/nn/functional/variable_length_memory_efficient_attention.py —
    the CUTLASS varlen kernel). TPU-native: one fully vectorized masked
    attention over the padded [B, H, S, D] batch — padding positions are
    masked at -inf and zeroed in the output, which XLA fuses without any
    per-sample host loop.

    query [B, H, Sq, D]; key/value [B, Hkv, Sk, D] (Hkv may divide H — GQA);
    seq_lens / kv_seq_lens [B] or [B, 1]; mask [B, 1, Sq, Sk] additive.
    """
    if pre_cache_length:
        raise NotImplementedError(
            "variable_length_memory_efficient_attention: pre_cache_length != 0 "
            "not supported — concatenate the pre-cache into key/value instead"
        )
    from ....core.tensor import Tensor as _T

    q = query if isinstance(query, _T) else _T(jnp.asarray(query))
    k = key if isinstance(key, _T) else _T(jnp.asarray(key))
    v = value if isinstance(value, _T) else _T(jnp.asarray(value))
    sl = seq_lens if isinstance(seq_lens, _T) else _T(jnp.asarray(seq_lens))
    kvl = kv_seq_lens if isinstance(kv_seq_lens, _T) else _T(jnp.asarray(kv_seq_lens))
    args = [q, k, v, sl, kvl] + ([mask if isinstance(mask, _T) else _T(jnp.asarray(mask))] if mask is not None else [])

    def fn(qv, kv, vv, slv, kvlv, *rest):
        B, H, Sq, D = qv.shape
        Hkv, Sk = kv.shape[1], kv.shape[2]
        if Hkv != H:  # GQA: repeat kv heads
            rep = H // Hkv
            kv = jnp.repeat(kv, rep, axis=1)
            vv = jnp.repeat(vv, rep, axis=1)
        sc = scale if scale is not None else 1.0 / math.sqrt(D)
        lg = jnp.einsum("bhqd,bhkd->bhqk", qv.astype(jnp.float32), kv.astype(jnp.float32)) * sc
        if rest:
            lg = lg + rest[0].astype(jnp.float32)
        kpos = jnp.arange(Sk)[None, None, None, :]
        kvalid = kpos < kvlv.reshape(-1)[:, None, None, None]
        lg = jnp.where(kvalid, lg, -jnp.inf)
        if causal:
            qpos = jnp.arange(Sq)[None, None, :, None]
            lg = jnp.where(qpos + (Sk - Sq) >= kpos, lg, -jnp.inf)
        p = jax.nn.softmax(lg, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vv.dtype), vv)
        qvalid = jnp.arange(Sq)[None, None, :, None] < slv.reshape(-1)[:, None, None, None]
        return jnp.where(qvalid, out, jnp.zeros((), out.dtype))

    return apply("variable_length_memory_efficient_attention", fn, *args)


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False, name=None):
    """reference fused_matmul_bias.py: matmul + bias epilogue (XLA fuses)."""
    def fn(xv, yv, *rest):
        a = jnp.swapaxes(xv, -1, -2) if transpose_x else xv
        b = jnp.swapaxes(yv, -1, -2) if transpose_y else yv
        out = a @ b
        return out + rest[0] if rest else out

    args = [x, y] + ([bias] if bias is not None else [])
    return apply("fused_matmul_bias", fn, *args)


def fused_bias_dropout_residual_layer_norm(
    x, residual, bias=None, ln_scale=None, ln_bias=None, dropout_rate=0.5,
    ln_epsilon=1e-5, training=True, mode="upscale_in_train", name=None,
):
    """reference fused_transformer.py fused_bias_dropout_residual_layer_norm:
    layer_norm(residual + dropout(x + bias))."""
    from ....nn.functional.common import dropout as _dropout
    from ....nn.functional.norm import layer_norm as _layer_norm
    from ....ops import math as _m

    h = x if bias is None else _m.add(x, bias)
    h = _dropout(h, p=dropout_rate, training=training, mode=mode)
    h = _m.add(h, residual)
    d = int(h.shape[-1])
    return _layer_norm(h, d, ln_scale, ln_bias, ln_epsilon)


def fused_ec_moe(x, gate, bmm0_weight, bmm0_bias, bmm1_weight, bmm1_bias, act_type):
    """reference fused_ec_moe.py: dense-evaluated MoE FFN — every expert's
    FFN over every token, combined with softmax gate weights. On the MXU a
    dense einsum over a modest expert count beats gather/scatter routing."""
    if act_type not in ("gelu", "relu"):
        raise ValueError("fused_ec_moe act_type must be gelu or relu")

    def fn(xv, gv, w0, b0, w1, b1):
        act = jax.nn.gelu if act_type == "gelu" else jax.nn.relu
        # h[e, b, s, f] = act(x @ w0[e] + b0[e])
        h = jnp.einsum("bsd,edf->ebsf", xv, w0) + b0[:, None]
        h = act(h)
        # fixed reference layout: bmm1_weight [E, FF, D]
        out_e = jnp.einsum("ebsf,efd->ebsd", h, w1) + b1[:, None]
        probs = jax.nn.softmax(gv.astype(jnp.float32), axis=-1).astype(xv.dtype)
        return jnp.einsum("ebsd,bse->bsd", out_e, probs)

    return apply("fused_ec_moe", fn, x, gate, bmm0_weight, bmm0_bias, bmm1_weight, bmm1_bias)


def fused_multi_transformer(
    x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
    linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights, ffn1_biases,
    ffn2_weights, ffn2_biases, pre_layer_norm=True, epsilon=1e-5,
    cache_kvs=None, pre_caches=None, seq_lens=None, rotary_embs=None,
    time_step=None, attn_mask=None, dropout_rate=0.0, rotary_emb_dims=0,
    activation="gelu", training=False, mode="upscale_in_train",
    trans_qkvw=True, ring_id=-1, name=None,
):
    """reference fused_transformer.py:964 — N fused transformer layers in
    one call (the serving fast path). Standard-precision path with optional
    decode kv caches (cache layout [2, B, H, max_seq, D], time_step = write
    position); rotary/pre_cache paths raise loudly. One XLA program does the
    fusing the CUDA mega-kernel does by hand."""
    for unsupported, what in (
        (rotary_embs, "rotary_embs"), (pre_caches, "pre_caches"),
        (seq_lens, "seq_lens (mask padded positions via attn_mask instead)"),
    ):
        if unsupported is not None:
            raise NotImplementedError(f"fused_multi_transformer: {what} not supported")
    from ....nn.functional.common import dropout as _dropout
    from ....nn.functional.norm import layer_norm as _layer_norm
    from ....ops import math as _m, manipulation as _mp
    from ....core.tensor import Tensor as _T
    import math as _pm

    n_layers = len(qkv_weights)
    out = x
    new_caches = []
    ts = int(time_step.numpy()) if isinstance(time_step, _T) else time_step

    for i in range(n_layers):
        residual = out
        h = _layer_norm(out, int(out.shape[-1]), ln_scales[i], ln_biases[i], epsilon) if pre_layer_norm else out

        def attn_fn(hv, qkvw, *rest):
            b, s, d = hv.shape
            qkvb = rest[0] if qkv_biases is not None and qkv_biases[i] is not None else None
            w = qkvw
            if trans_qkvw:  # [3, H, Dh, d] -> project via einsum
                three, H, Dh, _ = w.shape
                qkv = jnp.einsum("bsd,thed->bsthe", hv, w)
            else:           # [d, 3, H, Dh]
                _, three, H, Dh = w.shape
                qkv = jnp.einsum("bsd,dthe->bsthe", hv, w)
            if qkvb is not None:
                qkv = qkv + qkvb.reshape(1, 1, 3, H, Dh)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]     # [B,S,H,Dh]
            qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))  # [B,H,S,Dh]
            cache = rest[-1] if cache_kvs is not None else None
            if cache is not None and ts is not None:
                # decode: append this step at position ts, attend over cache
                ck = cache[0].astype(kh.dtype)
                cv = cache[1].astype(vh.dtype)
                ck = jax.lax.dynamic_update_slice(ck, kh, (0, 0, ts, 0))
                cv = jax.lax.dynamic_update_slice(cv, vh, (0, 0, ts, 0))
                kh2, vh2 = ck[:, :, : ts + 1], cv[:, :, : ts + 1]
                new_cache = jnp.stack([ck, cv])
            else:
                kh2, vh2 = kh, vh
                new_cache = None
                if cache is not None:  # prefill into the cache
                    ck = jax.lax.dynamic_update_slice(
                        cache[0].astype(kh.dtype), kh, (0, 0, 0, 0))
                    cv = jax.lax.dynamic_update_slice(
                        cache[1].astype(vh.dtype), vh, (0, 0, 0, 0))
                    new_cache = jnp.stack([ck, cv])
            scale = 1.0 / _pm.sqrt(q.shape[-1])
            logits = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32), kh2.astype(jnp.float32)) * scale
            if attn_mask is not None:
                mv = attn_mask._raw() if isinstance(attn_mask, _T) else jnp.asarray(attn_mask)
                logits = logits + mv[:, :, :logits.shape[2], :logits.shape[3]].astype(jnp.float32)
            elif cache is None or ts is None:
                sq, sk = logits.shape[-2], logits.shape[-1]
                cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
                logits = jnp.where(cm, logits, -1e30)
            p = jax.nn.softmax(logits, -1).astype(vh2.dtype)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, vh2)
            o = jnp.swapaxes(o, 1, 2).reshape(b, s, -1)
            return (o, new_cache) if new_cache is not None else o

        args = [h, qkv_weights[i]]
        if qkv_biases is not None and qkv_biases[i] is not None:
            args.append(qkv_biases[i])
        if cache_kvs is not None:
            args.append(cache_kvs[i])
        attn_out = apply(f"fmt_attn_{i}", attn_fn, *args,
                         n_outputs=2 if cache_kvs is not None else None)
        if cache_kvs is not None:
            attn_out, cache_out = attn_out
            new_caches.append(cache_out)

        proj = fused_linear(attn_out, linear_weights[i],
                            linear_biases[i] if linear_biases is not None else None)
        proj = _dropout(proj, p=dropout_rate, training=training, mode=mode)
        out = _m.add(residual, proj)
        if not pre_layer_norm:
            out = _layer_norm(out, int(out.shape[-1]), ln_scales[i], ln_biases[i], epsilon)

        residual = out
        h = _layer_norm(out, int(out.shape[-1]), ffn_ln_scales[i], ffn_ln_biases[i], epsilon) if pre_layer_norm else out
        h = fused_linear(h, ffn1_weights[i], ffn1_biases[i] if ffn1_biases is not None else None)
        h = fused_bias_act(h, act_method=activation)
        h = fused_linear(h, ffn2_weights[i], ffn2_biases[i] if ffn2_biases is not None else None)
        h = _dropout(h, p=dropout_rate, training=training, mode=mode)
        out = _m.add(residual, h)
        if not pre_layer_norm:
            out = _layer_norm(out, int(out.shape[-1]), ffn_ln_scales[i], ffn_ln_biases[i], epsilon)

    if cache_kvs is not None:
        for c, nc in zip(cache_kvs, new_caches):
            if isinstance(c, _T) and nc is not None:
                c._replace_value(nc._raw() if isinstance(nc, _T) else nc)
        return out, cache_kvs
    return out
