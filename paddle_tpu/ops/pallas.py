"""Pallas TPU kernels (flash attention; more hot ops over time).

Reference parity: the role of paddle/phi/kernels/gpu/flash_attn_kernel.cu
(forward AND backward flash kernels) and the fused CUDA ops in
paddle/fluid/operators/fused/ — but written as Pallas TPU kernels
(MXU-tiled, VMEM-resident softmax accumulators) per
/opt/skills/guides/pallas_guide.md. Falls back to the XLA-fused reference
implementation when the platform or shapes don't fit the kernel grid.

Shapes: [B, S, H, D] (paddle layout). Self- AND cross-attention are
supported (kv length may differ from q length — the kv-cache prefill /
encoder-decoder case); causal masking uses bottom-right alignment when
kv is longer than q (flash-attn convention, matches the XLA reference
chain below). The backward is the recompute-based O(S) flash backward:
forward saves only (out, logsumexp); dq/dk/dv kernels recompute the
probability tiles blockwise.

Round 5 capabilities (reference bar:
python/paddle/nn/functional/flash_attention.py:151 `dropout`,
paddle/phi/kernels/gpu/flash_attn_utils.h:140 `num_heads_k`):

- **Attention dropout in-kernel.** The keep/drop decision is a STATELESS
  hash of (seed, q-head index, absolute q position, absolute k position)
  — a murmur3-style integer mix computed on the VPU per logits tile. No
  mask is ever materialized in HBM, and because the hash depends only on
  absolute positions, the dq and dk/dv kernels regenerate the exact same
  mask even though they tile the score matrix differently. Semantics are
  upscale-in-train: kept probabilities are scaled by 1/(1-p); the softmax
  normalizer (and the saved logsumexp) stay dropout-free, matching
  dropout(softmax(s)) @ v.
- **Native GQA/MQA.** k/v carry their own head count h_kv | h_q; the
  kernel grids map each q head to its kv head via index arithmetic
  (q head j reads kv head j // (h_q // h_kv) — the reference repeat_kv
  ordering) so repeated K/V are never materialized. dk/dv accumulate
  over the q heads of a group in-VMEM via a group-innermost grid axis.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import numpy as np
from jax import lax
from jax import numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shape granularity accepted by the kernel (usable() gate): seq lengths
# must be multiples of this. Actual block sizes are picked per call by
# _pick_block — measured on TPU v5 lite, 512x512 blocks run the S=4096
# fwd+bwd ~5x faster than 128x128 (6.0 vs 32.7 ms; loop/revisit overhead
# dominates small blocks). At head_dim 128 the tiles are MXU-full-width
# and 1024x1024 is another ~10% faster (3.2 -> 2.85 ms measured); at
# head_dim 64 the 1024 tiling exceeds the 16MB VMEM stack, so the cap is
# head-dim-conditional (_block_cap: exactly 128 gets the wide tiles).
_MIN_BLOCK = 128
_MAX_BLOCK_Q = 512
_MAX_BLOCK_K = 512
_MAX_BLOCK_WIDE = 1024  # head_dim == 128 exactly (the validated point)


def _block_cap(d, base):
    """1024 tiles only at head_dim 128 — the configuration measured to fit
    VMEM and run ~10% faster; d=64 at 1024 overflows the 16MB VMEM stack
    and d in (128, 256] is unvalidated (usable() admits it), so both keep
    the 512 cap and larger heads never compile-fail without a fallback."""
    return _MAX_BLOCK_WIDE if d == 128 else base


def _pick_block(s, cap):
    for b in (1024, 512, 384, 256, 128):
        if b <= cap and s % b == 0:
            return b
    return _MIN_BLOCK


def _dot_nt(a, b):
    """a @ b.T with f32 accumulation, inputs kept in their storage dtype so
    the MXU runs at the bf16 rate (casting to f32 first quarters it)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_nn(a, b):
    """a @ b with f32 accumulation (see _dot_nt)."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_bnt(a, b):
    """_dot_nt batched over a leading axis: [h, r, d] x [h, k, d] -> [h, r, k]."""
    return jax.lax.dot_general(
        a, b, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )


def _dot_bnn(a, b):
    """_dot_nn batched over a leading axis: [h, r, k] x [h, k, d] -> [h, r, d]."""
    return jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )


def _dot_tn(a, b):
    """a.T @ b with f32 accumulation (see _dot_nt)."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

# Auto-dispatch threshold: below this kv length the XLA-fused plain-softmax
# chain WINS — measured on TPU v5 lite with the r4 tuned kernel (bf16 MXU
# inputs + 512x512 blocks at head_dim 64; head_dim 128 additionally runs
# 1024x1024 tiles above S=1024, measured ~10% faster than its 512 config —
# the gate itself was derived at d=64, the conservative point, since flash
# only gets FASTER with the wide tiling; benchmarks/attn_crossover.py,
# fwd+bwd, random
# cotangents, tokens held constant at B*S=8192): S=128: xla 0.65ms vs
# flash 1.69; S=256: 1.10 vs 1.88; S=512: 2.10 vs 1.64; S=1024: 3.93 vs
# 2.69; S=4096: 22.6 vs 4-6. Explicit flash_attention()/
# flash_attention_bshd() calls are NOT gated — only the
# scaled_dot_product_attention auto-dispatch.
try:
    _FLASH_MIN_SK = int(os.environ.get("PADDLE_TPU_FLASH_MIN_SEQ", 512))
except ValueError:
    import warnings

    warnings.warn("PADDLE_TPU_FLASH_MIN_SEQ is not an integer; using 512")
    _FLASH_MIN_SK = 512

# tests on the CPU mesh flip this to run kernels in pallas interpret mode
_INTERPRET = False

# The wide-tile (1024-block, d=128) configs need ~16.8MB of scoped VMEM —
# just over the compiler's 16MB default budget (physical VMEM on v5e is
# much larger); raise the per-kernel budget so the tuned tiles compile.
_VMEM_LIMIT = 40 * 1024 * 1024

# every grid axis is an independent (bh, block) tile — declaring them
# parallel lets Mosaic pipeline HBM->VMEM copies across grid steps
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=_VMEM_LIMIT,
)
# dkdv grid is (b*h_kv, n_k, group): the group axis REVISITS the same
# dk/dv block on consecutive steps (in-VMEM accumulation), so it must be
# sequential ("arbitrary"), not parallel
_COMPILER_PARAMS_3D = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT,
)


def _on_tpu() -> bool:
    """The ONE kernel-dispatch gate (every Pallas entry point in the tree
    asks here): a TPU backend, or interpret mode on the CPU test mesh."""
    return _INTERPRET or jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# dropout: stateless position hash (murmur3-style fmix32 on the VPU)
# ---------------------------------------------------------------------------

def _i32(x):
    """uint32 literal -> the int32 with the same bit pattern."""
    return np.uint32(x & 0xFFFFFFFF).astype(np.int32)


_C_Q = _i32(0x9E3779B1)   # golden-ratio odd constants: distinct per input
_C_K = _i32(0x85EBCA77)
_C_BH = _i32(0x27D4EB2F)
_C_M1 = _i32(0x85EBCA6B)  # murmur3 fmix32 multipliers
_C_M2 = _i32(0xC2B2AE35)
_DROP_BITS = 23           # dropout probability resolution: 2^-23


def _keep_threshold(dropout_p: float) -> int:
    return int(round((1.0 - float(dropout_p)) * (1 << _DROP_BITS)))


def _hash_keep(seed, bh, qpos, kpos, thresh):
    """keep-mask for absolute score positions (qpos, kpos) — both int32
    arrays of the same shape — under (seed, q-head bh). Pure int32 VPU ops,
    identical algebra in-kernel and in the jnp reference path, so every
    tiling of the score matrix regenerates the same mask."""
    _16 = np.int32(16)
    _13 = np.int32(13)
    u = (qpos * _C_Q) ^ (kpos * _C_K) ^ (seed + bh * _C_BH)
    u = u ^ lax.shift_right_logical(u, _16)
    u = u * _C_M1
    u = u ^ lax.shift_right_logical(u, _13)
    u = u * _C_M2
    u = u ^ lax.shift_right_logical(u, _16)
    return (u & _i32((1 << _DROP_BITS) - 1)) < np.int32(thresh)


def _tile_keep(seed, bh, q0, k0, bq, bk, thresh):
    """keep-mask for one (bq, bk) logits tile whose top-left score position
    is (q0, k0)."""
    qpos = q0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return _hash_keep(seed, bh, qpos, kpos, thresh)


def dropout_keep_reference(seed, n_bh, sq, sk, dropout_p):
    """[n_bh, sq, sk] bool keep-mask — the exact mask the kernels apply
    (oracle for tests and for the XLA fallback path, which therefore has
    bitwise-identical dropout semantics to the kernel)."""
    thresh = _keep_threshold(dropout_p)
    seed = jnp.asarray(seed, jnp.int32).reshape(())

    def one(bh):
        return _tile_keep(seed, bh, np.int32(0), np.int32(0), sq, sk, thresh)

    return jax.vmap(one)(jnp.arange(n_bh, dtype=jnp.int32))


def _as_seed(dropout_seed, dropout_p=0.0):
    """Normalize the user seed to the (1,) int32 scalar-prefetch operand.

    None with active dropout draws a FRESH seed from the framework generator
    (trace-aware under to_static, like sdpa's) — the one source of truth for
    the default, so the flash entry points can't drift apart. Validates the
    common foot-guns loudly: a non-scalar seed would silently take element 0
    after reshape, a float would truncate, and a python int outside int32
    range would wrap to a different mask than the caller thinks they seeded.
    """
    if dropout_seed is None:
        if dropout_p > 0.0:
            return _fresh_dropout_seed()
        return jnp.zeros((1,), jnp.int32)
    import numbers

    if isinstance(dropout_seed, bool) or isinstance(dropout_seed, float):
        raise ValueError(
            f"dropout_seed must be an int32-range integer scalar, got "
            f"{type(dropout_seed).__name__} {dropout_seed!r}"
        )
    if isinstance(dropout_seed, numbers.Integral):
        v = int(dropout_seed)
        if not (-(2 ** 31) <= v < 2 ** 31):
            raise ValueError(
                f"dropout_seed {v} is outside int32 range [-2**31, 2**31)"
            )
        return jnp.full((1,), v, jnp.int32)
    arr = jnp.asarray(dropout_seed)
    if arr.size != 1:
        raise ValueError(
            f"dropout_seed must be a scalar, got shape {tuple(arr.shape)}"
        )
    if not jnp.issubdtype(arr.dtype, jnp.integer):
        raise ValueError(
            f"dropout_seed must be an integer scalar, got dtype {arr.dtype}"
        )
    return arr.astype(jnp.int32).reshape((1,))


def _fresh_dropout_seed():
    """Per-call int32 seed drawn from the framework generator (trace-aware
    under to_static, like sdpa's): dropout_p > 0 with dropout_seed=None must
    mean fresh dropout each step, not the silent fixed seed 0."""
    from ..framework.random import next_key

    return jax.random.randint(next_key(), (1,), 0, 2 ** 31 - 1, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# dispatch gates
# ---------------------------------------------------------------------------

def flash_attention_usable(q, causal, dropout_p, k=None, v=None) -> bool:
    """Kernel constraints: TPU platform, q seq and kv seq each a multiple of
    the block, head_dim <= 256. Cross-attention / kv-cache prefill (kv
    length != q length) is supported; GQA/MQA is supported natively (kv
    heads must divide q heads — reference flash_attn_utils.h:140
    num_heads_k); dropout is supported in-kernel (reference
    flash_attention.py:151). [B, S, H, D]."""
    if not _on_tpu():
        return False
    if not (0.0 <= dropout_p < 1.0):
        return False
    if q.ndim != 4:
        return False
    b, sq, h, d = q.shape
    if not (sq % _MIN_BLOCK == 0 and d <= 256 and sq >= _MIN_BLOCK):
        return False
    kv_heads = set()
    for other in (k, v):
        if other is None:
            continue
        ob, sk, oh, od = other.shape
        if (ob, od) != (b, d):
            return False
        if oh > h or h % oh != 0:
            return False
        kv_heads.add(int(oh))
        if not (sk % _MIN_BLOCK == 0 and sk >= _MIN_BLOCK):
            return False
        if causal and sk < sq:
            # bottom-right-aligned causal with kv shorter than q fully masks
            # the leading q rows (0/0 in the kernel; the XLA chain's output
            # for those rows is garbage-by-construction too) — fall back
            return False
    if len(kv_heads) > 1:  # k and v must agree on head count
        return False
    return True


def flash_attention_profitable(q, causal, dropout_p, k=None, v=None) -> bool:
    """Auto-dispatch gate: usable AND long enough that the O(S) memory of the
    flash kernel pays for itself. Below _FLASH_MIN_SK the XLA-fused plain
    chain is faster on this hardware (see _FLASH_MIN_SK comment)."""
    if not flash_attention_usable(q, causal, dropout_p, k, v):
        return False
    sk = (k if k is not None else q).shape[1]
    return sk >= _FLASH_MIN_SK


def _mask_boundary(logits, off, qi, ki, bq, bk):
    """Causal mask for one (qi, ki) tile, applied ONLY when the tile
    straddles the diagonal — fully-visible tiles skip the iota/select VPU
    work entirely (fully-hidden tiles are never visited: the kmax/qmin loop
    bounds exclude them). A tile is fully visible iff its smallest q
    position sees its largest k position: off + qi*bq >= ki*bk + bk - 1."""
    qi = jnp.asarray(qi, jnp.int32)
    ki = jnp.asarray(ki, jnp.int32)

    def apply(l):
        qpos = off + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        return jnp.where(qpos >= kpos, l, -1e30)

    full = off + qi * bq >= ki * bk + bk - 1
    return jax.lax.cond(full, lambda l: l, apply, logits)


def _ref_attention_bshd(q, k, v, causal, sm_scale, dropout_p=0.0, seed=None):
    """XLA reference chain (fallback + numerics oracle in tests). GQA kv is
    repeated here (the fallback pays the HBM cost the kernel avoids); the
    dropout mask is the SAME position hash the kernel applies."""
    h, hkv = q.shape[2], k.shape[2]
    if hkv != h:
        k = repeat_kv(k, h // hkv)
        v = repeat_kv(v, h // hkv)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    d = qh.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh).astype(jnp.float32) * scale
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(cm, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0:
        b, _, sq, sk = logits.shape
        keep = dropout_keep_reference(seed, b * h, sq, sk, dropout_p)
        keep = keep.reshape(b, h, sq, sk)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    p = p.astype(qh.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# forward kernel: online softmax over K blocks, emits out + logsumexp
# ---------------------------------------------------------------------------

def _fwd_kernels(sq, sk, d, causal, scale, bq, bk, dropout_p):
    n_k = sk // bk
    off = sk - sq  # causal bottom-right alignment offset
    use_drop = dropout_p > 0.0
    thresh = _keep_threshold(dropout_p)
    inv_keep = np.float32(1.0 / (1.0 - dropout_p)) if use_drop else None

    def kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
        bh = pl.program_id(0)
        qi = pl.program_id(1)
        seed = seed_ref[0]
        qb = q_ref[...]  # storage dtype — bf16 in, MXU at bf16 rate

        m0 = jnp.full((bq, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((bq, 1), jnp.float32)
        acc0 = jnp.zeros((bq, d), jnp.float32)

        if causal:
            # last k position visible to this q block: off + (qi+1)*BQ - 1
            kmax_dyn = (off + (qi + 1) * bq + bk - 1) // bk
            kmax = jnp.minimum(jnp.asarray(kmax_dyn, jnp.int32), n_k)
        else:
            kmax = jnp.asarray(n_k, jnp.int32)

        def body(ki, carry):
            m, l, acc = carry
            ki = jnp.asarray(ki, jnp.int32)
            kb = k_ref[pl.dslice(ki * bk, bk), :]
            vb = v_ref[pl.dslice(ki * bk, bk), :]
            logits = _dot_nt(qb, kb) * scale
            if causal:
                logits = _mask_boundary(logits, off, qi, ki, bq, bk)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m - m_new)
            # the softmax normalizer is dropout-free (dropout applies to the
            # normalized probabilities) — l accumulates the full p sum
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if use_drop:
                keep = _tile_keep(seed, bh, qi * bq, ki * bk, bq, bk, thresh)
                p_acc = jnp.where(keep, p, 0.0) * inv_keep
            else:
                p_acc = p
            # p cast to the storage dtype before the MXU matmul — the same
            # precision the XLA fallback uses (softmax.astype(q.dtype) @ v)
            acc_new = acc * alpha + _dot_nn(p_acc.astype(vb.dtype), vb)
            return m_new, l_new, acc_new

        m, l, acc = jax.lax.fori_loop(
            jnp.asarray(0, jnp.int32), kmax, body, (m0, l0, acc0)
        )
        o_ref[...] = (acc / l).astype(o_ref.dtype)
        lse_ref[...] = (m + jnp.log(l)).astype(jnp.float32)

    return kernel


def _flash_fwd_impl(q, k, v, seed, causal, sm_scale, dropout_p):
    """[B, S, H, D] -> (out, lse[B*Hq, Sq, 1]). k/v may carry fewer heads
    (GQA): q head j reads kv head j // group."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qr = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kr = jnp.swapaxes(k, 1, 2).reshape(b * hkv, sk, d)
    vr = jnp.swapaxes(v, 1, 2).reshape(b * hkv, sk, d)
    bq = _pick_block(sq, _block_cap(d, _MAX_BLOCK_Q))
    bk = _pick_block(sk, _block_cap(d, _MAX_BLOCK_K))
    n_q = sq // bq

    # group == 1 keeps the identity index map — the kv_of arithmetic is
    # algebraically bh there, and spelling it plainly preserves the r4
    # kernel's exact VMEM footprint (the tuned wide-tile configs sit within
    # ~2% of the 16MB scoped-vmem budget)
    if group == 1:
        kv_of = lambda bh: bh
    else:
        def kv_of(bh):
            # q-head grid index -> kv-head row of kr/vr
            return (bh // h) * hkv + (bh % h) // group

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, n_q),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((None, sk, d), lambda bh, qi, *_: (kv_of(bh), 0, 0)),
            pl.BlockSpec((None, sk, d), lambda bh, qi, *_: (kv_of(bh), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((None, bq, 1), lambda bh, qi, *_: (bh, qi, 0)),
        ],
    )
    out, lse = pl.pallas_call(
        _fwd_kernels(sq, sk, d, causal, scale, bq, bk, dropout_p),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_INTERPRET,
        name="flash_fwd",
    )(seed, qr, kr, vr)
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2), lse


# ---------------------------------------------------------------------------
# backward kernels: recompute-based (O(S) memory), FA2 formulation
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(sq, sk, d, causal, scale, bq, bk, dropout_p):
    n_k = sk // bk
    off = sk - sq
    use_drop = dropout_p > 0.0
    thresh = _keep_threshold(dropout_p)
    inv_keep = np.float32(1.0 / (1.0 - dropout_p)) if use_drop else None

    def kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref):
        bh = pl.program_id(0)
        qi = pl.program_id(1)
        seed = seed_ref[0]
        qb = q_ref[...]
        dob = do_ref[...]
        lse = lse_ref[...].astype(jnp.float32)      # [BQ, 1]
        delta = delta_ref[...].astype(jnp.float32)  # [BQ, 1]

        if causal:
            kmax_dyn = (off + (qi + 1) * bq + bk - 1) // bk
            kmax = jnp.minimum(jnp.asarray(kmax_dyn, jnp.int32), n_k)
        else:
            kmax = jnp.asarray(n_k, jnp.int32)

        def body(ki, dq):
            ki = jnp.asarray(ki, jnp.int32)
            kb = k_ref[pl.dslice(ki * bk, bk), :]
            vb = v_ref[pl.dslice(ki * bk, bk), :]
            s = _dot_nt(qb, kb) * scale
            if causal:
                s = _mask_boundary(s, off, qi, ki, bq, bk)
            p = jnp.exp(s - lse)
            dp = _dot_nt(dob, vb)  # = d(dropped P) for the dropout case
            if use_drop:
                keep = _tile_keep(seed, bh, qi * bq, ki * bk, bq, bk, thresh)
                # z-form: keep-select and the 1/(1-p) upscale collapse into
                # one mask product (same shape as the dkdv kernel's z)
                z = jnp.where(keep, inv_keep, 0.0)
                dp = dp * z
            ds = p * (dp - delta) * scale
            return dq + _dot_nn(ds.astype(kb.dtype), kb)

        dq = jax.lax.fori_loop(
            jnp.asarray(0, jnp.int32), kmax, body, jnp.zeros((bq, d), jnp.float32)
        )
        dq_ref[...] = dq.astype(dq_ref.dtype)

    return kernel


def _bwd_dkdv_kernel(sq, sk, d, causal, scale, bq, bk, dropout_p, h, hkv):
    n_q = sq // bq
    off = sk - sq
    group = h // hkv
    use_drop = dropout_p > 0.0
    thresh = _keep_threshold(dropout_p)
    inv_keep = np.float32(1.0 / (1.0 - dropout_p)) if use_drop else None

    def kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref):
        kv = pl.program_id(0)
        ki = pl.program_id(1)
        gi = pl.program_id(2)
        seed = seed_ref[0]
        # the q-head identity of this grid step (drives the dropout hash —
        # it must match the bh the fwd/dq kernels hashed with)
        bh_q = (kv // hkv) * h + (kv % hkv) * group + gi
        kb = k_ref[...]
        vb = v_ref[...]

        if causal:
            # first q block whose last position sees this k block:
            # need off + q_end > ki*BK  ->  q from (ki*BK - off) // BQ
            qmin_dyn = jnp.maximum(ki * bk - off, 0) // bq
            qmin = jnp.asarray(qmin_dyn, jnp.int32)
        else:
            qmin = jnp.asarray(0, jnp.int32)

        def body(qi, carry):
            dk, dv = carry
            qi = jnp.asarray(qi, jnp.int32)
            qb = q_ref[pl.dslice(qi * bq, bq), :]
            dob = do_ref[pl.dslice(qi * bq, bq), :]
            lse = lse_ref[pl.dslice(qi * bq, bq), :].astype(jnp.float32)
            delta = delta_ref[pl.dslice(qi * bq, bq), :].astype(jnp.float32)
            s = _dot_nt(qb, kb) * scale
            if causal:
                s = _mask_boundary(s, off, qi, ki, bq, bk)
            p = jnp.exp(s - lse)
            if use_drop:
                # the dropout mask product materializes ONCE per tile: z is
                # computed here and reused for BOTH the dv operand (p * z)
                # and the dp rescale below — not re-derived per product
                keep = _tile_keep(seed, bh_q, qi * bq, ki * bk, bq, bk, thresh)
                z = jnp.where(keep, inv_keep, 0.0)
                pd = p * z
            else:
                pd = p
            dv2 = dv + _dot_tn(pd.astype(dob.dtype), dob)
            dp = _dot_nt(dob, vb)
            if use_drop:
                dp = dp * z
            ds = p * (dp - delta) * scale
            dk2 = dk + _dot_tn(ds.astype(qb.dtype), qb)
            return dk2, dv2

        dk, dv = jax.lax.fori_loop(
            qmin,
            jnp.asarray(n_q, jnp.int32),
            body,
            (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)),
        )
        if group > 1:
            # accumulate over the q heads of this kv group: the (kv, ki)
            # output block stays VMEM-resident across consecutive gi steps
            # (grid axis 2 is sequential)
            @pl.when(gi == 0)
            def _init():
                dk_ref[...] = jnp.zeros_like(dk_ref)
                dv_ref[...] = jnp.zeros_like(dv_ref)

            dk_ref[...] += dk.astype(dk_ref.dtype)
            dv_ref[...] += dv.astype(dv_ref.dtype)
        else:
            dk_ref[...] = dk.astype(dk_ref.dtype)
            dv_ref[...] = dv.astype(dv_ref.dtype)

    return kernel


def _flash_bwd_impl(q, k, v, out, lse, g, g_lse, seed, causal, sm_scale, dropout_p):
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qr = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kr = jnp.swapaxes(k, 1, 2).reshape(b * hkv, sk, d)
    vr = jnp.swapaxes(v, 1, 2).reshape(b * hkv, sk, d)
    orr = jnp.swapaxes(out, 1, 2).reshape(b * h, sq, d)
    gr = jnp.swapaxes(g, 1, 2).reshape(b * h, sq, d)
    # delta_i = rowsum(dO * O) — cheap, XLA-fused. The lse output's
    # cotangent folds in exactly here: d lse_i has score-gradient
    # g_lse_i * P_ij, i.e. ds = p * (zdp - (delta - g_lse)) — so delta
    # simply absorbs -g_lse and the kernels stay unchanged.
    delta = jnp.sum(
        gr.astype(jnp.float32) * orr.astype(jnp.float32), axis=-1, keepdims=True
    )
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32).reshape(b * h, sq, 1)

    bq = _pick_block(sq, _block_cap(d, _MAX_BLOCK_Q))
    bk = _pick_block(sk, _block_cap(d, _MAX_BLOCK_K))
    n_q, n_k = sq // bq, sk // bk

    def kv_of(bh):
        return (bh // h) * hkv + (bh % h) // group

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, n_q),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((None, sk, d), lambda bh, qi, *_: (kv_of(bh), 0, 0)),
            pl.BlockSpec((None, sk, d), lambda bh, qi, *_: (kv_of(bh), 0, 0)),
            pl.BlockSpec((None, bq, d), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((None, bq, 1), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((None, bq, 1), lambda bh, qi, *_: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda bh, qi, *_: (bh, qi, 0)),
    )
    dq = pl.pallas_call(
        _bwd_dq_kernel(sq, sk, d, causal, scale, bq, bk, dropout_p),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=_INTERPRET,
        name="flash_dq",
    )(seed, qr, kr, vr, gr, lse, delta)

    # dkdv holds the WHOLE q/do streams VMEM-resident on top of its tiles —
    # at 1024-wide tiles that overflows the 16MB VMEM stack inside fused
    # programs, so its q-loop tile caps at 512 (the k tile keeps the wide
    # pick; measured: fwd/dq at 1024 + dkdv q-tile 512 retains the win)
    bq_kv = min(bq, _MAX_BLOCK_Q)

    def qh_of(kv, g):
        # kv-head grid index + in-group position -> q-head row of qr/gr/lse
        return (kv // hkv) * h + (kv % hkv) * group + g

    # group > 1 accumulates dk/dv across grid steps in the output block —
    # keep that accumulation in f32 (bf16 += over 4-8 partials loses bits),
    # cast to the storage dtype outside the kernel
    acc_dtype = jnp.float32 if group > 1 else k.dtype
    dkdv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n_k, group),
        in_specs=[
            pl.BlockSpec((None, sq, d), lambda kv, ki, g, *_: (qh_of(kv, g), 0, 0)),
            pl.BlockSpec((None, bk, d), lambda kv, ki, g, *_: (kv, ki, 0)),
            pl.BlockSpec((None, bk, d), lambda kv, ki, g, *_: (kv, ki, 0)),
            pl.BlockSpec((None, sq, d), lambda kv, ki, g, *_: (qh_of(kv, g), 0, 0)),
            pl.BlockSpec((None, sq, 1), lambda kv, ki, g, *_: (qh_of(kv, g), 0, 0)),
            pl.BlockSpec((None, sq, 1), lambda kv, ki, g, *_: (qh_of(kv, g), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, d), lambda kv, ki, g, *_: (kv, ki, 0)),
            pl.BlockSpec((None, bk, d), lambda kv, ki, g, *_: (kv, ki, 0)),
        ],
    )
    dk, dv = pl.pallas_call(
        _bwd_dkdv_kernel(sq, sk, d, causal, scale, bq_kv, bk, dropout_p, h, hkv),
        grid_spec=dkdv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, sk, d), acc_dtype),
            jax.ShapeDtypeStruct((b * hkv, sk, d), acc_dtype),
        ],
        compiler_params=_COMPILER_PARAMS_3D,
        interpret=_INTERPRET,
        name="flash_dkdv",
    )(seed, qr, kr, vr, gr, lse, delta)

    unshape = lambda a, s, hh, dt: jnp.swapaxes(
        a.reshape(b, hh, s, d), 1, 2
    ).astype(dt)
    return (
        unshape(dq, sq, h, q.dtype),
        unshape(dk, sk, hkv, k.dtype),
        unshape(dv, sk, hkv, v.dtype),
    )


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_core(q, k, v, seed, causal, sm_scale, dropout_p):
    """(out [B,Sq,H,D], lse [B,H,Sq]) — both differentiable outputs."""
    out, lse = _flash_fwd_x32_wrap(q, k, v, seed, causal, sm_scale, dropout_p)
    b, sq, h, _ = q.shape
    return out, lse.reshape(b, h, sq)


def _core_fwd(q, k, v, seed, causal, sm_scale, dropout_p):
    out, lse = _flash_fwd_x32_wrap(q, k, v, seed, causal, sm_scale, dropout_p)
    b, sq, h, _ = q.shape
    return (out, lse.reshape(b, h, sq)), (q, k, v, seed, out, lse)


def _core_bwd(causal, sm_scale, dropout_p, res, g):
    q, k, v, seed, out, lse = res
    g_out, g_lse = g
    with jax.enable_x64(False):
        dq, dk, dv = _flash_bwd_jit(
            q, k, v, out, lse, g_out, g_lse, seed, causal, sm_scale, dropout_p
        )
    seed_ct = np.zeros(np.shape(seed), jax.dtypes.float0)
    return dq, dk, dv, seed_ct


_flash_core.defvjp(_core_fwd, _core_bwd)


def _check_heads(q, k, v):
    h, hk, hv = q.shape[2], k.shape[2], v.shape[2]
    if hk != hv or h % hk != 0:
        raise ValueError(
            f"flash attention GQA needs k/v heads equal and dividing q heads; "
            f"got q={h}, k={hk}, v={hv}"
        )


def repeat_kv(k, n_rep: int):
    """GQA: repeat kv heads to match q heads, [B, S, Hkv, D] -> [B, S, H, D]
    (kv head i serves q heads [i*n_rep, (i+1)*n_rep) — the ordering the
    kernel's head-group index maps use). Shared by every dense fallback."""
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def flash_attention_bshd(
    q, k, v, causal=False, sm_scale=None, dropout_p=0.0, dropout_seed=None
):
    """Flash attention, paddle [B, S, H, D] layout. k/v may carry fewer
    heads than q (GQA/MQA, h_kv | h_q); dropout_p > 0 applies in-kernel
    upscale-in-train attention dropout keyed by `dropout_seed` (an int32
    scalar; None draws a fresh one from the framework generator)."""
    _check_heads(q, k, v)
    seed = _as_seed(dropout_seed, float(dropout_p))
    out, _ = _flash_core(q, k, v, seed, causal, sm_scale, float(dropout_p))
    return out


def flash_attention_bshd_lse(
    q, k, v, causal=False, sm_scale=None, dropout_p=0.0, dropout_seed=None
):
    """Like flash_attention_bshd but also returns the per-row logsumexp
    [B, H, Sq] (f32) — the ingredient ring attention needs to merge chunk
    outputs across devices. Differentiable in both outputs."""
    _check_heads(q, k, v)
    seed = _as_seed(dropout_seed, float(dropout_p))
    return _flash_core(q, k, v, seed, causal, sm_scale, float(dropout_p))


def _flash_fwd_x32_wrap(q, k, v, seed, causal, sm_scale, dropout_p):
    # Mosaic rejects i64 grid/index types, and the framework enables x64
    # globally (paddle dtype semantics) — trace the kernel with x64 off.
    # All kernel dtypes are explicit so numerics are unchanged.
    with jax.enable_x64(False):
        return _flash_fwd_jit(q, k, v, seed, causal, sm_scale, dropout_p)


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "dropout_p")
)
def _flash_fwd_jit(q, k, v, seed, causal=False, sm_scale=None, dropout_p=0.0):
    return _flash_fwd_impl(q, k, v, seed, causal, sm_scale, dropout_p)


# A jit of its own, like the forward's: inside it the name stack starts
# afresh, so the kernels' names (`flash_dq`, `flash_dkdv`) reach the compiled
# program as they are and not wrapped in the transpose(jvp(...)) that runs
# the backward.
@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "dropout_p")
)
def _flash_bwd_jit(q, k, v, out, lse, g, g_lse, seed, causal=False,
                   sm_scale=None, dropout_p=0.0):
    return _flash_bwd_impl(q, k, v, out, lse, g, g_lse, seed, causal, sm_scale, dropout_p)


# ---------------------------------------------------------------------------
# paged flash-decode (serving tier): single-query GQA attention reading a
# block-allocated (paged) KV cache
# ---------------------------------------------------------------------------
#
# The decode regime is the transpose of prefill: one query token per
# sequence against a long, NON-CONTIGUOUS context — the KV lives in
# fixed-size pages scattered through a preallocated pool, addressed by a
# per-sequence block table (vLLM's PagedAttention layout). The kernel grid
# is (batch, page block): a step reads a block of P pages (P * bs = 128
# positions, one lane tile of logits; 8 pages of 16) for EVERY kv head. The
# block table rides in as a SCALAR-PREFETCH operand and each of the P page
# operands has an index map that picks its own page of the step's block
# (the page fetch is a table lookup, never a gather in HBM); the
# online-softmax state (m, l, acc) of a sequence lives in VMEM scratch
# across the sequential page-block axis — the same accumulator pattern as
# the dkdv kernel's group axis. GQA is native: q is viewed
# [B, Hkv, group, D], so the whole q-head group of a kv head shares its page
# stream and the MXU does one [group, P * bs] logits tile per kv head and
# step, batched over the kv heads.
#
# The page axis stops at the row's frontier. A third scalar-prefetch
# operand counts each row's live page blocks (up to its last query
# position); a step past them computes nothing, and its index maps name the
# row's last live block again, so the pipeline sees the block it already
# holds and starts no copy. The step still costs its fixed ~1 us, so the
# grid is rows x table width / P whatever the contexts; what it reads is
# the pages the rows hold. A pad row (seq_lens 1, a table of zeros) costs
# one live block.
#
# Pool layout: pages are [N, Hkv, bs, D] (kv-head major, the layout of
# jax's own TPU paged attention), so a page's (Hkv, bs, D) slab is one
# contiguous read whose minor two dims are whole array dims — the only block
# shape Mosaic accepts here without bs/D being multiples of the (8, 128)
# tile. The int8 pool's scale planes are [N, Hkv, bs]; a step fetches each
# page's whole (Hkv, bs) plane beside it.
#
# Masking contract: positions >= seq_lens[b] score -1e30 (the page slots
# past the sequence end inside the last live block contribute
# exp(-1e30 - m) == 0; later blocks are never scored). Callers pad block
# tables with a valid page index (the pool's reserved page 0), so a masked
# slot may READ garbage but can never fault or influence the output.
# seq_lens must be >= 1 (a zero-length row would normalize an all-masked
# softmax).

_DECODE_SUBLANE = 8  # page slots must tile the VPU sublane dimension
_DECODE_LANES = 128  # positions a grid step scores: one lane tile of logits


def paged_decode_usable(q, k_pages) -> bool:
    """Kernel constraints: TPU platform (or interpret mode), head_dim <= 256
    and lane-aligned, page slots a multiple of the sublane. q [B, H, D];
    k_pages [N, Hkv, bs, D], the K/V layout of a pool (a pool of latent
    pages `[N, bs, W]` is read by `mla_paged_attention`, not here). Off-gate
    callers fall back to the jnp reference — bitwise-equivalent masking/GQA
    semantics, XLA-gathered."""
    if not _on_tpu():
        return False
    if q.ndim != 3 or k_pages.ndim != 4:
        return False
    b, h, d = q.shape
    n, hkv, bs, dk = k_pages.shape
    if dk != d or not (0 < d <= 256 and d % 8 == 0):
        return False
    if bs % _DECODE_SUBLANE != 0:
        return False
    return hkv <= h and h % hkv == 0


def paged_page_blocks(block_size, table_width, positions=_DECODE_LANES):
    """(pages a grid step of the paged kernel reads, page blocks a table of
    `table_width` columns makes): a step scores `positions` positions (one
    lane tile: 8 pages of 16), or the whole of a narrower table."""
    pages = min(max(1, positions // block_size), table_width)
    return pages, -(-table_width // pages)


def paged_live_blocks(frontiers, block_size, table_width):
    """Page blocks of each row that reach a position someone wrote: up to
    the row's frontier (its last query position), one for a pad row. The
    kernel's grid steps past them compute nothing and start no copy; numpy
    or jax `frontiers` alike (the serving engine counts with this too)."""
    pages, blocks = paged_page_blocks(block_size, table_width)
    return (frontiers // (pages * block_size) + 1).clip(1, blocks)


def _dequant_pages(pages, scales):
    """int8 pages [*, Hkv, bs, D] + per-slot absmax scale planes
    [*, Hkv, bs] -> f32 values, via the OBSERVERS' dequant rule (the write
    side quantized with their grid — read and write must share one
    implementation; the in-kernel dequant mirrors it and the lockstep
    interpret==reference tests keep the two from drifting)."""
    from ..quantization.observers import dequantize_absmax

    return dequantize_absmax(pages, jnp.asarray(scales, jnp.float32)[..., None])


def paged_decode_reference(q, k_pages, v_pages, block_tables, seq_lens,
                           sm_scale=None, k_scales=None, v_scales=None):
    """jnp oracle for the paged decode kernel (and the off-TPU dispatch
    path). Same accumulation discipline as the kernel: f32 logits via
    preferred_element_type, probabilities cast to the storage dtype before
    the value matmul (f32 throughout on an int8 pool — the kernel
    dequantizes into f32 VMEM). q [B, H, D] -> [B, H, D]."""
    q_positions = jnp.asarray(seq_lens, jnp.int32) - 1
    out = paged_extend_reference(
        q[:, None], k_pages, v_pages, block_tables, q_positions[:, None],
        sm_scale=sm_scale, k_scales=k_scales, v_scales=v_scales,
    )
    return out[:, 0]


def paged_extend_reference(q, k_pages, v_pages, block_tables, q_positions,
                           sm_scale=None, k_scales=None, v_scales=None):
    """jnp oracle for the MULTI-query paged kernel: q [B, Q, H, D] holds Q
    query tokens per sequence; query j of row b attends to every cache
    position <= q_positions[b, j] (each draft/suffix token sees the context
    up through itself — the per-query causal frontier). Returns
    [B, Q, H, D]. The single-query decode is the Q == 1 special case with
    q_positions = seq_lens - 1."""
    b, qn, h, d = q.shape
    n, hkv, bs, _ = k_pages.shape
    group = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    q_positions = jnp.asarray(q_positions, jnp.int32)
    quantized = k_scales is not None

    def gather(pages, scales, bt):
        # this sequence's pages [M, Hkv, bs, D] -> a contiguous per-q-head
        # [H, S, D] view (kv head i serves q heads [i*group, (i+1)*group))
        x = pages[bt]
        if quantized:
            x = _dequant_pages(x, scales[bt])
        x = jnp.swapaxes(x, 0, 1).reshape(hkv, -1, d)
        return jnp.repeat(x, group, axis=0)

    def one(qb, bt, qp):
        kg = gather(k_pages, k_scales, bt)
        vg = gather(v_pages, v_scales, bt)
        logits = jnp.einsum(
            "qhd,hsd->qhs", qb, kg, preferred_element_type=jnp.float32
        ) * scale
        pos = jnp.arange(kg.shape[1], dtype=jnp.int32)
        logits = jnp.where(pos[None, None, :] <= qp[:, None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1).astype(vg.dtype)
        return jnp.einsum(
            "qhs,hsd->qhd", p, vg, preferred_element_type=jnp.float32
        ).astype(qb.dtype)

    return jax.vmap(one)(q, block_tables, q_positions)


def _paged_attn_kernel(bs, pages, group, q_count, rows, scale, quantized):
    """Unified paged-attention kernel body. One grid step is one sequence's
    block of `pages` pages, every kv head at once: the page refs are
    [hkv, bs, d] slabs that stack along the slot axis into a
    [hkv, width, d] tile (width = pages * bs), and both matmuls batch over
    kv heads. Q >= 1 query tokens per sequence ride as [hkv, rows, d]
    (query-major, so row r is query r // group of kv-head-group slot
    r % group; rows past Q * group are sublane padding), each masked to its
    own causal frontier: qpos[b] is (the one position) at Q == 1, and (first
    position, queries that count) at Q > 1, query j of the count at first + j
    and the rest at position 0. `quantized` adds
    per-page scale-plane operands; the per-slot scales are [hkv, bs] lane
    rows, so they apply on the logits / probability side of the two matmuls
    (algebraically the dequantized K/V, without a lane->sublane relayout of
    the scales)."""
    width = pages * bs

    def stacked(refs):
        # pages [hkv, bs, d] along the slots, scale planes [hkv, bs] along the lanes
        tiles = [r[...].astype(jnp.float32) if quantized else r[...] for r in refs]
        return jnp.concatenate(tiles, axis=1)

    def kernel(bt_ref, qpos_ref, live_ref, q_ref, *rest):
        k_refs, v_refs, rest = rest[:pages], rest[pages:2 * pages], rest[2 * pages:]
        if quantized:
            ksc_refs, vsc_refs, rest = rest[:pages], rest[pages:2 * pages], rest[2 * pages:]
        o_ref, m_scr, l_scr, acc_scr = rest
        b = pl.program_id(0)
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -1e30)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # a page block past the row's frontier holds nothing anyone wrote:
        # no compute, and the index maps name the last live block again, so
        # the pipeline starts no copy for it either
        @pl.when(i < live_ref[b])
        def _block():
            qb = q_ref[...]  # [hkv, rows, d] — storage dtype, MXU at bf16 rate
            if quantized:
                qb = qb.astype(jnp.float32)
            kb = stacked(k_refs)  # [hkv, width, d]
            vb = stacked(v_refs)
            logits = _dot_bnt(qb, kb) * scale  # [hkv, rows, width] f32
            if quantized:
                logits = logits * (stacked(ksc_refs)[:, None, :] * (1.0 / 127.0))
            row = lax.broadcasted_iota(jnp.int32, (rows, width), 0)
            pos = i * width + lax.broadcasted_iota(jnp.int32, (rows, width), 1)
            if q_count == 1:
                frontier = jnp.full((rows, width), qpos_ref[b, 0], jnp.int32)
            else:
                # a row's queries stand at CONSECUTIVE positions, so query
                # row // group stands at the first position plus that; the
                # queries past the row's count (pad slots, sublane padding)
                # stand at position 0, as the callers' pad slots do
                qi = lax.div(row, jnp.int32(group))
                frontier = jnp.where(qi < qpos_ref[b, 1], qpos_ref[b, 0] + qi, 0)
            logits = jnp.where((pos <= frontier)[None], logits, -1e30)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = p * (stacked(vsc_refs)[:, None, :] * (1.0 / 127.0))
            acc_new = acc_scr[...] * alpha + _dot_bnn(p.astype(vb.dtype), vb)
            m_scr[...] = m_new
            l_scr[...] = l_new
            acc_scr[...] = acc_new

        @pl.when(i == pl.num_programs(1) - 1)
        def _emit():
            o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)

    return kernel


def _paged_extend_impl(q, k_pages, v_pages, block_tables, q_positions,
                       sm_scale, k_scales=None, v_scales=None):
    b, qn, h, d = q.shape
    n, hkv, bs, _ = k_pages.shape
    group = h // hkv
    # Q * group query rows, padded up to whole sublanes (zero rows score a
    # finite uniform softmax and are sliced away below)
    rows = -(-qn * group // _DECODE_SUBLANE) * _DECODE_SUBLANE
    m = block_tables.shape[1]
    # a table `pages` does not divide is padded with the reserved page 0
    # like the rest of its padding
    pages, blocks = paged_page_blocks(bs, m)
    block_tables = jnp.pad(block_tables, ((0, 0), (0, blocks * pages - m)))
    # the row's frontier is the max over Q, because pad slots of an extend
    # row carry position 0
    last = jnp.max(q_positions, axis=1)
    live = paged_live_blocks(last, bs, m)
    if qn > 1:
        # consecutive positions a row: the kernel takes (first, count)
        q_positions = jnp.stack([q_positions[:, 0], last - q_positions[:, 0] + 1], axis=1)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    quantized = k_scales is not None
    # pack queries query-major per kv head: row qi*group + g is query qi of
    # group slot g (q head hi*group + g reads kv head hi)
    qg = (
        q.reshape(b, qn, hkv, group, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, hkv, qn * group, d)
    )
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - qn * group), (0, 0)))

    # page fetch: the block table names the pool page for page j of grid
    # step (bi, pi), a dead step that of the row's last live block; padded
    # table entries point at the reserved page 0
    def page_specs(block):
        def spec(j):
            def index(bi, pi, bt, qp, lv):
                col = jnp.minimum(pi, lv[bi] - 1) * pages + j
                return (bt[bi, col],) + (0,) * (len(block) - 1)

            return pl.BlockSpec(block, index)

        return [spec(j) for j in range(pages)]

    q_spec = pl.BlockSpec((None, hkv, rows, d), lambda bi, pi, *_: (bi, 0, 0, 0))
    in_specs = [q_spec] + 2 * page_specs((None, hkv, bs, d))
    operands = [qg] + pages * [k_pages] + pages * [v_pages]
    if quantized:
        in_specs += 2 * page_specs((None, hkv, bs))
        operands += pages * [k_scales] + pages * [v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block table, query frontiers, live block counts drive the maps
        num_scalar_prefetch=3,
        grid=(b, blocks),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, d), jnp.float32),
        ],
    )
    # the page axis REVISITS the row's accumulator scratch + out block on
    # consecutive steps — it must stay sequential ("arbitrary"); each row
    # starts a fresh accumulator at pi == 0
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )
    out = pl.pallas_call(
        _paged_attn_kernel(bs, pages, group, qn, rows, scale, quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        compiler_params=params,
        interpret=_INTERPRET,
        name="paged_attn",
    )(block_tables, q_positions, live, *operands)
    return (
        out[:, :, :qn * group]
        .reshape(b, hkv, qn, group, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, qn, h, d)
    )


def _paged_decode_impl(q, k_pages, v_pages, block_tables, seq_lens, sm_scale,
                       k_scales=None, v_scales=None):
    q_positions = (jnp.asarray(seq_lens, jnp.int32) - 1)[:, None]
    out = _paged_extend_impl(
        q[:, None], k_pages, v_pages, block_tables, q_positions, sm_scale,
        k_scales=k_scales, v_scales=v_scales,
    )
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def _paged_decode_jit(q, k_pages, v_pages, block_tables, seq_lens,
                      sm_scale=None, k_scales=None, v_scales=None):
    return _paged_decode_impl(q, k_pages, v_pages, block_tables, seq_lens,
                              sm_scale, k_scales=k_scales, v_scales=v_scales)


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def _paged_extend_jit(q, k_pages, v_pages, block_tables, q_positions,
                      sm_scale=None, k_scales=None, v_scales=None):
    return _paged_extend_impl(q, k_pages, v_pages, block_tables, q_positions,
                              sm_scale, k_scales=k_scales, v_scales=v_scales)


def _validate_paged(q, k_pages, k_scales, v_scales, fname):
    if q.shape[-1] != k_pages.shape[3]:
        raise ValueError(
            f"{fname}: head_dim mismatch q={q.shape} pages={k_pages.shape}"
        )
    h, hkv = q.shape[-2], k_pages.shape[1]
    if hkv > h or h % hkv != 0:
        raise ValueError(
            f"{fname}: kv heads must divide q heads; got q={h}, kv={hkv}"
        )
    if (k_scales is None) != (v_scales is None):
        raise ValueError(f"{fname}: k_scales and v_scales must come together")
    if k_scales is not None and tuple(k_scales.shape) != tuple(k_pages.shape[:3]):
        raise ValueError(
            f"{fname}: scale planes {k_scales.shape} do not match pages "
            f"{k_pages.shape[:3]} (per-slot-per-kv-head absmax)"
        )


def flash_decode_paged(q, k_pages, v_pages, block_tables, seq_lens,
                       sm_scale=None, k_scales=None, v_scales=None):
    """Single-query attention over the paged KV cache.

    q            [B, H, D]     — one query token per sequence
    k_pages      [N, Hkv, bs, D] — the pool's key pages (one model layer)
    v_pages      [N, Hkv, bs, D]
    block_tables [B, M] int32  — page indices per sequence, padded with the
                                 reserved page 0 past the last real page
    seq_lens     [B]   int32   — valid context length per sequence (>= 1)
    k_scales/v_scales [N, Hkv, bs] f32 — per-slot absmax scale planes of an
                                 int8 pool; reads dequantize on the fly

    Dispatches the Pallas kernel on TPU (or under interpret mode), else the
    jnp reference — identical masking/GQA/dequant semantics either way."""
    _validate_paged(q, k_pages, k_scales, v_scales, "flash_decode_paged")
    block_tables = jnp.asarray(block_tables, jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    if paged_decode_usable(q, k_pages):
        with jax.enable_x64(False):
            return _paged_decode_jit(q, k_pages, v_pages, block_tables, seq_lens,
                                     sm_scale, k_scales=k_scales, v_scales=v_scales)
    return paged_decode_reference(q, k_pages, v_pages, block_tables, seq_lens,
                                  sm_scale, k_scales=k_scales, v_scales=v_scales)


def flash_decode_paged_multi(q, k_pages, v_pages, block_tables, q_positions,
                             sm_scale=None, k_scales=None, v_scales=None):
    """Multi-query paged attention: Q consecutive tokens per sequence in
    one call — the speculative-decode verify step (k draft positions
    checked by one kernel launch) and chunked suffix prefill share this.

    q            [B, Q, H, D]  — Q query tokens per sequence
    q_positions  [B, Q] int32  — absolute cache position of each query;
                                 query j attends to positions <= its own
                                 (the K/V for all Q tokens must already be
                                 written — write-then-read like decode).
                                 A row's positions are CONSECUTIVE from its
                                 first; slots past its last token carry 0

    Same dispatch contract as flash_decode_paged."""
    if q.ndim != 4:
        raise ValueError(f"flash_decode_paged_multi: q must be [B, Q, H, D], got {q.shape}")
    _validate_paged(q, k_pages, k_scales, v_scales, "flash_decode_paged_multi")
    block_tables = jnp.asarray(block_tables, jnp.int32)
    q_positions = jnp.asarray(q_positions, jnp.int32)
    if q_positions.shape != q.shape[:2]:
        raise ValueError(
            f"flash_decode_paged_multi: q_positions {q_positions.shape} must "
            f"match q's [B, Q] {q.shape[:2]}"
        )
    if paged_decode_usable(q[:, 0], k_pages):
        with jax.enable_x64(False):
            return _paged_extend_jit(q, k_pages, v_pages, block_tables, q_positions,
                                     sm_scale, k_scales=k_scales, v_scales=v_scales)
    return paged_extend_reference(q, k_pages, v_pages, block_tables, q_positions,
                                  sm_scale, k_scales=k_scales, v_scales=v_scales)


# ---------------------------------------------------------------------------
# paged attention over LATENT pages (mla_paged_attn): multi-head latent
# attention in its absorbed form
# ---------------------------------------------------------------------------
#
# A latent (MLA) cache keeps ONE vector a token a layer: the normed
# compressed key/value `c_kv` (value_width wide) followed by the rotated
# shared key `k_r`. With the key up-projection absorbed into the query
# (`q_nope W_UK`), every one of the H query heads scores against that one
# vector, and the context is a weighted sum of its first `value_width`
# columns (the value up-projection `W_UV` comes after, outside). So a page
# [bs, W] is read ONCE and serves as key and as value for all heads.
#
# Pool layout: pages are [N, bs, W] (no kv-head axis; W whole lane tiles, the
# entry in its first columns and zeros behind, the query padded alike so that
# the tail adds nothing to a score). The grid is (rows,
# query tiles, page blocks): a query tile is TQ consecutive queries of a row,
# all heads, as [TQ * H, W] query rows (query-major: row r is query r // H,
# head r % H); a page block is P pages (P * bs = `_MLA_POSITIONS` positions). One query a
# row (decode), a chunk of a prompt and `extend` are the same call: a row's
# queries stand at CONSECUTIVE positions from its first, `count` of them
# real, the rest (pad slots, sublane padding) at position 0. Each query tile
# has its own frontier (its last real query's position): page blocks past it
# compute nothing and start no copy, exactly as `paged_attn`'s do, so a
# chunk's early tiles do not walk the blocks only its late tiles see.

# A grid step scores `_MLA_TILE_ROWS` query rows (queries x heads) against
# `_MLA_POSITIONS` cached positions (32 pages of 16). On a v5e at 128 heads,
# a 128-query chunk over 2,048 / 6,144 cached tokens took 1.51 / 3.46 ms a
# call at 1024 rows x 128 positions, 1.01 / 1.87 at 1024 x 512 and 0.78 /
# 1.62 at 2048 x 512 (48% and 70% of the MXU's peak): the accumulator's
# rescale is paid once a step whatever the step's width, and a page is read
# once a query tile. Sixteen rows of one query (6 of them live, 24k cached
# tokens) took 0.64 -> 0.54 ms: there a page's copy, 20 KB at about 0.25 us,
# sets the time. 25 MB of VMEM at the published widths.
_MLA_TILE_ROWS = 2048
_MLA_POSITIONS = 512


def mla_page_blocks(block_size, table_width):
    """`paged_page_blocks` of the latent kernel: (pages a grid step reads,
    page blocks a table makes)."""
    return paged_page_blocks(block_size, table_width, _MLA_POSITIONS)


def mla_query_tile(heads: int, q_len: int) -> int:
    """Queries of a row a grid step of the latent kernel holds: whole
    sublane tiles of query rows (16 for bf16), about `_MLA_TILE_ROWS` rows,
    never more queries than the call has (rounded up to the sublane unit)."""
    unit = 16 // math.gcd(heads, 16)
    return unit * max(1, min(-(-q_len // unit), _MLA_TILE_ROWS // (unit * heads)))


def mla_live_blocks(first, count, q_len, heads, block_size, table_width):
    """[B, tiles] page blocks each query tile of the latent kernel reads: up
    to the position of the tile's last real query (`first + count - 1` at
    most), one for a tile of padding. numpy or jax `first` / `count` [B]
    alike (the serving engine counts with this too)."""
    return _tile_live_blocks(first, count, q_len, mla_query_tile(heads, q_len), block_size, table_width)


def _tile_live_blocks(first, count, q_len, tq, block_size, table_width):
    """`mla_live_blocks` for tiles of `tq` queries (`dsa_index` holds larger ones)."""
    tiles = -(-q_len // tq)
    pages, blocks = mla_page_blocks(block_size, table_width)
    start = np.arange(tiles, dtype=np.int32)[None, :] * tq  # each tile's first query
    count = count[:, None]
    frontier = first[:, None] + count.clip(None, start + tq) - 1
    frontier = frontier * (count > start)  # a tile of padding stands at position 0
    return (frontier // (pages * block_size) + 1).clip(1, blocks)


def mla_paged_reference(q, pages, block_tables, q_positions, value_width, sm_scale=None):
    """jnp oracle for the latent paged kernel (and the off-TPU path). q
    [B, Q, H, W] absorbed queries; pages [N, bs, W]; query j of row b attends
    to every cached position <= q_positions[b, j]. f32 logits, probabilities
    cast to the storage dtype before the value product. Returns
    [B, Q, H, value_width]."""
    w = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(w)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    q_positions = jnp.asarray(q_positions, jnp.int32)

    def one(qb, bt, qp):
        kg = pages[bt][..., :w].reshape(-1, w)  # [M * bs, entry]
        logits = jnp.einsum("qhw,sw->qhs", qb, kg, preferred_element_type=jnp.float32) * scale
        pos = jnp.arange(kg.shape[0], dtype=jnp.int32)
        logits = jnp.where(pos[None, None, :] <= qp[:, None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1).astype(kg.dtype)
        return jnp.einsum("qhs,sv->qhv", p, kg[:, :value_width],
                          preferred_element_type=jnp.float32).astype(qb.dtype)

    return jax.vmap(one)(q, block_tables, q_positions)


def _mla_paged_kernel(pages, width, heads, tq, tiles, value_width, scale):
    rows = tq * heads

    def kernel(bt_ref, qpos_ref, live_ref, q_ref, *rest):
        page_refs, (o_ref, m_scr, l_scr, acc_scr) = rest[:pages], rest[pages:]
        b = pl.program_id(0)
        t = pl.program_id(1)
        i = pl.program_id(2)

        @pl.when(i == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -1e30)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(i < live_ref[b * tiles + t])
        def _block():
            qb = q_ref[...]  # [rows, W], storage dtype
            kb = jnp.concatenate([r[...] for r in page_refs], axis=0)  # [width, W]
            logits = _dot_nt(qb, kb) * scale  # [rows, width] f32
            row = lax.broadcasted_iota(jnp.int32, (rows, width), 0)
            pos = i * width + lax.broadcasted_iota(jnp.int32, (rows, width), 1)
            qi = t * tq + lax.div(row, jnp.int32(heads))
            frontier = jnp.where(qi < qpos_ref[b, 1], qpos_ref[b, 0] + qi, 0)
            logits = jnp.where(pos <= frontier, logits, -1e30)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # the page's first columns are the value: no second read
            acc_scr[...] = acc_scr[...] * alpha + _dot_nn(p.astype(kb.dtype), kb[:, :value_width])
            m_scr[...] = m_new

        @pl.when(i == pl.num_programs(2) - 1)
        def _emit():
            o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)

    return kernel


def _tile_page_spec(j, pages, tiles, block_size, width):
    """The j-th page of a grid step's page block, for a grid (rows, query
    tiles, page blocks) whose scalar operands are (block table, (first, count)
    a row, live blocks a tile): a step past the tile's live blocks names the
    last live block's page again, so it starts no copy."""
    def index(bi, ti, pi, bt, qp, lv):
        col = jnp.minimum(pi, lv[bi * tiles + ti] - 1) * pages + j
        return (bt[bi, col], 0, 0)

    return pl.BlockSpec((None, block_size, width), index)


def _mla_paged_impl(q, pages_arr, block_tables, q_positions, value_width, sm_scale):
    n, bs, w = pages_arr.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, w - q.shape[-1]),))  # zeros against the slot's tail
    b, qn, h, _ = q.shape
    m = block_tables.shape[1]
    pages, blocks = mla_page_blocks(bs, m)
    block_tables = jnp.pad(block_tables, ((0, 0), (0, blocks * pages - m)))
    tq = mla_query_tile(h, qn)
    tiles = -(-qn // tq)
    rows = tq * h
    # consecutive positions a row: (first, count); pad slots carry position 0
    first = q_positions[:, 0]
    count = jnp.max(q_positions, axis=1) - first + 1
    live = mla_live_blocks(first, count, qn, h, bs, m).astype(jnp.int32).reshape(-1)
    qr = jnp.pad(q, ((0, 0), (0, tiles * tq - qn), (0, 0), (0, 0))).reshape(b, tiles * rows, w)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block table, (first, count) a row, live blocks a tile
        grid=(b, tiles, blocks),
        in_specs=[pl.BlockSpec((None, rows, w), lambda bi, ti, pi, *_: (bi, ti, 0))]
        + [_tile_page_spec(j, pages, tiles, bs, w) for j in range(pages)],
        out_specs=pl.BlockSpec((None, rows, value_width), lambda bi, ti, pi, *_: (bi, ti, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, value_width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        _mla_paged_kernel(pages, pages * bs, h, tq, tiles, value_width, scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tiles * rows, value_width), q.dtype),
        # the page axis revisits the tile's accumulator and out block: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_INTERPRET,
        name="mla_paged_attn",
    )(block_tables, jnp.stack([first, count], axis=1), live, qr, *(pages * [pages_arr]))
    return out.reshape(b, tiles * tq, h, value_width)[:, :qn]


@functools.partial(jax.jit, static_argnames=("value_width", "sm_scale"))
def _mla_paged_jit(q, pages, block_tables, q_positions, value_width, sm_scale=None):
    return _mla_paged_impl(q, pages, block_tables, q_positions, value_width, sm_scale)


def mla_paged_usable(q, pages, value_width) -> bool:
    """Kernel constraints: TPU platform (or interpret mode), page slots a
    multiple of the sublane, the value a lane-aligned prefix of the entry.
    q [B, Q, H, entry]; pages [N, bs, W], W >= entry."""
    if not _on_tpu() or q.ndim != 4 or pages.ndim != 3:
        return False
    return (pages.shape[1] % _DECODE_SUBLANE == 0 and 0 < value_width <= q.shape[-1]
            and (value_width % _DECODE_LANES == 0 or value_width == pages.shape[2]))


def mla_paged_attention(q, pages, block_tables, q_positions, value_width, sm_scale=None):
    """Absorbed multi-head latent attention over a paged latent cache.

    q            [B, Q, H, E]  — Q absorbed queries a row (`q_nope W_UK` then
                                 the rotated `q_rope`), every head against
                                 the ONE cached vector a position
    pages        [N, bs, W]    — the pool's latent pages (one model layer):
                                 `c_kv` then `k_r` in a slot's first E columns
                                 (W >= E: whole lane tiles, zeros behind); the
                                 first `value_width` columns are also the value
    block_tables [B, M] int32  — page indices, padded with the reserved page 0
    q_positions  [B, Q] int32  — each query's cache position, CONSECUTIVE
                                 from the row's first, pad slots 0; query j
                                 attends to positions <= its own (the entries
                                 of all Q tokens already written)

    Returns the context in the latent, [B, Q, H, value_width] (the value
    up-projection comes after). One query a row (decode: q_positions =
    seq_lens - 1), a prompt's chunk and `extend` are this one call. Dispatches
    the Pallas kernel `mla_paged_attn` on TPU (or under interpret mode), else
    the jnp reference."""
    if q.ndim != 4 or pages.ndim != 3 or q.shape[-1] > pages.shape[2]:
        raise ValueError(f"mla_paged_attention: q {q.shape} does not fit latent pages {pages.shape} "
                         "([B, Q, H, E] against [N, bs, W >= E])")
    block_tables = jnp.asarray(block_tables, jnp.int32)
    q_positions = jnp.asarray(q_positions, jnp.int32)
    if q_positions.shape != q.shape[:2]:
        raise ValueError(f"mla_paged_attention: q_positions {q_positions.shape} must match q's "
                         f"[B, Q] {q.shape[:2]}")
    if mla_paged_usable(q, pages, value_width):
        with jax.enable_x64(False):
            return _mla_paged_jit(q, pages, block_tables, q_positions, value_width, sm_scale)
    return mla_paged_reference(q, pages, block_tables, q_positions, value_width, sm_scale)


# ---------------------------------------------------------------------------
# learned sparse attention over a latent pool (dsa_index, mla_sparse_paged_attn)
# ---------------------------------------------------------------------------
#
# A decoder with a token selector (DeepSeek sparse attention) keeps, beside a
# token's latent entry, an INDEX KEY (one lane tile wide) under the same page
# and slot. A query first scores every cached position of its sequence with a
# light many-headed dot product against those keys,
#
#     I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])      (s <= t)
#
# (`dsa_index`), keeps the `topk` positions of largest score (`dsa_select`,
# exact), and attends, in the absorbed latent form, over the chosen positions'
# entries alone (`mla_sparse_attention`): what it reads of the latent pool
# scales with the positions chosen, not with the context.
#
# `dsa_index`: the grid is (rows, query tiles, page blocks) over the index-key
# pages [N, bs, D], as `mla_paged_attn`'s over the latent ones: a query tile is
# up to `_DSA_TILE_QUERIES` consecutive queries of a row with all their index
# heads as [queries * J, D] rows, held in VMEM while the row's page blocks
# (`_MLA_POSITIONS` positions each) pass under it, so a page is copied once a
# tile; inside a step the tile's queries are scored `sub` at a time ([sub * J,
# positions] float32 logits), ReLU, each head's weight (a column, float32),
# the sum over heads. Blocks past a tile's frontier compute nothing and start
# no copy; they, and positions past a query's own, read -inf.
#
# `mla_sparse_paged_attn`: the chosen positions come out of the selection as
# rows of the pool (page * bs + slot: `pool_rows` rides the sort, so no lookup
# through the block table follows) and are gathered into [queries, K, W]
# (K = topk; XLA's gather: 2 x W bytes a (query, chosen position) pair, nothing
# of [queries, context, W]); the kernel's grid is (queries, K blocks), a step
# all H heads of ONE query ([H, W] rows) against a block of its own entries,
# the first `value_width` columns also the value, the slots past the query's
# count of chosen positions masked.

_DSA_TILE_QUERIES = 128   # queries of a row the index kernel holds in VMEM
_DSA_SUB_ROWS = 1024      # (query, head) rows it scores at a time
_DSA_SPARSE_POSITIONS = 2048  # chosen entries a step of the sparse kernel holds


def dsa_query_tiles(heads: int, q_len: int):
    """(queries scored at a time, queries a grid step of `dsa_index` holds):
    whole sublane tiles of (query, head) rows, about `_DSA_SUB_ROWS` rows at a
    time, `_DSA_TILE_QUERIES` queries a step at most, never more than the
    call has (rounded up to the unit)."""
    unit = 16 // math.gcd(heads, 16)
    sub = unit * max(1, min(-(-q_len // unit), _DSA_SUB_ROWS // (unit * heads)))
    return sub, sub * max(1, min(-(-q_len // sub), _DSA_TILE_QUERIES // sub))


def dsa_live_blocks(first, count, q_len, heads, block_size, table_width):
    """[B, tiles] page blocks each query tile of `dsa_index` scores: up to the
    position of the tile's last real query, one for a tile of padding."""
    return _tile_live_blocks(first, count, q_len, dsa_query_tiles(heads, q_len)[1], block_size, table_width)


def dsa_index_reference(q, w, pages, block_tables, q_positions):
    """jnp oracle for `dsa_index` (and the off-TPU path). q [B, Q, J, D] index
    queries, w [B, Q, J] float32 head weights, pages [N, bs, D] index keys;
    returns scores [B, Q, M * bs] float32: `sum_j w_j relu(q_j . k_s)` at the
    positions s <= q_positions[b, q], -inf past them."""
    d = q.shape[-1]
    block_tables = jnp.asarray(block_tables, jnp.int32)
    q_positions = jnp.asarray(q_positions, jnp.int32)

    def one(qb, wb, bt, qp):
        kg = pages[bt].reshape(-1, d)
        dots = jnp.einsum("qjd,sd->qjs", qb, kg, preferred_element_type=jnp.float32)
        sc = jnp.sum(jnp.maximum(dots, 0.0) * wb[..., None].astype(jnp.float32), axis=1)
        pos = jnp.arange(kg.shape[0], dtype=jnp.int32)
        return jnp.where(pos[None, :] <= qp[:, None], sc, -jnp.inf)

    return jax.vmap(one)(q, w, block_tables, q_positions)


def _dsa_index_kernel(pages, width, heads, sub, tq, tiles):
    def kernel(bt_ref, qpos_ref, live_ref, q_ref, w_ref, *rest):
        page_refs, o_ref = rest[:pages], rest[pages]
        b = pl.program_id(0)
        t = pl.program_id(1)
        i = pl.program_id(2)
        live = live_ref[b * tiles + t]

        @pl.when(i >= live)
        def _dead():
            o_ref[...] = jnp.full_like(o_ref, -jnp.inf)

        @pl.when(i < live)
        def _block():
            kb = jnp.concatenate([r[...] for r in page_refs], axis=0)  # [width, D]
            pos = i * width + lax.broadcasted_iota(jnp.int32, (sub, width), 1)
            for s in range(tq // sub):
                rows = pl.ds(s * sub * heads, sub * heads)
                dots = _dot_nt(q_ref[rows, :], kb)  # [sub * J, width] f32
                sc = (jnp.maximum(dots, 0.0) * w_ref[rows, :]).reshape(sub, heads, width).sum(axis=1)
                qi = t * tq + s * sub + lax.broadcasted_iota(jnp.int32, (sub, width), 0)
                frontier = jnp.where(qi < qpos_ref[b, 1], qpos_ref[b, 0] + qi, 0)
                o_ref[pl.ds(s * sub, sub), :] = jnp.where(pos <= frontier, sc, -jnp.inf)

    return kernel


def _dsa_index_impl(q, w, pages_arr, block_tables, q_positions):
    n, bs, d = pages_arr.shape
    b, qn, h, _ = q.shape
    m = block_tables.shape[1]
    pages, blocks = mla_page_blocks(bs, m)
    width = pages * bs
    block_tables = jnp.pad(block_tables, ((0, 0), (0, blocks * pages - m)))
    sub, tq = dsa_query_tiles(h, qn)
    tiles = -(-qn // tq)
    rows = tq * h
    first = q_positions[:, 0]
    count = jnp.max(q_positions, axis=1) - first + 1
    live = dsa_live_blocks(first, count, qn, h, bs, m).astype(jnp.int32).reshape(-1)
    pad = ((0, 0), (0, tiles * tq - qn), (0, 0))
    qr = jnp.pad(q, pad + ((0, 0),)).reshape(b, tiles * rows, d)
    wr = jnp.pad(w.astype(jnp.float32), pad).reshape(b, tiles * rows, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block table, (first, count) a row, live blocks a tile
        grid=(b, tiles, blocks),
        in_specs=[pl.BlockSpec((None, rows, d), lambda bi, ti, pi, *_: (bi, ti, 0)),
                  pl.BlockSpec((None, rows, 1), lambda bi, ti, pi, *_: (bi, ti, 0))]
        + [_tile_page_spec(j, pages, tiles, bs, d) for j in range(pages)],
        out_specs=pl.BlockSpec((None, tq, width), lambda bi, ti, pi, *_: (bi, ti, pi)),
    )
    out = pl.pallas_call(
        _dsa_index_kernel(pages, width, h, sub, tq, tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tiles * tq, blocks * width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_INTERPRET,
        name="dsa_index",
    )(block_tables, jnp.stack([first, count], axis=1), live, qr, wr, *(pages * [pages_arr]))
    return out[:, :qn, :m * bs]


@jax.jit
def _dsa_index_jit(q, w, pages, block_tables, q_positions):
    return _dsa_index_impl(q, w, pages, block_tables, q_positions)


def dsa_index_usable(q, pages) -> bool:
    """Kernel constraints: TPU platform (or interpret mode), page slots a
    multiple of the sublane, keys whole lane tiles wide."""
    if not _on_tpu() or q.ndim != 4 or pages.ndim != 3:
        return False
    return pages.shape[1] % _DECODE_SUBLANE == 0 and pages.shape[2] % _DECODE_LANES == 0


def dsa_index_scores(q, w, pages, block_tables, q_positions):
    """Index scores of a step's queries against the paged index keys.

    q            [B, Q, J, D]  — Q index queries a row, J heads each
    w            [B, Q, J]     — each head's weight (float32)
    pages        [N, bs, D]    — the pool's index-key pages (one model layer)
    block_tables [B, M] int32  — page indices, padded with the reserved page 0
    q_positions  [B, Q] int32  — each query's position, CONSECUTIVE from the
                                 row's first, pad slots 0 (as `mla_paged_attention`)

    Returns [B, Q, M * bs] float32: `sum_j w_j relu(q_j . k_s)` for the
    positions s up to each query's own, -inf past them. Dispatches the Pallas
    kernel `dsa_index` on TPU (or under interpret mode), else the jnp
    reference."""
    if q.ndim != 4 or pages.ndim != 3 or q.shape[-1] != pages.shape[2] or w.shape != q.shape[:3]:
        raise ValueError(f"dsa_index_scores: q {q.shape}, w {w.shape} do not fit index pages {pages.shape} "
                         "([B, Q, J, D], [B, Q, J] against [N, bs, D])")
    block_tables = jnp.asarray(block_tables, jnp.int32)
    q_positions = jnp.asarray(q_positions, jnp.int32)
    if dsa_index_usable(q, pages):
        with jax.enable_x64(False):
            return _dsa_index_jit(q, w, pages, block_tables, q_positions)
    return dsa_index_reference(q, w, pages, block_tables, q_positions)


def pool_rows(block_tables, block_size: int):
    """[B, M * bs] int32: the row of the pool (page * bs + slot, the pool seen
    as [N * bs, W]) that holds each position of a row's sequence."""
    block_tables = jnp.asarray(block_tables, jnp.int32)
    rows = block_tables[:, :, None] * block_size + jnp.arange(block_size, dtype=jnp.int32)
    return rows.reshape(block_tables.shape[0], -1)


def dsa_select(scores, topk: int, carry=None, frontier=None):
    """The `min(topk, P)` positions of largest score a query, [B, Q, K] int32,
    best first; exact: ONE stable sort of a query's scores (of equal scores
    the earlier position). `carry` [B, P] int32 rides the sort in the
    positions' place, so that what comes back is `carry` at the chosen
    positions (their rows of the pool: no lookup of 2048 positions a query
    afterwards). `frontier` (a traced scalar: every score at or past it is
    -inf) lets the sort run over the narrowest of a quarter, a half or all of
    the P positions that holds the frontier. A query with fewer than K
    positions at or before its own has -inf scores chosen last: the reader
    masks them by the query's count."""
    b, _, p = scores.shape
    k = min(int(topk), p)
    if carry is None:
        carry = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (b, p))

    def over(width):
        def pick(scores, carry):
            keys = -scores[..., :width]
            vals = jnp.broadcast_to(carry[:, None, :width], keys.shape)
            return lax.sort_key_val(keys, vals, dimension=-1, is_stable=True)[1][..., :k]

        return pick

    widths = sorted({w for w in (p // 4, p // 2) if w >= k and w % _DECODE_LANES == 0} | {p})
    with jax.enable_x64(False):
        if frontier is None or len(widths) == 1:
            return over(p)(scores, carry)
        branch = sum((frontier > w).astype(jnp.int32) for w in widths[:-1])
        return lax.switch(branch, [over(w) for w in widths], scores, carry)


def mla_sparse_reference(q, pages, rows, counts, value_width, sm_scale=None):
    """jnp oracle for the sparse latent attention (and the off-TPU path). q
    [B, Q, H, E] absorbed queries; pages [N, bs, W]; rows [B, Q, K] int32 rows
    of the pool (`pool_rows`) of each query's chosen positions; counts [B, Q]:
    query (b, q) attends to its first counts[b, q] of them. Returns
    [B, Q, H, value_width]."""
    e = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(e)
    flat = pages.reshape(-1, pages.shape[-1])

    def one(qb, ch, cnt):
        kg = flat[ch][..., :e]  # [Q, K, E]
        logits = jnp.einsum("qhe,qke->qhk", qb, kg, preferred_element_type=jnp.float32) * scale
        live = jnp.arange(ch.shape[-1], dtype=jnp.int32)[None, :] < cnt[:, None]
        p = jax.nn.softmax(jnp.where(live[:, None, :], logits, -1e30), axis=-1).astype(kg.dtype)
        return jnp.einsum("qhk,qkv->qhv", p, kg[..., :value_width],
                          preferred_element_type=jnp.float32).astype(qb.dtype)

    return jax.vmap(one)(q, jnp.asarray(rows, jnp.int32), jnp.asarray(counts, jnp.int32))


def _mla_sparse_kernel(width, value_width, scale):
    def kernel(cnt_ref, q_ref, kv_ref, o_ref, m_scr, l_scr, acc_scr):
        r = pl.program_id(0)
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -1e30)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(i * width < jnp.maximum(cnt_ref[r], 1))
        def _block():
            kb = kv_ref[...]  # [width, W], the query's own chosen entries
            logits = _dot_nt(q_ref[...], kb) * scale  # [H, width] f32
            slot = i * width + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            logits = jnp.where(slot < cnt_ref[r], logits, -1e30)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + _dot_nn(p.astype(kb.dtype), kb[:, :value_width])
            m_scr[...] = m_new

        @pl.when(i == pl.num_programs(1) - 1)
        def _emit():
            o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)

    return kernel


def _mla_sparse_impl(q, pages_arr, rows, counts, value_width, sm_scale):
    n, bs, w = pages_arr.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, qn, h, _ = q.shape
    k = rows.shape[-1]
    width = min(_DSA_SPARSE_POSITIONS, -(-k // 16) * 16)
    blocks = -(-k // width)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, blocks * width - k)))  # the trash page's: masked by the count
    # ONE gather of whole entries: [queries, K, W]
    entries = jnp.take(pages_arr.reshape(n * bs, w), rows.reshape(-1), axis=0).reshape(b * qn, blocks * width, w)
    qr = jnp.pad(q, ((0, 0),) * 3 + ((0, w - q.shape[-1]),)).reshape(b * qn, h, w)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # chosen positions a query
        grid=(b * qn, blocks),
        in_specs=[pl.BlockSpec((None, h, w), lambda r, i, *_: (r, 0, 0)),
                  pl.BlockSpec((None, width, w), lambda r, i, *_: (r, i, 0))],
        out_specs=pl.BlockSpec((None, h, value_width), lambda r, i, *_: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, value_width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        _mla_sparse_kernel(width, value_width, scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * qn, h, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_INTERPRET,
        name="mla_sparse_paged_attn",
    )(counts.reshape(-1).astype(jnp.int32), qr, entries)
    return out.reshape(b, qn, h, value_width)


@functools.partial(jax.jit, static_argnames=("value_width", "sm_scale"))
def _mla_sparse_jit(q, pages, rows, counts, value_width, sm_scale=None):
    return _mla_sparse_impl(q, pages, rows, counts, value_width, sm_scale)


def mla_sparse_attention(q, pages, rows, counts, value_width, sm_scale=None):
    """Absorbed multi-head latent attention over CHOSEN positions of a paged
    latent cache.

    q       [B, Q, H, E]    — absorbed queries (as `mla_paged_attention`)
    pages   [N, bs, W]      — the pool's latent pages (one model layer)
    rows    [B, Q, K] int32 — each query's chosen positions as rows of the pool
                              (page * bs + slot: `dsa_select` with `carry=
                              pool_rows(block_tables, bs)`), any order, the
                              real ones first
    counts  [B, Q] int32    — how many of them are real (at least 1)

    Returns the context in the latent, [B, Q, H, value_width]. What it reads
    of the pool is the chosen entries alone (one gather of [B * Q * K] whole
    entries), whatever the context. Dispatches the Pallas kernel
    `mla_sparse_paged_attn` on TPU (or under interpret mode), else the jnp
    reference."""
    if q.ndim != 4 or pages.ndim != 3 or q.shape[-1] > pages.shape[2] or rows.shape[:2] != q.shape[:2]:
        raise ValueError(f"mla_sparse_attention: q {q.shape}, rows {rows.shape} do not fit latent "
                         f"pages {pages.shape} ([B, Q, H, E], [B, Q, K] against [N, bs, W >= E])")
    rows, counts = jnp.asarray(rows, jnp.int32), jnp.asarray(counts, jnp.int32)
    if mla_paged_usable(q, pages, value_width):
        with jax.enable_x64(False):
            return _mla_sparse_jit(q, pages, rows, counts, value_width, sm_scale)
    return mla_sparse_reference(q, pages, rows, counts, value_width, sm_scale)


# ---------------------------------------------------------------------------
# grouped matmul over the experts a mixture-of-experts layer HOLDS (moe_gmm)
# ---------------------------------------------------------------------------
#
# A dropless expert layer sorts its (token, expert) assignments by expert and
# multiplies each group of rows by its expert's matrix. The rows are laid out
# so that every tile of `MOE_TILE_M` rows belongs to ONE expert (each group is
# padded up to whole tiles with zero rows): a grid step is one tile against
# one expert's matrix, consecutive tiles of an expert name the same weight
# block (no second copy), and the tiles past the last group compute nothing
# and start no copy (their index maps name the last live tile again, as the
# paged kernel's dead steps do).

MOE_TILE_M = 16            # rows a grid step multiplies: one bf16 sublane tile
_MOE_WEIGHT_BLOCK = 6 << 20  # bytes of one expert's weight block in VMEM (x2 buffers)


def moe_padded_rows(assignments: int, groups: int, tile_m: int = MOE_TILE_M) -> int:
    """Rows the padded layout needs in the worst case: every assignment
    held, and every group that can be non-empty one row over a tile."""
    rows = assignments + min(groups, assignments) * (tile_m - 1)
    return -(-rows // tile_m) * tile_m


def moe_group_layout(group_ids, groups: int, tile_m: int = MOE_TILE_M):
    """Where each assignment's row goes. `group_ids` [A] int32: the group
    (held expert) of each assignment, `groups` for one that is not computed
    here (an absent expert, a pad row). Returns

        dest       [A] int32   the assignment's row in the padded layout,
                               `moe_padded_rows(A, groups)` for a dropped one
        tile_group [tiles]     the group of each tile of `tile_m` rows
        live       [1]  int32  tiles that hold rows (the rest are dead)
        sizes      [groups]    assignments of each group

    Rows of a group are consecutive and in the order of `group_ids` (a
    stable sort), each group starts on a tile boundary."""
    group_ids = jnp.asarray(group_ids, jnp.int32)
    a = group_ids.shape[0]
    rows = moe_padded_rows(a, groups, tile_m)
    sizes = jnp.zeros((groups + 1,), jnp.int32).at[group_ids].add(1)[:groups]
    padded = -(-sizes // tile_m) * tile_m
    ends_p = jnp.cumsum(padded)
    starts_p, starts = ends_p - padded, jnp.cumsum(sizes) - sizes
    order = jnp.argsort(group_ids, stable=True)
    g = group_ids[order]
    gc = jnp.minimum(g, groups - 1)
    dest_sorted = jnp.where(g < groups, starts_p[gc] + jnp.arange(a, dtype=jnp.int32) - starts[gc], rows)
    dest = jnp.zeros((a,), jnp.int32).at[order].set(dest_sorted.astype(jnp.int32))
    tile_start = jnp.arange(rows // tile_m, dtype=jnp.int32) * tile_m
    tile_group = jnp.minimum(jnp.searchsorted(ends_p, tile_start, side="right"), groups - 1)
    live = (ends_p[-1] // tile_m).astype(jnp.int32).reshape(1)
    return dest, tile_group.astype(jnp.int32), live, sizes


def _moe_tile_n(k: int, n: int, itemsize: int) -> int:
    """Columns of an expert's matrix a grid step holds: all of them where
    the block fits the budget (one contiguous copy), else the largest
    lane-aligned divisor that does."""
    if n % _DECODE_LANES or k * n * itemsize <= _MOE_WEIGHT_BLOCK:
        return n
    fits = [t for t in range(_DECODE_LANES, n, _DECODE_LANES)
            if n % t == 0 and k * t * itemsize <= _MOE_WEIGHT_BLOCK]
    return max(fits) if fits else _DECODE_LANES


_MOE_ACTIVATIONS = {
    None: lambda acc: acc,
    "relu2": lambda acc: jnp.square(jnp.maximum(acc, 0.0)),
    "silu": jax.nn.silu,
}


def _apply_moe_activation(acc, activation):
    if activation not in _MOE_ACTIVATIONS:
        raise ValueError(f"moe_gmm: unknown activation {activation!r} "
                         f"(known: {sorted(a for a in _MOE_ACTIVATIONS if a)} or None)")
    return _MOE_ACTIVATIONS[activation](acc)


def moe_gmm_reference(x_rows, w, tile_group, live, activation=None, out_dtype=None,
                      tile_m: int = MOE_TILE_M, w_gate=None):
    """jnp oracle for the grouped matmul (and the off-TPU path): each tile
    of rows against its group's matrix, f32 accumulation; dead tiles give
    zeros (the kernel leaves them unwritten: nothing may read them)."""
    out_dtype = out_dtype or x_rows.dtype
    tiles = x_rows.shape[0] // tile_m
    xt = x_rows.reshape(tiles, tile_m, -1)
    acc = jnp.einsum("tmk,tkn->tmn", xt, w[tile_group], preferred_element_type=jnp.float32)
    if w_gate is None:
        acc = _apply_moe_activation(acc, activation)
    else:
        gate = jnp.einsum("tmk,tkn->tmn", xt, w_gate[tile_group], preferred_element_type=jnp.float32)
        acc = _apply_moe_activation(gate, activation) * acc
    alive = jnp.arange(tiles)[:, None, None] < live[0]
    return jnp.where(alive, acc, 0.0).astype(out_dtype).reshape(tiles * tile_m, -1)


def _moe_gmm_impl(x_rows, w, tile_group, live, activation, out_dtype, tile_m, w_gate=None):
    rows, k = x_rows.shape
    _, _, n = w.shape
    gated = w_gate is not None  # two weight blocks a step then, each of the one budget
    tn = _moe_tile_n(k, n, jnp.dtype(w.dtype).itemsize)

    def kernel(tg_ref, live_ref, x_ref, w_ref, o_ref):
        @pl.when(pl.program_id(1) < live_ref[0])
        def _tile():
            acc = _dot_nn(x_ref[...], w_ref[...])
            o_ref[...] = _apply_moe_activation(acc, activation).astype(o_ref.dtype)

    def gated_kernel(tg_ref, live_ref, x_ref, g_ref, w_ref, o_ref):
        @pl.when(pl.program_id(1) < live_ref[0])
        def _tile():
            x = x_ref[...]
            gate = _apply_moe_activation(_dot_nn(x, g_ref[...]), activation)
            o_ref[...] = (gate * _dot_nn(x, w_ref[...])).astype(o_ref.dtype)

    def tile(i, lv):
        # a dead step names the last live tile again: no copy starts
        return jnp.maximum(jnp.minimum(i, lv[0] - 1), 0)

    w_spec = pl.BlockSpec((None, k, tn), lambda j, i, tg, lv: (tg[tile(i, lv)], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # the group of each tile, the live tile count
        grid=(n // tn, rows // tile_m),
        in_specs=[
            pl.BlockSpec((tile_m, k), lambda j, i, tg, lv: (tile(i, lv), 0)),
        ] + (2 if gated else 1) * [w_spec],
        out_specs=pl.BlockSpec((tile_m, tn), lambda j, i, tg, lv: (tile(i, lv), j)),
    )
    return pl.pallas_call(
        gated_kernel if gated else kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        # a dead step REVISITS the last live tile's out block: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_INTERPRET,
        name="moe_gmm",
    )(tile_group, live, x_rows, *((w_gate, w) if gated else (w,)))


@functools.partial(jax.jit, static_argnames=("activation", "out_dtype", "tile_m"))
def _moe_gmm_jit(x_rows, w, tile_group, live, activation=None, out_dtype=None,
                 tile_m=MOE_TILE_M, w_gate=None):
    return _moe_gmm_impl(x_rows, w, tile_group, live, activation, out_dtype, tile_m, w_gate)


def moe_gmm(x_rows, w, tile_group, live, activation=None, out_dtype=None,
            tile_m: int = MOE_TILE_M, w_gate=None):
    """Grouped matmul over the experts held: rows `x_rows` [R, K] in the
    layout of `moe_group_layout` (R a multiple of `tile_m`, every tile one
    group's rows), `w` [groups, K, N] one matrix a group, `tile_group`
    [R / tile_m] and `live` [1] from the layout. Returns [R, N] in
    `out_dtype` (default: x's), `activation` ("relu2", "silu" or None)
    applied to the f32 accumulator. With `w_gate` [groups, K, N] the product
    is GATED: `activation(x W_gate) * (x W)`, both matrices of a group read in
    the one step. Rows of dead tiles are NOT written on the chip.

    Dispatches the Pallas kernel `moe_gmm` on TPU (or under interpret
    mode), else the jnp reference."""
    if x_rows.ndim != 2 or w.ndim != 3 or x_rows.shape[1] != w.shape[1]:
        raise ValueError(f"moe_gmm: x_rows {x_rows.shape} does not fit w {w.shape} ([groups, K, N])")
    if w_gate is not None and w_gate.shape != w.shape:
        raise ValueError(f"moe_gmm: w_gate {w_gate.shape} must have w's shape {w.shape}")
    if x_rows.shape[0] % tile_m or tile_group.shape[0] != x_rows.shape[0] // tile_m:
        raise ValueError(f"moe_gmm: {x_rows.shape[0]} rows are not {tile_group.shape[0]} tiles of {tile_m}")
    out_dtype = jnp.dtype(out_dtype or x_rows.dtype)
    if _on_tpu():
        gate = {} if w_gate is None else {"w_gate": w_gate}
        with jax.enable_x64(False):
            return _moe_gmm_jit(x_rows, w, jnp.asarray(tile_group, jnp.int32),
                                jnp.asarray(live, jnp.int32), activation=activation,
                                out_dtype=out_dtype, tile_m=tile_m, **gate)
    return moe_gmm_reference(x_rows, w, tile_group, live, activation, out_dtype, tile_m, w_gate)
