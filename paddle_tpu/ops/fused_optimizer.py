"""Fused one-pass optimizer kernel (bucketed AdamW) — Pallas TPU.

Reference parity: the role of paddle/phi/kernels/gpu/multi_tensor_adam_kernel.cu
and fleet's tensor_fusion_helper fused buffers — but taken one level further,
per the PHI "one kernel, one HBM pass" capability this rebuild targets: the
optimizer's entire elementwise update for a *bucket* of parameters (params,
moment1, moment2, grads flattened into contiguous same-dtype buffers) runs as
ONE Pallas kernel that streams aligned tiles through VMEM exactly once,
applying

  - the global-norm grad-clip scale (a scalar operand — the norm reduction
    happens outside, the scaling costs nothing extra in-stream),
  - coupled (Adam) or decoupled (AdamW) weight decay,
  - bias-corrected AdamW math with per-bucket beta-pow corrections
    (scalar operands, not per-param tensors),
  - optional bfloat16 second-moment storage with the same hash-noise
    stochastic rounding the per-tensor path uses (framework-seeded, so a
    bucket step is reproducible under a fixed seed).

XLA lowers the per-parameter update loop into dozens of separate small
fusions, each re-reading its param/moment/grad operands from HBM; on the
r05 profile that soup is ~9 ms of a 53 ms seq-128 ERNIE step. This kernel
replaces it with (#buckets) launches whose HBM traffic is the information-
theoretic minimum: read p/m/v/g once, write p/m/v once.

Off-TPU (and when the Pallas grid can't be used) the same math runs as
`_reference_apply` — a single jnp expression over the flat bucket, which XLA
fuses into one loop on any backend. Both implementations share one update
function and one flat-index stochastic-rounding hash, so they agree to FMA
reassociation (a couple of ULPs) — the interpret-mode kernel tests pin this.

Layout contract (enforced by the callers in optimizer/fused_engine.py and
static/executor.py): flat buffers are padded to a multiple of
`PAD_ELEMS = 16384` elements = 16 sublane rows of 1024 lanes — legal tile
granularity for every dtype the engine stores (f32 (8,128), bf16 (16,128)).
Padding lanes hold zeros and stay zeros through the update (g=0 -> m,v,upd
all 0), so they never poison real lanes and buffers can be sliced back
without masking.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax import numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# flat buffers are viewed as (rows, LANES); LANES = 8 * 128 keeps every row
# a whole VPU register row and makes the min-tile math dtype-uniform
LANES = 1024
# pad granularity: 16 rows covers the bf16 (16, 128) min tile
PAD_ROWS = 16
PAD_ELEMS = PAD_ROWS * LANES
# rows per grid step: 128 rows x 1024 lanes x 4B = 512KB per f32 operand;
# 7 streams (4 in + 3 out) double-buffered is ~7MB of VMEM — half the
# 16MB budget, leaving Mosaic room to pipeline HBM copies across steps
_MAX_BLOCK_ROWS = 128


def _block_rows(rows):
    for b in (_MAX_BLOCK_ROWS, 64, 32, PAD_ROWS):
        if rows % b == 0:
            return b
    raise ValueError(f"flat bucket rows {rows} not a multiple of {PAD_ROWS}")


def pad_to_tile(n: int) -> int:
    """Smallest legal flat-buffer length >= n."""
    return max(PAD_ELEMS, -(-n // PAD_ELEMS) * PAD_ELEMS)


# --- stochastic rounding, flat-index keyed -------------------------------
# Same murmur-style fmix as optimizer._sr_round, but hashed on the
# *flat bucket index* so the Pallas tiles and the jnp reference path (which
# see different shapes of the same buffer) produce identical bits.

_M1 = 0x9E3779B1
_M2 = 0x85EBCA6B


def _sr_bits_flat(x32, idx_u32, seed_u32):
    """f32 -> bf16-representable f32 bits with stochastic rounding: add
    uniform noise below the mantissa cut, truncate the low 16 bits. Stays in
    uint32/f32 the whole way (no 16-bit ops — Mosaic-friendly) and is
    unbiased: E[round(x)] = x."""
    bits = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    u = idx_u32 * np.uint32(_M1) ^ seed_u32
    u = u ^ jax.lax.shift_right_logical(u, jnp.uint32(16))
    u = u * np.uint32(_M2)
    u = u ^ jax.lax.shift_right_logical(u, jnp.uint32(13))
    noise = u & jnp.uint32(0xFFFF)
    kept = (bits + noise) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(kept, jnp.float32)


def _update_math(p, m, v, g, lr, clip, c1, c2, *, beta1, beta2, eps, wd, decoupled):
    """The one shared AdamW/Adam elementwise update (f32 in, f32 out).
    Both the kernel tiles and the reference path call exactly this, so the
    two implementations cannot drift."""
    g = g * clip
    if wd and not decoupled:  # Adam: L2 folds into the gradient
        g = g + wd * p
    m_new = beta1 * m + (1.0 - beta1) * g
    v_new = beta2 * v + (1.0 - beta2) * g * g
    upd = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
    if wd and decoupled:  # AdamW: decoupled decay joins the update
        upd = upd + wd * p
    return p - lr * upd, m_new, v_new


def _kernel(block_rows, beta1, beta2, eps, wd, decoupled, m2_bf16):
    def kernel(scal_ref, seed_ref, p_ref, m_ref, v_ref, g_ref, po_ref, mo_ref, vo_ref):
        lr, clip = scal_ref[0], scal_ref[1]
        c1, c2 = scal_ref[2], scal_ref[3]
        p32 = p_ref[...].astype(jnp.float32)
        p_new, m_new, v_new = _update_math(
            p32,
            m_ref[...],
            v_ref[...].astype(jnp.float32),
            g_ref[...].astype(jnp.float32),
            lr, clip, c1, c2,
            beta1=beta1, beta2=beta2, eps=eps, wd=wd, decoupled=decoupled,
        )
        po_ref[...] = p_new.astype(po_ref.dtype)
        mo_ref[...] = m_new
        if not m2_bf16:
            vo_ref[...] = v_new
        else:
            base = (pl.program_id(0) * block_rows).astype(jnp.uint32) * np.uint32(LANES)
            rows = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, LANES), 0)
            cols = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, LANES), 1)
            idx = base + rows * np.uint32(LANES) + cols
            vo_ref[...] = _sr_bits_flat(v_new, idx, seed_ref[0]).astype(jnp.bfloat16)

    return kernel


def _pallas_apply(p, m, v, g, scal, seed, beta1, beta2, eps, wd, decoupled, m2_bf16):
    n = p.shape[0]
    rows = n // LANES
    br = _block_rows(rows)
    view = lambda a: a.reshape(rows, LANES)
    spec = lambda: pl.BlockSpec((br, LANES), lambda i, *_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # scal f32[4], seed uint32[1]
        grid=(rows // br,),
        in_specs=[spec(), spec(), spec(), spec()],
        out_specs=[spec(), spec(), spec()],
    )
    from . import pallas as _pk  # one interpret switch for every kernel

    p2, m2, v2 = pl.pallas_call(
        _kernel(br, beta1, beta2, eps, wd, decoupled, m2_bf16),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), p.dtype),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=_pk._INTERPRET,
        name="fused_adamw",
    )(scal, seed, view(p), view(m), view(v), view(g))
    return p2.reshape(n), m2.reshape(n), v2.reshape(n)


def _reference_apply(p, m, v, g, scal, seed, beta1, beta2, eps, wd, decoupled, m2_bf16):
    """Off-TPU path: identical math over the whole flat buffer — XLA fuses it
    into one elementwise loop on any backend (this is already most of the
    win vs the per-tensor soup: one launch, one pass)."""
    lr, clip, c1, c2 = scal[0], scal[1], scal[2], scal[3]
    p_new, m_new, v_new = _update_math(
        p.astype(jnp.float32), m, v.astype(jnp.float32), g.astype(jnp.float32),
        lr, clip, c1, c2,
        beta1=beta1, beta2=beta2, eps=eps, wd=wd, decoupled=decoupled,
    )
    if m2_bf16:
        idx = jax.lax.iota(jnp.uint32, p.shape[0])
        v_new = _sr_bits_flat(v_new, idx, seed[0]).astype(jnp.bfloat16)
    return p_new.astype(p.dtype), m_new, v_new.astype(v.dtype)


def fused_adamw_apply(
    p, m, v, g, *,
    lr, clip_scale, c1, c2, seed,
    beta1, beta2, eps, wd, decoupled=True,
):
    """One-pass AdamW/Adam update over one flat bucket.

    Args:
      p: [N] flat params (float32 or bfloat16), N a multiple of PAD_ELEMS.
      m: [N] float32 moment1.
      v: [N] moment2 — float32, or bfloat16 for halved second-moment HBM.
      g: [N] grads (any float dtype; cast to f32 in-stream).
      lr / clip_scale / c1 / c2: scalar operands (may be traced). c1/c2 are
        the bias corrections 1 - beta^t.
      seed: uint32 scalar for the stochastic-rounding hash (ignored when v
        is float32).
      beta1 / beta2 / eps / wd / decoupled: static per-bucket config; wd is
      the resolved scalar decay, decoupled selects AdamW (True) vs Adam.

    Returns (p_new, m_new, v_new) with the input dtypes.
    """
    if p.ndim != 1 or p.shape[0] % PAD_ELEMS:
        raise ValueError(
            f"flat bucket must be 1-D with length a multiple of {PAD_ELEMS}, "
            f"got shape {p.shape}"
        )
    scal = jnp.stack(
        [jnp.asarray(x, jnp.float32).reshape(()) for x in (lr, clip_scale, c1, c2)]
    )
    seed = jnp.asarray(seed, jnp.uint32).reshape((1,))
    m2_bf16 = v.dtype == jnp.bfloat16
    wd = float(wd)
    args = (p, m, v, g, scal, seed, float(beta1), float(beta2), float(eps),
            wd, bool(decoupled), m2_bf16)
    from . import pallas as _pk

    if _pk._on_tpu():
        # Mosaic rejects the i64 grid/index types the framework's global
        # x64 would trace — like the flash and paged wrappers, trace the
        # kernel with x64 off (every kernel dtype is explicit)
        with jax.enable_x64(False):
            return _pallas_apply(*args)
    return _reference_apply(*args)
