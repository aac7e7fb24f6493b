"""Decomposed collective matmul: latency-hiding TP/SP primitives.

The GSPMD layers (mp_layers.py, sequence_parallel_utils.py) express their
collectives as layout constraints, which compiles to all-gather → matmul /
matmul → reduce-scatter / matmul → all-reduce sequences that SERIALIZE the
transfer against the math: the matmul cannot start before the whole gather
lands, and the reduce cannot start before the whole matmul finishes. On a
pod the ICI time is pure bubble.

This module decomposes those fused ops into a `ppermute`-chunked ring loop
(the "collective matmul" of Wang et al., ASPLOS'23 — overlap communication
with *dependent* computation via decomposition): each step's shard transfer
has no data dependence on the same step's chunk matmul, so the XLA
latency-hiding scheduler runs them concurrently. Four directions:

  ag_matmul      seq-sharded x  @ col-sharded w  -> full-seq, col-sharded out
                 (ColumnSequenceParallelLinear: the ag→mm direction — each
                 ring step matmuls the shard it holds while ppermuting it
                 onward, writing output rows per originating rank)
  matmul_rs      full-seq x @ row-sharded w -> seq-sharded REDUCED out
                 (RowSequenceParallelLinear: the mm→rs direction — the
                 accumulator rides the ring; step k's block matmul is
                 independent of step k-1's ppermute)
  matmul_ar      full x @ row-sharded w -> replicated out
                 (RowParallelLinear: the all-reduce is split into per-column
                 -chunk psums; chunk c's psum overlaps chunk c+1's matmul)
  matmul_ag_cols x @ col-sharded w -> replicated (gathered) out
                 (ColumnParallelLinear gather_output=True: row-chunked
                 matmul, each chunk all-gathered as soon as it's computed)

All four are exact up to float reassociation of the reduction (the ring sum
order differs from XLA's tree), i.e. allclose at dtype tolerance vs the
GSPMD dispatch — asserted on the 8-device mesh in tests/test_overlap.py.
The vjp of each decomposition is itself a decomposition (ppermute/psum have
ring transpose rules), so the BACKWARD collectives overlap too.

Knob: FLAGS_collective_matmul — 0 disables (GSPMD constraint path); N >= 1
enables, with N the matmul sub-chunk count for the chunked directions
(matmul_ar / matmul_ag_cols, and the per-shard row split of ag_matmul).
`autotune_chunks` times candidates on the live mesh and returns the best.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
from jax import numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ....core.apply import apply
from ....core.tensor import Tensor
from ....framework import flags as _flags

_flags.define_flag(
    "FLAGS_collective_matmul",
    0,
    "decomposed collective matmul for TP/SP layers: 0 = off (GSPMD layout "
    "constraints; transfer serializes against the matmul), N >= 1 = replace "
    "the all-gather→matmul / matmul→reduce-scatter / matmul→all-reduce in "
    "the parallel linear layers with ppermute-chunked ring loops whose "
    "shard transfers overlap the previous chunk's matmul; N is the matmul "
    "sub-chunk count for the chunked directions (autotune_chunks helps "
    "pick it)",
)


def enabled() -> int:
    """The FLAGS_collective_matmul chunk count (0 = disabled)."""
    return int(_flags.get_flag("FLAGS_collective_matmul"))


def _ring_fwd(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def _splits(total: int, chunks: int):
    """Static (offset, size) column/row chunks; degrades to 1 chunk when
    `chunks` doesn't divide cleanly into at-least-1-wide pieces."""
    chunks = max(1, min(int(chunks), total))
    base, rem = divmod(total, chunks)
    out, off = [], 0
    for i in range(chunks):
        size = base + (1 if i < rem else 0)
        out.append((off, size))
        off += size
    return out


# ---------------------------------------------------------------------------
# per-device ring bodies (run under shard_map over the named mesh axis)
# ---------------------------------------------------------------------------


def _ag_mm_body(x, w, b, *, axis, n, sub):
    """x: [s_loc, ..., in] this rank's seq shard; w: [in, out_loc];
    b: [out_loc] or None. Returns [s_loc * n, ..., out_loc]."""
    idx = jax.lax.axis_index(axis)
    s_loc = x.shape[0]
    fwd = _ring_fwd(n)

    def mm(blk):
        if sub <= 1 or s_loc < sub:
            return blk @ w
        parts = [
            jax.lax.dynamic_slice_in_dim(blk, off, size, axis=0) @ w
            for off, size in _splits(s_loc, sub)
        ]
        return jnp.concatenate(parts, axis=0)

    y0 = mm(x)
    out = jnp.zeros((s_loc * n,) + y0.shape[1:], y0.dtype)
    cur = x
    for k in range(n):
        # issue the transfer of the NEXT shard before this shard's matmul in
        # program order — neither depends on the other, so the scheduler
        # overlaps the ppermute with the chunk matmul
        nxt = jax.lax.ppermute(cur, axis, fwd) if k < n - 1 else None
        y = y0 if k == 0 else mm(cur)
        # after k forward shifts rank `idx` holds rank (idx - k)'s shard
        row = ((idx - k) % n) * s_loc
        out = jax.lax.dynamic_update_slice_in_dim(out, y, row, axis=0)
        cur = nxt
    if b is not None:
        out = out + b
    return out


def _mm_rs_body(x, w, b, *, axis, n):
    """x: [S, ..., in_loc] full seq, last dim sharded; w: [in_loc, out];
    b: [out] or None (added once, post-reduction). Returns the seq-sharded
    reduced block [S // n, ..., out]."""
    idx = jax.lax.axis_index(axis)
    s_loc = x.shape[0] // n
    fwd = _ring_fwd(n)
    acc = None
    for k in range(n):
        # the partial riding the ring targets seq block (idx + n-1-k) at
        # step 0 on rank idx; every rank it visits adds ITS partial for the
        # same final block, landing on the owner after n-1 shifts
        row = ((idx + n - 1 - k) % n) * s_loc
        part = jax.lax.dynamic_slice_in_dim(x, row, s_loc, axis=0) @ w
        acc = part if acc is None else acc + part
        if k < n - 1:
            acc = jax.lax.ppermute(acc, axis, fwd)
    if b is not None:
        acc = acc + b
    return acc


def _mm_ar_body(x, w, b, *, axis, chunks):
    """x: [..., in_loc]; w: [in_loc, out]; psum per output-column chunk so
    chunk c's all-reduce overlaps chunk c+1's matmul. chunks=1 degrades to
    the single fused psum (no overlap — the knob means what it says, and
    autotune can time the degenerate case honestly). Returns replicated
    [..., out]."""
    outs = []
    for off, size in _splits(w.shape[1], chunks):
        wc = jax.lax.dynamic_slice_in_dim(w, off, size, axis=1)
        outs.append(jax.lax.psum(x @ wc, axis))
    out = jnp.concatenate(outs, axis=-1)
    if b is not None:
        out = out + b
    return out


def _mm_ag_cols_body(x, w, b, *, axis, chunks):
    """x: [S, ..., in]; w: [in, out_loc]; each row-chunk's local matmul is
    all-gathered (concat over the ranks' column blocks) as soon as it is
    computed. b (column-sharded, [out_loc]) is added BEFORE the gather so
    each rank biases its own columns. chunks=1 degrades to one matmul +
    one gather (no overlap). Returns [S, ..., out_loc * n]."""
    s = x.shape[0]
    outs = []
    for off, size in _splits(s, chunks):
        y = jax.lax.dynamic_slice_in_dim(x, off, size, axis=0) @ w
        if b is not None:
            y = y + b
        outs.append(jax.lax.all_gather(y, axis, axis=y.ndim - 1, tiled=True))
    return jnp.concatenate(outs, axis=0)


# ---------------------------------------------------------------------------
# shard_map builders (cached per mesh/axis/rank/knob)
# ---------------------------------------------------------------------------


def _rep(nd):
    return P(*([None] * nd))


def _axis_at(nd, pos, axis):
    spec = [None] * nd
    spec[pos] = axis
    return P(*spec)


@functools.lru_cache(maxsize=64)
def _build(kind: str, mesh: Mesh, axis: str, x_nd: int, has_bias: bool, sub: int):
    n = mesh.shape[axis]
    if kind == "ag_mm":
        body = functools.partial(_ag_mm_body, axis=axis, n=n, sub=sub)
        in_specs = (_axis_at(x_nd, 0, axis), P(None, axis),
                    P(axis) if has_bias else None)
        out_specs = _axis_at(x_nd, x_nd - 1, axis)
    elif kind == "mm_rs":
        body = functools.partial(_mm_rs_body, axis=axis, n=n)
        in_specs = (_axis_at(x_nd, x_nd - 1, axis), P(axis, None),
                    _rep(1) if has_bias else None)
        out_specs = _axis_at(x_nd, 0, axis)
    elif kind == "mm_ar":
        body = functools.partial(_mm_ar_body, axis=axis, chunks=sub)
        in_specs = (_axis_at(x_nd, x_nd - 1, axis), P(axis, None),
                    _rep(1) if has_bias else None)
        out_specs = _rep(x_nd)
    elif kind == "mm_ag_cols":
        body = functools.partial(_mm_ag_cols_body, axis=axis, chunks=sub)
        in_specs = (_rep(x_nd), P(None, axis), P(axis) if has_bias else None)
        out_specs = _rep(x_nd)
    else:  # pragma: no cover
        raise ValueError(kind)

    if has_bias:
        fn = body
        specs = in_specs
    else:
        fn = lambda x, w: body(x, w, None)  # noqa: E731
        specs = in_specs[:2]
    return _shard_map(fn, mesh=mesh, in_specs=specs, out_specs=out_specs,
                      check_vma=False)


def _run(kind, x: Tensor, w: Tensor, b: Optional[Tensor], mesh, axis, sub):
    f = _build(kind, mesh, axis, len(x.shape), b is not None, int(sub))
    name = f"collective_matmul_{kind}"
    if b is not None:
        return apply(name, f, x, w, b)
    return apply(name, f, x, w)


def ag_matmul(x, w, b, mesh, axis="mp", sub=1):
    """all_gather(x over seq) @ w, decomposed (ag→mm). x seq-sharded on
    axis 0 over `axis`; w column-sharded; out full-seq, column-sharded."""
    return _run("ag_mm", x, w, b, mesh, axis, sub)


def matmul_rs(x, w, b, mesh, axis="mp", sub=1):
    """reduce_scatter(x @ w over seq), decomposed (mm→rs). x last-dim
    sharded; w row-sharded; out seq-sharded (axis 0), fully reduced."""
    return _run("mm_rs", x, w, b, mesh, axis, sub)


def matmul_ar(x, w, b, mesh, axis="mp", chunks=2):
    """all_reduce(x @ w), decomposed into per-column-chunk psums."""
    return _run("mm_ar", x, w, b, mesh, axis, chunks)


def matmul_ag_cols(x, w, b, mesh, axis="mp", chunks=2):
    """all_gather(x @ w over the column-sharded dim), row-chunked."""
    return _run("mm_ag_cols", x, w, b, mesh, axis, chunks)


def _divisible(x: Tensor, mesh, axis, seq_axis=0) -> bool:
    n = mesh.shape[axis]
    return n > 1 and x.shape[seq_axis] % n == 0


def usable(x: Tensor, w: Tensor, mesh, axis: str, kind: str) -> bool:
    """Gate: the decomposition needs the ring dimension to divide cleanly
    and a real (>1) axis; anything else falls back to the GSPMD path."""
    n = mesh.shape.get(axis, 1) if hasattr(mesh.shape, "get") else mesh.shape[axis]
    if n <= 1 or len(x.shape) < 2:
        return False
    if kind == "ag_mm":
        # x is seq-sharded: its GLOBAL seq dim is s_loc * n by construction
        return x.shape[0] % n == 0 and w.shape[1] % n == 0
    if kind == "mm_rs":
        return x.shape[0] % n == 0 and x.shape[-1] % n == 0
    if kind == "mm_ar":
        return x.shape[-1] % n == 0
    if kind == "mm_ag_cols":
        return w.shape[1] % n == 0
    return False


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------


def autotune_chunks(
    seq: int,
    in_features: int,
    out_features: int,
    mesh: Optional[Mesh] = None,
    axis: str = "mp",
    candidates=(1, 2, 4),
    iters: int = 5,
    kind: str = "ag_mm",
    dtype=jnp.float32,
    set_flag: bool = False,
):
    """Time the decomposed kernel at each candidate sub-chunk count on the
    live mesh and return {'best': int, 'timings': {chunks: seconds}}.

    Shapes are the GLOBAL problem (full seq / features); the helper builds
    synthetic operands with the layer's layouts and times `iters` dispatches
    per candidate (min-of-k). With set_flag=True the winner is written to
    FLAGS_collective_matmul so the layers pick it up immediately.
    """
    if mesh is None:
        from ..base.topology import get_hybrid_communicate_group

        hcg = get_hybrid_communicate_group()
        if hcg is None:
            raise RuntimeError("autotune_chunks needs a mesh (or fleet.init first)")
        mesh = hcg.mesh
    n = mesh.shape[axis]
    import numpy as np
    from jax.sharding import NamedSharding

    rng = np.random.RandomState(0)
    # operand layouts must match each kernel's in_specs exactly — a
    # mismatched put either crashes on a divisibility the kernel never
    # needed or hides a resharding inside the timed dispatch, polluting
    # every candidate's timing the same way
    if kind == "ag_mm":
        x_spec, w_spec = P(axis, None), P(None, axis)
    elif kind in ("mm_rs", "mm_ar"):
        x_spec, w_spec = P(None, axis), P(axis, None)
    elif kind == "mm_ag_cols":
        x_spec, w_spec = P(None, None), P(None, axis)
    else:
        raise ValueError(f"unknown kind {kind!r}")
    x = jax.device_put(
        jnp.asarray(rng.randn(seq, in_features), dtype),
        NamedSharding(mesh, x_spec),
    )
    w = jax.device_put(
        jnp.asarray(rng.randn(in_features, out_features), dtype),
        NamedSharding(mesh, w_spec),
    )
    timings = {}
    for c in candidates:
        f = _build(kind, mesh, axis, 2, False, int(c))
        jf = jax.jit(f)
        jax.block_until_ready(jf(x, w))  # compile
        best = float("inf")
        for _ in range(max(1, iters)):
            t0 = time.perf_counter()
            jax.block_until_ready(jf(x, w))
            best = min(best, time.perf_counter() - t0)
        timings[int(c)] = best
    best_c = min(timings, key=timings.get)
    if set_flag:
        _flags.set_flags({"FLAGS_collective_matmul": int(best_c)})
    return {"best": int(best_c), "timings": timings, "axis_size": int(n)}
