"""Compiled circular pipeline over the pp mesh axis.

This is the TPU-native answer to the reference's actor/interceptor pipeline
runtime (paddle/fluid/distributed/fleet_executor/: Carrier,
ComputeInterceptor message loops) and NCCL p2p micro-batch exchange
(fleet/meta_parallel/pp_utils/p2p_communication.py): instead of host-driven
per-micro-batch send/recv, the WHOLE schedule compiles into one XLA program
— a lax.scan over time steps where every pp device runs its stage and
hands its activation to the next stage with lax.ppermute (one ICI hop).
All stages stay busy once the pipeline fills (GPipe-style fill/drain of a
circular schedule; 1F1B's memory benefit is obtained by jax.checkpoint on
the stage function + reverse-mode through the scan).

Two schedules:
- pipeline_spmd: one stage per pp rank, bubble = (pp-1)/(M+pp-1).
- pipeline_spmd_interleave: the VPP analog (reference
  PipelineParallelWithInterleave, pipeline_parallel.py:942) — v virtual
  stage chunks per rank assigned round-robin (rank d owns chunks d, d+pp,
  d+2*pp, ...), micro-batches wrap the ring v times. The per-wrap chunk is
  1/v-th the work, so the fill/drain bubble time shrinks by ~v, the same
  bubble economics that motivate VPP on GPUs.

Requirements: every stage (chunk) has the same structure (stage_fn), with
per-stage params stacked on a leading axis sharded over pp; activations may
be arbitrary pytrees but each leaf keeps one shape across stage boundaries.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
from jax import numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ....framework import flags as _flags

_flags.define_flag(
    "FLAGS_pipeline_double_buffer",
    False,
    "double-buffer the pipeline's stage-boundary ppermute: each stage "
    "consumes the activation permuted TWO steps ago while this step's "
    "output transfer is in flight, so the ICI hop of micro-batch t overlaps "
    "the stage compute of t+1 instead of serializing against it; costs "
    "S-1 extra fill/drain steps (T = M + 2(S-1)) and one extra carry "
    "buffer per stage",
)


def _double_buffer_default(double_buffer):
    if double_buffer is None:
        return bool(_flags.get_flag("FLAGS_pipeline_double_buffer"))
    return bool(double_buffer)


def _tree_where(pred, a, b):
    return jax.tree_util.tree_map(lambda x, y: jnp.where(pred, x, y), a, b)


def _tree_index(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def _shift_carry(y, axis, fwd_perm, carry_shift_keys):
    """Hand the carry to the next stage: ppermute every leaf, or — when
    carry_shift_keys names a subset of a dict carry — only those keys
    (others reset to zeros so e.g. a vocab-sized output slot never rides
    the ring; it is collected from the scan ys instead)."""
    if carry_shift_keys is not None and isinstance(y, dict):
        return {
            key: (
                jax.tree_util.tree_map(
                    lambda l: jax.lax.ppermute(l, axis, fwd_perm), val
                )
                if key in carry_shift_keys
                else jax.tree_util.tree_map(jnp.zeros_like, val)
            )
            for key, val in y.items()
        }
    return jax.tree_util.tree_map(
        lambda l: jax.lax.ppermute(l, axis, fwd_perm), y
    )


def _wrap_index(t, sidx, pp, v):
    """Local chunk wrap c at time t on rank sidx (global chunk is
    c*pp + sidx) under the group-synchronous circular schedule."""
    return jnp.clip((t - sidx) // pp, 0, None) % v


def _aligned_feed(t, j, pp, v, M):
    """Index of the micro-batch sitting at global chunk j at time t:
    micro-batch m enters chunk 0 at t_in = (m//pp)*pp*v + m%pp and reaches
    chunk j at t_in + j, so m = ((t-j)//(pp*v))*pp + (t-j)%(pp*v) with the
    remainder in [0, pp) during valid steps (clamped during fill/drain).
    This is what lets ANY chunk read its own micro-batch's feed (labels in
    the last chunk, ids in the first) — the hetero stage contract."""
    tp = jnp.clip(t - j, 0, None)
    g = tp // (pp * v)
    return jnp.clip(g * pp + jnp.minimum(tp % (pp * v), pp - 1), 0, M - 1)


def _interleave_finish(M, pp, v):
    """Time step at which micro-batch m finishes the last chunk on rank
    pp-1 under the group-synchronous circular schedule (static schedule ->
    static gather indices)."""
    S_total = v * pp
    return jnp.asarray(
        [(m // pp) * pp * v + m % pp + S_total - 1 for m in range(M)]
    )


def pipeline_spmd(stage_fn: Callable, mesh: Mesh, axis: str = "pp", checkpoint_stages: bool = True,
                  data_axis: str = None, param_specs=None, double_buffer: bool = None):
    """Build fn(stacked_params, microbatches) -> outputs.

    stage_fn(params, x) -> y: one stage's computation; x/y are pytrees whose
    leaves keep their shapes across stages.
    stacked_params: pytree with leading stage axis S (sharded over `axis`).
    microbatches: pytree of [M, ...] micro-batch streams (replicated over the
    pipeline axis; sharded over `data_axis` on the batch dim when given —
    the dp x pp composition: each dp slice runs its own micro-batch stream
    through the same pp ring). RANK CONTRACT when `data_axis` is set: every
    micro-batch leaf must be [M, B, ...] (batch at dim 1) and every stage
    output leaf >= 2-D — the shard specs below assume it. `run` validates
    the INPUT leaves loudly; a 1-D stage OUTPUT still surfaces as a
    PartitionSpec rank error from jit (outputs aren't known until trace).
    param_specs: optional pytree of PartitionSpec matching stacked_params
    (each spec must lead with the stage axis). Extra axes express hybrid
    layouts: P(axis, None, 'tp') for Megatron-style stages whose stage_fn
    psums over 'tp'; P(axis, 'dp') for ZeRO-3-style stages that all_gather
    their weights over the data axis before use.
    double_buffer: None reads FLAGS_pipeline_double_buffer. When on, each
    stage consumes the carry permuted TWO steps ago while the current
    output's ppermute is in flight — transfer of micro-batch t overlaps
    compute of t+1 (the XLA scheduler sees no dependence between them).
    Stage s then runs micro-batch m at step m + 2s, so fill/drain costs
    2(S-1) instead of S-1; identical math, same outputs.
    Returns the final stage's outputs, each leaf [M, ...].
    """
    S = mesh.shape[axis]
    db = _double_buffer_default(double_buffer)
    fn = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn

    def per_device(params, mbs):
        # params leaves: [1, ...] local stage slice; mbs leaves: [M, ...]
        params = _tree_index(params, 0)
        sidx = jax.lax.axis_index(axis)
        leaves = jax.tree_util.tree_leaves(mbs)
        M = leaves[0].shape[0]
        fwd_perm = [(s, (s + 1) % S) for s in range(S)]

        def step(carry, t):
            buf = carry
            # stage 0 ingests micro-batch t (clipped during drain)
            feed = _tree_index(mbs, jnp.clip(t, 0, M - 1))
            x = _tree_where(sidx == 0, feed, buf)
            y = fn(params, x)
            shifted = jax.tree_util.tree_map(
                lambda l: jax.lax.ppermute(l, axis, fwd_perm), y
            )
            return shifted, y

        def step_db(carry, t):
            # double buffer: (arrived, in_flight) — this step consumes the
            # value permuted two steps ago; ppermute(y) has no consumer
            # this step OR next, so it overlaps the next stage compute
            arrived, in_flight = carry
            feed = _tree_index(mbs, jnp.clip(t, 0, M - 1))
            x = _tree_where(sidx == 0, feed, arrived)
            y = fn(params, x)
            shifted = jax.tree_util.tree_map(
                lambda l: jax.lax.ppermute(l, axis, fwd_perm), y
            )
            return (in_flight, shifted), y

        zeros = jax.tree_util.tree_map(jnp.zeros_like, _tree_index(mbs, 0))
        if db:
            T = M + 2 * (S - 1)
            _, ys = jax.lax.scan(step_db, (zeros, zeros), jnp.arange(T))
        else:
            _, ys = jax.lax.scan(step, zeros, jnp.arange(M + S - 1))
        return jax.tree_util.tree_map(lambda l: l[None], ys)  # [1, T, ...]

    param_in_spec = P(axis) if param_specs is None else param_specs
    # micro-batch leaves are [M, B, ...]: shard B (dim 1) over data_axis
    mb_in_spec = P(None, data_axis) if data_axis else P()
    # per-device output leaves are [1, T, B, ...]
    out_spec = P(axis, None, data_axis) if data_axis else P(axis)

    sharded = _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(param_in_spec, mb_in_spec),
        out_specs=out_spec,
        check_vma=False,
    )

    def run(stacked_params, microbatches):
        leaves = jax.tree_util.tree_leaves(microbatches)
        M = leaves[0].shape[0]
        if data_axis:
            bad = [tuple(l.shape) for l in leaves if l.ndim < 2]
            if bad:
                raise ValueError(
                    "pipeline_spmd(data_axis=...) requires every micro-batch "
                    f"leaf to be [M, B, ...] (batch at dim 1); got leaves of "
                    f"shape {bad}"
                )
        ys = sharded(stacked_params, microbatches)  # [S, T, ...]
        # final stage's outputs for micro-batch m appear at t = m + S - 1
        # (m + 2(S-1) under double buffering: two steps per hop)
        lead = 2 * (S - 1) if db else (S - 1)
        return jax.tree_util.tree_map(lambda l: l[S - 1, lead : lead + M], ys)

    return run


def pipeline_spmd_interleave(
    stage_fn: Callable,
    mesh: Mesh,
    num_virtual_stages: int,
    axis: str = "pp",
    checkpoint_stages: bool = True,
):
    """VPP circular schedule: S_total = v * pp stage chunks, chunk k lives on
    rank k % pp (round-robin, the reference's interleave assignment,
    pp_layers.py get_stage_from_index for interleave). A micro-batch hops the
    ring v times; consecutive chunks are on consecutive ranks so every hop is
    still one ppermute. Rank d selects its local chunk (k // pp) by how many
    wraps the arriving activation has completed.

    stacked_params: leading axis S_total in ROUND-ROBIN device order — use
    stack_stage_params_interleave so chunk k % pp == its rank.
    Returns the final chunk's outputs, each leaf [M, ...].
    """
    pp = mesh.shape[axis]
    v = num_virtual_stages
    S_total = v * pp
    fn = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn

    def per_device(params, mbs):
        # params leaves: [v, ...] this rank's chunks (round-robin order:
        # local index c is global chunk c*pp + d)
        sidx = jax.lax.axis_index(axis)
        leaves = jax.tree_util.tree_leaves(mbs)
        M = leaves[0].shape[0]
        fwd_perm = [(s, (s + 1) % pp) for s in range(pp)]
        # group-synchronous circular schedule: micro-batches advance in
        # groups of pp; group g's member m enters rank 0 / chunk 0 at
        # t_ingest = g*pp*v + (m % pp) and hops one chunk per step, so a
        # full batch takes T = M*v + pp - 1 steps — the fill/drain bubble is
        # pp-1 chunk-steps, v times less wall-time than the non-interleaved
        # schedule's (pp-1) full-stage steps.
        T = M * v + pp - 1

        def step(carry, t):
            buf = carry
            # the activation arriving at rank d at time t sits at global
            # chunk k = d + pp*c with local wrap c = ((t - d) // pp) mod v
            # (see t_ingest above: (t - t_ingest - d) / pp == c)
            c = _wrap_index(t, sidx, pp, v)
            feed = _tree_index(mbs, _aligned_feed(t, 0, pp, v, M))
            # rank 0 ingests a fresh micro-batch while its wrap slot is 0
            ingest = (sidx == 0) & (c == 0)
            x = _tree_where(ingest, feed, buf)
            local = _tree_index(params, c)
            y = fn(local, x)
            shifted = jax.tree_util.tree_map(
                lambda l: jax.lax.ppermute(l, axis, fwd_perm), y
            )
            return shifted, y

        init = jax.tree_util.tree_map(jnp.zeros_like, _tree_index(mbs, 0))
        _, ys = jax.lax.scan(step, init, jnp.arange(T))
        return jax.tree_util.tree_map(lambda l: l[None], ys)

    sharded = _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )

    def run(stacked_params, microbatches):
        leaves = jax.tree_util.tree_leaves(microbatches)
        M = leaves[0].shape[0]
        if M % pp != 0:
            raise ValueError(
                f"interleaved pipeline needs micro-batches ({M}) divisible by pp ({pp})"
            )
        ys = sharded(stacked_params, microbatches)  # [pp, T, ...]
        # micro-batch m finishes chunk S_total-1 on rank pp-1 at
        # t = t_ingest(m) + S_total - 1 (static schedule -> static gather)
        finish = _interleave_finish(M, pp, v)
        return jax.tree_util.tree_map(lambda l: l[pp - 1, finish], ys)

    return run


def _stacked_spec(ndim: int, axis: str) -> P:
    """Leading-axis pp shard for stacked stage params — the SpecLayout
    stage_stacked layout (spec built through the unified table so the
    checkpoint/reshard layer sees the same naming)."""
    from ...sharding import spec_layout as _sl

    return _sl.SpecLayout(pp_axis=axis).stage_stacked(ndim)


def stack_stage_params(param_trees, mesh: Mesh, axis: str = "pp"):
    """Stack S per-stage param pytrees on a new leading axis sharded over pp."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *param_trees)

    def put(x):
        return jax.device_put(x, NamedSharding(mesh, _stacked_spec(x.ndim, axis)))

    return jax.tree_util.tree_map(put, stacked)


def stack_stage_params_interleave(param_trees, mesh: Mesh, num_virtual_stages: int, axis: str = "pp"):
    """Stack v*pp chunk param trees so that rank d's local [v, ...] block is
    (chunk d, chunk d+pp, ...) — the round-robin VPP placement. The leading
    axis is ordered rank-major: [d*v + c] = global chunk c*pp + d."""
    pp = mesh.shape[axis]
    v = num_virtual_stages
    assert len(param_trees) == pp * v, (len(param_trees), pp, v)
    order = [c * pp + d for d in range(pp) for c in range(v)]
    reordered = [param_trees[k] for k in order]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *reordered)

    def put(x):
        # leading axis pp*v sharded over pp -> rank d holds rows [d*v, (d+1)*v)
        return jax.device_put(x, NamedSharding(mesh, _stacked_spec(x.ndim, axis)))

    return jax.tree_util.tree_map(put, stacked)


def pipeline_spmd_hetero(stage_fns, mesh: Mesh, axis: str = "pp",
                         checkpoint_stages: bool = True,
                         carry_shift_keys=None, double_buffer: bool = None):
    """Compiled schedule for NON-uniform stages (VERDICT r3 next-round #5:
    embedding-first / LM-head-last models). Per-stage param trees differ, so
    each stage's params ravel into a flat f32-promoted vector zero-padded to
    a common width (stack_stage_params_hetero) — the padded superstructure —
    and the per-device stage body is ONE lax.switch over the stage functions
    (each unravels its own prefix). The inter-hop carry is a fixed pytree
    the caller chooses (e.g. {'h': hidden, 'out': final-output slot}): every
    stage emits the same structure, so the ppermute ring stays uniform while
    the computation does not.

    stage_fns[s](flat_local, carry, feed) -> carry'; feed is that device's
    time-aligned micro-batch element (stage s at step t sees micro-batch
    t - s — stage 0 consumes it as input, later stages may read labels).
    carry_shift_keys: when the carry is a dict, the subset of keys the NEXT
    stage actually reads — only those ride the ppermute ring (e.g. ship the
    hidden state but not a vocab-sized output slot that is only collected
    from ys); None ships everything.
    double_buffer: None reads FLAGS_pipeline_double_buffer; same overlap /
    timing change as pipeline_spmd (stage s sees micro-batch t - 2s, the
    schedule grows to T = M + 2(S-1)).
    Returns run(stacked_flat, feeds) -> final-stage outputs [M, ...].
    """
    S = mesh.shape[axis]
    assert len(stage_fns) == S, (len(stage_fns), S)
    db = _double_buffer_default(double_buffer)
    fns = [jax.checkpoint(f) if checkpoint_stages else f for f in stage_fns]

    def per_device(flat_params, feeds):
        p = flat_params[0]  # [Pmax] local stage row
        sidx = jax.lax.axis_index(axis)
        M = jax.tree_util.tree_leaves(feeds)[0].shape[0]
        fwd_perm = [(s, (s + 1) % S) for s in range(S)]
        hop = 2 if db else 1

        def step(carry, t):
            # stage s at step t runs micro-batch (t - hop*s): feeds stay
            # aligned with the activation that just arrived
            m = jnp.clip(t - hop * sidx, 0, M - 1)
            feed = _tree_index(feeds, m)
            buf = carry[0] if db else carry
            y = jax.lax.switch(sidx, fns, p, buf, feed)
            shifted = _shift_carry(y, axis, fwd_perm, carry_shift_keys)
            if db:
                return (carry[1], shifted), y
            return shifted, y

        # carry template: zeros with the structure stage 0 emits
        init = _hetero_init(fns[0], p, _tree_index(feeds, 0))
        if db:
            init = (init, init)
        _, ys = jax.lax.scan(step, init, jnp.arange(M + hop * (S - 1)))
        return jax.tree_util.tree_map(lambda l: l[None], ys)

    sharded = _shard_map(
        per_device, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis),
        check_vma=False,
    )

    def run(stacked_flat, feeds):
        M = jax.tree_util.tree_leaves(feeds)[0].shape[0]
        ys = sharded(stacked_flat, feeds)
        lead = (2 if db else 1) * (S - 1)
        return jax.tree_util.tree_map(lambda l: l[S - 1, lead : lead + M], ys)

    return run


def _hetero_init(fn0, p, feed0):
    """Zero carry with the structure stage 0 emits (abstract eval only —
    stage 0 must accept carry=None for shape inference... it receives a
    zeros carry instead, built from its own output: two-pass eval_shape)."""
    # first pass: stage 0 ignores its carry (it consumes the feed), so give
    # it a dummy scalar tree and read the OUTPUT structure
    out_shape = jax.eval_shape(lambda pp, ff: fn0(pp, None, ff), p, feed0)
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), out_shape
    )


def stack_stage_params_hetero(param_trees, mesh: Mesh, axis: str = "pp"):
    """Ravel each stage's param tree to a flat vector, zero-pad to the
    widest, stack [S, Pmax] sharded over the pipeline axis. Returns
    (stacked_flat, unravels, sizes) — stage s rebuilds its tree with
    unravels[s](flat[:sizes[s]])."""
    from jax.flatten_util import ravel_pytree

    flats, unravels, sizes = [], [], []
    for tree in param_trees:
        f, un = ravel_pytree(tree)
        flats.append(f)
        unravels.append(un)
        sizes.append(int(f.shape[0]))
    pmax = max(sizes)
    # per-stage params live on their own pp rank's device (the engine's
    # placement; with v chunks/rank the caller orders rows rank-major so a
    # rank's rows are contiguous) — pad + stack each rank's group on ITS
    # device and assemble the sharded stack zero-copy, like _gather_stacked
    # does for uniform stages
    rows = [
        (jnp.pad(f, (0, pmax - s)) if s < pmax else f).reshape(1, pmax)
        for f, s in zip(flats, sizes)
    ]
    n_rows = len(rows)
    pp = mesh.shape[axis]
    sharding = NamedSharding(mesh, _stacked_spec(2, axis))
    try:
        if n_rows % pp != 0:
            raise ValueError("rows not evenly groupable over the mesh axis")
        g = n_rows // pp
        shards = [
            jnp.concatenate(rows[d * g:(d + 1) * g], axis=0) if g > 1 else rows[d * g]
            for d in range(pp)
        ]
        stacked = jax.make_array_from_single_device_arrays(
            (n_rows, pmax), sharding, shards
        )
    except ValueError:
        # rows not pre-placed on their mesh devices (caller-built trees on
        # one device, or a multi-axis mesh needing replicas): host-stack and
        # let device_put distribute
        import numpy as _np

        stacked = jax.device_put(
            jnp.asarray(_np.concatenate([_np.asarray(r) for r in rows], 0)),
            sharding,
        )
    return stacked, unravels, sizes


def pipeline_spmd_hetero_interleave(stage_fns, mesh: Mesh, num_virtual_stages,
                                    axis: str = "pp",
                                    checkpoint_stages: bool = True,
                                    carry_shift_keys=None):
    """VPP circular schedule for NON-uniform chunks: the interleave timing
    of pipeline_spmd_interleave (v chunks per rank round-robin, bubble /v)
    with the flat-padded superstructure + lax.switch bodies of
    pipeline_spmd_hetero. stacked_flat rows are in ROUND-ROBIN order (row
    d*v + c = global chunk c*pp + d, matching stack_stage_params_hetero
    applied per-rank); the switch selects the GLOBAL chunk function
    k = c*pp + d at each step.

    stage_fns[k](flat_local, carry, feed) -> carry'; k in [0, v*pp).
    """
    pp = mesh.shape[axis]
    v = num_virtual_stages
    S_total = v * pp
    assert len(stage_fns) == S_total, (len(stage_fns), S_total)
    fns = [jax.checkpoint(f) if checkpoint_stages else f for f in stage_fns]

    def per_device(flat_params, feeds):
        # flat_params: [v, Pmax] this rank's chunks (round-robin rows)
        sidx = jax.lax.axis_index(axis)
        M = jax.tree_util.tree_leaves(feeds)[0].shape[0]
        fwd_perm = [(s, (s + 1) % pp) for s in range(pp)]
        T = M * v + pp - 1

        def step(carry, t):
            # same timing as pipeline_spmd_interleave, but the feed is
            # aligned PER CHUNK: chunk j at time t reads ITS micro-batch's
            # feed element (t - j timing inversion in _aligned_feed), so
            # later chunks may read labels just like pipeline_spmd_hetero
            c = _wrap_index(t, sidx, pp, v)
            k = c * pp + sidx  # global chunk id -> stage function
            feed = _tree_index(feeds, _aligned_feed(t, k, pp, v, M))
            local = flat_params[c]
            # chunk 0 ignores its carry and consumes the feed; other chunks
            # read the carry — both behaviors live INSIDE the stage fns
            # (k == 0 reads feed), so no _tree_where blend is needed here
            y = jax.lax.switch(k, fns, local, carry, feed)
            return _shift_carry(y, axis, fwd_perm, carry_shift_keys), y

        init = _hetero_init(fns[0], flat_params[0], _tree_index(feeds, 0))
        _, ys = jax.lax.scan(step, init, jnp.arange(T))
        return jax.tree_util.tree_map(lambda l: l[None], ys)

    sharded = _shard_map(
        per_device, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis),
        check_vma=False,
    )

    def run(stacked_flat, feeds):
        M = jax.tree_util.tree_leaves(feeds)[0].shape[0]
        if M % pp != 0:
            # NotImplementedError, not ValueError: the engine's demote-to-
            # eager contract catches this and falls back
            raise NotImplementedError(
                f"interleaved pipeline needs micro-batches ({M}) divisible by pp ({pp})"
            )
        ys = sharded(stacked_flat, feeds)  # [pp, T, ...]
        finish = _interleave_finish(M, pp, v)
        return jax.tree_util.tree_map(lambda l: l[pp - 1, finish], ys)

    return run
