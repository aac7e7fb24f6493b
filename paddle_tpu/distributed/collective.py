"""Eager collective communication.

Reference parity: python/paddle/distributed/collective.py +
communication/*.py (all_reduce, all_gather, all_to_all, reduce_scatter,
broadcast, scatter, reduce, barrier) and the C++ ProcessGroup they call
(paddle/fluid/distributed/collective/process_group.h:47,
process_group_nccl.cc). TPU-native design: there is no ProcessGroup /
CommContext pair and no NCCL — a Group owns a 1-D jax mesh over its devices,
and every collective is a tiny jitted XLA program whose input/output
shardings make GSPMD emit the collective (all-reduce, all-gather,
reduce-scatter, all-to-all) over ICI/DCN. The watchdog/timeout machinery
(comm_task_manager.h) collapses into XLA's own hang detection; TCPStore
bootstrap collapses into jax.distributed (see parallel_env.py).

Distributed-tensor convention (single-controller SPMD): the eager collective
API works on RANK-STACKED tensors — axis 0 indexes the group's ranks and is
sharded over the group's devices, so slice r is physically rank r's local
tensor. A tensor whose leading dim != nranks is treated as "every rank holds
this same value" (replicated). This is the faithful image of the reference's
per-process local tensors in a single-controller world.
"""
from __future__ import annotations

import functools
import time
from typing import List, Optional, Sequence

import numpy as np
import jax
from jax import shard_map as _shard_map
from jax import numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from . import parallel_env


class ReduceOp:
    """Reference parity: paddle.distributed.ReduceOp."""

    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    """A communication group = an ordered device subset + its 1-D mesh.

    Reference parity: python/paddle/distributed/communication/group.py Group
    (backed there by ProcessGroupNCCL). `ranks` index into the world device
    list.
    """

    def __init__(self, ranks: Sequence[int], gid: int, name: Optional[str] = None):
        self.ranks = list(ranks)
        self.id = gid
        self.name = name or f"_default_pg{gid}"
        devs = parallel_env.world_devices()
        self.devices = [devs[r] for r in self.ranks]
        self.mesh = Mesh(np.array(self.devices), ("g",))
        self.sharding = NamedSharding(self.mesh, P("g"))
        self.replicated = NamedSharding(self.mesh, P())

    @property
    def nranks(self) -> int:
        return len(self.ranks)

    @property
    def world_size(self) -> int:
        return len(self.ranks)

    @property
    def rank(self) -> int:
        return self.get_group_rank(parallel_env.get_rank())

    def get_group_rank(self, global_rank: int) -> int:
        return self.ranks.index(global_rank) if global_rank in self.ranks else -1

    @property
    def process_group(self):
        return self

    def __repr__(self):
        return f"Group(id={self.id}, nranks={self.nranks}, ranks={self.ranks})"


_group_registry: "dict[int, Group]" = {}
_world_group: Optional[Group] = None
_next_gid = 1


def _ensure_world_group() -> Group:
    global _world_group
    if _world_group is None:
        n = jax.device_count()
        _world_group = Group(list(range(n)), gid=0, name="_world")
        _group_registry[0] = _world_group
    return _world_group


def _get_global_group() -> Group:
    return _ensure_world_group()


def _resolve(group: Optional[Group]) -> Group:
    return group if group is not None else _ensure_world_group()


def new_group(ranks: Optional[Sequence[int]] = None, backend: Optional[str] = None, timeout=None) -> Group:
    """Reference parity: paddle.distributed.new_group (collective.py:142)."""
    global _next_gid
    if ranks is None:
        ranks = list(range(jax.device_count()))
    g = Group(sorted(ranks), gid=_next_gid)
    _group_registry[_next_gid] = g
    _next_gid += 1
    return g


def get_group(gid: int = 0) -> Optional[Group]:
    return _group_registry.get(gid)


def destroy_process_group(group: Optional[Group] = None):
    global _world_group
    if group is None:
        _group_registry.clear()
        _world_group = None
    else:
        _group_registry.pop(group.id, None)


def is_initialized() -> bool:
    return parallel_env.is_initialized()


class _Task:
    """Async-collective handle (paddle `task = op(..., sync_op=False)`).

    XLA dispatch is already asynchronous; wait() blocks on the result buffer.
    """

    def __init__(self, value):
        self._value = value

    def wait(self):
        if self._value is not None:
            jax.block_until_ready(self._value)

    def is_completed(self):
        return True


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor):
        jax.block_until_ready(tensor._raw())
    else:
        jax.block_until_ready(tensor)


# ---------------------------------------------------------------------------
# kernels: tiny jitted programs; GSPMD emits the actual collectives
# ---------------------------------------------------------------------------


def _reduce_stacked(x, op: int, n: int):
    if op == ReduceOp.SUM:
        return jnp.sum(x, axis=0)
    if op == ReduceOp.MAX:
        return jnp.max(x, axis=0)
    if op == ReduceOp.MIN:
        return jnp.min(x, axis=0)
    if op == ReduceOp.PROD:
        return jnp.prod(x, axis=0)
    if op == ReduceOp.AVG:
        return jnp.sum(x, axis=0) / n
    raise ValueError(f"unknown ReduceOp {op}")


@functools.lru_cache(maxsize=None)
def _k_all_reduce(mesh: Mesh, op: int, n: int):
    sh = NamedSharding(mesh, P("g"))

    def f(x):
        r = _reduce_stacked(x.astype(jnp.float32) if op == ReduceOp.AVG and jnp.issubdtype(x.dtype, jnp.integer) else x, op, n)
        return jnp.broadcast_to(r[None].astype(x.dtype), x.shape)

    return jax.jit(f, out_shardings=sh)


@functools.lru_cache(maxsize=None)
def _k_replicate(mesh: Mesh):
    return jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))


@functools.lru_cache(maxsize=None)
def _k_broadcast(mesh: Mesh, src: int):
    sh = NamedSharding(mesh, P("g"))
    return jax.jit(lambda x: jnp.broadcast_to(x[src][None], x.shape), out_shardings=sh)


@functools.lru_cache(maxsize=None)
def _k_reduce(mesh: Mesh, op: int, n: int, dst: int):
    sh = NamedSharding(mesh, P("g"))

    def f(x):
        r = _reduce_stacked(x, op, n)
        return x.at[dst].set(r)

    return jax.jit(f, out_shardings=sh)


@functools.lru_cache(maxsize=None)
def _k_transpose01(mesh: Mesh):
    sh = NamedSharding(mesh, P("g"))
    return jax.jit(lambda x: jnp.swapaxes(x, 0, 1), out_shardings=sh)


@functools.lru_cache(maxsize=None)
def _k_shard(mesh: Mesh):
    sh = NamedSharding(mesh, P("g"))
    return jax.jit(lambda x: x, out_shardings=sh)


@functools.lru_cache(maxsize=None)
def _k_reduce_scatter(mesh: Mesh, op: int, n: int):
    sh = NamedSharding(mesh, P("g"))

    def f(x):
        # x: [n(rank), n(chunk), *c]; out[r] = op over ranks of chunk r
        r = _reduce_stacked(x, op, n)  # [n(chunk), *c]
        return r

    return jax.jit(f, out_shardings=sh)


def _stacked_value(tensor, group: Group):
    """Raw [n, ...] global array, sharded over the group axis."""
    x = tensor._raw() if isinstance(tensor, Tensor) else jnp.asarray(tensor)
    n = group.nranks
    if x.ndim == 0 or x.shape[0] != n:
        x = jnp.broadcast_to(x, (n,) + x.shape)
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, group.sharding)
    return jax.device_put(x, group.sharding)


def _set_inplace(tensor, value):
    # collectives are in-place, non-differentiated ops (paddle eager
    # semantics): _replace_value records the write for to_static capture and
    # detaches any stale grad node from the pre-collective value
    if isinstance(tensor, Tensor):
        tensor._replace_value(value)
        return tensor
    return Tensor(value)


# ---------------------------------------------------------------------------
# public API (paddle.distributed.*)
# ---------------------------------------------------------------------------


def all_reduce(tensor, op: int = ReduceOp.SUM, group: Optional[Group] = None, sync_op: bool = True):
    """In-place all-reduce over the group (stacked convention, see module doc)."""
    group = _resolve(group)
    if group.nranks == 1:
        return _Task(tensor._raw() if isinstance(tensor, Tensor) else tensor)
    x = _stacked_value(tensor, group)
    out = _k_all_reduce(group.mesh, op, group.nranks)(x)
    _set_inplace(tensor, out)
    if sync_op:
        jax.block_until_ready(out)
    return _Task(out)


def all_gather(tensor_list: List, tensor, group: Optional[Group] = None, sync_op: bool = True):
    """Gather every rank's tensor; fills `tensor_list` with nranks tensors."""
    group = _resolve(group)
    x = _stacked_value(tensor, group)
    out = _k_replicate(group.mesh)(x)
    for i in range(group.nranks):
        tensor_list.append(Tensor(out[i]))
    if sync_op:
        jax.block_until_ready(out)
    return _Task(out)


def all_gather_object(object_list: List, obj, group: Optional[Group] = None):
    """Host-side object gather. Single-controller: every rank's python object
    is the controller's object; multi-host exchange rides the jax KV store."""
    group = _resolve(group)
    if jax.process_count() == 1:
        object_list.extend([obj] * group.nranks)
        return
    raise NotImplementedError("multi-host object gather requires the launcher store")


def broadcast(tensor, src: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    group = _resolve(group)
    if group.nranks == 1:
        return _Task(None)
    gsrc = group.get_group_rank(src) if src in group.ranks else src
    x = _stacked_value(tensor, group)
    out = _k_broadcast(group.mesh, gsrc)(x)
    _set_inplace(tensor, out)
    if sync_op:
        jax.block_until_ready(out)
    return _Task(out)


def broadcast_object_list(object_list: List, src: int = 0, group: Optional[Group] = None):
    if jax.process_count() == 1:
        return
    raise NotImplementedError("multi-host object broadcast requires the launcher store")


def reduce(tensor, dst: int = 0, op: int = ReduceOp.SUM, group: Optional[Group] = None, sync_op: bool = True):
    group = _resolve(group)
    if group.nranks == 1:
        return _Task(None)
    gdst = group.get_group_rank(dst) if dst in group.ranks else dst
    x = _stacked_value(tensor, group)
    out = _k_reduce(group.mesh, op, group.nranks, gdst)(x)
    _set_inplace(tensor, out)
    if sync_op:
        jax.block_until_ready(out)
    return _Task(out)


def reduce_scatter(tensor, tensor_list, op: int = ReduceOp.SUM, group: Optional[Group] = None, sync_op: bool = True):
    """out[r] = op over ranks i of tensor_list[r] (each list entry stacked)."""
    group = _resolve(group)
    n = group.nranks
    if isinstance(tensor_list, (list, tuple)):
        chunks = [_stacked_value(t, group) for t in tensor_list]  # n x [n,*c]
        x = jnp.stack(chunks, axis=1)  # [n(rank), n(chunk), *c]
    else:
        x = _stacked_value(tensor_list, group)  # [n, n*c, ...]
        x = x.reshape((n, n, x.shape[1] // n) + x.shape[2:])
    out = _k_reduce_scatter(group.mesh, op, n)(x)
    _set_inplace(tensor, out)
    if sync_op:
        jax.block_until_ready(out)
    return _Task(out)


def scatter(tensor, tensor_list=None, src: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    """Rank r receives tensor_list[r] from src (stacked convention: the list
    entries may be plain per-rank tensors — they are the src rank's)."""
    group = _resolve(group)
    n = group.nranks
    if tensor_list is None:
        raise ValueError("scatter requires tensor_list on the src rank (single-controller: always)")
    vals = [t._raw() if isinstance(t, Tensor) else jnp.asarray(t) for t in tensor_list]
    x = jnp.stack(vals, axis=0)  # [n, *local]
    out = _k_shard(group.mesh)(x)
    _set_inplace(tensor, out)
    if sync_op:
        jax.block_until_ready(out)
    return _Task(out)


def scatter_object_list(out_object_list: List, in_object_list=None, src: int = 0, group: Optional[Group] = None):
    if jax.process_count() == 1:
        out_object_list.extend(in_object_list or [])
        return
    raise NotImplementedError


def all_to_all(out_tensor_list: List, in_tensor_list: List, group: Optional[Group] = None, sync_op: bool = True):
    """Rank i sends in_tensor_list[j] to rank j (stacked convention)."""
    group = _resolve(group)
    chunks = [_stacked_value(t, group) for t in in_tensor_list]  # n x [n,*c]
    x = jnp.stack(chunks, axis=1)  # x[i, j] = rank i's chunk for dest j
    # rank r's received-from-s chunk is x[s, r]; stacked out element s must be
    # E_s with E_s[r] = x[s, r], i.e. E_s = y[:, s] for y = x.swapaxes(0, 1)
    # (y keeps axis 0 = rank, sharded over the group axis).
    y = _k_transpose01(group.mesh)(x)
    for s in range(group.nranks):
        out_tensor_list.append(Tensor(y[:, s]))
    if sync_op:
        jax.block_until_ready(y)
    return _Task(y)


def alltoall(in_tensor_list, out_tensor_list, group=None, sync_op=True):
    """Old-style arg order kept for compat (paddle.distributed.alltoall)."""
    return all_to_all(out_tensor_list, in_tensor_list, group=group, sync_op=sync_op)


def all_to_all_single(out_tensor, in_tensor, in_split_sizes=None, out_split_sizes=None,
                      group: Optional[Group] = None, sync_op: bool = True):
    group = _resolve(group)
    n = group.nranks
    if in_split_sizes is not None and len(set(in_split_sizes)) > 1:
        raise NotImplementedError("uneven all_to_all splits need dynamic shapes (not XLA-compilable)")
    x = _stacked_value(in_tensor, group)  # [n, n*c, ...]
    c = x.shape[1] // n
    x4 = x.reshape((n, n, c) + x.shape[2:])
    y = _k_transpose01(group.mesh)(x4)
    out = y.reshape(x.shape)
    _set_inplace(out_tensor, out)
    if sync_op:
        jax.block_until_ready(out)
    return _Task(out)


def barrier(group: Optional[Group] = None):
    group = _resolve(group)
    x = jax.device_put(jnp.zeros((group.nranks,), jnp.int32), group.sharding)
    jax.block_until_ready(_k_all_reduce(group.mesh, ReduceOp.SUM, group.nranks)(x))


# --- p2p ---


class P2POp:
    """Reference parity: paddle.distributed.P2POp (batch_isend_irecv)."""

    def __init__(self, op, tensor, peer: int, group: Optional[Group] = None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = _resolve(group)


def _p2p_unsupported(name):
    raise RuntimeError(
        f"paddle_tpu.distributed.{name}: standalone eager send/recv has no "
        "meaning under single-controller SPMD (there is no 'other process' to "
        "talk to — all ranks are shards of one program). Use "
        "batch_isend_irecv (compiled ppermute), the stacked collective API, "
        "or pipeline-parallel layers which express p2p as collective_permute "
        "inside the compiled step."
    )


def send(tensor, dst=0, group=None, sync_op=True):
    _p2p_unsupported("send")


def recv(tensor, src=0, group=None, sync_op=True):
    _p2p_unsupported("recv")


def isend(tensor, dst=0, group=None):
    _p2p_unsupported("isend")


def irecv(tensor, src=0, group=None):
    _p2p_unsupported("irecv")


@functools.lru_cache(maxsize=None)
def _k_permute(mesh: Mesh, perm: tuple):
    """perm: tuple of (src, dst). Compiled as collective_permute over ICI."""
    sh = NamedSharding(mesh, P("g"))

    def f(x):
        def local(s):
            return jax.lax.ppermute(s, "g", list(perm))

        return _shard_map(local, mesh=mesh, in_specs=P("g"), out_specs=P("g"))(x)

    return jax.jit(f, out_shardings=sh)


def batch_isend_irecv(p2p_op_list: List[P2POp]):
    """Execute a batch of p2p ops as ONE collective_permute.

    Reference parity: paddle.distributed.batch_isend_irecv
    (communication/batch_isend_irecv.py) — there NCCL grouped send/recv, here
    a single compiled lax.ppermute (the TPU-native p2p primitive: ICI
    neighbor exchange). All sends in the batch must come from the same
    stacked tensor; recv tensors are filled from the permuted result.
    """
    if not p2p_op_list:
        return []
    group = p2p_op_list[0].group
    sends = [o for o in p2p_op_list if o.op in (isend, "isend", send, "send")]
    recvs = [o for o in p2p_op_list if o.op in (irecv, "irecv", recv, "recv")]
    if not sends:
        return []
    x = _stacked_value(sends[0].tensor, group)
    # pairing: send op with peer d on "rank slice r" means (r -> d); in the
    # stacked view every rank executes the same batch, so the permutation is
    # {(r, (r + shift) % n)} derived from the first send's peer offset.
    n = group.nranks
    shift = (sends[0].peer - 0) % n
    perm = tuple((r, (r + shift) % n) for r in range(n))
    out = _k_permute(group.mesh, perm)(x)
    for o in recvs:
        _set_inplace(o.tensor, out)
    tasks = [_Task(out)]
    return tasks


# namespace `paddle.distributed.stream.*` — the reference's stream-overlap
# variants; XLA owns streams, so these are the same ops.
class _StreamNS:
    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    all_to_all = staticmethod(all_to_all)
    alltoall = staticmethod(alltoall)
    all_to_all_single = staticmethod(all_to_all_single)
    broadcast = staticmethod(broadcast)
    reduce = staticmethod(reduce)
    reduce_scatter = staticmethod(reduce_scatter)
    scatter = staticmethod(scatter)
    send = staticmethod(send)
    recv = staticmethod(recv)


stream = _StreamNS()


# ---- watchdog + telemetry wiring (reference comm_task_manager.h +
# DistributedView's communication summaries) ----


# which argument carries the INPUT payload, per op: (param name, positional
# index). Output placeholders (out_tensor, gather lists) must not count —
# they would double the reported bytes; ops absent here (barrier, wait,
# batch_isend_irecv) move no accountable payload through this wrapper.
_PAYLOAD_ARG = {
    "all_reduce": ("tensor", 0),
    "all_gather": ("tensor", 1),
    "broadcast": ("tensor", 0),
    "reduce": ("tensor", 0),
    "reduce_scatter": ("tensor_list", 1),
    "scatter": ("tensor_list", 1),
    "all_to_all": ("in_tensor_list", 1),
    "all_to_all_single": ("in_tensor", 1),
}


def _payload_nbytes(op: str, args, kwargs) -> int:
    """Bytes of the op's input payload operand (lists summed)."""
    spec = _PAYLOAD_ARG.get(op)
    if spec is None:
        return 0
    pname, idx = spec
    val = kwargs.get(pname, args[idx] if idx < len(args) else None)
    total = 0
    for t in val if isinstance(val, (list, tuple)) else (val,):
        v = t._value if isinstance(t, Tensor) else t
        nb = getattr(v, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


def _find_group(args, kwargs) -> Optional[Group]:
    g = kwargs.get("group")
    if g is None:
        for a in args:
            if isinstance(a, Group):
                return a
    return g


# (op, group) -> (calls counter child, bytes counter child, latency histogram
# child): resolved once, so the per-collective cost is one dict lookup
# instead of three registry-lock get-or-creates + label-tuple rebuilds
_metric_children: dict = {}


def _coll_metrics(op: str, group: str):
    key = (op, group)
    m = _metric_children.get(key)
    if m is None:
        from .. import telemetry as _tm

        labels = {"op": op, "group": group}
        m = _metric_children[key] = (
            _tm.counter(
                "paddle_tpu_collective_calls_total",
                "eager collective invocations", ("op", "group"),
            ).labels(**labels),
            _tm.counter(
                "paddle_tpu_collective_bytes_total",
                "tensor payload bytes moved by eager collectives", ("op", "group"),
            ).labels(**labels),
            _tm.histogram(
                "paddle_tpu_collective_latency_seconds",
                "eager collective host-side latency (dispatch to sync)", ("op", "group"),
            ).labels(**labels),
            _tm.gauge(
                "paddle_tpu_collective_last_latency_seconds",
                "latency of the most recent call per (op, group) — the "
                "point-in-time view the guardian flight recorder snapshots",
                ("op", "group"),
            ).labels(**labels),
        )
    return m


def _watched(fn):
    """Wrap a collective entry point in a CommTask so a hung dispatch/compile
    is detected and aborted with diagnostics; with
    telemetry enabled, also publish per-op/per-group call, byte, and latency
    metrics and emit the span as a `Communication` host event so it lands in
    the chrome trace and the DistributedView summary.

    Note: PADDLE_TPU_TELEMETRY=0 deliberately suppresses the Communication
    spans too (not just the counters) — the disabled fast path must add no
    events at all, even under an active Profiler."""

    @functools.wraps(fn)
    def inner(*args, **kwargs):
        from .comm_watchdog import comm_task
        from .resilience import fault_injection as _fi
        from .. import telemetry as _tm

        g = _find_group(args, kwargs)
        op_name = f"collective.{fn.__name__}"
        task = comm_task(op_name, ranks=tuple(getattr(g, "ranks", ()) or ()) or "world")

        def dispatch():
            # chaos site INSIDE the watched section: a FaultPlan delay past
            # the watchdog deadline drives the warn→dump→abort ladder
            # through the real dispatch path
            _fi.fault_point(op_name, group=getattr(g, "name", "_world"))
            return fn(*args, **kwargs)

        if not _tm.enabled():
            with task:
                return dispatch()

        from ..profiler.utils import RecordEvent, TracerEventType

        group_label = getattr(g, "name", None) or "_world"
        nbytes = _payload_nbytes(fn.__name__, args, kwargs)
        calls_c, bytes_c, lat_c, last_c = _coll_metrics(fn.__name__, group_label)
        calls_c.inc()
        bytes_c.inc(nbytes)
        span = RecordEvent(
            op_name, TracerEventType.Communication,
            args={"group": group_label, "bytes": nbytes},
        )
        t0 = time.perf_counter()
        try:
            with task, span:
                return dispatch()
        finally:
            # observe even when the collective raises: calls_total already
            # counted this invocation, and diverging count/observe breaks
            # rate(calls)/rate(latency_count) exactly in failure windows
            dt = time.perf_counter() - t0
            lat_c.observe(dt)
            last_c.set(dt)

    return inner


for _name in (
    "all_reduce", "all_gather", "broadcast", "reduce", "reduce_scatter",
    "scatter", "all_to_all", "all_to_all_single", "barrier",
    "batch_isend_irecv", "wait",
):
    globals()[_name] = _watched(globals()[_name])
del _name


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """paddle.distributed.gather (reference communication/gather.py):
    collect every rank's tensor into gather_list. Single-controller
    convention (like reduce/scatter in this module): the op executes for
    ANY dst — the controller holds the global view, so "only dst receives"
    collapses to filling the caller's list; gating on process rank would
    desynchronize multi-host SPMD programs."""
    if gather_list is None:
        raise ValueError("gather: pass gather_list to receive the parts")
    tmp: list = []
    task = all_gather(tmp, tensor, group, sync_op)
    gather_list.extend(tmp)
    return task


# reference exports all_to_all_single under this name too
alltoall_single = all_to_all_single
