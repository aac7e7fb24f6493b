"""Eager autograd engine: reverse-mode tape over jax.vjp closures.

Reference parity: paddle/fluid/eager/ — GradNodeBase/Edge
(grad_node_info.h:197,62), engine RunBackward (backward.cc:105 — queue-driven
reverse topological walk), GradTensorHolder accumulation, leaf accumulation
nodes (accumulation/).

TPU-native design: instead of per-op hand-written GradNode classes generated
from backward.yaml, every op records the jax.vjp pullback closure of its
(pure, jax-traceable) forward function. The pullback already holds the saved
residuals (the TensorWrapper analog) and is itself jax-traceable, so the same
engine runs eagerly on device or under jax.jit tracing for whole-program
capture.
"""
from __future__ import annotations

import itertools
import weakref
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import numpy as jnp

from . import state

float0 = jax.dtypes.float0

# ---------------------------------------------------------------------------
# backward-end hooks: observers (grad reducers) that must act once per
# run_backward AFTER every leaf has its merged grad — per-leaf hooks alone
# cannot see "this backward is over", which a bucket with a never-used param
# needs in order to dispatch its stragglers (reference EagerReducer marks
# unused params ready at the end of backward).
# ---------------------------------------------------------------------------

_backward_end_hooks: dict = {}
_backward_end_ids = itertools.count()
_grad_collection_depth = 0


def grad_collection_active() -> bool:
    """True while a walk collects into a custom accumulate_fn
    (paddle.autograd.grad / double-backward inner walks) instead of
    accumulating training grads into .grad — observers that treat every
    backward as a training cycle (grad reducers) must sit those out."""
    return _grad_collection_depth > 0


class _BackwardEndHookHandle:
    __slots__ = ("_key",)

    def __init__(self, key):
        self._key = key

    def remove(self):
        _backward_end_hooks.pop(self._key, None)


def register_backward_end_hook(fn) -> _BackwardEndHookHandle:
    """Call fn(completed: bool) at the end of every run_backward —
    completed=False means the walk raised and leaf grads may be partial,
    so observers must drop (not dispatch) their per-cycle state. A bound
    method is held weakly (its owner stays collectable); any other
    callable is held strongly until the handle is removed."""
    entry = weakref.WeakMethod(fn) if hasattr(fn, "__self__") else fn
    key = next(_backward_end_ids)
    _backward_end_hooks[key] = entry
    return _BackwardEndHookHandle(key)


def _fire_backward_end_hooks(completed: bool):
    for key, entry in list(_backward_end_hooks.items()):
        fn = entry() if isinstance(entry, weakref.WeakMethod) else entry
        if fn is None:
            _backward_end_hooks.pop(key, None)
        else:
            fn(completed)


class Edge:
    """Where one cotangent of a node's input flows.

    Analog of egr::Edge (paddle/fluid/eager/grad_node_info.h:62): either an
    interior edge (parent node, output slot) or a leaf edge (accumulate into
    Tensor.grad).
    """

    __slots__ = ("node", "slot", "leaf")

    def __init__(self, node=None, slot: int = 0, leaf=None):
        self.node = node
        self.slot = slot
        self.leaf = leaf  # Tensor (leaf) or None

    def is_leaf(self):
        return self.leaf is not None


class GradNode:
    """Analog of egr::GradNodeBase (grad_node_info.h:197).

    Holds the vjp pullback (residuals included), the output metadata (to build
    zero cotangents for unused outputs), and one Edge per differentiable input.
    """

    __slots__ = (
        "name",
        "vjp_fn",
        "edges",
        "out_avals",
        "single_output",
        "released",
        "op_pure",
        "op_primals",
        "scope",
    )

    def __init__(self, name: str, vjp_fn: Callable, edges: List[Edge], out_avals, single_output: bool,
                 op_pure=None, op_primals=None):
        self.name = name
        self.vjp_fn = vjp_fn
        self.edges = edges
        self.out_avals = out_avals  # list of jax.ShapeDtypeStruct
        self.single_output = single_output
        self.released = False
        # higher-order support: the op's pure forward (diff-args only -> out)
        # plus its primal input Tensors. The taped backward (autograd.grad
        # create_graph=True) re-applies jax.vjp over these THROUGH apply(),
        # so the backward computation itself lands on the tape with edges to
        # the primals — residual-as-constant vjp closures can't express
        # d(backward)/d(primal), this can. Recompute-based (jax-idiomatic).
        self.op_pure = op_pure
        self.op_primals = op_primals
        # the named scopes the forward ran under: the pullback's ops carry
        # the same path (state.named_scope)
        self.scope = state.scope_path()

    def __repr__(self):
        return f"GradNode({self.name}, n_in={len(self.edges)}, n_out={len(self.out_avals)})"


def _zeros_cotangent(aval):
    if jnp.issubdtype(aval.dtype, jnp.inexact):
        return jnp.zeros(aval.shape, aval.dtype)
    return np.zeros(aval.shape, dtype=float0)


def _is_meaningful(cot) -> bool:
    if cot is None:
        return False
    dt = getattr(cot, "dtype", None)
    return dt != float0


def _accumulate(a, b):
    if a is None:
        return b
    return a + b


def run_backward(
    tensors: Sequence,
    grad_tensors: Optional[Sequence] = None,
    retain_graph: bool = False,
    accumulate_fn: Optional[Callable] = None,
    watches: Optional[dict] = None,
    watch_fn: Optional[Callable] = None,
):
    """The engine. Analog of egr::RunBackward (paddle/fluid/eager/backward.cc:105).

    tensors: output Tensors to seed.
    grad_tensors: optional cotangents (raw arrays or Tensors), ones by default.
    accumulate_fn(leaf_tensor, raw_cotangent): override leaf accumulation
      (used by autograd.grad to collect into a dict instead of .grad).
    watches: {(node, slot): key} interior positions whose accumulated cotangent
      should be reported via watch_fn(key, raw_cotangent) — this is how
      paddle.grad supports non-leaf input tensors (general_grad.h analog).
    """
    # backward-end hooks fire on EVERY exit: completed=False on an aborted
    # walk (a leaf hook raising, backward-twice) so observers drop their
    # per-cycle state instead of leaking it into — or dispatching partial
    # grads during — the next backward. A grad-COLLECTION walk (custom
    # accumulate_fn: paddle.autograd.grad, double-backward inners) is not
    # a training cycle at all: no end hooks, and grad_collection_active()
    # is raised so per-leaf observers sit it out too.
    global _grad_collection_depth
    collection = accumulate_fn is not None
    if collection:
        _grad_collection_depth += 1
    try:
        _run_backward_walk(tensors, grad_tensors, retain_graph,
                           accumulate_fn, watches, watch_fn)
    except BaseException:
        if not collection:
            _fire_backward_end_hooks(False)
        raise
    finally:
        if collection:
            _grad_collection_depth -= 1
    if not collection:
        _fire_backward_end_hooks(True)


def _run_backward_walk(tensors, grad_tensors, retain_graph, accumulate_fn,
                       watches, watch_fn):
    from .tensor import Tensor  # cycle

    # --- seed holders ---
    holders: dict = {}  # node -> list of cotangents per output slot
    roots: list = []

    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    if len(grad_tensors) != len(tensors):
        raise ValueError("grad_tensors must match tensors in length")

    for t, g in zip(tensors, grad_tensors):
        node = t._grad_node
        if g is None:
            g_val = jnp.ones(t._value.shape, t._value.dtype)
        else:
            g_val = g._value if isinstance(g, Tensor) else jnp.asarray(g)
            if tuple(g_val.shape) != tuple(t._value.shape):
                raise ValueError(
                    f"grad tensor shape {g_val.shape} mismatches output shape {t._value.shape}"
                )
        if node is None:
            # output is itself a leaf
            if not t.stop_gradient:
                _leaf_accumulate(t, g_val, accumulate_fn)
            continue
        slots = holders.setdefault(node, [None] * len(node.out_avals))
        slots[t._out_index] = _accumulate(slots[t._out_index], g_val)
        roots.append(node)

    # --- dependency counting: how many pending consumer-edges feed each node ---
    # Leaf edges are counted too: a leaf consumed by several ops (tied
    # embedding, shared projection) receives one cotangent per edge, but its
    # hooks must observe the MERGED gradient exactly once per backward
    # (paddle's AccumulateGrad semantics) — per-edge hook fires would hand
    # observers (grad reducers, user hooks) partial gradients.
    indeg: dict = {}
    leaf_pending: dict = {}  # id(leaf) -> [tensor, edges_left, merged_cot]
    visited = set()
    stack = list(dict.fromkeys(roots))
    order_check = list(stack)
    while stack:
        node = stack.pop()
        if node in visited:
            continue
        visited.add(node)
        for e in node.edges:
            if e.node is not None:
                indeg[e.node] = indeg.get(e.node, 0) + 1
                if e.node not in visited:
                    stack.append(e.node)
            elif e.is_leaf():
                ent = leaf_pending.setdefault(id(e.leaf), [e.leaf, 0, None])
                ent[1] += 1

    ready = [n for n in dict.fromkeys(order_check) if indeg.get(n, 0) == 0]
    # nodes seeded but also consumed by other seeded nodes wait for their deps

    processed = set()
    while ready:
        node = ready.pop()
        if node in processed:
            continue
        processed.add(node)
        slots = holders.pop(node, None)
        if slots is None:
            slots = [None] * len(node.out_avals)
        if watches:
            for si, s in enumerate(slots):
                key = watches.get((node, si))
                if key is not None and s is not None:
                    watch_fn(key, s)
        cots = [
            s if s is not None else _zeros_cotangent(a)
            for s, a in zip(slots, node.out_avals)
        ]
        if node.released:
            raise RuntimeError(
                f"Trying to backward through {node.name} a second time; "
                "set retain_graph=True if you need to."
            )
        cot_struct = cots[0] if node.single_output else tuple(cots)
        if node.scope:
            with jax.named_scope(node.scope):
                in_cots = node.vjp_fn(cot_struct)
        else:
            in_cots = node.vjp_fn(cot_struct)
        if not retain_graph:
            node.vjp_fn = None
            # op_pure closes over the op's raw inputs and op_primals holds
            # the input Tensors — release them too or every node pins its
            # activation-sized buffers for the graph's lifetime
            node.op_pure = None
            node.op_primals = None
            node.released = True
        if not isinstance(in_cots, (tuple, list)):
            in_cots = (in_cots,)
        if len(in_cots) != len(node.edges):
            raise RuntimeError(
                f"vjp of {node.name} returned {len(in_cots)} cotangents for {len(node.edges)} edges"
            )
        for e, c in zip(node.edges, in_cots):
            if not _is_meaningful(c):
                c = None
            if e.is_leaf():
                ent = leaf_pending.get(id(e.leaf))
                if ent is None:  # pragma: no cover - leaf edge outside the walk
                    if c is not None and not e.leaf.stop_gradient:
                        _leaf_accumulate(e.leaf, c, accumulate_fn)
                    continue
                if c is not None and not e.leaf.stop_gradient:
                    ent[2] = _accumulate(ent[2], c)
                ent[1] -= 1
                if ent[1] == 0 and ent[2] is not None:
                    _leaf_accumulate(ent[0], ent[2], accumulate_fn)
            elif e.node is not None:
                if c is not None:
                    pslots = holders.setdefault(e.node, [None] * len(e.node.out_avals))
                    pslots[e.slot] = _accumulate(pslots[e.slot], c)
                indeg[e.node] -= 1
                if indeg[e.node] == 0:
                    ready.append(e.node)


def _leaf_accumulate(tensor, cot, accumulate_fn):
    for hook in tensor._backward_hooks:
        out = hook(_wrap_grad(tensor, cot))
        if out is not None:
            cot = out._value if hasattr(out, "_value") else jnp.asarray(out)
    if accumulate_fn is not None:
        accumulate_fn(tensor, cot)
        return
    from .tensor import Tensor

    state.record_grad_write(tensor)  # pre-write: capture original for undo
    if tensor.grad is None:
        tensor.grad = Tensor(cot, stop_gradient=True)
    else:
        tensor.grad = Tensor(tensor.grad._value + cot, stop_gradient=True)


def _wrap_grad(tensor, cot):
    from .tensor import Tensor

    return Tensor(cot, stop_gradient=True)
