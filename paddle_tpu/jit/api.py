"""Program capture: to_static.

Reference parity: python/paddle/jit/api.py:135 (to_static) +
dy2static/pir_partial_program.py (run captured program as one fused op) +
the SOT guard-based retrace policy (python/paddle/jit/sot/).

TPU-native design: instead of bytecode translation building a PIR program,
capture = (1) one eager "recording" run that discovers the program state
(every framework Tensor read or mutated — params, buffers, optimizer
accumulators, LR), then (2) jax.jit of a functionalized replay: state in ->
(outputs, state out). The whole train step — forward, tape backward, optimizer
update — traces into ONE XLA program (CINN's role is played by XLA). Guards:
input shapes/dtypes + layer train/eval epoch; any change retraces.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
from jax import numpy as jnp, tree_util

from ..core import state as core_state
from ..core.tensor import Tensor
from ..profiler.utils import RecordEvent
from ..framework import random as random_mod


class _Recorder:
    """Active during the recording run: collects framework-state tensors."""

    def __init__(self, exclude_ids):
        self.reads: "dict[int, Tensor]" = {}
        self.writes: "dict[int, Tensor]" = {}
        self.grad_writes: "dict[int, Tensor]" = {}
        self.created: set = set()
        self.exclude = exclude_ids

    def on_create(self, t):
        self.created.add(id(t))

    def on_read(self, t):
        # only persistent framework state counts: not the call's inputs, not
        # temporaries created inside the recorded run
        if id(t) in self.exclude or id(t) in self.created:
            return
        if not isinstance(t._value, jax.core.Tracer):
            self.reads.setdefault(id(t), t)

    def on_write(self, t):
        if id(t) in self.exclude or id(t) in self.created:
            return
        # fires pre-mutation: snapshot the original value so trace-time side
        # effects on not-yet-known state can be undone
        self.writes.setdefault(id(t), (t, t._value))
        self.reads.setdefault(id(t), t)

    def on_grad_write(self, t):
        if id(t) in self.created:
            return
        # pre-write: snapshot original .grad for undo
        self.grad_writes.setdefault(id(t), (t, t.grad))


def _tensor_flatten(obj):
    """Flatten args pytree with Tensor leaves -> (raw leaves, rebuild)."""
    leaves, treedef = tree_util.tree_flatten(obj, is_leaf=lambda x: isinstance(x, Tensor))
    tensor_idx = [i for i, l in enumerate(leaves) if isinstance(l, Tensor)]
    raw = [leaves[i]._value for i in tensor_idx]
    sg = [leaves[i].stop_gradient for i in tensor_idx]

    def rebuild(new_raw):
        out = list(leaves)
        for i, v, s in zip(tensor_idx, new_raw, sg):
            t = Tensor(v)
            t.stop_gradient = s
            out[i] = t
        return tree_util.tree_unflatten(treedef, out)

    return raw, tensor_idx, leaves, treedef, rebuild


_CONCRETIZATION_ERRORS = (
    jax.errors.ConcretizationTypeError,       # incl. TracerBoolConversionError
    jax.errors.TracerArrayConversionError,    # sibling of, not child of, the above
    jax.errors.TracerIntegerConversionError,
    jax.errors.NonConcreteBooleanIndexError,
)


_TO_STATIC_ENABLED = [True]  # paddle.jit.enable_to_static global switch


class StaticFunction:
    """The compiled-callable wrapper (analog of dy2static StaticFunction)."""

    def __init__(self, fn: Callable, build_strategy=None, full_graph=True):
        self._fn = fn
        self._cache: dict = {}
        self._calls = 0  # numbers the `to_static.call` spans, from 1
        self._warned_fallback = False
        functools.update_wrapper(self, fn, updated=[])

    # guard key: arg structure + shapes/dtypes + global layer-mode epoch + grad mode
    def _guard_key(self, args, kwargs):
        def leaf_key(x):
            if isinstance(x, Tensor):
                return ("T", tuple(x._value.shape), str(x._value.dtype), x.stop_gradient)
            if isinstance(x, (int, float, bool, str, bytes, type(None))):
                return ("C", x)
            return ("O", type(x).__name__)

        leaves, treedef = tree_util.tree_flatten((args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        from ..nn.layer import Layer

        return (
            tuple(leaf_key(l) for l in leaves),
            str(treedef),
            _mode_epoch[0],
            core_state.is_grad_enabled(),
        )

    def __call__(self, *args, **kwargs):
        if not _TO_STATIC_ENABLED[0]:
            return self._fn(*args, **kwargs)
        self._calls += 1
        fname = getattr(self._fn, "__name__", "<fn>")
        # in the profiler's trace a StepTraceAnnotation: one per train step
        with RecordEvent("to_static.call", args={"fn": fname, "step": self._calls},
                         step_num=self._calls):
            with RecordEvent("to_static.guard"):
                key = self._guard_key(args, kwargs)
                entry = self._cache.get(key)
                from .. import telemetry as _tm

                if _tm.enabled():
                    _tm.counter(
                        "paddle_tpu_jit_cache_total",
                        "to_static guard-cache lookups", ("function", "result"),
                    ).labels(
                        function=fname,
                        result="hit" if entry is not None else "miss",
                    ).inc()
            if entry is None:
                with RecordEvent("to_static.record"):
                    entry = self._trace(args, kwargs, key)
                if entry is None:  # recording run already produced the result
                    return self._last_record_output
            return self._run_compiled(entry, args, kwargs)

    # ---- phase 1: eager recording run ----
    def _trace(self, args, kwargs, key):
        import time

        from .. import telemetry as _tm

        t0 = time.perf_counter()
        arg_leaves = [l for l in tree_util.tree_leaves((args, kwargs), is_leaf=lambda x: isinstance(x, Tensor)) if isinstance(l, Tensor)]
        rec = _Recorder(exclude_ids={id(t) for t in arg_leaves})
        prev = core_state.set_recorder(rec)
        try:
            out = self._fn(*args, **kwargs)
        finally:
            core_state.set_recorder(prev)
            if _tm.enabled():
                fn_label = getattr(self._fn, "__name__", "<fn>")
                _tm.counter(
                    "paddle_tpu_jit_trace_total",
                    "to_static recording-run traces", ("function",),
                ).labels(function=fn_label).inc()
                _tm.histogram(
                    "paddle_tpu_jit_trace_seconds",
                    "wall time of the to_static eager recording run", ("function",),
                ).labels(function=fn_label).observe(time.perf_counter() - t0)

        state_tensors = list(rec.reads.values())
        grad_tensors = [t for t, _ in rec.grad_writes.values()]
        entry = _CompiledEntry(self._fn, state_tensors, grad_tensors)
        self._cache[key] = entry
        self._last_record_output = out
        return None  # signal: output already computed by the recording run

    def _run_compiled(self, entry, args, kwargs):
        if entry.fallback_eager:
            return self._fn(*args, **kwargs)
        try:
            return entry.run(args, kwargs)
        except _CONCRETIZATION_ERRORS as e:
            # the SOT graph-break contract (reference python/paddle/jit/sot/):
            # value-dependent Python control flow that cannot be captured
            # falls back to eager for this function, loudly, once
            entry.fallback_eager = True
            if not self._warned_fallback:
                self._warned_fallback = True
                import warnings

                warnings.warn(
                    f"paddle.jit.to_static: {self._fn.__name__} "
                    f"({self._source_site(e)}) uses value-dependent Python "
                    "control flow that cannot be captured into one program; "
                    "falling back to EAGER execution for this function. Use "
                    "paddle.jit.cond / lax-style control flow to keep it "
                    f"compiled. ({type(e).__name__})",
                    stacklevel=3,
                )
            return self._fn(*args, **kwargs)

    def _source_site(self, exc):
        """file:line inside the user's function where tracing broke."""
        import inspect
        import traceback

        try:
            fn_file = inspect.getsourcefile(self._fn)
            for fr in reversed(traceback.extract_tb(exc.__traceback__)):
                if fr.filename == fn_file:
                    return f"{fr.filename}:{fr.lineno}"
            return fn_file or "<unknown>"
        except Exception:
            return "<unknown>"

    @property
    def code(self):
        import inspect

        try:
            return inspect.getsource(self._fn)
        except OSError:
            return "<source unavailable>"

    def concrete_program(self):
        return self._cache


class _CompiledEntry:
    def __init__(self, fn, state_tensors, grad_tensors):
        self.fn = fn
        self.state = state_tensors
        self.grad_tensors = grad_tensors
        self.jitted = None
        self.out_rebuild = None
        self.donated = False
        self.fallback_eager = False

    def _grad_inputs(self):
        """Incoming .grad values (accumulation pattern): mask + present values."""
        vals = [t.grad._value if t.grad is not None else None for t in self.grad_tensors]
        mask = tuple(v is not None for v in vals)
        return mask, [v for v in vals if v is not None]

    def run(self, args, kwargs):
        with RecordEvent("to_static.gather"):
            raw_args, t_idx, leaves, treedef, _ = _tensor_flatten((args, kwargs))
        # the key's split is the call's first work for the device (two small
        # programs): where a full queue holds the host back, so it counts
        # with the dispatch and not with the Python around it
        with RecordEvent("to_static.dispatch"):
            rng = random_mod.next_key()

        if self.jitted is not None and self._grad_inputs()[0] != self.grad_in_mask:
            self.jitted = None  # grad presence changed -> rebuild

        if self.jitted is None:
            with RecordEvent("to_static.compile", args={"outcome": "compile"}):
                self._compile(args, kwargs, treedef, t_idx, leaves, raw_args, rng)

        with RecordEvent("to_static.gather"):
            state_vals = [t._value for t in self.state]
            grad_vals = self._grad_inputs()[1]
        with RecordEvent("to_static.dispatch"):
            outs, new_state, new_grads = self.jitted(raw_args, state_vals, rng, grad_vals)
        with RecordEvent("to_static.writeback"):
            return self._write_back(outs, new_state, new_grads)

    def _write_back(self, outs, new_state, new_grads):
        # write back state. Donated runs must adopt EVERY entry's (aliased)
        # output buffer — the input arrays are dead after the call. Without
        # donation, touch only mutated entries so read-only state keeps its
        # eager autograd wiring (_replace_value clears _grad_node).
        for t, mask, v in zip(self.state, self.mut_mask, new_state):
            if mask or self.donated:
                t._replace_value(v)
                if mask and hasattr(t, "trainable"):
                    t.stop_gradient = not t.trainable
        for t, v in zip(self.grad_tensors, new_grads):
            t.grad = Tensor(v) if v is not None else None
        # compiled-step boundary: Optimizer.step's HBM probe never fires
        # inside the replay (the step is python-free), so sample here —
        # no-op when telemetry is off
        from ..profiler import perf_attribution as _pa

        _pa.sample_watermark(tag="to_static_step")
        from ..framework import flags as _flags

        if _flags._registry.get("FLAGS_check_nan_inf", False):
            # guardian hook: the per-op scan can't see inside a compiled
            # program (tracers), so the anomaly check runs over the CONCRETE
            # state the replay wrote back — one fused reduction, only when
            # the flag is on
            from ..framework import guardian as _guardian

            _guardian.check_compiled_state(
                [t for t, mask in zip(self.state, self.mut_mask) if mask],
                origin=f"to_static:{getattr(self.fn, '__name__', '<fn>')}",
            )
        return self._rebuild_out(outs)

    def _compile(self, args, kwargs, treedef, t_idx, leaves, raw_args, rng):
        """Discover the step's state, then compile its program."""
        # Fixpoint state discovery: any CONCRETE tensor read during tracing
        # is framework state the eager recording missed (e.g. optimizer
        # accumulators created lazily inside the recorded step) — it must
        # become a program input, not a baked constant. Re-trace until the
        # trace touches no concrete framework tensors.
        for _ in range(8):
            self._build(args, kwargs, treedef, t_idx, leaves)
            rec = _Recorder(exclude_ids=set())
            prev = core_state.set_recorder(rec)
            try:
                traced = self.jitted.trace(
                    raw_args, [t._value for t in self.state], rng, self._grad_inputs()[1]
                )
            except Exception:
                # failed mid-trace (e.g. concretization error): pure()'s
                # finally restored the KNOWN state; scrub any tensor
                # discovered only this iteration that still carries a
                # tracer, so the eager fallback starts from clean values
                for _tid, (t, orig) in rec.writes.items():
                    if isinstance(t._value, jax.core.Tracer):
                        t._value = orig
                        t._grad_node = None
                for _tid, (t, orig_g) in rec.grad_writes.items():
                    if t.grad is not None and isinstance(t.grad._value, jax.core.Tracer):
                        t.grad = orig_g
                raise
            finally:
                core_state.set_recorder(prev)
            known = {id(t) for t in self.state}
            # undo trace-time mutation of tensors pure()'s finally doesn't
            # cover (state discovered only this iteration)
            for tid, (t, orig) in rec.writes.items():
                if tid not in known and isinstance(t._value, jax.core.Tracer):
                    t._value = orig
                    t._grad_node = None
            known_grads = {id(g) for g in self.grad_tensors}
            for tid, (t, orig_g) in rec.grad_writes.items():
                if tid not in known_grads and t.grad is not None and isinstance(t.grad._value, jax.core.Tracer):
                    t.grad = orig_g
            missed = [t for t in rec.reads.values() if id(t) not in known]
            new_grad_ts = [
                t for t, _ in rec.grad_writes.values() if id(t) not in known_grads
            ]
            self.grad_tensors.extend(new_grad_ts)
            if not missed and not new_grad_ts:
                import time as _time

                if self.donated:
                    # donation safety over EVERYTHING donate_argnums
                    # covers — discovered state (argnum 1) AND incoming
                    # grads (argnum 3): two entries sharing one buffer
                    # would donate it twice — fail HERE naming the
                    # tensors, not inside XLA's anonymous
                    # duplicate-donation error
                    from ..static.analysis import (
                        verify_donated_state,
                        verify_enabled,
                    )

                    if verify_enabled():
                        donated = list(self.state)
                        labels = [f"state[{i}]" for i in range(len(donated))]
                        for j, t in enumerate(self.grad_tensors):
                            if t.grad is not None:
                                donated.append(t.grad)
                                name = getattr(t, "name", None) or f"#{j}"
                                labels.append(f"grad-of[{name}]")
                        try:
                            verify_donated_state(
                                donated,
                                origin=f"to_static:{getattr(self.fn, '__name__', '<fn>')}",
                                labels=labels,
                            )
                        except Exception:
                            # _build already installed the donating jit
                            # wrapper; leaving it set would let the NEXT
                            # call skip this check and hit XLA's
                            # anonymous duplicate-donation error
                            self.jitted = None
                            raise
                t0 = _time.perf_counter()
                fname = getattr(self.fn, "__name__", "<fn>")
                lowered = traced.lower()
                self.jitted = lowered.compile()
                dt = _time.perf_counter() - t0
                # attribution capture at the one place the whole train
                # step exists as a compiled XLA program: FLOPs, HBM
                # bytes, memory footprint, compile time (telemetry-gated
                # inside record_compiled; never raises)
                from .. import compile_cache as _cc
                from ..profiler import perf_attribution as _pa

                _pa.record_compiled(
                    "to_static",
                    fname,
                    lowered=lowered,
                    compiled=self.jitted,
                    compile_seconds=dt,
                    extra={"n_state": len(self.state)},
                )
                _cc.record(
                    "to_static", fname, "miss", seconds=dt,
                    signature=f"n_state={len(self.state)}",
                )
                return
            self.state.extend(missed)
        raise RuntimeError("to_static: state discovery did not converge")

    def _build(self, args, kwargs, treedef, t_idx, template_leaves):
        entry = self
        state = self.state
        grad_ts = self.grad_tensors
        fn = self.fn
        gen = random_mod.default_generator()
        grad_in_mask = self._grad_inputs()[0]
        self.grad_in_mask = grad_in_mask

        def pure(raw_args, state_vals, rng, grad_vals):
            # reconstruct args with tracer-backed Tensors
            new_leaves = list(template_leaves)
            for i, v in zip(t_idx, raw_args):
                t = Tensor(v)
                t.stop_gradient = template_leaves[i].stop_gradient
                new_leaves[i] = t
            a, kw = tree_util.tree_unflatten(treedef, new_leaves)

            originals = [t._value for t in state]
            orig_nodes = [(t._grad_node, t._out_index) for t in state]
            orig_grads = [t.grad for t in grad_ts]
            markers = list(state_vals)
            try:
                for t, v in zip(state, state_vals):
                    t._value = v
                    t._grad_node = None
                gi = iter(grad_vals)
                for t, present in zip(grad_ts, grad_in_mask):
                    t.grad = Tensor(next(gi)) if present else None
                with gen.trace_scope(rng):
                    out = fn(*a, **kw)
                out_raw, out_spec = _flatten_output(out)
                new_state = [t._value for t in state]
                mutated = [ns is not m for ns, m in zip(new_state, markers)]
                new_grads = [t.grad._value if t.grad is not None else None for t in grad_ts]
                entry.out_spec = out_spec
                entry.mut_mask = mutated
                return out_raw, new_state, new_grads
            finally:
                for t, v, (n, oi) in zip(state, originals, orig_nodes):
                    t._value = v
                    t._grad_node = n
                    t._out_index = oi
                for t, g in zip(grad_ts, orig_grads):
                    t.grad = g

        # Donate state + incoming grads: the write-back in run() adopts the
        # output buffers, so the input copies XLA would otherwise keep alive
        # (params + optimizer moments, ~3x param bytes for Adam) are saved —
        # both the copy bandwidth and the memory high-water mark.
        # FLAGS_to_static_donate=False restores copying semantics (needed if
        # user code holds detach()-style aliases of parameters or `p.grad`
        # array references across compiled steps).
        from ..framework import flags as _flags

        self.donated = bool(_flags.get_flag("FLAGS_to_static_donate"))
        # State THREADS through the step: entry i of new_state is written
        # back and fed to the next call as state_vals[i]. On a mesh, pin
        # each sharded entry's output to its input sharding — left to
        # GSPMD, an updated param can come back laid out differently and the
        # AOT-compiled step then rejects its own output on the next call.
        pinned = [
            sh if sh is not None and len(sh.device_set) > 1 else None
            for sh in (getattr(t._value, "sharding", None) for t in state)
        ]
        jit_kwargs = {}
        if any(sh is not None for sh in pinned):
            jit_kwargs["out_shardings"] = (None, pinned, None)
        self.jitted = jax.jit(
            pure, donate_argnums=(1, 3) if self.donated else (), **jit_kwargs
        )

    def _rebuild_out(self, out_raw):
        return _unflatten_output(out_raw, self.out_spec)


def _flatten_output(out):
    leaves, treedef = tree_util.tree_flatten(out, is_leaf=lambda x: isinstance(x, Tensor))
    raw = []
    spec = []
    for l in leaves:
        if isinstance(l, Tensor):
            raw.append(l._value)
            spec.append(("T", l.stop_gradient))
        else:
            raw.append(None)
            spec.append(("C", l))
    return raw, (treedef, spec)


def _unflatten_output(raw, out_spec):
    treedef, spec = out_spec
    leaves = []
    for v, (kind, meta) in zip(raw, spec):
        if kind == "T":
            t = Tensor(v)
            t.stop_gradient = meta
            leaves.append(t)
        else:
            leaves.append(meta)
    return tree_util.tree_unflatten(treedef, leaves)


# global train/eval mode epoch for guard keys (bumped by Layer.train/eval)
_mode_epoch = [0]


def _bump_mode_epoch():
    _mode_epoch[0] += 1


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, full_graph=True, **kwargs):
    """paddle.jit.to_static — decorator or call (api.py:135)."""
    from ..nn.layer import Layer

    def decorate(fn):
        if isinstance(fn, Layer):
            layer = fn
            orig_forward = layer.forward  # bind BEFORE replacement
            sf = StaticFunction(lambda *a, **kw: orig_forward(*a, **kw))
            layer.forward = sf
            return layer
        return StaticFunction(fn, build_strategy, full_graph)

    if function is not None:
        return decorate(function)
    return decorate


def functional_call(layer, params: dict, *args, training=None, **kwargs):
    """Run layer.forward with parameter/buffer VALUES substituted from
    `params` (name -> raw array or Tensor). The functional bridge for
    jax.jit/grad/pjit over framework Layers (the role of the reference's
    run_program_op parameter feeding, dy2static/partial_program.py).

    Values may be jax tracers — this is how entry()/dryrun paths stage
    framework models into pure XLA programs.
    """
    sd = layer.state_dict()
    unknown = set(params) - set(sd)
    if unknown:
        raise KeyError(
            f"functional_call: params keys not in {type(layer).__name__}.state_dict(): "
            f"{sorted(unknown)[:5]}{'...' if len(unknown) > 5 else ''} — a typo here "
            "would silently bake the layer's stored weight in as a constant"
        )
    originals = {}
    try:
        for name, t in sd.items():
            if name in params:
                v = params[name]
                originals[name] = (t, t._value, t._grad_node, t._out_index)
                t._value = v._value if isinstance(v, Tensor) else v
                t._grad_node = None
        prev_training = None
        if training is not None:
            prev_training = [l.training for l in layer.sublayers(include_self=True)]
            for l in layer.sublayers(include_self=True):
                l.training = training
        try:
            return layer(*args, **kwargs)
        finally:
            if prev_training is not None:
                for l, tr in zip(layer.sublayers(include_self=True), prev_training):
                    l.training = tr
    finally:
        for name, (t, v, n, oi) in originals.items():
            t._value = v
            t._grad_node = n
            t._out_index = oi


def state_values(layer) -> dict:
    """name -> raw jax array for every param/buffer (functional_call input)."""
    return {k: v._value for k, v in layer.state_dict().items()}


def capture_program(function, *example_args, feed_names=None):
    """Eager-convert `function` (a callable or Layer) into a recorded
    static Program with ZERO model-code changes: one eager run under
    program_guard with each example arg replaced by a static.data feed
    placeholder of the same shape/dtype. Returns
    (program, feed_names, fetch_list) ready for Executor.run — and for the
    static.passes pipeline, which rewrites exactly this recorded form
    (DCE, canonicalization, DRR fusion into the Pallas kernels).

    This is the op-level ProgramTranslator counterpart of `to_static`
    (which stages the same eager run straight into one jax.jit): to_static
    gives you a compiled step, capture_program gives you the inspectable,
    rewritable IR — `program.to_text()`, `verify()`, the pass pipeline.

    `example_args` must be Tensors (or array-likes); outputs that are
    Tensors recorded in the program become the fetch_list. `feed_names`
    overrides the default arg0..argN placeholder names."""
    from ..static import program as static_program

    names = list(feed_names) if feed_names is not None else [
        f"arg{i}" for i in range(len(example_args))
    ]
    if len(names) != len(example_args):
        raise ValueError(
            f"capture_program: {len(example_args)} example arg(s) but "
            f"{len(names)} feed name(s)"
        )
    main = static_program.Program()
    with static_program.program_guard(main, static_program.Program()):
        feeds = []
        for name, a in zip(names, example_args):
            raw = a._value if isinstance(a, Tensor) else jnp.asarray(a)
            feeds.append(
                static_program.data(name, list(raw.shape), str(raw.dtype))
            )
            # the placeholder carries the EXAMPLE values, not zeros: the
            # eager dry-run then computes real activations (value-dependent
            # capture paths behave as they would on this input), and the
            # harvested shape/dtype metadata is identical either way (jax
            # arrays are immutable, so sharing the caller's buffer is safe)
            feeds[-1]._value = raw
        out = function(*feeds)
    leaves, _ = tree_util.tree_flatten(
        out, is_leaf=lambda x: isinstance(x, Tensor)
    )
    fetch_list = [
        t for t in leaves
        if isinstance(t, Tensor) and id(t) in main._id2var
    ]
    return main, names, fetch_list


def not_to_static(fn):
    fn._paddle_not_to_static = True
    return fn


def ignore_module(modules):
    return None


# ---- lax control-flow re-exports for data-dependent control under capture ----

def cond(pred, true_fn, false_fn, *operands):
    """paddle.static.nn.cond analog over lax.cond for captured programs."""
    from ..core.apply import apply

    pred_t = pred if isinstance(pred, Tensor) else Tensor(jnp.asarray(pred))
    ts = [o for o in operands if isinstance(o, Tensor)]

    def f(p, *vals):
        return jax.lax.cond(p, lambda *v: _call_raw(true_fn, v), lambda *v: _call_raw(false_fn, v), *vals)

    return apply("cond", f, pred_t, *ts)


def _call_raw(fn, raw_vals):
    ts = [Tensor(v) for v in raw_vals]
    out = fn(*ts)
    if isinstance(out, Tensor):
        return out._value
    return tuple(o._value for o in out)
