"""Optimizer base + concrete optimizers.

Reference parity: python/paddle/optimizer/optimizer.py:104 (Optimizer:
accumulators, step/minimize, grad clip, weight decay, LR scheduler bridge)
with the per-op kernels (_C_ops.sgd_/adamw_...) re-expressed as pure jax
update functions applied via in-place value replacement — the mutation points
the to_static recorder captures, so a whole train step compiles to one XLA
program.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Iterable, List, Optional

import jax
from jax import numpy as jnp

from ..core import state as core_state
from ..core.state import no_grad
from ..core.tensor import Tensor
from ..framework import dtype as dtype_mod
from .lr import LRScheduler


def _mesh_placement(t):
    """The sharding of a tensor whose CONCRETE value is spread over more than
    one device, else None (single-device values, and tracers inside a
    compiled step — there the step's pinned output shardings hold state in
    place, jit/api.py)."""
    v = t._value
    if isinstance(v, jax.core.Tracer):
        return None
    sh = getattr(v, "sharding", None)
    return sh if sh is not None and len(sh.device_set) > 1 else None


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None, grad_clip=None, name=None):
        if parameters is None:
            raise ValueError("parameters must be provided (dygraph mode)")
        self._param_groups = self._build_param_groups(parameters)
        self._lr_scheduler = learning_rate if isinstance(learning_rate, LRScheduler) else None
        base_lr = learning_rate.last_lr if self._lr_scheduler else float(learning_rate)
        # LR lives on device so compiled steps treat it as data
        self._lr_tensor = Tensor(jnp.asarray(base_lr, jnp.float32))
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._accumulators: dict = defaultdict(dict)  # name -> {id(param): Tensor}
        self._accumulator_fills: dict = {}  # name -> creation fill value
        self._pending_state: dict = {}  # loaded state awaiting lazy accumulator creation
        self._step_count = Tensor(jnp.zeros((), jnp.int64))
        # fused flat accumulators: ids-tuple -> bucket dict (see _apply_fused)
        self._fused_buckets: dict = {}
        # FLAGS_fused_optimizer flat-bucket engine (fused_engine.py), created
        # lazily by optimizers that support it (Adam/AdamW)
        self._flat_engine = None
        # wrappers that need per-param accumulators (shard_optimizer, ZeRO
        # sharding) flip this off to force the per-param path
        self._fuse_allowed = True
        # ids of params seen placed on a mesh (spec_layout.place): they take
        # the per-param path too — remembered, because inside a compiled
        # step's trace the value is a tracer and cannot say where it lives
        self._mesh_placed: set = set()

    # ---- param groups ----
    def _build_param_groups(self, parameters):
        params = list(parameters)
        if params and isinstance(params[0], dict):
            groups = []
            for g in params:
                g = dict(g)
                g["params"] = list(g["params"])
                groups.append(g)
            return groups
        return [{"params": params}]

    def _all_params(self):
        for g in self._param_groups:
            for p in g["params"]:
                yield g, p

    # ---- lr ----
    def get_lr(self) -> float:
        if self._lr_scheduler is not None:
            return self._lr_scheduler.last_lr
        return float(self._lr_tensor.numpy())

    def set_lr(self, value: float):
        if self._lr_scheduler is not None:
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr_tensor._replace_value(jnp.asarray(float(value), jnp.float32))

    def set_lr_scheduler(self, scheduler):
        self._lr_scheduler = scheduler

    def _sync_lr(self):
        if self._lr_scheduler is not None:
            self._lr_tensor._replace_value(jnp.asarray(self._lr_scheduler.last_lr, jnp.float32))

    # ---- accumulators ----
    def _add_accumulator(self, name, param, fill=0.0, dtype=None, shape=None):
        key = id(param)
        if key not in self._accumulators[name]:
            self._accumulator_fills.setdefault(name, fill)
            pending = self._pending_state.pop((name, key), None)
            if pending is not None:
                self._accumulators[name][key] = Tensor(pending)
            else:
                shp = tuple(shape) if shape is not None else tuple(param._value.shape)
                d = dtype or (jnp.float32 if param._value.dtype == jnp.bfloat16 else param._value.dtype)
                acc = jnp.full(shp, fill, d)
                placement = _mesh_placement(param)
                if placement is not None and shp == tuple(param._value.shape):
                    # state of a mesh-placed param is born where the param
                    # lives, not on device 0
                    acc = jax.device_put(acc, placement)
                self._accumulators[name][key] = Tensor(acc)
        return self._accumulators[name][key]

    def _get_accumulator(self, name, param):
        return self._accumulators[name][id(param)]

    # ---- the step ----
    def _record_step(self, body):
        """Run one optimizer step `body` under telemetry: step counter +
        wall-time histogram per optimizer class, plus an Optimization span
        for the profiler. Subclasses overriding step() (LBFGS) route their
        body through this too so instrumentation stays uniform."""
        from .. import telemetry as _tm

        if not _tm.enabled():
            return body()
        import time

        from ..profiler.utils import RecordEvent, TracerEventType

        cls = type(self).__name__
        t0 = time.perf_counter()
        with RecordEvent(f"Optimizer.step#{cls}", TracerEventType.Optimization):
            out = body()
        _tm.counter(
            "paddle_tpu_optimizer_step_total", "optimizer steps", ("optimizer",)
        ).labels(optimizer=cls).inc()
        _tm.histogram(
            "paddle_tpu_optimizer_step_seconds",
            "host wall time of Optimizer.step", ("optimizer",),
        ).labels(optimizer=cls).observe(time.perf_counter() - t0)
        # step-boundary HBM probe: the live-bytes high-water mark the perf
        # report / flight recorder cite (metadata walk, no device sync)
        from ..profiler import perf_attribution as _pa

        _pa.sample_watermark(tag="optimizer_step")
        return out

    @no_grad()
    def step(self):
        with core_state.named_scope("optimizer"):  # the update's ops in a traced step
            return self._record_step(self._step_impl)

    def _step_impl(self):
        self._sync_lr()
        self._step_count._replace_value(self._step_count._value + 1)
        for entries in self._collect_entries():
            self._apply_entries(self._grads_like_params(entries))

    def _grads_like_params(self, entries):
        """Lay each mesh-placed param's grad out like the param before the
        eager update. An eager op's result lands wherever XLA propagates its
        operands' shardings, and backward leaves grads laid out as their
        producing matmul chose; with param, moments (born on the param's
        placement, _add_accumulator) and grad all on ONE sharding, every
        update op keeps it, so a tensor placed by spec_layout.place stays
        where it was put."""
        out = []
        for p, g, wd, s in entries:
            placement = _mesh_placement(p)
            if placement is not None:
                self._mesh_placed.add(id(p))
                gv = g._value
                if (not isinstance(gv, jax.core.Tracer)
                        and gv.shape == p._value.shape
                        and not placement.is_equivalent_to(gv.sharding, gv.ndim)):
                    g = Tensor(jax.device_put(gv, placement))
            out.append((p, g, wd, s))
        return out

    def _collect_groups(self):
        """Per param-group: (clip, [(param, grad, weight_decay, lr_scale)])
        with UNCLIPPED grads and per-param overrides resolved — the flat
        engine needs the raw grads plus the clip object (global-norm clip
        becomes one scalar kernel operand there)."""
        out = []
        for group, params_grads in self._grouped_params_grads():
            if not params_grads:
                continue
            clip = group.get("grad_clip", self._grad_clip)
            wd = group.get("weight_decay", self._weight_decay)
            lr_scale = group.get("learning_rate", 1.0)
            entries = []
            for p, g in params_grads:
                if g is None:
                    continue
                # per-param overrides: ParamAttr.learning_rate / regularizer
                p_scale = lr_scale * getattr(p, "optimize_attr", {}).get("learning_rate", 1.0)
                p_wd = getattr(p, "regularizer", None)
                entries.append((p, g, p_wd if p_wd is not None else wd, p_scale))
            if entries:
                out.append((clip, entries))
        return out

    def _collect_entries(self, apply_clip=True):
        """Per param-group: [(param, grad, weight_decay, lr_scale)] with
        grad clip applied (unless apply_clip=False — bucket-composition-only
        consumers like _materialize_state skip the clip graph)."""
        out = []
        for clip, entries in self._collect_groups():
            if clip is not None and apply_clip:
                pgs = clip([(p, g) for p, g, _, _ in entries])
                entries = [
                    (p, g2, wd, s)
                    for (p, _, wd, s), (_, g2) in zip(entries, pgs)
                ]
            out.append(entries)
        return out

    def _materialize_state(self):
        """Force lazily-created optimizer state (fused buckets) into
        existence for the CURRENT param/grad composition without updating
        anything — so snapshot/restore consumers (GradScaler's branchless
        skip) see every state tensor before the step mutates it."""
        return None

    def _apply_entries(self, entries):
        """Per-param fallback; optimizers with a fused update override this
        (the role of the reference's multi_tensor_adam /
        fleet tensor_fusion_helper fused buffers — one elementwise XLA kernel
        over a flat buffer instead of hundreds of small per-tensor kernels)."""
        for p, g, wd, s in entries:
            self._apply_one(p, g, wd, s)

    def _grouped_params_grads(self):
        for g in self._param_groups:
            pgs = [(p, p.grad) for p in g["params"] if not p.stop_gradient and p.grad is not None]
            yield g, pgs

    def _apply_one(self, param, grad, weight_decay, lr_scale):
        raise NotImplementedError

    def _lr_value(self, lr_scale):
        v = self._lr_tensor.value
        if lr_scale != 1.0:
            v = v * lr_scale
        return v

    def _decayed_grad(self, param, grad_val, weight_decay):
        """Fold weight decay into the gradient (SGD/Momentum/Adam semantics):
        L2 adds wd*param, L1 adds wd*sign(param)."""
        from ..regularizer import L1Decay

        wd = _wd_value(weight_decay)
        if wd:
            pv = param._value.astype(grad_val.dtype)
            if isinstance(weight_decay, L1Decay):
                return grad_val + wd * jnp.sign(pv)
            return grad_val + wd * pv
        return grad_val

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from ..core import state as _state

        if _state.get_program_capture() is not None:
            # static mode: append backward + update instructions instead of
            # executing (reference: static _append_optimize_op path)
            from ..static.optimizer_hooks import static_minimize

            return static_minimize(self, loss, parameters)
        loss.backward()
        self.step()
        return None, None

    def clear_grad(self, set_to_zero=False):
        for _, p in self._all_params():
            p.clear_grad()

    clear_gradients = clear_grad

    # ---- fused-bucket plumbing ----
    # A bucket (one (weight_decay, lr_scale) combination) holds shape groups:
    # params of identical shape stacked along a new leading axis. Stacking is
    # layout-preserving on TPU (unlike ravel+concat, which forces a tiled->
    # linear relayout of every tensor — measured 2x slower end to end), so
    # the whole optimizer update runs as ~a dozen big elementwise kernels.
    def _defuse_bucket(self, st):
        """Dissolve one bucket's stacked state into per-param pending entries."""
        for grp in st["groups"]:
            for i, pid in enumerate(grp["ids"]):
                for nm, stacked in grp["flat"].items():
                    self._pending_state[(nm, pid)] = stacked._value[i]
                for nm in st["scalars"]:
                    self._pending_state[(nm, pid)] = st["scalars"][nm]._value

    def _defuse_all(self):
        """Dissolve fused stacked buffers back into per-param pending entries
        so state_dict round-trips and bucket recomposition stay exact."""
        for st in list(self._fused_buckets.values()):
            self._defuse_bucket(st)
        self._fused_buckets.clear()
        if self._flat_engine is not None:
            self._flat_engine.defuse_all()

    def disable_fusion(self):
        """Switch to per-param updates, preserving any state already living
        in fused buckets (wrappers that need per-param accumulators —
        shard_optimizer, ZeRO sharding, pipeline placement — call this)."""
        self._fuse_allowed = False
        self._defuse_all()

    def _accumulator_view(self):
        """name -> {id(param): Tensor}, with fused buckets exposed as
        per-param slices (state_dict format is fusion-agnostic)."""
        view = {name: dict(store) for name, store in self._accumulators.items()}
        for st in self._fused_buckets.values():
            for grp in st["groups"]:
                for i, pid in enumerate(grp["ids"]):
                    for nm, stacked in grp["flat"].items():
                        view.setdefault(nm, {})[pid] = Tensor(stacked._value[i])
                    for nm, sc in st["scalars"].items():
                        view.setdefault(nm, {})[pid] = sc
        if self._flat_engine is not None:
            self._flat_engine.view_into(view)
        # loaded-but-not-yet-applied entries (set_state_dict before a step)
        for (nm, pid), v in self._pending_state.items():
            view.setdefault(nm, {}).setdefault(pid, Tensor(jnp.asarray(v)))
        return view

    def _pop_param_state(self, name, pid):
        """Fetch a param's accumulator value for fused-bucket init: loaded
        pending state first, then an existing per-param accumulator."""
        v = self._pending_state.pop((name, pid), None)
        if v is not None:
            return v
        t = self._accumulators.get(name, {}).pop(pid, None)
        return t._value if t is not None else None

    def _fused_state_entries(self):
        """[(Tensor, fill)] for every fused-bucket state tensor — consumers
        that snapshot/restore optimizer state (e.g. GradScaler's branchless
        skip) must cover these alongside _accumulators."""
        out = []
        for st in self._fused_buckets.values():
            for grp in st["groups"]:
                for nm, t in grp["flat"].items():
                    out.append((t, 0.0))
            for nm, t in st["scalars"].items():
                out.append((t, 1.0 if nm.endswith("_pow") else 0.0))
        if self._flat_engine is not None:
            out.extend(self._flat_engine.state_entries())
        return out

    # ---- state dict ----
    def state_dict(self):
        sd = {}
        # accumulators keyed by (name, parameter order) for stable naming
        for name, store in self._accumulator_view().items():
            i = 0
            for _, p in self._all_params():
                if id(p) in store:
                    sd[f"{name}_{i}"] = store[id(p)]
                i += 1
        if self._lr_scheduler is not None:
            sd["LR_Scheduler"] = self._lr_scheduler.state_dict()
        sd["@step"] = self._step_count
        return sd

    def set_state_dict(self, sd):
        # group loaded keys "name_i" by accumulator name; accumulators may not
        # exist yet (lazy creation in _apply_one) — stash those as pending so
        # _add_accumulator picks them up instead of zeros on the first step.
        import re

        # dissolve fused buffers first: loaded per-param values overwrite the
        # pending entries, and the next step rebuilds buckets from them
        self._defuse_all()
        params = [p for _, p in self._all_params()]
        for key, v in sd.items():
            m = re.fullmatch(r"(.+)_(\d+)", key)
            if not m:
                continue
            name, idx = m.group(1), int(m.group(2))
            if idx >= len(params):
                continue
            p = params[idx]
            val = v._value if isinstance(v, Tensor) else jnp.asarray(v)
            store = self._accumulators.get(name)
            if store is not None and id(p) in store:
                store[id(p)]._replace_value(val)
            else:
                self._pending_state[(name, id(p))] = val
        if "LR_Scheduler" in sd and self._lr_scheduler is not None:
            self._lr_scheduler.set_state_dict(sd["LR_Scheduler"])
        if "@step" in sd:
            v = sd["@step"]
            self._step_count._replace_value(v._value if isinstance(v, Tensor) else jnp.asarray(v))


def _wd_value(weight_decay):
    if weight_decay is None:
        return 0.0
    if hasattr(weight_decay, "_coeff"):  # regularizer.L2Decay
        return float(weight_decay._coeff)
    return float(weight_decay)


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _apply_one(self, p, g, wd, lr_scale):
        lr = self._lr_value(lr_scale)
        gv = self._decayed_grad(p, g.value, wd)
        p._replace_value((p._value - lr.astype(p._value.dtype) * gv.astype(p._value.dtype)))
        p.stop_gradient = False


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None, use_nesterov=False, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _apply_one(self, p, g, wd, lr_scale):
        vel = self._add_accumulator("velocity", p)
        lr = self._lr_value(lr_scale)
        gv = self._decayed_grad(p, g.value, wd)
        mu = self._momentum
        v_new = mu * vel.value + gv.astype(vel._value.dtype)
        if self._nesterov:
            upd = gv.astype(p._value.dtype) + mu * v_new.astype(p._value.dtype)
        else:
            upd = v_new.astype(p._value.dtype)
        vel._replace_value(v_new)
        p._replace_value(p._value - lr.astype(p._value.dtype) * upd)
        p.stop_gradient = False


def _sr_round(x32, dtype, seed):
    """Cast f32 -> `dtype` with STOCHASTIC rounding: add uniform noise below
    the mantissa cut, then truncate. Unbiased (E[round(x)] = x), which is
    what lets a bf16 second moment accumulate tiny (1-b2)*g^2 increments
    that round-to-nearest would swallow. bf16 is the f32 top half, so the
    truncation is a 16-bit shift.

    The noise is a murmur-style hash of (element index, per-step seed) —
    ~6 VPU int ops/element, ~2x cheaper than a counter-PRNG stream, which
    is what keeps bf16 moments from costing more than the HBM they save
    (measured A/B in BASELINE.md)."""
    if dtype == jnp.float32:
        return x32
    assert dtype == jnp.bfloat16, dtype
    import numpy as _np

    bits = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    idx = jax.lax.iota(jnp.uint32, x32.size).reshape(x32.shape)
    u = idx * _np.uint32(0x9E3779B1) ^ seed
    u = u ^ jax.lax.shift_right_logical(u, jnp.uint32(16))
    u = u * _np.uint32(0x85EBCA6B)
    u = u ^ jax.lax.shift_right_logical(u, jnp.uint32(13))
    noise = u & jnp.uint32(0xFFFF)
    out16 = jax.lax.shift_right_logical(bits + noise, jnp.uint32(16)).astype(jnp.uint16)
    return jax.lax.bitcast_convert_type(out16, jnp.bfloat16)


def _m2_dtype_from(name, kw):
    """moment2_dtype kwarg (or PADDLE_TPU_ADAM_M2_DTYPE env default):
    'float32' (default) or 'bfloat16' (halves the second-moment HBM traffic;
    stochastically rounded — see BASELINE.md A/B)."""
    import os as _os

    v = kw.pop("moment2_dtype", None) or _os.environ.get("PADDLE_TPU_ADAM_M2_DTYPE")
    if v in (None, "", "float32", jnp.float32):
        return jnp.float32
    if v in ("bfloat16", "bf16", jnp.bfloat16):
        return jnp.bfloat16
    raise ValueError(f"moment2_dtype must be float32 or bfloat16, got {v!r}")


class Adam(Optimizer):
    _wd_mode = "l2"  # adam applies wd to grad; adamw decouples

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False, multi_precision=True, name=None, **kw):
        self._m2_dtype = _m2_dtype_from("moment2_dtype", kw)
        # reference kwargs that are accepted-and-inert here (tensor fusion is
        # FLAGS_fused_optimizer-driven, not a constructor knob)
        kw.pop("use_multi_tensor", None)
        if kw:
            # a misspelled kwarg (e.g. weight_dacay=) silently swallowed here
            # trains with the default — fail loudly instead
            raise TypeError(
                f"{type(self).__name__}() got unexpected keyword argument(s) "
                f"{sorted(kw)}"
            )
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._eps = epsilon
        self._multi_precision = multi_precision

    def _m2_key(self):
        """Per-step uint32 seed for the stochastic-rounding noise hash."""
        from ..framework.random import default_generator

        key = default_generator().next_key()
        return jax.random.bits(key, (), dtype=jnp.uint32)

    def _effective_wd(self, p, wd):
        return wd

    def _use_flat_fusion(self):
        """FLAGS_fused_optimizer routes updates through the flat-bucket
        one-pass Pallas engine (fused_engine.FlatAdamWEngine). Checked per
        step so set_flags() toggles take effect live; wrappers that
        disable_fusion() (ZeRO, shard_optimizer) win over the flag."""
        from ..framework import flags as _flags

        return self._fuse_allowed and bool(_flags.get_flag("FLAGS_fused_optimizer"))

    def _flat_engine_or_create(self):
        if self._flat_engine is None:
            from .fused_engine import FlatAdamWEngine

            self._flat_engine = FlatAdamWEngine(self)
        return self._flat_engine

    def _step_impl(self):
        if self._use_flat_fusion():
            self._sync_lr()
            self._step_count._replace_value(self._step_count._value + 1)
            self._flat_engine_or_create().step(self._collect_groups())
            return
        if self._flat_engine is not None and self._flat_engine.buckets:
            # flag flipped off mid-training: migrate flat state to per-param
            # pending entries instead of silently resetting moments
            self._flat_engine.defuse_all()
        super()._step_impl()

    def _apply_entries(self, entries):
        """Bucket homogeneous params and update each bucket with ONE fused
        elementwise kernel over a flat buffer (reference's multi_tensor_adam,
        paddle/phi/kernels/gpu/multi_tensor_adam_kernel.cu; the flat update
        also shares one beta-pow pair per bucket instead of per-param scalars
        — several hundred fewer tiny kernels per step on a 100M-param model)."""
        buckets, rest = self._fuse_partition(entries)
        for (wdv, s), plist in buckets.items():
            if len(plist) == 1:
                self._apply_one(plist[0][0], plist[0][1], wdv, s)
            else:
                self._apply_fused(plist, wdv, s)
        for p, g, wd, s in rest:
            self._apply_one(p, g, wd, s)

    def _fuse_partition(self, entries):
        """Split entries into fusable buckets keyed by (wd, lr_scale) and a
        per-param remainder."""
        from ..regularizer import L1Decay

        buckets = defaultdict(list)
        rest = []
        if not getattr(self, "_fuse_allowed", True):
            if self._fused_buckets:
                # fusion was turned off by poking the flag: migrate bucket
                # state to per-param instead of silently resetting moments
                self._defuse_all()
            return buckets, [(p, g, self._effective_wd(p, wd), s) for p, g, wd, s in entries]
        for p, g, wd, s in entries:
            wd = self._effective_wd(p, wd)
            fusable = (
                not isinstance(wd, L1Decay)
                and p._value.dtype == jnp.float32
                and getattr(p, "_dist_attr", None) is None
                and id(p) not in self._mesh_placed
                and tuple(g.value.shape) == tuple(p._value.shape)
            )
            if fusable:
                buckets[(_wd_value(wd), float(s))].append((p, g))
            else:
                rest.append((p, g, wd, s))
        return buckets, rest

    def _materialize_state(self):
        if self._use_flat_fusion():
            self._flat_engine_or_create().materialize(self._collect_groups())
            return
        for entries in self._collect_entries(apply_clip=False):
            buckets, _ = self._fuse_partition(entries)
            for plist in buckets.values():
                if len(plist) > 1:
                    ids = tuple(id(p) for p, _ in plist)
                    if ids not in self._fused_buckets:
                        self._build_bucket(plist)

    def _apply_fused(self, plist, wdv, lr_scale):
        ids = tuple(id(p) for p, _ in plist)
        st = self._fused_buckets.get(ids)
        if st is None:
            st = self._build_bucket(plist)
        b1, b2, eps = self._beta1, self._beta2, self._eps
        lr = self._lr_value(lr_scale)
        b1p, b2p = st["scalars"]["beta1_pow"], st["scalars"]["beta2_pow"]
        b1p_new = b1p.value * b1
        b2p_new = b2p.value * b2
        c1 = 1 - b1p_new
        c2 = 1 - b2p_new

        by_id = {id(p): (p, g) for p, g in plist}
        for grp in st["groups"]:
            pgs = [by_id[pid] for pid in grp["ids"]]
            G = jnp.stack([g.value for _, g in pgs]).astype(jnp.float32)
            P = jnp.stack([p._value for p, _ in pgs])
            m, v = grp["flat"]["moment1"], grp["flat"]["moment2"]
            if self._wd_mode == "l2" and wdv:
                G = G + wdv * P
            m_new = b1 * m.value + (1 - b1) * G
            v_new = b2 * v.value.astype(jnp.float32) + (1 - b2) * G * G
            upd = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
            if self._wd_mode == "decoupled" and wdv:
                upd = upd + wdv * P
            P2 = P - lr * upd
            m._replace_value(m_new)
            v._replace_value(
                v_new if self._m2_dtype == jnp.float32
                else _sr_round(v_new, self._m2_dtype, self._m2_key())
            )
            for i, (p, _) in enumerate(pgs):
                p._replace_value(P2[i])
                p.stop_gradient = False
        b1p._replace_value(b1p_new)
        b2p._replace_value(b2p_new)

    def _build_bucket(self, plist):
        ids = tuple(id(p) for p, _ in plist)
        # composition changed (e.g. params frozen/unfrozen between steps):
        # dissolve any bucket sharing params with this one so its per-param
        # state lands in _pending_state and is inherited below, not zeroed
        new_ids = set(ids)
        for old_ids, old_st in list(self._fused_buckets.items()):
            if new_ids.intersection(old_ids):
                self._defuse_bucket(old_st)
                del self._fused_buckets[old_ids]
        by_shape = defaultdict(list)
        for p, _ in plist:
            by_shape[tuple(p._value.shape)].append(p)

        def gather(name, group):
            dt = self._m2_dtype if name == "moment2" else jnp.float32
            parts, have_any = [], False
            for p in group:
                prev = self._pop_param_state(name, id(p))
                if prev is not None:
                    have_any = True
                    parts.append(jnp.asarray(prev).astype(dt))
                else:
                    parts.append(jnp.zeros(p._value.shape, dt))
            if not have_any:
                return jnp.zeros((len(group),) + tuple(group[0]._value.shape), dt)
            return jnp.stack(parts)

        def gather_scalar(name, fill):
            # pop every param's entry (no stale leftovers); the bucket shares
            # one scalar — use the first loaded value
            first = None
            for p, _ in plist:
                prev = self._pop_param_state(name, id(p))
                if prev is not None and first is None:
                    first = jnp.asarray(prev, jnp.float32).reshape(())
            return first if first is not None else jnp.asarray(fill, jnp.float32)

        groups = [
            {
                "ids": tuple(id(p) for p in group),
                "shape": shape,
                "flat": {
                    "moment1": Tensor(gather("moment1", group)),
                    "moment2": Tensor(gather("moment2", group)),
                },
            }
            for shape, group in by_shape.items()
        ]
        st = {
            "groups": groups,
            "scalars": {
                "beta1_pow": Tensor(gather_scalar("beta1_pow", 1.0)),
                "beta2_pow": Tensor(gather_scalar("beta2_pow", 1.0)),
            },
        }
        self._fused_buckets[ids] = st
        return st

    def _apply_one(self, p, g, wd, lr_scale):
        m = self._add_accumulator("moment1", p)
        v = self._add_accumulator("moment2", p, dtype=self._m2_dtype)
        b1p = self._add_accumulator("beta1_pow", p, fill=1.0, dtype=jnp.float32, shape=())
        b2p = self._add_accumulator("beta2_pow", p, fill=1.0, dtype=jnp.float32, shape=())
        lr = self._lr_value(lr_scale)
        b1, b2, eps = self._beta1, self._beta2, self._eps

        gv = g.value.astype(m._value.dtype)
        pv32 = p._value.astype(m._value.dtype)
        wdv = _wd_value(wd)
        if self._wd_mode == "l2" and wdv:
            gv = gv + wdv * pv32

        b1p_new = b1p.value * b1
        b2p_new = b2p.value * b2
        m_new = b1 * m.value + (1 - b1) * gv
        v_new = b2 * v.value.astype(jnp.float32) + (1 - b2) * gv * gv
        mhat = m_new / (1 - b1p_new)
        vhat = v_new / (1 - b2p_new)
        upd = mhat / (jnp.sqrt(vhat) + eps)
        if self._wd_mode == "decoupled" and wdv:
            upd = upd + wdv * pv32
        new_p = pv32 - lr * upd
        m._replace_value(m_new)
        v._replace_value(
            v_new if self._m2_dtype == jnp.float32
            else _sr_round(v_new, self._m2_dtype, self._m2_key())
        )
        b1p._replace_value(b1p_new)
        b2p._replace_value(b2p_new)
        p._replace_value(new_p.astype(p._value.dtype))
        p.stop_gradient = False


class AdamW(Adam):
    """Decoupled weight decay (python/paddle/optimizer/adamw.py)."""

    _wd_mode = "decoupled"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=None, weight_decay=0.01, lr_ratio=None, apply_decay_param_fun=None, grad_clip=None, lazy_mode=False, multi_precision=True, name=None, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters, weight_decay, grad_clip, lazy_mode, multi_precision, name, **kw)
        self._apply_decay_param_fun = apply_decay_param_fun

    def _effective_wd(self, p, wd):
        if self._apply_decay_param_fun is not None and not self._apply_decay_param_fun(p.name or ""):
            return 0.0
        return wd


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None, weight_decay=None, grad_clip=None, initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def _apply_one(self, p, g, wd, lr_scale):
        acc = self._add_accumulator("moment", p, fill=self._init_acc)
        lr = self._lr_value(lr_scale)
        gv = self._decayed_grad(p, g.value, wd).astype(acc._value.dtype)
        acc_new = acc.value + gv * gv
        upd = gv / (jnp.sqrt(acc_new) + self._eps)
        acc._replace_value(acc_new)
        p._replace_value((p._value.astype(acc_new.dtype) - lr * upd).astype(p._value.dtype))
        p.stop_gradient = False


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0, centered=False, parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho = rho
        self._eps = epsilon
        self._momentum = momentum
        self._centered = centered

    def _apply_one(self, p, g, wd, lr_scale):
        ms = self._add_accumulator("mean_square", p)
        mom = self._add_accumulator("momentum", p)
        lr = self._lr_value(lr_scale)
        gv = self._decayed_grad(p, g.value, wd).astype(ms._value.dtype)
        ms_new = self._rho * ms.value + (1 - self._rho) * gv * gv
        if self._centered:
            mg = self._add_accumulator("mean_grad", p)
            mg_new = self._rho * mg.value + (1 - self._rho) * gv
            denom = jnp.sqrt(ms_new - mg_new * mg_new + self._eps)
            mg._replace_value(mg_new)
        else:
            denom = jnp.sqrt(ms_new + self._eps)
        mom_new = self._momentum * mom.value + lr * gv / denom
        ms._replace_value(ms_new)
        mom._replace_value(mom_new)
        p._replace_value((p._value.astype(mom_new.dtype) - mom_new).astype(p._value.dtype))
        p.stop_gradient = False


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95, parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._eps = epsilon
        self._rho = rho

    def _apply_one(self, p, g, wd, lr_scale):
        avg_sq = self._add_accumulator("avg_squared_grad", p)
        avg_upd = self._add_accumulator("avg_squared_update", p)
        lr = self._lr_value(lr_scale)
        gv = self._decayed_grad(p, g.value, wd).astype(avg_sq._value.dtype)
        sq_new = self._rho * avg_sq.value + (1 - self._rho) * gv * gv
        upd = jnp.sqrt(avg_upd.value + self._eps) / jnp.sqrt(sq_new + self._eps) * gv
        upd_new = self._rho * avg_upd.value + (1 - self._rho) * upd * upd
        avg_sq._replace_value(sq_new)
        avg_upd._replace_value(upd_new)
        p._replace_value((p._value.astype(upd.dtype) - lr * upd).astype(p._value.dtype))
        p.stop_gradient = False


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _apply_one(self, p, g, wd, lr_scale):
        m = self._add_accumulator("moment", p)
        inf_norm = self._add_accumulator("inf_norm", p)
        b1p = self._add_accumulator("beta1_pow", p, fill=1.0, dtype=jnp.float32, shape=())
        lr = self._lr_value(lr_scale)
        gv = self._decayed_grad(p, g.value, wd).astype(m._value.dtype)
        b1p_new = b1p.value * self._beta1
        m_new = self._beta1 * m.value + (1 - self._beta1) * gv
        u_new = jnp.maximum(self._beta2 * inf_norm.value, jnp.abs(gv))
        upd = lr / (1 - b1p_new) * m_new / (u_new + self._eps)
        m._replace_value(m_new)
        inf_norm._replace_value(u_new)
        b1p._replace_value(b1p_new)
        p._replace_value((p._value.astype(upd.dtype) - upd).astype(p._value.dtype))
        p.stop_gradient = False


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None, exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._wd = lamb_weight_decay
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _apply_one(self, p, g, wd, lr_scale):
        m = self._add_accumulator("moment1", p)
        v = self._add_accumulator("moment2", p)
        b1p = self._add_accumulator("beta1_pow", p, fill=1.0, dtype=jnp.float32, shape=())
        b2p = self._add_accumulator("beta2_pow", p, fill=1.0, dtype=jnp.float32, shape=())
        lr = self._lr_value(lr_scale)
        gv = g.value.astype(m._value.dtype)
        pv = p._value.astype(m._value.dtype)
        b1p_new, b2p_new = b1p.value * self._beta1, b2p.value * self._beta2
        m_new = self._beta1 * m.value + (1 - self._beta1) * gv
        v_new = self._beta2 * v.value + (1 - self._beta2) * gv * gv
        mhat = m_new / (1 - b1p_new)
        vhat = v_new / (1 - b2p_new)
        r = mhat / (jnp.sqrt(vhat) + self._eps)
        wd = self._wd if (self._exclude_fn is None or not self._exclude_fn(p)) else 0.0
        r = r + wd * pv
        w_norm = jnp.linalg.norm(pv)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        m._replace_value(m_new)
        v._replace_value(v_new)
        b1p._replace_value(b1p_new)
        b2p._replace_value(b2p_new)
        p._replace_value((pv - lr * trust * r).astype(p._value.dtype))
        p.stop_gradient = False


class ASGD(Optimizer):
    """Averaged SGD (reference optimizer/asgd.py): plain SGD steps plus a
    running average of the iterates; `d` and `y` buffers follow the
    reference's recursive-average formulation averaged over the last n
    gradients."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._n = max(int(batch_num), 1)

    def _apply_one(self, p, g, wd, lr_scale):
        d = self._add_accumulator("d", p)       # running gradient sum
        ys = self._add_accumulator("ys", p, shape=(self._n,) + tuple(p._value.shape))
        step = self._add_accumulator("step", p, shape=(), dtype=jnp.int32)
        lr = self._lr_value(lr_scale)
        gv = self._decayed_grad(p, g.value, wd).astype(d._value.dtype)
        idx = (step.value % self._n).astype(jnp.int32)
        old = ys.value[idx]
        d_new = d.value - old + gv
        ys._replace_value(ys.value.at[idx].set(gv))
        d._replace_value(d_new)
        step._replace_value(step.value + 1)
        # denom = number of gradients currently held = min(step, n)
        denom = jnp.minimum(step.value, self._n).astype(d_new.dtype)
        p._replace_value((p._value.astype(d_new.dtype) - lr * d_new / denom).astype(p._value.dtype))
        p.stop_gradient = False


class Rprop(Optimizer):
    """Resilient backprop (reference optimizer/rprop.py): per-element step
    sizes grown/shrunk by gradient sign agreement; updates use sign only."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._lr_min, self._lr_max = learning_rate_range
        self._eta_neg, self._eta_pos = etas

    def _apply_one(self, p, g, wd, lr_scale):
        prev = self._add_accumulator("prev_grad", p)
        lrs = self._add_accumulator("step_sizes", p, fill=float(self._lr_value(lr_scale)))
        gv = g.value.astype(lrs._value.dtype)
        sign = jnp.sign(gv * prev.value)
        scale = jnp.where(sign > 0, self._eta_pos, jnp.where(sign < 0, self._eta_neg, 1.0))
        lr_new = jnp.clip(lrs.value * scale, self._lr_min, self._lr_max)
        # where the sign flipped, skip the update (classic Rprop-)
        g_eff = jnp.where(sign < 0, 0.0, gv)
        p._replace_value((p._value.astype(gv.dtype) - lr_new * jnp.sign(g_eff)).astype(p._value.dtype))
        prev._replace_value(g_eff)
        lrs._replace_value(lr_new)
        p.stop_gradient = False


class LBFGS(Optimizer):
    """Limited-memory BFGS with strong-Wolfe-free backtracking closure line
    search (reference optimizer/lbfgs.py contract: step(closure) re-evaluates
    the loss). History is kept host-side as device arrays; the two-loop
    recursion runs as jnp ops."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9, history_size=100,
                 line_search_fn=None, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._max_iter = max_iter
        self._tol_grad = tolerance_grad
        self._tol_change = tolerance_change
        self._hist = history_size
        self._line_search = line_search_fn
        self._s, self._y = [], []
        self._prev_flat_grad = None
        self.disable_fusion()

    def _flat(self, arrs):
        return jnp.concatenate([a.reshape(-1) for a in arrs])

    def _gather(self):
        params = [p for p in self._param_list() if p.grad is not None]
        flat_g = self._flat([p.grad._value.astype(jnp.float32) for p in params])
        return params, flat_g

    def _param_list(self):
        return [p for _g, p in self._all_params()]

    def _direction(self, flat_g):
        q = flat_g
        alphas = []
        for s, y in zip(reversed(self._s), reversed(self._y)):
            rho = 1.0 / jnp.maximum(jnp.vdot(y, s), 1e-10)
            a = rho * jnp.vdot(s, q)
            alphas.append((a, rho, s, y))
            q = q - a * y
        if self._y:
            y_last, s_last = self._y[-1], self._s[-1]
            gamma = jnp.vdot(s_last, y_last) / jnp.maximum(jnp.vdot(y_last, y_last), 1e-10)
            q = q * gamma
        for a, rho, s, y in reversed(alphas):
            b = rho * jnp.vdot(y, q)
            q = q + s * (a - b)
        return -q

    def step(self, closure=None):
        if closure is None:
            raise ValueError("LBFGS.step requires a closure re-evaluating the loss")
        return self._record_step(lambda: self._lbfgs_step(closure))

    def _lbfgs_step(self, closure):
        loss = closure()
        params, flat_g = self._gather()
        shapes = [tuple(p._value.shape) for p in params]
        import numpy as _np

        sizes = [int(_np.prod(s)) if s else 1 for s in shapes]
        lr = float(self._lr_value(1.0))

        for _ in range(self._max_iter):
            if float(jnp.max(jnp.abs(flat_g))) <= self._tol_grad:
                break
            d = self._direction(flat_g)
            flat_p = self._flat([p._value.astype(jnp.float32) for p in params])
            t = lr
            t_applied = t
            # backtracking on the closure
            for _ls in range(10):
                t_applied = t
                new_flat = flat_p + t * d
                off = 0
                for p, shp, n in zip(params, shapes, sizes):
                    p._replace_value(new_flat[off:off + n].reshape(shp).astype(p._value.dtype))
                    p.stop_gradient = False
                    off += n
                new_loss = closure()
                if float(new_loss.numpy()) <= float(loss.numpy()) + 1e-4 * t * float(jnp.vdot(flat_g, d)):
                    break
                t *= 0.5
            t = t_applied  # the step actually in the params (s must match it)
            _, new_g = self._gather()
            s = (t * d).astype(jnp.float32)
            yv = new_g - flat_g
            if float(jnp.vdot(s, yv)) > 1e-10:
                self._s.append(s)
                self._y.append(yv)
                if len(self._s) > self._hist:
                    self._s.pop(0)
                    self._y.pop(0)
            if float(jnp.max(jnp.abs(t * d))) <= self._tol_change:
                loss = new_loss
                flat_g = new_g
                break
            loss = new_loss
            flat_g = new_g
        self.clear_grad()
        return loss
