"""Static-graph Executor: whole-program jit replay.

Reference parity: python/paddle/base/executor.py:1158 `Executor.run(program,
feed, fetch_list)` + the C++ StandaloneExecutor/PirInterpreter
(paddle/fluid/framework/new_executor/pir_interpreter.h:32). TPU-native: the
instruction list replays inside ONE `jax.jit` — dependency analysis,
multi-stream scheduling, fusion, and memory planning are all XLA's job, which
is precisely the CinnJitInstruction end-state the reference was converging
toward. Gradients (append_backward) ride `jax.value_and_grad` over the same
replay; optimizer updates are extra pure instructions whose results are
written back to the persistable tensors after each run.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from .program import Program, default_main_program


class _OptUpdate:
    """One parameter's pure update: (new_param, new_accums) =
    update_fn(param, grad, lr, *accums). `clip` (shared per minimize call)
    applies global-norm scaling across the group before updates; `wd` is the
    coupled L2 decay folded into the gradient (decoupled decay lives inside
    the update fn, see optimizer_hooks)."""

    __slots__ = ("param_var", "grad_var", "update_fn", "accum_tensors", "lr", "clip", "wd")

    def __init__(self, param_var, grad_var, update_fn, accum_tensors, lr, clip=None, wd=0.0):
        self.param_var = param_var
        self.grad_var = grad_var
        self.update_fn = update_fn
        self.accum_tensors = accum_tensors  # persistable state (momentum etc.)
        self.lr = lr
        self.clip = clip
        self.wd = wd


class _FusedAdamWUpdate:
    """Grouped one-pass update (FLAGS_fused_optimizer): every parameter of
    one minimize() call with the same storage dtype updates through a single
    `ops.fused_optimizer.fused_adamw_apply` over a flat bucket inside the
    compiled replay — the moments live persistently flat in `accum_tensors`
    ([m_flat, v_flat, t]) and the param gather/scatter is a concat/slice
    pair XLA schedules around the kernel."""

    __slots__ = ("param_vars", "grad_vars", "index", "n_pad", "accum_tensors",
                 "lr", "clip", "beta1", "beta2", "eps", "wd", "decoupled")

    def __init__(self, param_vars, grad_vars, index, n_pad, accum_tensors, lr,
                 clip, beta1, beta2, eps, wd, decoupled):
        self.param_vars = list(param_vars)
        self.grad_vars = list(grad_vars)
        self.index = index  # param_var -> (offset, size, shape)
        self.n_pad = n_pad
        self.accum_tensors = accum_tensors
        self.lr = lr
        self.clip = clip
        self.beta1, self.beta2, self.eps = beta1, beta2, eps
        # decay (coupled for Adam, decoupled for AdamW) runs IN-KERNEL; the
        # replay's per-update wd fold never fires for fused updates
        self.wd = wd
        self.decoupled = decoupled

    # the structure key and write-back treat param_var/grad_var uniformly
    @property
    def param_var(self):
        return tuple(self.param_vars)

    @property
    def grad_var(self):
        return tuple(self.grad_vars)


def _update_params_of(upd):
    """Positions-of-write-back helper: per-param updates own one var, fused
    updates own a tuple."""
    if isinstance(upd, _FusedAdamWUpdate):
        return upd.param_vars
    return (upd.param_var,)


def append_backward(loss: Tensor, parameter_list=None, no_grad_set=None):
    """paddle.static.append_backward parity (python/paddle/base/backward.py):
    registers grad computation for every trainable parameter the program
    read; returns [(param, grad_placeholder)] — grads are fetchable."""
    prog = default_main_program()
    loss_var = prog._id2var.get(id(loss))
    if loss_var is None:
        raise ValueError("loss is not an output of the current default_main_program")
    from ..nn.layer import Parameter

    if parameter_list is None:
        params = [
            prog._var_tensors[v]
            for v in prog.param_vars
            if isinstance(prog._var_tensors.get(v), Parameter) and not prog._var_tensors[v].stop_gradient
        ]
    else:
        params = list(parameter_list)
    pairs = []
    param_vars, grad_vars = [], []
    for p in params:
        pv = prog.var_of(p)
        g = Tensor(jnp.zeros_like(p._value), stop_gradient=True, name=(p.name or f"v{pv}") + "@GRAD")
        gv = prog._new_var(g)
        param_vars.append(pv)
        grad_vars.append(gv)
        pairs.append((p, g))
    prog.grad_requests.append((loss_var, param_vars, grad_vars))
    prog._compiled.clear()
    return pairs


class Executor:
    """paddle.static.Executor parity."""

    def __init__(self, place=None):
        self.place = place

    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, np.ndarray]] = None,
        fetch_list: Optional[Sequence] = None,
        return_numpy: bool = True,
        **kwargs,
    ):
        # loaded inference program (static.load_inference_model)
        from .io import _InferenceProgram

        if isinstance(program, _InferenceProgram):
            return program._run(feed or {}, return_numpy)
        from .extras import CompiledProgram

        if isinstance(program, CompiledProgram):
            program = program._program
        program = program if program is not None else default_main_program()
        feed = feed or {}
        fetch_list = list(fetch_list or [])
        fetch_vars = [program.resolve_fetch(f) for f in fetch_list]

        compiled = self._compile(program, tuple(sorted(feed)), tuple(fetch_vars))

        feed_arrays = [jnp.asarray(feed[n]) for n in sorted(feed)]
        param_arrays = [program._var_tensors[v]._value for v in program.param_vars]
        accum_arrays = [
            [a._value for a in upd.accum_tensors] for upd in program.opt_updates
        ]
        lr_arrays = [jnp.asarray(upd.lr() if callable(upd.lr) else upd.lr, jnp.float32) for upd in program.opt_updates]
        fetches, updated, new_accums = compiled(feed_arrays, param_arrays, accum_arrays, lr_arrays)

        # write back persistables (optimizer-touched params + accumulators)
        pos_of = {v: i for i, v in enumerate(program.param_vars)}
        updated_positions = sorted(
            {pos_of[pv] for u in program.opt_updates for pv in _update_params_of(u)}
        )
        for i, new in zip(updated_positions, updated):
            program._var_tensors[program.param_vars[i]]._replace_value(new)
        for upd, accs in zip(program.opt_updates, new_accums):
            for t, new in zip(upd.accum_tensors, accs):
                t._replace_value(new)

        from ..framework import flags as _flags

        if _flags._registry.get("FLAGS_check_nan_inf", False):
            # guardian hook: the compiled replay is opaque to the per-op
            # scan, so check the state it wrote back (updated params +
            # optimizer accumulators) — one fused reduction, flag-gated
            from ..framework import guardian as _guardian

            touched = [
                program._var_tensors[program.param_vars[i]]
                for i in updated_positions
            ]
            for upd in program.opt_updates:
                touched.extend(upd.accum_tensors)
            _guardian.check_compiled_state(touched, origin="static_executor")

        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return [Tensor(f) for f in fetches]

    # ---- compilation ----
    @staticmethod
    def _program_structure_key(program: Program):
        """Structural identity of the instruction list. Every OpInstr carries
        a process-global monotonic serial (program.py `_op_serial`) that is
        never reused, so an op REPLACED in-place (same op count — which a
        length-based key can't see) gets a fresh serial and therefore a new
        key; the stale compiled callable is evicted instead of silently
        replayed. Deliberately O(#ops) per run: detecting an in-place
        `program.ops[i] = ...` edit requires looking at the list — a cached
        key invalidated only at record_op/append_backward would miss exactly
        that mutation — and run() is already O(#params + #ops) in its
        feed/param marshalling, so one flat int tuple adds no new asymptote."""
        ops_key = tuple(op.seq for op in program.ops)
        grads_key = tuple(
            (loss, tuple(pvs), tuple(gvs)) for loss, pvs, gvs in program.grad_requests
        )
        opts_key = tuple((u.param_var, u.grad_var) for u in program.opt_updates)
        return (ops_key, grads_key, opts_key)

    def _compile(self, program: Program, feed_names, fetch_vars):
        from .. import telemetry as _tm
        from . import passes as _passes

        telemetry_on = _tm.enabled()
        structure = self._program_structure_key(program)
        # the pipeline flag is part of compiled identity: toggling
        # FLAGS_program_passes must recompile, not replay the other mode's
        # cached artifact (the flag's contract is "replay the capture
        # exactly as recorded" when off)
        passes_on = _passes.pipeline_enabled()
        key = (feed_names, fetch_vars, structure, passes_on)
        hit = program._compiled.get(key)
        if telemetry_on:
            _tm.counter(
                "paddle_tpu_executor_compile_cache_total",
                "static Executor compiled-program cache lookups", ("result",),
            ).labels(result="hit" if hit is not None else "miss").inc()
        if hit is not None:
            return hit
        # evict entries for the same (feed, fetch, passes-mode) signature
        # whose program structure went stale — they can never hit again
        # (the OTHER pipeline mode's entry stays valid: its structure is
        # checked when that mode next runs)
        stale = [
            k for k in program._compiled
            if k[0] == feed_names and k[1] == fetch_vars
            and (len(k) < 4 or k[3] == passes_on)
        ]
        for k in stale:
            del program._compiled[k]
        if stale and telemetry_on:
            _tm.counter(
                "paddle_tpu_executor_compile_cache_evictions_total",
                "stale compiled-program cache entries dropped on recompile",
            ).inc(len(stale))

        # verify BEFORE passes and lowering (flag-gated, compile-miss only):
        # a malformed program fails here with a diagnostic naming the
        # op/var, not as a KeyError/XLA traceback from inside the jit trace
        # below. The pipeline then re-verifies after every rewriting pass
        # and once more post-pipeline (a miscompiling pass fails with ITS
        # name in the message), so the program that lowers is verified in
        # exactly the form it replays.
        from .analysis import verifier as _verifier

        if _verifier.verify_enabled():
            _verifier.verify(program, feed_names=feed_names, fetch_vars=fetch_vars)

        # pass pipeline (FLAGS_program_passes, default on): rewrite a CLONE
        # per compiled signature — DCE prunes to THIS fetch set and fusion
        # patterns collapse clusters, while the caller's Program keeps every
        # recorded op for other signatures. param_vars/feed_vars/opt lists
        # are shared verbatim, so run()'s marshalling stays aligned.
        work = program
        if passes_on:
            work, _pass_result = _passes.run_default_pipeline(
                program, fetch_vars=fetch_vars, feed_names=feed_names
            )

        feed_var_ids = [work.feed_vars[n] for n in feed_names]
        grad_requests = list(work.grad_requests)
        opt_updates = list(work.opt_updates)

        def forward_env(feed_arrays, param_arrays):
            return work.replay_env(dict(zip(feed_var_ids, feed_arrays)), param_arrays)

        pos_of_param = {v: i for i, v in enumerate(work.param_vars)}
        updated_positions = sorted(
            {pos_of_param[pv] for u in opt_updates for pv in _update_params_of(u)}
        )

        def replay(feed_arrays, param_arrays, accum_arrays, lr_arrays):
            env = None
            grad_vals = {}
            # one grad pass PER request (losses must not contaminate each
            # other), differentiating only wrt that request's parameters
            for loss_var, pvars, gvars in grad_requests:
                sel = [pos_of_param[pv] for pv in pvars]

                def loss_fn(sel_arrays, _lv=loss_var, _sel=sel):
                    full = list(param_arrays)
                    for i, a in zip(_sel, sel_arrays):
                        full[i] = a
                    e = forward_env(feed_arrays, full)
                    return jnp.sum(e[_lv]), e

                (_, env), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    [param_arrays[i] for i in sel]
                )
                for gv, g in zip(gvars, grads):
                    grad_vals[gv] = g
            if env is None:
                env = forward_env(feed_arrays, param_arrays)
            env.update(grad_vals)

            new_params = list(param_arrays)
            # coupled L2 decay folds into the gradient; global-norm clip
            # scales each minimize-call's gradient group jointly (parity with
            # the eager step(): clip -> decay -> update). Fused updates carry
            # a LIST of grads; clip flattens over them.
            eff_grads = []
            for upd in opt_updates:
                if isinstance(upd, _FusedAdamWUpdate):
                    gs = [env.get(gv) for gv in upd.grad_vars]
                    if any(g is None for g in gs):
                        raise RuntimeError("optimizer update without computed gradient")
                    eff_grads.append(gs)
                    continue
                g = env.get(upd.grad_var)
                if g is None:
                    raise RuntimeError("optimizer update without computed gradient")
                eff_grads.append(g)
            from ..nn.clip import ClipGradByGlobalNorm

            def _as_list(g):
                return g if isinstance(g, list) else [g]

            clip_groups = {}
            for i, upd in enumerate(opt_updates):
                if isinstance(upd.clip, ClipGradByGlobalNorm):
                    clip_groups.setdefault(id(upd.clip), (upd.clip, []))[1].append(i)
            for clip, idxs in clip_groups.values():
                gn = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for i in idxs for g in _as_list(eff_grads[i])
                ))
                scale = jnp.minimum(1.0, clip.clip_norm / jnp.maximum(gn, 1e-12))

                def _scaled(g):
                    return (g.astype(jnp.float32) * scale).astype(g.dtype)

                for i in idxs:
                    if isinstance(eff_grads[i], list):
                        eff_grads[i] = [_scaled(g) for g in eff_grads[i]]
                    else:
                        eff_grads[i] = _scaled(eff_grads[i])
            new_accums = []
            for upd, accs, lr, g in zip(opt_updates, accum_arrays, lr_arrays, eff_grads):
                if isinstance(upd, _FusedAdamWUpdate):
                    new_accums.append(
                        self._apply_fused_update(upd, accs, lr, g, new_params, pos_of_param)
                    )
                    continue
                i = pos_of_param[upd.param_var]
                if upd.wd:
                    g = g + jnp.asarray(upd.wd, g.dtype) * new_params[i].astype(g.dtype)
                res = upd.update_fn(new_params[i], g, lr, *accs)
                new_p, new_a = res[0], list(res[1:])
                new_params[i] = new_p
                new_accums.append(new_a)
            fetches = [env[v] for v in fetch_vars]
            # only parameters an optimizer touched leave the jit — frozen
            # weights must not round-trip through outputs every run
            updated = [new_params[i] for i in updated_positions]
            return fetches, updated, new_accums

        compiled = jax.jit(replay)
        if telemetry_on:
            compiled = self._attributed_compile(compiled, program)
        program._compiled[key] = compiled
        return compiled

    @staticmethod
    def _apply_fused_update(upd, accs, lr, grads, new_params, pos_of_param):
        """One flat-bucket kernel for a whole minimize() call's params: gather
        grads/params into padded flat buffers, run fused_adamw_apply, scatter
        params back. Returns the update's new accums [m_flat, v_flat, t]."""
        from ..ops.fused_optimizer import fused_adamw_apply

        m_flat, v_flat, t = accs
        t2 = t + 1
        c1 = 1.0 - jnp.power(jnp.float32(upd.beta1), t2.astype(jnp.float32))
        c2 = 1.0 - jnp.power(jnp.float32(upd.beta2), t2.astype(jnp.float32))
        first = new_params[pos_of_param[upd.param_vars[0]]]
        n = sum(upd.index[pv][1] for pv in upd.param_vars)
        g_parts = [g.ravel().astype(jnp.float32) for g in grads]
        p_parts = [new_params[pos_of_param[pv]].ravel() for pv in upd.param_vars]
        if upd.n_pad > n:
            g_parts.append(jnp.zeros((upd.n_pad - n,), jnp.float32))
            p_parts.append(jnp.zeros((upd.n_pad - n,), first.dtype))
        P2, M2, V2 = fused_adamw_apply(
            jnp.concatenate(p_parts) if len(p_parts) > 1 else p_parts[0],
            m_flat,
            v_flat,
            jnp.concatenate(g_parts) if len(g_parts) > 1 else g_parts[0],
            lr=lr,
            clip_scale=1.0,  # global-norm clip already scaled eff_grads
            c1=c1,
            c2=c2,
            seed=0,
            beta1=upd.beta1,
            beta2=upd.beta2,
            eps=upd.eps,
            wd=upd.wd,
            decoupled=upd.decoupled,
        )
        for pv in upd.param_vars:
            off, size, shape = upd.index[pv]
            new_params[pos_of_param[pv]] = P2[off:off + size].reshape(shape)
        return [M2, V2, t2]

    @staticmethod
    def _attributed_compile(jitted, program):
        """AOT (lower -> compile) per input-shape signature instead of the
        lazy jit first call, so the replay program's XLA `cost_analysis()` /
        `memory_analysis()` can be captured into the attribution layer at
        compile time (perf_attribution.record_compiled) along with the
        compile wall time. Shape polymorphism is preserved: a new signature
        lowers again, exactly like jit retracing. The telemetry gate is
        re-checked at call time — disabled means record NOTHING and run the
        plain jitted path; any AOT failure (aval drift, backend without the
        AOT API) falls back to the jitted callable permanently."""
        import time

        cache = {}
        fallback = [False]

        def wrapper(feed_arrays, param_arrays, accum_arrays, lr_arrays):
            args = (feed_arrays, param_arrays, accum_arrays, lr_arrays)
            if fallback[0]:
                return jitted(*args)
            # key on the FEEDS only: param/accum/lr shapes are fixed for a
            # given program structure (a structure change lands a different
            # outer cache entry), so walking them per call would tax every
            # step O(n_params) for an always-identical suffix. If that
            # invariant ever breaks, the AOT executable rejects the call
            # (TypeError below) and the program falls back to plain jit.
            key = tuple((tuple(a.shape), str(a.dtype)) for a in feed_arrays)
            exe = cache.get(key)
            if exe is None:
                from .. import compile_cache as _cc
                from .. import telemetry as _tm

                if not _tm.enabled():
                    # disabled contract: record nothing, compile nothing
                    # extra — but already-compiled signatures (below) keep
                    # serving their AOT executables
                    return jitted(*args)
                name = f"replay[{len(program.ops)}ops,{len(feed_arrays)}feeds]"
                try:
                    t0 = time.perf_counter()
                    lowered = jitted.lower(*args)
                    exe = lowered.compile()
                    dt = time.perf_counter() - t0
                except Exception:
                    fallback[0] = True
                    return jitted(*args)
                cache[key] = exe
                _tm.histogram(
                    "paddle_tpu_executor_compile_seconds",
                    "wall time of a static Executor program's first "
                    "(tracing + XLA compile) run",
                ).observe(dt)
                _cc.record("static_executor", name, "miss", seconds=dt,
                           signature=f"{len(feed_arrays)}feeds")
                from ..profiler import perf_attribution as _pa

                _pa.record_compiled(
                    "static_executor",
                    name,
                    lowered=lowered,
                    compiled=exe,
                    compile_seconds=dt,
                    # lets CostModel.profile_measure find THIS program's
                    # record on a warm cache instead of the global newest
                    extra={"program_id": id(program)},
                )
            else:
                from .. import compile_cache as _cc

                _cc.record("static_executor", "replay", "hit")
            try:
                return exe(*args)
            except TypeError:
                # aval mismatch (weak-type drift, ...) the AOT executable
                # rejects but jit handles by retracing — our shape/dtype key
                # is evidently too coarse for this program, so stop AOT'ing
                # it. Anything else (OOM, a real in-program error) must
                # propagate, NOT re-execute the whole program via jit.
                fallback[0] = True
                return jitted(*args)

        return wrapper


def global_scope():
    """Minimal Scope analog (paddle.static.global_scope)."""

    class _Scope:
        def find_var(self, name):
            prog = default_main_program()
            for t in prog._var_tensors.values():
                if t.name == name:
                    return t
            return None

    return _Scope()


class scope_guard:
    def __init__(self, scope):
        self.scope = scope

    def __enter__(self):
        return self.scope

    def __exit__(self, *exc):
        return False
