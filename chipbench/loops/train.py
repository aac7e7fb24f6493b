"""The training loop: one `paddle.jit.to_static` train step, driven from
the seed through its first three steps in set-up (the plain reference
follows those), then timed.

The window drives the same step object with the same batch. A loss is
fetched for every step, but a group of `fetch_every` steps is enqueued
before the group before it is fetched, so a step always has work queued
behind it and the host never stands between two steps. The clock is read
only when a group's last loss has arrived.
"""
import gc
import math

import numpy as np

from chipbench import harness, traffic, weights
from chipbench.harness import clock


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(t):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in t.items()}

    return {k: float(v) for k, v in f(tree).items()}


def _diff_norms(a, b):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(a[k].astype(jnp.float32) - b[k].astype(jnp.float32))))
                for k in a}

    return {k: float(v) for k, v in f(a, b).items()}


def build(ctx):
    """The compiled step with its state, weights and batch from the seed."""
    import jax

    import paddle_tpu as paddle

    cfg, mix = ctx.cfg, ctx.mix
    mesh_shape = mix.get("mesh") or {}
    replicas = int(mesh_shape.get("dp", 1))
    mesh = None
    if mesh_shape:
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.sharding import spec_layout as sl

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": int(mesh_shape.get("dp", 1)),
                                   "mp_degree": int(mesh_shape.get("mp", 1))}
        fleet.init(is_collective=True, strategy=strategy)
        mesh = sl.global_mesh()

    paddle.seed(ctx.seed % (2 ** 31))
    model = ctx.builder.build(cfg)
    specs = ctx.reference.leaf_specs(cfg)
    with ctx.spans.span("make_weights"):
        vals = weights.make(specs, ctx.seed, np.float32)
    named = dict(model.named_parameters())
    missing = set(named) ^ set(vals)
    if missing:
        raise RuntimeError(f"reference leaf names differ from the program's: {sorted(missing)[:6]}")
    for name, p in named.items():
        p._replace_value(vals[name])
        p.stop_gradient = not p.trainable
    del vals

    hp = cfg["optimizer"]
    opt = paddle.optimizer.AdamW(
        hp["learning_rate"], parameters=model.parameters(), weight_decay=hp["weight_decay"],
        beta1=hp["beta1"], beta2=hp["beta2"], epsilon=hp["epsilon"])
    ids_np, labels_np = traffic.train_batch(mix, ctx.seed, cfg["vocab_size"], replicas)
    ids, labels = paddle.to_tensor(ids_np), paddle.to_tensor(labels_np)

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        table = sl.transformer_layout_table(dp=replicas)
        for name, p in named.items():
            sl.place(p, table.spec_for(name, p.shape))
        batch_sharding = NamedSharding(mesh, P(sl.layout().data_axis, None))
        for t in (ids, labels):
            t._replace_value(jax.device_put(t._raw(), batch_sharding))

    def train_step(ids, labels):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            loss, _ = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step)
    return model, opt, step, ids, labels, ids_np, labels_np


def first_steps(ctx, model, opt, step, ids, labels):
    """Steps 1 to 3 through the window's own call, one at a time: each
    loss; the first gradient's norm per leaf as the optimizer got it (from
    its first moment after one step, m1 = (1 - beta1) g1); the norm of each
    leaf's change after the three."""
    names = [n for n, _ in model.named_parameters()]
    b1 = ctx.cfg["optimizer"]["beta1"]
    losses = [float(step(ids, labels).numpy())]
    state = opt.state_dict()
    m1 = {}
    for i, n in enumerate(names):
        t = state.get(f"moment1_{i}")
        if t is not None:
            m1[n] = t._raw() if hasattr(t, "_raw") else t._value
    grad_norms = {k: v / (1.0 - b1) for k, v in _leaf_norms(m1).items()}
    del m1, state
    for _ in range(2):
        losses.append(float(step(ids, labels).numpy()))
    p3 = {n: p._value for n, p in model.named_parameters() if n in grad_norms}
    p0 = weights.make({n: s for n, s in ctx.reference.leaf_specs(ctx.cfg).items() if n in p3},
                      ctx.seed, np.float32)
    change_norms = _diff_norms(p3, p0)
    del p0, p3
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change_norms}


def worst_leaf_gap(prog: dict, ref: dict, leaves=None):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Returns (gap, leaf)."""
    leaves = list(leaves if leaves is not None else ref)
    med = float(np.median([ref[k] for k in leaves]))
    worst, where = 0.0, None
    for k in leaves:
        if k not in prog:
            return 1.0, k  # a leaf the reference steps and the program does not
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def judge(prog: dict, ref: dict, limits: dict, compared: dict):
    """The numbers compared, each beside its limit."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    g_gap, g_leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: out of the change, by a rule on that gradient
    med_g = float(np.median(list(ref["grad_norms"].values())))
    moved = [k for k, g in ref["grad_norms"].items() if g >= 1e-3 * med_g]
    c_gap, c_leaf = worst_leaf_gap(prog["change_norms"], ref["change_norms"], moved)
    ok = harness.compare("loss_gap", loss_gap, limits["loss_gap"], compared)
    ok &= harness.compare("grad_norm_gap", g_gap, limits["grad_norm_gap"], compared)
    ok &= harness.compare("change_norm_gap", c_gap, limits["change_norm_gap"], compared)
    return ok, {"grad_leaf": g_leaf, "change_leaf": c_leaf,
                "left_out_of_change": sorted(set(ref["grad_norms"]) - set(moved))}


def run(ctx):
    import jax

    cfg, mix, spans = ctx.cfg, ctx.mix, ctx.spans
    chips = ctx.chips
    k = int(mix["fetch_every"])
    with spans.span("build"):
        model, opt, step, ids, labels, ids_np, labels_np = build(ctx)
    tokens_per_step = int(ids_np.shape[0] * ids_np.shape[1])
    with spans.span("first_steps"):
        prog = first_steps(ctx, model, opt, step, ids, labels)
    harness.log("first steps", losses=prog["losses"])
    entries = [e for e in step.concrete_program().values() if e.jitted is not None]
    try:
        ma = entries[0].jitted.memory_analysis()
        harness.log("compiled step memory", temp_bytes=ma.temp_size_in_bytes,
                    argument_bytes=ma.argument_size_in_bytes, output_bytes=ma.output_size_in_bytes,
                    alias_bytes=ma.alias_size_in_bytes)
    except Exception as e:  # noqa: BLE001 — a report only, never the result
        harness.log("compiled step memory unavailable", error=repr(e))

    def enqueue():
        with spans.span("step"):
            return [step(ids, labels) for _ in range(k)]

    def fetch(group):
        with spans.span("fetch"):
            return [float(x.numpy()) for x in group]

    # warm start: discard `discard_steps` steps, leave one group in flight
    pending = enqueue()
    for _ in range(max(1, math.ceil(int(mix["discard_steps"]) / k))):
        nxt = enqueue()
        fetch(pending)
        pending = nxt
    setup_s = clock() - ctx.t0
    in_use = harness.memory_in_use_bytes(chips)

    compiles0 = ctx.compiles.mark()
    losses, group_s = [], []
    t_start = last = clock()
    traced = False
    tracer = harness.Tracer(spans) if ctx.trace else None
    trace_s = float(mix.get("trace_seconds", 3.0))
    while True:
        if tracer and not traced and (clock() - t_start) >= max(0.0, ctx.seconds - trace_s):
            tracer.start()
            traced = True
            last = clock()
            opened = False
        elif tracer and traced and not opened:
            tracer.open()  # one group after the profiler's start
            opened = True
        nxt = enqueue()
        losses.extend(fetch(pending))
        pending = nxt
        now = clock()
        group_s.append(now - last)
        last = now
        if now - t_start >= ctx.seconds:
            break
    window_s = last - t_start
    if tracer and traced:
        tracer.stop()
    fetch(pending)  # the group still in flight: waited for, not counted
    compiles = ctx.compiles.since(compiles0)
    steps = len(losses)
    peak = harness.memory_peak_bytes(chips)
    harness.log("window", steps=steps, window_s=window_s, group_ms=[round(g * 1e3, 2) for g in group_s],
                first_loss=losses[0], last_loss=losses[-1], in_use_bytes_before_window=in_use,
                setup_spans={n: round(spans.total(n), 3) for n in ("build", "make_weights", "first_steps")})

    failed = sum(1 for x in losses if not math.isfinite(x))
    # the program's state goes before the reference comes
    del model, opt, step, ids, labels, pending, nxt, entries
    gc.collect()
    jax.clear_caches()
    gc.collect()

    with spans.span("reference"):
        t_ref = clock()
        params = weights.make(ctx.reference.leaf_specs(cfg), ctx.seed, np.float32)
        ref = ctx.reference.train_steps(params, ids_np, labels_np, cfg, cfg["optimizer"],
                                        steps=3, row_block=int(mix.get("reference_row_block", 4)))
        ref_s = clock() - t_ref
    compared = {}
    ok, where = judge(prog, ref, mix["limits"], compared)
    harness.log("reference", seconds=ref_s, ref_losses=ref["losses"], prog_losses=prog["losses"], **where)
    correct = ok and failed == 0

    ctx.facts = {
        "tokens_per_step": tokens_per_step, "steps": steps, "window_s": window_s,
        "group_s": group_s, "fetch_every": k, "setup_s": setup_s, "compiles": compiles,
        "peak_bytes": peak, "in_use_bytes": in_use, "seq": int(mix["seq"]),
        "batch": int(ids_np.shape[0]), "heads": cfg["num_attention_heads"],
        "tokens_per_s": steps * tokens_per_step / window_s,
    }
    if tracer and traced:
        ctx.ir = tracer.reduce()
    end_to_end = {
        "train_tokens_per_s": ctx.facts["tokens_per_s"],
        "setup_s": setup_s,
    }
    return correct, steps, failed, end_to_end, compared, peak
