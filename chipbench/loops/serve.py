"""The serving loop, open or closed: requests from the traffic generator
into `ContinuousBatchingScheduler.step` over `InferenceEngine`, one thread.

Open loop: arrivals on the schedule the mix fixes, started `ramp_s` before
the window; each request is timed from when it was DUE, and how late the
generator sent it is reported. Closed loop: one client, the next request
sent when the last one ends. After the window the scheduler drains; a
request of the window that has not finished a minute after the close, or
that raised, counts as failed and misses every latency.

Throughput counts the tokens emitted inside the window over the window's
seconds; latencies are over all requests that were due inside it.
"""
import gc
import time

import numpy as np

from chipbench import harness, traffic, weights
from chipbench.harness import clock

DRAIN_LIMIT_S = 60.0


def build(ctx):
    """Engine and scheduler over bfloat16 weights made from the seed."""
    import jax.numpy as jnp

    from paddle_tpu.inference.engine import InferenceEngine
    from paddle_tpu.inference.scheduler import ContinuousBatchingScheduler

    cfg, eng = ctx.cfg, ctx.mix["engine"]
    model = ctx.builder.build(cfg)
    specs = ctx.reference.leaf_specs(cfg)
    with ctx.spans.span("make_weights"):
        vals = weights.make(specs, ctx.seed, jnp.bfloat16)
    state = model.state_dict()
    if set(state) != set(vals):
        raise RuntimeError("reference leaf names differ from the program's: "
                           f"{sorted(set(state) ^ set(vals))[:6]}")
    for name, t in state.items():
        t._value = vals[name]
    del vals
    engine = InferenceEngine(
        model, max_seq_len=int(eng["max_seq_len"]), block_size=int(eng["block_size"]),
        num_blocks=int(eng["num_blocks"]), max_batch=int(eng["max_batch"]),
        prefill_buckets=eng.get("prefill_buckets"),
        decode_batch_buckets=eng.get("decode_batch_buckets"))
    with ctx.spans.span("prewarm"):
        # only the shapes this mix uses
        for s in engine.prefill_buckets:
            engine._get_compiled("prefill", s)
        for b in engine.decode_batch_buckets:
            engine._get_compiled("decode", b)
    sched = ContinuousBatchingScheduler(engine, clock=clock)
    return model, engine, sched


def instrument(ctx, engine, sched):
    """Spans and counters around the calls into each layer, from the
    harness's side: nothing of the program is edited. `ctx.events` gets one
    record per engine call, `ctx.admitted` the time each request was first
    seen in a decode slot."""
    spans, events, admitted = ctx.spans, ctx.events, ctx.admitted
    decode, prefill, step = engine.decode, engine.prefill, sched.step

    def timed_decode(tokens, positions, seq_lens, page_rows):
        with spans.span("engine_decode"):
            out = decode(tokens=tokens, positions=positions, seq_lens=seq_lens,
                         page_rows=page_rows)
        events.append(("decode", clock(), len(tokens), int(sum(seq_lens))))
        return out

    def timed_prefill(prompt_ids, pages):
        with spans.span("engine_prefill"):
            out = prefill(prompt_ids, pages)
        events.append(("prefill", clock(), len(prompt_ids), len(prompt_ids)))
        return out

    def timed_step():
        with spans.span("sched_step"):
            out = step()
        now = clock()
        for r in sched.running:
            if r.rid not in admitted:
                admitted[r.rid] = now
        for r in sched.finished[-4:]:
            if r.rid not in admitted:
                admitted[r.rid] = now
        events.append(("pool", now, engine.pool.used(), 0))
        return out

    engine.decode, engine.prefill, sched.step = timed_decode, timed_prefill, timed_step


def run(ctx):
    import jax

    from paddle_tpu.inference.scheduler import Request

    cfg, mix, spans = ctx.cfg, ctx.mix, ctx.spans
    open_loop = mix["loop"] == "open"
    ramp_s = float(mix.get("ramp_s", 0.0))
    with spans.span("build"):
        model, engine, sched = build(ctx)
    instrument(ctx, engine, sched)
    plan = traffic.requests(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    reqs = [Request(rid=i, prompt=list(ids), max_new_tokens=n) for i, (_, ids, n) in enumerate(plan)]
    harness.log("traffic", requests=len(plan), bucket_stats=dict(engine.bucket_stats),
                pool_blocks=engine.pool.num_blocks, pool_bytes=engine.pool.pool_bytes())

    # warm the host side of every shape too: a decode call at each row
    # count (the slice of the logits is a program per count), on the trash
    # page, and one request through the whole path
    with spans.span("warm_calls"):
        for n in range(1, engine.max_batch + 1):
            engine.decode(tokens=[1] * n, positions=[0] * n, seq_lens=[1] * n,
                          page_rows=[[] for _ in range(n)])
        # token 0 is in no prompt: the warm request's pages can never be a
        # prefix-cache hit for a request of the traffic
        warm = Request(rid=-1, prompt=[0] * 16, max_new_tokens=4)
        sched.submit(warm)
        while not sched.idle():
            sched.step()
    sched.finished.clear()
    ctx.events.clear()
    ctx.admitted.clear()

    setup_s = clock() - ctx.t0
    in_use = harness.memory_in_use_bytes(1)
    compiles0 = ctx.compiles.mark()
    t_zero = clock()                    # arrivals are offsets from here
    t_start = t_zero + ramp_s           # the window
    t_end = t_start + ctx.seconds
    due = {}                            # rid -> absolute due time
    late = []                           # how late the generator sent each
    errors = 0
    nxt = 0
    in_flight = None                    # closed loop: the client's request
    trace_s = float(mix.get("trace_seconds", 3.0))
    tracer = harness.Tracer(spans) if ctx.trace else None
    traced = False
    while True:
        now = clock()
        if now >= t_end:
            break
        if tracer and not traced and now >= t_end - trace_s:
            tracer.start()
            tracer.open()
            traced = True
        if open_loop:
            while nxt < len(plan) and t_zero + plan[nxt][0] <= now:
                r = reqs[nxt]
                due[r.rid] = t_zero + plan[nxt][0]
                late.append(now - due[r.rid])
                sched.submit(r)
                nxt += 1
        elif in_flight is None or in_flight.done:
            if nxt >= len(plan):
                raise RuntimeError("the closed loop ran out of requests: raise least_request_s")
            in_flight = reqs[nxt]
            due[in_flight.rid] = now
            late.append(0.0)
            sched.submit(in_flight)
            nxt += 1
        if sched.idle():
            with spans.span("wait_arrival"):
                gap = (t_zero + plan[nxt][0] - clock()) if nxt < len(plan) else 0.001
                time.sleep(min(0.002, max(0.0, gap)))
            continue
        try:
            sched.step()
        except Exception as e:  # noqa: BLE001 — a failed step fails the run, loudly
            errors += 1
            harness.log("scheduler step raised", error=repr(e))
            break
    backlog = {"waiting_at_close": len(sched.waiting), "running_at_close": len(sched.running)}
    if tracer and traced:
        tracer.stop()
    # drain: everything submitted is waited for, up to a minute past the close
    t_drain = clock()
    while not sched.idle() and clock() - t_drain < DRAIN_LIMIT_S and not errors:
        sched.step()
    drain_s = clock() - t_drain
    compiles = ctx.compiles.since(compiles0)
    peak = harness.memory_peak_bytes(1)

    sent = reqs[:nxt]
    in_window = [r for r in sent if t_start <= due[r.rid] < t_end]
    done = [r for r in in_window if r.outcome == "completed"]
    failed = len(in_window) - len(done) + errors
    tokens_in_window = sum(1 for r in sent for t in r.token_times if t_start <= t < t_end)
    ttft = [r.first_token_time - due[r.rid] for r in done]
    itl = [b - a for r in sent for a, b in zip(r.token_times, r.token_times[1:]) if t_start <= b < t_end]
    harness.log("window", requests_due_in_window=len(in_window), completed=len(done),
                tokens_in_window=tokens_in_window, drain_s=drain_s,
                generator_late_ms_p50=harness.percentile(late, 50) * 1e3 if late else None,
                generator_late_ms_max=max(late) * 1e3 if late else None,
                preempted=sched.preempted_total, in_use_bytes_before_window=in_use, **backlog,
                ttft_ms_p50=(harness.percentile(ttft, 50) or 0) * 1e3,
                ttft_ms_p95=(harness.percentile(ttft, 95) or 0) * 1e3,
                itl_ms_p50=(harness.percentile(itl, 50) or 0) * 1e3,
                setup_spans={n: round(ctx.spans.total(n), 3) for n in
                             ("build", "make_weights", "prewarm", "warm_calls")})

    # the sample the reference follows, drawn from the seed, the longest in it
    rng = np.random.RandomState((ctx.seed + 1) % (2 ** 32))
    finished = [r for r in sent if r.outcome == "completed" and r.preemptions == 0]
    k = min(int(mix["reference_requests"]), len(finished))
    sample = []
    if finished:
        longest = max(finished, key=lambda r: r.prompt_len + len(r.generated))
        rest = [r for r in finished if r is not longest]
        pick = rng.permutation(len(rest))[:max(0, k - 1)]
        sample = [longest] + [rest[i] for i in pick]
    seqs = [list(r.prompt[:r.prompt_len]) + list(r.generated) for r in sample]
    served_from = [r.prompt_len for r in sample]
    facts = {
        "window_s": ctx.seconds, "t_start": t_start, "t_end": t_end, "setup_s": setup_s,
        "tokens_in_window": tokens_in_window, "ttft_s": ttft, "itl_s": itl,
        "queue_wait_s": [ctx.admitted[r.rid] - due[r.rid] for r in in_window if r.rid in ctx.admitted],
        "compiles": compiles, "peak_bytes": peak, "in_use_bytes": in_use,
        "pool_pages": engine.pool.num_blocks - 1, "late_s": late,
        "requests_in_window": len(in_window), "open_loop": open_loop,
    }
    ctx.facts = facts

    # the program's state goes before the reference comes
    pool_used_after = engine.pool.used()
    del model, engine, sched, reqs, sent, in_window, done, finished, sample, warm, in_flight
    gc.collect()
    jax.clear_caches()
    gc.collect()

    compared = {}
    ok = harness.compare("pool_pages_held_after_drain", float(pool_used_after), 0.0, compared)
    if seqs:
        with spans.span("reference"):
            t_ref = clock()
            precisions = ("f32", "int8") if ctx.control else ("f32",)
            logits, served = ctx.reference.token_gaps(cfg, ctx.seed, seqs, served_from, precisions)
            gap = ctx.reference.gaps(logits["f32"], served)
            ref_s = clock() - t_ref
        harness.log("reference", seconds=ref_s, sequences=len(seqs), served_tokens=int(len(served)),
                    longest=max(len(s) for s in seqs), gap_max=float(gap.max()),
                    gap_p99=float(np.percentile(gap, 99)), gap_mean=float(gap.mean()),
                    tokens_off_best=int((gap > 0).sum()))
        lim = mix["limits"]
        ok &= harness.compare("served_logit_gap_max", float(gap.max()), lim["served_logit_gap_max"], compared)
        ok &= harness.compare("served_logit_gap_mean", float(gap.mean()), lim["served_logit_gap_mean"], compared)
        if ctx.control:
            # the token the lower precision puts first, at the same positions
            cg = ctx.reference.gaps(logits["f32"], logits["int8"].argmax(-1))
            harness.log("control", precision="int8", gap_max=float(cg.max()),
                        gap_p99=float(np.percentile(cg, 99)), gap_mean=float(cg.mean()),
                        tokens_off_best=int((cg > 0).sum()),
                        correct=bool(cg.max() <= lim["served_logit_gap_max"]
                                     and cg.mean() <= lim["served_logit_gap_mean"]))
    else:
        ok = False  # nothing finished: nothing to compare
        for name in ("served_logit_gap_max", "served_logit_gap_mean"):
            compared[name] = {"value": None, "limit": mix["limits"][name], "ok": False}
    correct = bool(ok) and failed == 0 and len(ttft) > 0

    end_to_end = {
        "serve_tokens_per_s": tokens_in_window / ctx.seconds,
        "itl_p95_ms": (harness.percentile(itl, 95) or 0.0) * 1e3,
        "setup_s": setup_s,
    }
    if open_loop:
        end_to_end["ttft_p95_ms"] = (harness.percentile(ttft, 95) or 0.0) * 1e3
    if tracer and traced:
        ctx.ir = tracer.reduce()
    return correct, len(ttft) + failed, failed, end_to_end, compared, peak
