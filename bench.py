"""Benchmark: ERNIE-3.0-base MLM pretrain throughput on one TPU chip.

Three operating points (round 5):
  A. seq 128, batch 64  — the historical headline (BASELINE.json metric
     "ERNIE-3.0 tokens/sec/chip"); matmul-dominated.
  B. seq 4096, batch 2-3 — the long-context point where the Pallas flash
     attention kernel IS the auto-dispatched path (gate is S >= 512) and
     attention is ~40% of the step. Same ERNIE-3.0-base dims (12 layers,
     hidden 768, ffn 3072) with the TPU-native head shape 6 heads x 128:
     the MXU is 128 lanes wide, so head_dim 64 runs every attention matmul
     at half utilization (measured: fwd+bwd 6.9 ms vs 2.7 ms per layer at
     S=4096). Param count is identical to the 12x64 config.
     ROUND 5: runs with attention_probs_dropout_prob=0.1 — the REAL
     ERNIE/BERT pretrain regime (r4 VERDICT Missing #1) — now that the
     kernel applies dropout in-kernel via the stateless position hash.
  C. Llama-3-8B layer shape (BASELINE configs[4]): hidden 4096, 32q/8kv
     GQA heads at head_dim 128, SwiGLU ffn 14336, seq 4096, causal — as
     many decoder layers as fit one chip's HBM with AdamW state (2).
     Exercises the kernel's native GQA head-group mapping (no repeated
     KV materialization).

The reference publishes no tokens/s number (BASELINE.md records
published: {}), so vs_baseline reports measured MFU as the comparable
hardware-efficiency figure.

MFU accounting: model matmul FLOPs per token = 6 * (params excluding
position/token-type lookup tables) + bidirectional attention
12 * S * hidden * layers (fwd 4*S*hidden per layer + backward 2x). Peak is
CO-MEASURED: the bf16 matmul peak is re-measured immediately around each
config in the same run, and each config's MFU is reported against the mean
of its two adjacent peaks.

Timing methodology (round 2): JAX dispatch is asynchronous, so every timed
region here ends in a host fetch of a scalar that data-depends on the work,
and step time is the SLOPE between a short and a long run, which cancels the
constant fetch latency. Peak is measured the same way: matmuls chained
inside one compiled fori_loop reduced to a fetched scalar.

One process per chip (round 21): a chip belongs to one process at a time, so
the parent process NEVER imports jax — the matmul-peak probe, the seq-128
headline and the pass probe run as children like every other config
(`BENCH_CHILD=<kind>`), one after another. Every record a child prints
carries `device` = {platform, kind, count} as jax reports it, so a number
measured on the CPU backend can never sit under a device metric's name
unmarked. Children keep JAX's persistent compilation cache where
`JAX_COMPILATION_CACHE_DIR` says, else in the checkout's `.jax_cache/`
(paddle_tpu.framework.persistent_cache).

Capture contract (round 6 — the un-forfeitable bench): a complete,
parsable JSON line is printed after EVERY config (snapshot-and-extend;
the driver reads the LAST valid line), a global deadline
(`BENCH_DEADLINE_S`, default 3000 s) converts not-yet-run configs into
explicit `{"skipped": "deadline"}` entries instead of losing the whole
record to the driver's timeout, per-config failures are recorded as
explicit skips instead of aborting the run, and after the headline the
configs run CHEAPEST-FIRST (ocr, resnet, ernie-4096, llama) so a tight
budget forfeits the expensive tail, never the whole record. r05 lost every
number it measured to exactly this failure mode (the r05 capture: rc=124,
parsed=null).

Round 9: the driver retains only a short stdout TAIL, and r5's retry
chatter pushed the last snapshot line out of it — so bench now also traps
SIGTERM (what the driver's timeout sends first) and re-emits the terminal
snapshot as the process's very last line, with still-pending configs
marked `skipped:sigterm`. A torn capture now requires an outright SIGKILL
with no grace period.

Round 6 headline regime: the seq-128 config runs with
FLAGS_fused_optimizer=1 (flat-bucket one-pass Pallas AdamW,
ops/fused_optimizer.py) and moment2_dtype='bfloat16' (stochastic-rounding
bf16 second moment — the measured ~2.3% win; see BASELINE.md for the
loss-curve caveat). `detail.optimizer` names both so the capture carries
the change. BENCH_FUSED_OPT=0 / BENCH_M2_BF16=0 restore the r5 regime.

Round 8: every measured config's record carries a `attribution` block —
the XLA cost/memory numbers the perf-attribution layer captured when the
step compiled (FLOPs, HBM bytes, program memory, live-HBM watermark,
compile time) plus a roofline verdict (mfu / hbm_util / bound) against
profiler.perf_attribution.DEFAULT_PEAK_TABLE, which holds published TPU
peaks only: on any other device the block says `roofline: unavailable`.
Platforms without cost analysis record an explicit `attribution:
unavailable` marker — the capture contract extends to attribution. vs_baseline MFU methodology is
unchanged (co-measured peak).

Round 12: an `input_stream` config measures the streaming data tier (tiny
MLP + input-heavy synthetic reader, prefetch-on vs prefetch-off with the
step delta attributed to `input_wait_s`; BENCH_INPUT_* shrink knobs,
BENCH_SKIP_INPUT=1 skips) and a `moe_longcontext` config covers the
ROADMAP-5 operating point (GQA flash + ring attention + capacity-limited
MoE EP routing with drop counters in guardian telemetry; BENCH_MOE_*
knobs, BENCH_SKIP_MOE=1 skips).

Round 13: a `fleet` config replays the serving traffic through a
ReplicaFleet at widths 1/2/4, recording tokens/s scaling vs replica count,
with the widest run taking a mid-run zero-downtime weight hot-swap AND a
FaultPlan-injected replica kill (swap-blip p99 + zero-loss asserted).
BENCH_FLEET_* shrink knobs; BENCH_SKIP_FLEET=1 skips it.

Round 16: the serving/fleet configs run their headline replays REQUEST-
TRACED (telemetry/request_trace.py) and record `detail.slo_breakdown` —
the per-component TTFT/TPOT decomposition (queue_wait/prefill/decode/
preempt/swap_overlap, cause-labeled), a p99 blame table, consistency
(component-sum vs measured wall, ≈1.0 by construction), and the SLO burn
rate against BENCH_{SERVE,FLEET}_SLO_{TTFT,TPOT}_MS targets. perf_gate
checks the candidate's consistency AND accepts/rejects p99 TTFT moves by
whether the breakdown explains them.

Round 11: a `serving` config measures the decode-optimized serving tier —
greedy decode through the paged-KV InferenceEngine (Pallas flash-decode on
TPU, AOT prefill/decode shape buckets) under a synthetic heavy-traffic
request replay, continuous batching vs static batching on the SAME seeded
trace: tokens/s, p50/p99 TTFT and TPOT (pooled inter-token intervals).
BENCH_SERVE_* shrink the model/replay; BENCH_SKIP_SERVING=1 skips it.

Round 17: the serving config adds the prefix-cache/int8-KV/speculative-
decode A/B — a session-template trace (requests share long system-prompt
prefixes) replayed through a baseline f32 engine vs an engine spending
the SAME pool bytes on int8 pages with ref-counted prefix sharing and
n-gram draft + extend-verify decoding. `prefix_hit_rate`,
`spec_accept_rate`, and `concurrency_vs_baseline` (mean in-flight
requests while queue-pressured, optimized/baseline) gate in
tools/perf_gate.py; knobs in `prefix_spec_dims` (BENCH_SERVE_TEMPLATES/
PREFIX/DRAFT/NGRAM/OPT_REQUESTS/BASE_CONCURRENT).

Run: python bench.py            -> JSON lines on stdout (last one wins)
Env: BENCH_STEPS / BENCH_BATCH / BENCH_SEQ override config A;
     BENCH_SKIP_4096=1 skips config B (quick runs);
     BENCH_DEADLINE_S=<s> global wall budget for the whole capture;
     BENCH_VOCAB/HIDDEN/LAYERS/FFN/HEADS shrink the ERNIE dims,
     BENCH_PEAK_N shrinks the peak-measure operands, BENCH_EST_<KIND>
     overrides the don't-even-start estimates — together these let the
     tier-1 capture tests run the real pipeline at seconds scale (a
     shrunken run records `dims_override`, so it can't masquerade as
     the headline).
"""
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_DEADLINE = [None]  # monotonic deadline, set in main()


def _remaining():
    if _DEADLINE[0] is None:
        return math.inf
    return _DEADLINE[0] - time.monotonic()


# minimum-plausible completion time of each config on the chip
# (process start + compile + steps + fetches) — used only to decide "don't even start" (a
# config with less budget than this left is recorded skipped:deadline
# immediately instead of burning the tail of the budget to produce
# nothing); never used to stop a config that already started (children get
# the remaining budget as their subprocess timeout instead)
_EST_S = {
    "peak": 60,
    "passes": 30,
    "seq128": 240,
    "ocr": 90,
    "input_stream": 90,
    # round 17: the serving child also replays the prefix/spec concurrency
    # A/B (baseline f32 vs int8+prefix+spec on the same pool bytes)
    "serving": 300,
    # round 21: the fleet child also replays the disaggregated-vs-
    # monolithic burst A/B (KV migration + tier-death chaos)
    "fleet": 360,
    "qos": 180,
    "resnet": 180,
    # round 20: compiled by default + warm-restore probe + fusion capture
    "moe_longcontext": 300,
    "ernie4096": 240,
    "llama": 300,
}


def _est(kind, default=None):
    """Per-config minimum-plausible estimate, overridable via
    BENCH_EST_<KIND> (the tier-1 capture tests run a shrunken model whose
    real cost is seconds, not the full-size default)."""
    fallback = _EST_S[kind] if default is None else _EST_S.get(kind, default)
    return float(os.environ.get(f"BENCH_EST_{kind.upper()}", fallback))


def _fused_opt_regime():
    """(fused, m2_bf16) for the ERNIE configs — round 6 defaults both ON;
    BENCH_FUSED_OPT=0 / BENCH_M2_BF16=0 restore the r5 per-tensor regime."""
    off = ("0", "false", "no")
    return (
        os.environ.get("BENCH_FUSED_OPT", "1").lower() not in off,
        os.environ.get("BENCH_M2_BF16", "1").lower() not in off,
    )


def _ernie_dims():
    """(vocab, hidden, layers, ffn) for the ERNIE configs — the real
    ERNIE-3.0-base dims unless shrunk via BENCH_VOCAB / BENCH_HIDDEN /
    BENCH_LAYERS / BENCH_FFN (the tier-1 capture tests exercise the full
    bench pipeline on a seconds-scale model; a shrunken run records its
    dims in the result, so the capture can't masquerade as the headline)."""
    return (
        int(os.environ.get("BENCH_VOCAB", 40000)),
        int(os.environ.get("BENCH_HIDDEN", 768)),
        int(os.environ.get("BENCH_LAYERS", 12)),
        int(os.environ.get("BENCH_FFN", 3072)),
    )


def build_train_step(batch, seq, heads, max_pos=None, attn_dropout=0.0):
    """The benchmark workload: ERNIE-3.0-base dims MLM + AdamW, bf16 AMP,
    to_static. (The device profile of this step is taken by
    `chipbench/run.py --trace 1`, reduced by `chipbench/xplane.py`.)"""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import ErnieForMaskedLM, ErnieModel

    vocab, hidden, layers, ffn = _ernie_dims()
    paddle.seed(0)
    model = ErnieForMaskedLM(
        ErnieModel(
            vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
            num_attention_heads=heads, intermediate_size=ffn,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=attn_dropout,
            max_position_embeddings=max_pos if max_pos is not None else max(512, seq),
        )
    )
    fused, m2_bf16 = _fused_opt_regime()
    paddle.set_flags({"FLAGS_fused_optimizer": fused})
    opt = paddle.optimizer.AdamW(
        1e-4, parameters=model.parameters(), weight_decay=0.01,
        moment2_dtype="bfloat16" if m2_bf16 else "float32",
    )

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, vocab, (batch, seq)).astype(np.int64))
    labels = paddle.to_tensor(rng.randint(0, vocab, (batch, seq)).astype(np.int64))

    @paddle.jit.to_static
    def train_step(ids, labels):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            loss, _ = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return model, train_step, ids, labels


def _slope_measure(run, steps, warm=3):
    """Shared slope-timing harness: `run(n)` does n iterations ENDING IN A
    HOST FETCH and returns (seconds, final_value). Per-step time is the
    slope between a short and a long run — the constant fetch latency
    cancels (see module docstring). Every config uses this one helper so
    the methodology cannot drift between configs."""
    run(warm)  # recording run + compile + steady steps
    short = max(2, steps // 4)
    t_short, _ = run(short)
    t_long, final = run(steps)
    return (t_long - t_short) / (steps - short), final


def _attribution(dt_step_s, origin="to_static", combine_last=1):
    """detail.attribution for one measured config: the XLA cost/memory
    record the attribution layer captured when the step compiled, plus the
    roofline (achieved vs peak) at the measured step time. `combine_last`
    sums the newest N programs for configs whose timed region spans several
    compiled programs (PP-OCR's det+rec e2e). Platforms (or runs) where
    cost analysis yielded nothing return an EXPLICIT
    `{"attribution": "unavailable"}` marker instead of silent omission —
    the capture contract extends to attribution (round 8)."""
    try:
        from paddle_tpu.profiler import perf_attribution as pa

        recs = [r for r in pa.program_records(origin) if r["available"]]
        if not recs:
            return {
                "attribution": "unavailable",
                "why": "no compiled-program cost records "
                       "(telemetry off or platform lacks cost analysis)",
            }
        # the step program is the last compiled (grad-mask rebuilds replace
        # the first trace); multi-program configs sum their last N so the
        # numerator covers the same work the timed region measured
        picked = recs[-max(1, combine_last):]
        r = {
            "name": "+".join(p["name"] for p in picked),
            "flops": sum(p["flops"] for p in picked),
            "bytes_accessed": sum(p["bytes_accessed"] for p in picked),
            "peak_memory_bytes": max(p["peak_memory_bytes"] for p in picked),
            "compile_seconds": sum(p["compile_seconds"] or 0 for p in picked),
        }
        wm = pa.sample_watermark(tag="bench", force=True) or pa.watermark()
        out = {
            "program": r["name"],
            "flops": r["flops"],
            "hbm_bytes": r["bytes_accessed"],
            "program_memory_bytes": r["peak_memory_bytes"],
            "peak_hbm_bytes": wm.get("peak_hbm_bytes"),
            "compile_seconds": r["compile_seconds"],
        }
        if r["flops"] and dt_step_s and dt_step_s > 0:
            try:
                roof = pa.roofline(r["flops"], r["bytes_accessed"], dt_step_s)
            except pa.UnknownDeviceError as e:
                # a device without a published peak (the CPU backend of a
                # sandbox run) gets counts, never a utilization
                out["roofline"] = f"unavailable: {e}"
            else:
                out.update(
                    mfu=round(roof["mfu"], 4),
                    hbm_util=round(roof["hbm_util"], 4),
                    bound=roof["bound"],
                    platform=roof["platform"],
                    peak_table_note="roofline vs perf_attribution."
                                    "DEFAULT_PEAK_TABLE (vs_baseline MFU "
                                    "stays co-measured)",
                )
        try:  # round 18: compile-ledger rollup rides every attribution
            from paddle_tpu import compile_cache as _cc

            cs = _cc.summary()
            if cs.get("available"):
                out["compile_cache"] = {
                    "hits": cs["hits"], "misses": cs["misses"],
                    "hit_rate": cs["hit_rate"],
                    "compile_seconds": cs["total_compile_seconds"],
                }
        except Exception:
            pass
        return out
    except Exception as e:  # noqa: BLE001 — attribution must never kill a config
        return {"attribution": "unavailable", "error": str(e)[-200:]}


def _measure_passes():
    """Round 15: the graph-pass pipeline probe. An eager-converted
    tiny-Llama capture (capture_program — ZERO model-code changes) runs the
    static.passes default pipeline; the record carries per-pass match /
    rewritten-op counts (GATED by tools/perf_gate.py: a pattern silently
    un-matching is a fusion-coverage regression, exit 1), the measured
    pipeline wall time per compile-miss, and an outputs_identical bit from
    compiling the same capture with FLAGS_program_passes on vs off."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.jit import capture_program
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.static import passes as passes_mod

    dims = {
        "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 176,
    }
    batch, seq = 1, 16
    model = LlamaForCausalLM(**dims)
    model.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, dims["vocab_size"], (batch, seq)).astype(np.int64)
    )
    program, feed_names, fetch_list = capture_program(
        model, ids, feed_names=["ids"]
    )
    fetch_vid = program.resolve_fetch(fetch_list[0])
    # pipeline cost per compile-miss: best of 3 (clone + full pipeline +
    # per-pass/post verify — exactly what Executor._compile pays on a miss)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        _work, res = passes_mod.run_default_pipeline(
            program, fetch_vars=[fetch_vid], feed_names=feed_names
        )
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    exe = static.Executor()
    feed = {"ids": ids.numpy()}
    (on,) = exe.run(program, feed=feed, fetch_list=fetch_list)
    paddle.set_flags({"FLAGS_program_passes": False})
    try:
        (off,) = exe.run(program, feed=feed, fetch_list=fetch_list)
    finally:
        paddle.set_flags({"FLAGS_program_passes": True})
    return {
        "passes_dims": {**dims, "batch": batch, "seq": seq},
        "n_ops_recorded": len(program.ops),
        "n_ops_after": len(_work.ops),
        "pipeline_ms": round(best * 1000, 3),
        "matches": res.matches,
        "rewritten_ops": res.rewritten_ops,
        "outputs_identical": bool(
            np.array_equal(np.asarray(on), np.asarray(off))
        ),
        "note": (
            "static.passes default pipeline over an eager-converted "
            "tiny-Llama eval capture; matches counts are perf-gated "
            "fusion coverage, pipeline_ms is the per-compile-miss cost "
            "(incl. per-pass + post-pipeline verify)"
        ),
    }


def _build(batch, seq, heads, max_pos, steps, attn_dropout=0.0):
    """Build one config and return its measured stats."""
    model, train_step, ids, labels = build_train_step(
        batch, seq, heads, max_pos, attn_dropout
    )

    def run(n):
        """n steps ending in a host fetch (forces the whole chain)."""
        t0 = time.perf_counter()
        for _ in range(n):
            loss = train_step(ids, labels)
        val = float(loss.numpy())
        return time.perf_counter() - t0, val

    dt_step, final_loss = _slope_measure(run, steps)

    # MFU numerator: 6 * matmul-params per token (fwd+bwd; word embeddings
    # are a lookup on input BUT also the tied MLM decoder matmul, so they
    # count once; position/token-type embeddings are pure lookups and
    # don't) + bidirectional attention 12 * S * hidden per layer.
    vocab, hidden, layers, ffn = _ernie_dims()
    n_params = sum(p.size for p in model.parameters())
    pos = model.ernie.embeddings.position_embeddings.weight.size
    tok = model.ernie.embeddings.token_type_embeddings.weight.size
    flops_per_token = 6 * (n_params - pos - tok) + 12 * seq * hidden * layers

    res = {
        "batch": batch,
        "seq": seq,
        "heads": heads,
        "steps": steps,
        "attn_dropout": attn_dropout,
        "ms_per_step": round(dt_step * 1000, 2),
        "tokens_per_sec": round(batch * seq / dt_step, 1),
        "final_loss": final_loss,
        "flops_per_token": flops_per_token,
        "attribution": _attribution(dt_step),
    }
    if (vocab, hidden, layers, ffn) != (40000, 768, 12, 3072):
        res["dims_override"] = {
            "vocab": vocab, "hidden": hidden, "layers": layers, "ffn": ffn,
        }
    return res


def _oom_backoff(candidates, build):
    """THE RESOURCE_EXHAUSTED backoff policy, shared by every config: try
    build(c) for each candidate in order; on OOM release device memory and
    try the next; the last candidate's failure propagates."""
    for i, c in enumerate(candidates):
        try:
            return build(c)
        except Exception as e:  # jax RESOURCE_EXHAUSTED surfaces as RuntimeError
            if i == len(candidates) - 1 or "RESOURCE_EXHAUSTED" not in str(e):
                raise
            _release_device_memory()


# The Llama OOM-fallback ladder (BASELINE configs[4]): each rung trades a
# little fidelity for a lot of HBM, and the rung that produced the number is
# RECORDED in the result — a degraded-but-real number with its config beats
# a skip (r5 Missing #2: this config has never produced an e2e number).
#   1. the full target: 2 decoder layers, seq 4096
#   2. halve the depth (params + AdamW state are the biggest tenant)
#   3. activation recompute on the decoder block (~1/3 more compute,
#      O(layers) less activation memory)
#   4. halve the sequence (attention activations go 4x down)
#   5. batch micro-splitting: 2 rows of 2048 stepped as 2 grad-accumulated
#      micro-batches of 1 — same tokens/step, half the live activations
_LLAMA_RUNGS = (
    dict(layers=2, seq=4096, recompute=False, micro=1),
    dict(layers=1, seq=4096, recompute=False, micro=1),
    dict(layers=1, seq=4096, recompute=True, micro=1),
    dict(layers=1, seq=2048, recompute=True, micro=1),
    dict(layers=1, seq=2048, recompute=True, micro=2),
)


def _build_llama(steps):
    """Llama-3-8B layer shape on one chip (BASELINE configs[4]): hidden
    4096, GQA 32q/8kv at head_dim 128, SwiGLU ffn 14336, causal flash
    attention with native GQA — descending the _LLAMA_RUNGS ladder on
    RESOURCE_EXHAUSTED until a rung fits the chip's HBM."""
    return _oom_backoff(
        _LLAMA_RUNGS, lambda rung: _build_llama_at(steps, **rung)
    )


def _build_llama_at(steps, layers, seq=4096, recompute=False, micro=1):
    import time

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM

    batch, hidden = micro, 4096  # micro rows step as grad-accum micro-batches
    paddle.seed(0)
    model = LlamaForCausalLM(
        vocab_size=32000, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=32, num_key_value_heads=8,
        intermediate_size=14336, recompute=recompute,
    )
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(), weight_decay=0.01)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 32000, (batch, seq)).astype(np.int64))
    labels = paddle.to_tensor(rng.randint(0, 32000, (batch, seq)).astype(np.int64))

    @paddle.jit.to_static
    def train_step(ids, labels):
        loss = None
        for i in range(micro):  # micro=1 degenerates to the plain step
            with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
                loss, _ = model(ids[i:i + 1], labels=labels[i:i + 1])
            (loss * (1.0 / micro)).backward()  # grads accumulate across rows
        opt.step()
        opt.clear_grad()
        return loss

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = train_step(ids, labels)
        val = float(loss.numpy())
        return time.perf_counter() - t0, val

    dt_step, final_loss = _slope_measure(run, steps)

    # 6 * matmul params (embedding excluded: lookup-only on input; lm_head
    # is untied and counts via its own matmul) + causal attention
    # 6 * S * hidden per layer (half the bidirectional 12: lower-triangle
    # scores only — both kernels skip fully-masked tiles). Recompute's extra
    # forward is deliberately NOT counted: MFU stays model FLOPs / time, so
    # a recompute rung honestly reports its efficiency loss.
    n_params = sum(p.size for p in model.parameters())
    embed = model.llama.embed_tokens.weight.size
    flops_per_token = 6 * (n_params - embed) + 6 * seq * hidden * layers
    return {
        "batch": batch,
        "seq": seq,
        "heads": "32q/8kv",
        "layers": layers,
        "steps": steps,
        "rung": {
            "layers": layers, "seq": seq, "recompute": recompute,
            "micro_batches": micro,
        },
        "ms_per_step": round(dt_step * 1000, 2),
        "tokens_per_sec": round(batch * seq / dt_step, 1),
        "final_loss": final_loss,
        "flops_per_token": flops_per_token,
        "attribution": _attribution(dt_step),
    }


def _serve_dims():
    """Serving-bench model dims + replay knobs, all BENCH_SERVE_*
    overridable (tier-1 capture tests run a seconds-scale replay; a
    shrunken run records serve_dims so it can't masquerade)."""
    g = os.environ.get
    return {
        "vocab": int(g("BENCH_SERVE_VOCAB", 8192)),
        "hidden": int(g("BENCH_SERVE_HIDDEN", 512)),
        "layers": int(g("BENCH_SERVE_LAYERS", 4)),
        "heads": int(g("BENCH_SERVE_HEADS", 8)),
        "kv_heads": int(g("BENCH_SERVE_KV_HEADS", 4)),
        "ffn": int(g("BENCH_SERVE_FFN", 1376)),
        "max_seq": int(g("BENCH_SERVE_MAX_SEQ", 256)),
        "block_size": int(g("BENCH_SERVE_BLOCK", 16)),
        "max_batch": int(g("BENCH_SERVE_BATCH", 8)),
        "n_requests": int(g("BENCH_SERVE_REQUESTS", 48)),
        "seed": int(g("BENCH_SERVE_SEED", 11)),
        "gap_s": float(g("BENCH_SERVE_GAP", 0.002)),
        # round 16: SLO targets the request-trace burn rate reports against
        # (generous CPU-scale defaults; real deployments override)
        "slo_ttft_ms": float(g("BENCH_SERVE_SLO_TTFT_MS", 1000.0)),
        "slo_tpot_ms": float(g("BENCH_SERVE_SLO_TPOT_MS", 200.0)),
        # round 17: prefix-cache + speculative-decode sub-run knobs — a
        # session-template trace (shared system prompts) replayed through a
        # baseline f32 engine vs an int8-KV + prefix-shared + spec-decoding
        # engine on the SAME pool bytes
        "prefix_templates": int(g("BENCH_SERVE_TEMPLATES", 4)),
        "prefix_len": int(g("BENCH_SERVE_PREFIX", 48)),
        "spec_draft": int(g("BENCH_SERVE_DRAFT", 3)),
        "spec_ngram": int(g("BENCH_SERVE_NGRAM", 2)),
        # defaults to 2/3 of the replay size so the tier-1 shrink knobs
        # (BENCH_SERVE_REQUESTS) scale this sub-run down with everything else
        "opt_requests": int(g("BENCH_SERVE_OPT_REQUESTS",
                              max(8, int(g("BENCH_SERVE_REQUESTS", 48)) * 2 // 3))),
        # baseline pool sized to hold this many FULL contexts (the binding
        # constraint the optimized engine relieves on equal bytes)
        "base_concurrent": int(g("BENCH_SERVE_BASE_CONCURRENT", 2)),
        # decode width for BOTH A/B engines — wider than the headline
        # max_batch so the POOL (not the batch bucket) caps concurrency
        "ab_batch": int(g("BENCH_SERVE_AB_BATCH",
                          2 * int(g("BENCH_SERVE_BATCH", 8)))),
    }


def _build_serving():
    """Serving tier under a synthetic heavy-traffic request replay
    (round 11): greedy decode through the paged-KV InferenceEngine with
    continuous batching vs the static-batching baseline on the SAME seeded
    trace. Reports tokens/s (generated tokens over replay wall) and
    p50/p99 TTFT + TPOT — TPOT percentiles over pooled inter-token
    intervals (the ITL convention; robust to one OS blip wrecking a short
    request's mean). Bucket compiles happen in a warmup pass so the
    measured replay sees steady-state serving, and GC is paused during the
    replay (both schedulers measured identically)."""
    import gc

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import InferenceEngine
    from paddle_tpu.inference.scheduler import (
        ContinuousBatchingScheduler,
        Request,
        StaticBatchingScheduler,
        replay,
    )
    from paddle_tpu.models.llama import LlamaForCausalLM

    d = _serve_dims()
    paddle.seed(0)
    model = LlamaForCausalLM(
        vocab_size=d["vocab"], hidden_size=d["hidden"],
        num_hidden_layers=d["layers"], num_attention_heads=d["heads"],
        num_key_value_heads=d["kv_heads"], intermediate_size=d["ffn"],
    )
    model.eval()

    def mk_requests():
        rng = np.random.RandomState(d["seed"])
        max_prompt = max(8, d["max_seq"] // 4)
        gen_mix = [4, 8, 16, max(24, d["max_seq"] // 4)]
        reqs, t = [], 0.0
        for i in range(d["n_requests"]):
            t += rng.exponential(d["gap_s"])
            reqs.append(Request(
                rid=i,
                prompt=rng.randint(0, d["vocab"], (int(rng.randint(4, max_prompt)),)).tolist(),
                max_new_tokens=int(rng.choice(gen_mix, p=[0.25, 0.3, 0.25, 0.2])),
                arrival_time=t,
            ))
        return reqs

    def fresh_engine():
        eng = InferenceEngine(
            model, max_seq_len=d["max_seq"], block_size=d["block_size"],
            max_batch=d["max_batch"],
            # one decode signature: step cost independent of occupancy, and
            # the bucket cache stays tiny (standard fixed-batch TPU serving)
            decode_batch_buckets=(d["max_batch"],),
        )
        for b in eng.prefill_buckets:  # warmup: compile outside the replay
            pages = eng.pool.alloc(eng.pool.blocks_for_tokens(b))
            eng.prefill(list(range(1, b + 1)), pages)
            eng.pool.reset()
        pages = eng.pool.alloc(1)
        eng.decode([1], [0], [1], [pages])
        eng.pool.reset()
        return eng

    def measured(kind):
        from paddle_tpu.telemetry import request_trace as _rt

        eng = fresh_engine()
        sched = (ContinuousBatchingScheduler(eng) if kind == "continuous"
                 else StaticBatchingScheduler(eng))
        # round 16: the continuous (headline) replay runs REQUEST-TRACED so
        # the capture carries the TTFT/TPOT decomposition of the very
        # numbers it reports (perf_gate checks the components sum to the
        # measured walls and explains p99 moves through them); measured
        # overhead is ~1 µs per lifecycle transition (BASELINE round-16),
        # noise against the ~10 ms CPU decode step
        traced = kind == "continuous"
        if traced:
            _rt.reset()
            paddle.set_flags({"FLAGS_request_trace": True})
        gc.collect()
        gc.disable()
        try:
            stats = replay(sched, mk_requests())
        finally:
            gc.enable()
            if traced:
                paddle.set_flags({"FLAGS_request_trace": False})
        stats["bucket_stats"] = dict(eng.bucket_stats)
        if traced:
            stats["slo_breakdown"] = _rt.slo_breakdown(
                slo_ttft_ms=d["slo_ttft_ms"], slo_tpot_ms=d["slo_tpot_ms"]
            )
        return stats

    # ---- round 17: prefix cache + int8 KV + speculative decoding on the
    # SAME pool bytes. A session-template trace (groups of requests share a
    # long system-prompt prefix — the shape real heavy traffic has) runs
    # through (a) a baseline f32 engine whose pool holds `base_concurrent`
    # full contexts with prefix/spec OFF, and (b) an engine whose pool
    # spends THE SAME BYTES on int8 pages (+absmax scale planes), shares
    # prefix pages ref-counted, and speculates through the n-gram draft +
    # extend-verify program. Reported: prefix_hit_rate (prompt tokens
    # served from shared pages), spec_accept_rate (drafts verified equal
    # to the greedy chain), and concurrency_vs_baseline (mean concurrent
    # in-flight requests, optimized / baseline) — all perf_gate-gated. ----
    from paddle_tpu.inference.scheduler import SpecDecodeConfig
    from paddle_tpu.telemetry import request_trace as _rt

    spec_gen = max(16, d["max_seq"] // 8)
    # template prefix clamped so prefix + max tail (16) + generation always
    # fits max_seq (shrunken tier-1 dims would otherwise reject admission)
    prefix_len = max(d["block_size"],
                     min(d["prefix_len"], d["max_seq"] - 16 - spec_gen))

    def mk_shared_requests():
        # BURST arrival (everyone at t=0) with a uniform generation budget:
        # demand saturates both engines, so in-flight concurrency measures
        # what the POOL sustains, not how fast requests happen to drain
        rng = np.random.RandomState(d["seed"] + 1)
        templates = [
            rng.randint(0, d["vocab"], (prefix_len,)).tolist()
            for _ in range(d["prefix_templates"])
        ]
        reqs = []
        for i in range(d["opt_requests"]):
            tail = rng.randint(0, d["vocab"], (int(rng.randint(4, 17)),)).tolist()
            reqs.append(Request(
                rid=i,
                prompt=templates[i % d["prefix_templates"]] + tail,
                max_new_tokens=spec_gen,
                arrival_time=0.0,
            ))
        return reqs

    full_ctx = prefix_len + 16 + spec_gen

    def concurrency_replay(engine, sched):
        """Replay tracking sustained concurrency: in-flight requests per
        step, sampled ONLY while the waiting queue is non-empty — while
        someone is queued, `running` IS the capacity bound (admission would
        have filled a free slot), so the mean is pool-sustained
        concurrency, uncontaminated by the drain tail."""
        pressured, peak = [], 0
        orig_step = sched.step

        def counting_step():
            produced = orig_step()
            peak_now = len(sched.running)
            nonlocal peak
            peak = max(peak, peak_now)
            if sched.waiting:
                pressured.append(peak_now)
            return produced

        sched.step = counting_step
        _rt.reset()
        paddle.set_flags({"FLAGS_request_trace": True})
        gc.collect()
        gc.disable()
        try:
            stats = replay(sched, mk_shared_requests())
        finally:
            gc.enable()
            paddle.set_flags({"FLAGS_request_trace": False})
        stats["mean_running"] = (
            round(sum(pressured) / len(pressured), 3) if pressured else None
        )
        stats["peak_running"] = peak
        stats["pool_bytes"] = engine.pool.pool_bytes()
        stats["slo_breakdown"] = _rt.slo_breakdown(
            slo_ttft_ms=d["slo_ttft_ms"], slo_tpot_ms=d["slo_tpot_ms"]
        )
        return stats

    base_blocks = 1 + d["base_concurrent"] * (
        -(-full_ctx // d["block_size"])
    )
    base_eng = InferenceEngine(
        model, max_seq_len=d["max_seq"], block_size=d["block_size"],
        max_batch=d["ab_batch"], num_blocks=base_blocks,
        decode_batch_buckets=(d["ab_batch"],),
    )
    base_stats = concurrency_replay(
        base_eng,
        ContinuousBatchingScheduler(base_eng, prefix_cache=False),
    )
    # same device bytes, int8 pages (+scale planes) — the capacity doubling
    # the roofline says decode is bound on
    from paddle_tpu.inference.kv_cache import BlockPool as _ProbePool

    probe_pool = _ProbePool(
        2, d["block_size"], d["layers"], d["kv_heads"],
        d["hidden"] // d["heads"], kv_dtype="int8",
    )
    opt_blocks = max(2, base_eng.pool.pool_bytes() // probe_pool.page_bytes())
    opt_eng = InferenceEngine(
        model, max_seq_len=d["max_seq"], block_size=d["block_size"],
        max_batch=d["ab_batch"], num_blocks=opt_blocks, kv_dtype="int8",
        decode_batch_buckets=(d["ab_batch"],),
    )
    assert opt_eng.pool.pool_bytes() <= base_eng.pool.pool_bytes(), (
        "optimized pool must not spend more bytes than the baseline"
    )
    opt_sched = ContinuousBatchingScheduler(
        opt_eng, prefix_cache=True,
        spec_decode=SpecDecodeConfig(draft_len=d["spec_draft"],
                                     ngram=d["spec_ngram"]),
    )
    opt_reqs_sched = opt_sched  # finished requests read back below
    opt_stats = concurrency_replay(opt_eng, opt_sched)
    done = list(opt_reqs_sched.finished)
    prompt_tokens = sum(r.prompt_len for r in done)
    cached = sum(r.cached_tokens for r in done)
    drafted = sum(r.drafted for r in done)
    accepted = sum(r.accepted for r in done)

    cont = measured("continuous")
    static = measured("static")
    res = {
        **cont,
        "n_requests": d["n_requests"],
        "static": static,
        # round 17 gated fields (larger is better; drops fail perf_gate)
        "prefix_hit_rate": round(cached / prompt_tokens, 4) if prompt_tokens else None,
        "spec_accept_rate": round(accepted / drafted, 4) if drafted else None,
        # a run whose waiting queue never backed up sustained its WHOLE
        # admitted peak — fall back to peak_running for it
        "concurrency_vs_baseline": (
            round(
                (opt_stats["mean_running"] or opt_stats["peak_running"])
                / (base_stats["mean_running"] or base_stats["peak_running"]),
                3,
            )
            if (base_stats["mean_running"] or base_stats["peak_running"])
            else None
        ),
        "prefix_spec_dims": {
            "templates": d["prefix_templates"],
            "prefix_len": prefix_len,
            "draft_len": d["spec_draft"],
            "ngram": d["spec_ngram"],
            "kv_dtype": "int8",
            "n_requests": d["opt_requests"],
            "ab_batch": d["ab_batch"],
            "base_blocks": base_blocks,
            "opt_blocks": int(opt_blocks),
        },
        "prefix_spec": {
            "baseline": base_stats,
            "optimized": opt_stats,
            "cached_tokens": int(cached),
            "prompt_tokens": int(prompt_tokens),
            "drafted_tokens": int(drafted),
            "accepted_tokens": int(accepted),
            "note": (
                "session-template replay: baseline f32 pool sized to "
                f"{d['base_concurrent']} full contexts vs int8+prefix+spec "
                "on the same bytes; concurrency = mean in-flight requests "
                "per non-idle step"
            ),
        },
        "speedup_vs_static": (
            round(cont["tokens_per_sec"] / static["tokens_per_sec"], 3)
            if cont.get("tokens_per_sec") and static.get("tokens_per_sec") else None
        ),
        "note": (
            "greedy decode, paged KV (Pallas flash-decode on TPU), AOT "
            "shape buckets, token-streamed continuous batching vs static "
            "groups on the same seeded replay; tpot percentiles pool all "
            "inter-token intervals"
        ),
        # decode step time is the serving hot path: attribute the decode
        # program (compiled last in warmup) at the median interval
        "attribution": _attribution(
            (cont.get("p50_tpot_ms") or 0) / 1000.0 or None, origin="serving"
        ),
    }
    res["serve_dims"] = {k: d[k] for k in ("vocab", "hidden", "layers", "heads",
                                           "kv_heads", "ffn", "max_seq",
                                           "block_size", "max_batch", "seed",
                                           "gap_s")}
    return res


def _fleet_dims():
    """Replica-fleet bench knobs (round 13), all BENCH_FLEET_* overridable
    (tier-1 capture tests run a seconds-scale fleet; a shrunken run records
    fleet_dims so it can't masquerade). `replicas` is the comma-separated
    ladder of fleet widths replayed; the LAST entry is the headline run
    that takes the mid-run weight swap + replica kill."""
    g = os.environ.get
    return {
        "vocab": int(g("BENCH_FLEET_VOCAB", 8192)),
        "hidden": int(g("BENCH_FLEET_HIDDEN", 256)),
        "layers": int(g("BENCH_FLEET_LAYERS", 2)),
        "heads": int(g("BENCH_FLEET_HEADS", 8)),
        "kv_heads": int(g("BENCH_FLEET_KV_HEADS", 4)),
        "ffn": int(g("BENCH_FLEET_FFN", 688)),
        "max_seq": int(g("BENCH_FLEET_MAX_SEQ", 128)),
        "block_size": int(g("BENCH_FLEET_BLOCK", 16)),
        "max_batch": int(g("BENCH_FLEET_BATCH", 4)),
        "n_requests": int(g("BENCH_FLEET_REQUESTS", 32)),
        "replicas": tuple(
            int(x) for x in g("BENCH_FLEET_REPLICAS", "1,2,4").split(",")
        ),
        "seed": int(g("BENCH_FLEET_SEED", 13)),
        "gap_s": float(g("BENCH_FLEET_GAP", 0.002)),
        # event triggers as completed-request fractions of the replay
        "swap_at": float(g("BENCH_FLEET_SWAP_AT", 0.3)),
        "kill_at": float(g("BENCH_FLEET_KILL_AT", 0.6)),
        # round 16: SLO targets for the request-trace burn rate
        "slo_ttft_ms": float(g("BENCH_FLEET_SLO_TTFT_MS", 1000.0)),
        "slo_tpot_ms": float(g("BENCH_FLEET_SLO_TPOT_MS", 200.0)),
        # round 21: the disaggregated-vs-monolithic burst A/B — requests
        # arriving near-simultaneously with a shared system-prompt prefix
        # (prefix_pages full pages), replayed on an untiered fleet and a
        # prefill/decode split of the SAME width (equal chips)
        "burst_requests": int(g("BENCH_FLEET_BURST_REQUESTS", 16)),
        "burst_gap_s": float(g("BENCH_FLEET_BURST_GAP", 0.0005)),
        "prefix_pages": int(g("BENCH_FLEET_PREFIX_PAGES", 2)),
        "decode_kv_dtype": g("BENCH_FLEET_DECODE_KV", "int8"),
    }


def _build_fleet():
    """Round 13: the replica fleet under the serving replay — the SAME
    seeded traffic replayed at each fleet width in `replicas`, recording
    tokens/s scaling vs replica count; the widest run additionally takes a
    mid-run zero-downtime weight hot-swap (a `step_<N>/` checkpoint of the
    same weights streamed into one drained replica at a time, so greedy
    ids are preserved while the drain/load machinery runs for real) AND a
    FaultPlan-injected replica kill (circuit breaker -> evacuation ->
    recompute-from-prompt re-dispatch). Gated fields: scaling_vs_1replica
    (throughput), p99_tpot_swap_ms (the swap-blip tail), n_replicas
    (shape). `lost`/`duplicated` must be zero — asserted here, not just
    reported."""
    import gc
    import shutil
    import tempfile

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import checkpoint as _ckpt
    from paddle_tpu.distributed.resilience import fault_injection as _fi
    from paddle_tpu.inference.engine import InferenceEngine
    from paddle_tpu.inference.fleet import ReplicaFleet, fleet_replay
    from paddle_tpu.inference.scheduler import Request
    from paddle_tpu.models.llama import LlamaForCausalLM

    d = _fleet_dims()
    paddle.seed(0)
    model = LlamaForCausalLM(
        vocab_size=d["vocab"], hidden_size=d["hidden"],
        num_hidden_layers=d["layers"], num_attention_heads=d["heads"],
        num_key_value_heads=d["kv_heads"], intermediate_size=d["ffn"],
    )
    model.eval()

    def mk_requests():
        rng = np.random.RandomState(d["seed"])
        max_prompt = max(8, d["max_seq"] // 4)
        gen_mix = [4, 8, 16, max(24, d["max_seq"] // 4)]
        reqs, t = [], 0.0
        for i in range(d["n_requests"]):
            t += rng.exponential(d["gap_s"])
            reqs.append(Request(
                rid=i,
                prompt=rng.randint(0, d["vocab"], (int(rng.randint(4, max_prompt)),)).tolist(),
                max_new_tokens=int(rng.choice(gen_mix, p=[0.25, 0.3, 0.25, 0.2])),
                arrival_time=t,
            ))
        return reqs

    def fresh_engine(kv_dtype=None):
        kw = {} if kv_dtype is None else {"kv_dtype": kv_dtype}
        eng = InferenceEngine(
            model, max_seq_len=d["max_seq"], block_size=d["block_size"],
            max_batch=d["max_batch"], decode_batch_buckets=(d["max_batch"],),
            **kw,
        )
        for b in eng.prefill_buckets:  # warmup: compile outside the replay
            pages = eng.pool.alloc(eng.pool.blocks_for_tokens(b))
            eng.prefill(list(range(1, b + 1)), pages)
            eng.pool.reset()
        pages = eng.pool.alloc(1)
        eng.decode([1], [0], [1], [pages])
        eng.pool.reset()
        return eng

    ck_root = tempfile.mkdtemp(prefix="bench_fleet_swap_")
    per_n = {}
    # round 22: the whole fleet capture runs with the incident timeline on —
    # every FaultPlan injection below (replica kills, migrate-site faults)
    # must surface as a causally-matched timeline event, and the resulting
    # unobserved_faults / dropped counts are perf-gated to exactly zero
    from paddle_tpu.telemetry import timeline as _tl

    _tl.reset()
    paddle.set_flags({"FLAGS_incident_timeline": True})
    try:
        _ckpt.save_state_dict({"model": model.state_dict()}, ck_root, step=1)
        widest = max(d["replicas"])
        slo_breakdown = None
        for n in d["replicas"]:
            fleet = ReplicaFleet([fresh_engine() for _ in range(n)])
            events = []
            chaos = n == widest
            if chaos:
                events.append((
                    max(1, int(d["swap_at"] * d["n_requests"])),
                    lambda f=fleet: f.request_swap(ck_root),
                ))
                if n > 1:
                    # kill the LAST replica: two consecutive injected step
                    # faults open its breaker (threshold 2) -> evacuation
                    def kill(idx=n - 1):
                        _fi.install_plan(
                            _fi.FaultPlan().add(
                                f"fleet.replica_step.{idx}", "fail", times=2
                            )
                        )
                    events.append((
                        max(2, int(d["kill_at"] * d["n_requests"])), kill,
                    ))
            # round 16: the chaos (headline) width runs request-traced so
            # the capture's decomposition covers evacuation + swap-drain
            # attribution (cause-labeled preempt spans, swap windows)
            from paddle_tpu.telemetry import request_trace as _rt

            if chaos:
                _rt.reset()
                paddle.set_flags({"FLAGS_request_trace": True})
            gc.collect()
            gc.disable()
            try:
                stats = fleet_replay(fleet, mk_requests(), events=events)
            finally:
                gc.enable()
                if chaos:
                    paddle.set_flags({"FLAGS_request_trace": False})
                    _fi.clear_plan()
            if chaos:
                slo_breakdown = _rt.slo_breakdown(
                    slo_ttft_ms=d["slo_ttft_ms"], slo_tpot_ms=d["slo_tpot_ms"]
                )
            assert stats["lost"] == 0 and stats["duplicated"] == 0, stats
            per_n[str(n)] = {
                k: stats.get(k)
                for k in ("tokens_per_sec", "p50_tpot_ms", "p99_tpot_ms",
                          "p50_ttft_ms", "p99_ttft_ms", "completed",
                          "evacuated", "replica_failures", "preempted",
                          "swaps_completed", "p99_tpot_swap_ms", "wall_s")
            }
        # ---- round 21: disaggregated-vs-monolithic burst A/B ----
        # the same near-simultaneous shared-prefix burst replayed twice at
        # EQUAL chips: an untiered fleet (replica-local prefix serving
        # only: owner map cut to one entry) vs a prefill/decode split with
        # fleet-global prefix routing, int8 decode KV, and injected
        # migration + decode-replica-death chaos. TTFT/TPOT/hit-rate land
        # in the capture for perf_gate; only the robustness invariants
        # (zero lost/duplicated/failed, global >= local hit rate) are
        # asserted here — timing claims gate across captures, not runs.
        def mk_burst():
            rng = np.random.RandomState(d["seed"] + 1)
            shared = rng.randint(
                0, d["vocab"], (d["prefix_pages"] * d["block_size"],)
            ).tolist()
            reqs, t = [], 0.0
            for i in range(d["burst_requests"]):
                t += rng.exponential(d["burst_gap_s"])
                reqs.append(Request(
                    rid=i,
                    prompt=shared + rng.randint(
                        0, d["vocab"], (int(rng.randint(2, 6)),)).tolist(),
                    max_new_tokens=int(rng.choice([4, 8, 12])),
                    arrival_time=t,
                ))
            return reqs

        def hit_rate(stats_fleet):
            # per-request cap: a preempted request prefills its folded
            # prompt more than once, so raw cached_tokens can exceed the
            # prompt — the rate reported is "fraction of prompt tokens a
            # request never had to compute at least once"
            done = [r for r in stats_fleet.finished
                    if r.outcome == "completed"]
            total = sum(r.prompt_len for r in done)
            return round(
                sum(min(r.cached_tokens, r.prompt_len) for r in done)
                / max(1, total), 4)

        def mk_disagg():
            f = ReplicaFleet(
                [fresh_engine() for _ in range(n_prefill)]
                + [fresh_engine(d["decode_kv_dtype"] or None)
                   for _ in range(width - n_prefill)],
                tiers=["prefill"] * n_prefill
                + ["decode"] * (width - n_prefill),
            )
            f.prewarm()
            return f

        width = max(2, widest)
        n_prefill = max(1, width // 2)
        mono = ReplicaFleet(
            [fresh_engine() for _ in range(width)],
            prefix_owner_cache_size=1,
        )
        gc.collect()
        gc.disable()
        try:
            mono_stats = fleet_replay(mono, mk_burst())
        finally:
            gc.enable()
        assert mono_stats["lost"] == 0 and mono_stats["duplicated"] == 0
        local_rate = hit_rate(mono)

        # clean disagg run: the headline TTFT/TPOT/hit-rate comparison
        # (chaos inflating only one side would make the A/B meaningless)
        disagg = mk_disagg()
        gc.collect()
        gc.disable()
        try:
            disagg_stats = fleet_replay(disagg, mk_burst())
        finally:
            gc.enable()
        assert disagg_stats["lost"] == 0 and disagg_stats["duplicated"] == 0
        assert disagg_stats["migration_failures"] == 0, disagg_stats
        fleet_rate = hit_rate(disagg)
        # fleet-global routing must never do WORSE than replica-local
        # luck on the same burst (one first-miss vs one per intake
        # replica is structural, not timing)
        assert fleet_rate >= local_rate, (fleet_rate, local_rate)

        # chaos disagg run: migrate-site faults mid-burst, then a decode
        # replica killed — the robustness invariants (zero lost/dup/
        # failed, recompute fallbacks fired) hold; its tail is recorded
        # separately, never mixed into the headline
        def migrate_chaos():
            _fi.install_plan(_fi.FaultPlan().add(
                "fleet.kv_migrate.*", "fail", times=2))

        def decode_kill(idx=width - 1):
            _fi.install_plan(_fi.FaultPlan().add(
                f"fleet.replica_step.{idx}", "fail", times=2))

        chaos_fleet = mk_disagg()
        gc.collect()
        gc.disable()
        try:
            chaos_stats = fleet_replay(chaos_fleet, mk_burst(), events=[
                (max(1, int(0.25 * d["burst_requests"])), migrate_chaos),
                (max(2, int(0.6 * d["burst_requests"])), decode_kill),
            ])
        finally:
            gc.enable()
            _fi.clear_plan()
        assert chaos_stats["lost"] == 0 and chaos_stats["duplicated"] == 0
        assert chaos_stats["migration_failures"] == 0, chaos_stats

        head = per_n[str(widest)]
        tps_1 = per_n.get("1", {}).get("tokens_per_sec")
        res = {
            "n_replicas": widest,
            "n_requests": d["n_requests"],
            "tokens_per_sec": head["tokens_per_sec"],
            "p50_tpot_ms": head["p50_tpot_ms"],
            "p99_tpot_ms": head["p99_tpot_ms"],
            "p99_ttft_ms": head["p99_ttft_ms"],
            "p99_tpot_swap_ms": head["p99_tpot_swap_ms"],
            "swap_blip_ratio": (
                round(head["p99_tpot_swap_ms"] / head["p99_tpot_ms"], 3)
                if head.get("p99_tpot_swap_ms") and head.get("p99_tpot_ms")
                else None
            ),
            "scaling_vs_1replica": (
                round(head["tokens_per_sec"] / tps_1, 3)
                if head.get("tokens_per_sec") and tps_1 else None
            ),
            # round 21: the disaggregated A/B headline fields (gated)
            "p99_ttft_burst_ms": disagg_stats.get("p99_ttft_ms"),
            "disagg_p99_tpot_ms": disagg_stats.get("p99_tpot_ms"),
            "mono_p99_ttft_burst_ms": mono_stats.get("p99_ttft_ms"),
            "ttft_burst_improvement": (
                round(mono_stats["p99_ttft_ms"]
                      / disagg_stats["p99_ttft_ms"], 3)
                if mono_stats.get("p99_ttft_ms")
                and disagg_stats.get("p99_ttft_ms") else None
            ),
            "fleet_prefix_hit_rate": fleet_rate,
            "local_prefix_hit_rate": local_rate,
            "migrations": disagg_stats["migrations"],
            "migration_fallbacks": chaos_stats["migration_fallbacks"],
            # max over the clean AND chaos runs: a failure anywhere fails
            "migration_failures": max(disagg_stats["migration_failures"],
                                      chaos_stats["migration_failures"]),
            "migration_cost_per_page_ms": (
                round(1000.0 * disagg.migration_wall_s
                      / disagg.migrated_pages_total, 4)
                if disagg.migrated_pages_total else None
            ),
            "p99_ttft_burst_chaos_ms": chaos_stats.get("p99_ttft_ms"),
            "chaos_crc_rejects": chaos_stats["crc_rejects"],
            "slo_breakdown": slo_breakdown,
            "replicas": per_n,
            "note": (
                "same seeded replay at each fleet width; widest run takes a "
                "mid-run step_<N>/ weight hot-swap (same weights: drain/"
                "stream/re-admit machinery measured, greedy ids preserved) "
                "and a FaultPlan replica kill (evacuation + re-dispatch); "
                "lost==duplicated==0 asserted"
            ),
            "attribution": _attribution(
                (head.get("p50_tpot_ms") or 0) / 1000.0 or None, origin="serving"
            ),
        }
        res["fleet_dims"] = {k: d[k] for k in (
            "vocab", "hidden", "layers", "heads", "kv_heads", "ffn",
            "max_seq", "block_size", "max_batch", "seed", "gap_s",
            "swap_at", "kill_at",
        )}
        res["fleet_dims"]["replicas"] = list(d["replicas"])
        res["disagg_dims"] = {
            "prefill_replicas": n_prefill,
            "decode_replicas": width - n_prefill,
            "kv_dtype": d["decode_kv_dtype"],
            "burst_requests": d["burst_requests"],
            "burst_gap_s": d["burst_gap_s"],
            "prefix_pages": d["prefix_pages"],
        }
        # chaos observability coverage over EVERY injection this capture
        # made (replica kills in the widest swap run, migrate faults and
        # the decode kill in the chaos disagg run) — zero-gated
        cov = _tl.chaos_coverage()
        res["chaos_faults_injected"] = cov["injected"]
        res["unobserved_faults"] = cov["unobserved_faults"]
        res["timeline_dropped_events"] = _tl.recorder().dropped
        return res
    finally:
        paddle.set_flags({"FLAGS_incident_timeline": False})
        shutil.rmtree(ck_root, ignore_errors=True)


def _qos_dims():
    """QoS overload-replay knobs (round 19), all BENCH_QOS_* overridable
    (tier-1 capture tests run a seconds-scale replay; a shrunken run
    records qos_dims so it can't masquerade). The replay offers
    `overload_factor` x the decode-slot capacity in a burst of mixed
    tenants/priorities; `free_rate`/`free_burst` are the rate-limited
    tenant's token bucket."""
    g = os.environ.get
    return {
        "vocab": int(g("BENCH_QOS_VOCAB", 8192)),
        "hidden": int(g("BENCH_QOS_HIDDEN", 256)),
        "layers": int(g("BENCH_QOS_LAYERS", 2)),
        "heads": int(g("BENCH_QOS_HEADS", 8)),
        "kv_heads": int(g("BENCH_QOS_KV_HEADS", 4)),
        "ffn": int(g("BENCH_QOS_FFN", 688)),
        "max_seq": int(g("BENCH_QOS_MAX_SEQ", 128)),
        "block_size": int(g("BENCH_QOS_BLOCK", 16)),
        "max_batch": int(g("BENCH_QOS_BATCH", 4)),
        "n_requests": int(g("BENCH_QOS_REQUESTS", 40)),
        "max_new": int(g("BENCH_QOS_MAX_NEW", 8)),
        "seed": int(g("BENCH_QOS_SEED", 19)),
        "gap_s": float(g("BENCH_QOS_GAP", 0.001)),
        "free_rate": float(g("BENCH_QOS_FREE_RATE", 300.0)),
        "free_burst": float(g("BENCH_QOS_FREE_BURST", 120.0)),
        "enter_pressure": float(g("BENCH_QOS_ENTER", 0.9)),
        "exit_pressure": float(g("BENCH_QOS_EXIT", 0.5)),
        "cooldown_s": float(g("BENCH_QOS_COOLDOWN", 0.05)),
        "capped_max_new": int(g("BENCH_QOS_CAP", 4)),
        "submit_probe_n": int(g("BENCH_QOS_SUBMIT_PROBE", 2000)),
    }


def _build_qos():
    """Round 19: overload protection under a >= 2x-capacity mixed-tenant
    burst. The SAME seeded traffic runs twice: the priority-0 ("gold")
    class alone (uncontended baseline), then the full burst through the
    QoS scheduler (weighted-fair dequeue, per-tenant rate limit, brownout
    ladder). Gated fields: fairness_index (throughput-polarity — falling
    means weighted-fair dequeue stopped holding), p99_tpot_gold_ms and
    gold_p99_vs_uncontended (time-polarity — growing means priority
    admission/preemption stopped shielding the top class); qos_dims is the
    shape guard. Sheds are counted by reason; zero-loss is asserted here
    (every offered request terminal exactly once), not just reported."""
    import gc
    import timeit

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import InferenceEngine
    from paddle_tpu.inference.qos import (
        BrownoutConfig, QoSConfig, QoSPolicy, TenantConfig, tenant_report,
    )
    from paddle_tpu.inference.scheduler import (
        ContinuousBatchingScheduler, Request, percentiles, replay,
    )
    from paddle_tpu.models.llama import LlamaForCausalLM

    d = _qos_dims()
    paddle.seed(0)
    model = LlamaForCausalLM(
        vocab_size=d["vocab"], hidden_size=d["hidden"],
        num_hidden_layers=d["layers"], num_attention_heads=d["heads"],
        num_key_value_heads=d["kv_heads"], intermediate_size=d["ffn"],
    )
    model.eval()

    TENANTS = (("gold", 0, 4.0), ("silver", 1, 2.0),
               ("bronze", 2, 1.0), ("free", 2, 1.0))

    def mk_requests(only_tenant=None):
        rng = np.random.RandomState(d["seed"])
        max_prompt = max(8, d["max_seq"] // 4)
        reqs, t = [], 0.0
        for i in range(d["n_requests"]):
            t += rng.exponential(d["gap_s"])
            tenant, prio, _w = TENANTS[i % len(TENANTS)]
            r = Request(
                rid=i,
                prompt=rng.randint(0, d["vocab"], (int(rng.randint(4, max_prompt)),)).tolist(),
                max_new_tokens=d["max_new"],
                arrival_time=t, tenant=tenant, priority=prio,
            )
            if only_tenant is None or tenant == only_tenant:
                reqs.append(r)
        return reqs

    def fresh_engine():
        eng = InferenceEngine(
            model, max_seq_len=d["max_seq"], block_size=d["block_size"],
            max_batch=d["max_batch"], decode_batch_buckets=(d["max_batch"],),
        )
        for b in eng.prefill_buckets:  # warmup: compile outside the replay
            pages = eng.pool.alloc(eng.pool.blocks_for_tokens(b))
            eng.prefill(list(range(1, b + 1)), pages)
            eng.pool.reset()
        pages = eng.pool.alloc(1)
        eng.decode([1], [0], [1], [pages])
        eng.pool.reset()
        return eng

    def mk_policy():
        return QoSPolicy(QoSConfig(
            tenants={
                name: TenantConfig(
                    weight=w,
                    rate_tokens_per_s=d["free_rate"] if name == "free" else None,
                    burst_tokens=d["free_burst"] if name == "free" else None,
                )
                for name, _p, w in TENANTS
            },
            brownout=BrownoutConfig(
                enter_pressure=d["enter_pressure"],
                exit_pressure=d["exit_pressure"],
                cooldown_s=d["cooldown_s"],
                capped_max_new=d["capped_max_new"],
            ),
        ))

    gc.collect()
    gc.disable()
    try:
        # uncontended baseline: the gold class alone, no QoS layer
        base_sched = ContinuousBatchingScheduler(fresh_engine())
        gold_only = mk_requests("gold")
        replay(base_sched, gold_only)
        base_gold_tpots = [iv * 1000.0 for r in gold_only
                           for iv in np.diff(r.token_times)]
        base_p99 = percentiles("x", base_gold_tpots)["p99_x"]

        # the contended run: full burst through the QoS scheduler
        qos = mk_policy()
        sched = ContinuousBatchingScheduler(fresh_engine(), qos=qos)
        reqs = mk_requests()
        stats = replay(sched, reqs)
    finally:
        gc.enable()

    # zero-loss: every offered request terminal exactly once
    assert len(sched.finished) == len(reqs), (len(sched.finished), len(reqs))
    assert sorted(r.rid for r in sched.finished) == [r.rid for r in reqs]
    assert all(r.outcome in ("completed", "shed") for r in reqs)

    rep = tenant_report(sched.finished, qos.config)
    per_tenant_p99 = {
        t: rep["tenants"][t].get("p99_tpot_ms")
        for t in rep["tenants"]
    }
    gold_tpots = [iv * 1000.0 for r in reqs if r.tenant == "gold"
                  for iv in np.diff(r.token_times)]
    gold_p99 = percentiles("x", gold_tpots)["p99_x"]
    sheds = sum(qos.shed_counts.values())

    # per-submit QoS overhead: the admission gates on an already-drained
    # scheduler (rate bucket + brownout + bounded-queue checks), measured
    # against the same submit with no QoS layer (BASELINE round 19)
    def probe(policy):
        s = ContinuousBatchingScheduler(fresh_engine(), qos=policy)
        s.drain()
        n = d["submit_probe_n"]
        reqs_p = [Request(rid=i, prompt=[1, 2, 3, 4], max_new_tokens=4)
                  for i in range(n)]
        it = iter(reqs_p)
        return timeit.timeit(lambda: s.submit(next(it)), number=n) / n

    t_plain = probe(None)
    t_qos = probe(mk_policy())

    res = {
        "n_requests": len(reqs),
        "overload_factor": round(len(reqs) / d["max_batch"], 2),
        "tokens_per_sec": stats["tokens_per_sec"],
        "p99_ttft_ms": stats["p99_ttft_ms"],
        "p99_tpot_ms": stats["p99_tpot_ms"],
        "p99_tpot_gold_ms": gold_p99,
        "p99_tpot_uncontended_ms": base_p99,
        "gold_p99_vs_uncontended": (
            round(gold_p99 / base_p99, 3) if gold_p99 and base_p99 else None
        ),
        "per_tenant_p99_tpot_ms": per_tenant_p99,
        "fairness_index": rep["fairness_index"],
        "completed": sum(1 for r in reqs if r.outcome == "completed"),
        "shed": sheds,
        "shed_rate": round(sheds / len(reqs), 3),
        "sheds_by_reason": dict(qos.shed_counts),
        "preempted": sched.preempted_total,
        "brownout_transitions": qos.brownout.transitions,
        "brownout_final_step": qos.brownout.step,
        "submit_overhead_us": round((t_qos - t_plain) * 1e6, 3),
        "submit_plain_us": round(t_plain * 1e6, 3),
        "wall_s": stats["wall_s"],
        "note": (
            "same seeded >= 2x-capacity burst: gold-alone baseline, then "
            "the full mixed-tenant run under weighted-fair dequeue + rate "
            "limit + brownout ladder; zero-loss asserted, sheds counted by "
            "reason, fairness over weight-normalized generated tokens"
        ),
        "attribution": _attribution(
            (stats.get("p50_tpot_ms") or 0) / 1000.0 or None, origin="serving"
        ),
    }
    res["qos_dims"] = {k: d[k] for k in (
        "vocab", "hidden", "layers", "heads", "kv_heads", "ffn", "max_seq",
        "block_size", "max_batch", "max_new", "seed", "gap_s", "free_rate",
        "free_burst", "enter_pressure", "exit_pressure", "cooldown_s",
        "capped_max_new",
    )}
    res["qos_dims"]["tenants"] = [
        {"name": n, "priority": p, "weight": w} for n, p, w in TENANTS
    ]
    return res


def _input_dims():
    """Input-bound streaming-bench knobs, all BENCH_INPUT_* overridable
    (tier-1 capture tests run a seconds-scale pipeline; a shrunken run
    records input_dims so it can't masquerade)."""
    g = os.environ.get
    return {
        "n_samples": int(g("BENCH_INPUT_SAMPLES", 4096)),
        "global_batch": int(g("BENCH_INPUT_BATCH", 64)),
        "features": int(g("BENCH_INPUT_FEATURES", 1024)),
        "hidden": int(g("BENCH_INPUT_HIDDEN", 2048)),
        "classes": int(g("BENCH_INPUT_CLASSES", 128)),
        # host work per SAMPLE: elements of np.sin ground through numpy in
        # __getitem__ — sized so the reader is comparable to the step (the
        # regime where prefetch overlap pays; a reader >> step is input-
        # bound no matter what, a reader << step hides for free)
        "reader_work": int(g("BENCH_INPUT_READER_WORK", 100_000)),
        "steps": int(g("BENCH_INPUT_STEPS", 24)),
        "seed": int(g("BENCH_INPUT_SEED", 7)),
    }


def _build_input_stream():
    """Round 12: the streaming data tier under an input-heavy synthetic
    reader — a tiny MLP step fed by paddle_tpu.io.streaming.StreamingLoader,
    measured prefetch-ON (double-buffered device ring, donated slots) vs
    prefetch-OFF (synchronous read+collate+H2D inline) on the same seeded
    stream. The step-time difference must be attributed by the pipeline's
    own input_wait_s measurements (the guardian/flight-recorder field), and
    samples/s + p99 wait gate in tools/perf_gate.py."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.io import Dataset
    from paddle_tpu.io.streaming import StreamingLoader
    from paddle_tpu.io.streaming import stats as instats

    d = _input_dims()

    class HeavyReader(Dataset):
        """Deterministic per-sample host work: the synthetic stand-in for
        decode/augment/tokenize CPU cost."""

        def __len__(self):
            return d["n_samples"]

        def __getitem__(self, i):
            rng = np.random.RandomState((d["seed"] * 1_000_003 + i) % 2**31)
            w = rng.standard_normal(d["reader_work"]).astype(np.float32)
            f = d["features"]
            feat = np.sin(w[: (w.size // f) * f]).reshape(f, -1).mean(axis=1)
            return feat.astype(np.float32), np.int64(i % d["classes"])

    dataset = HeavyReader()

    def build_step():
        paddle.seed(d["seed"])
        model = paddle.nn.Sequential(
            paddle.nn.Linear(d["features"], d["hidden"]),
            paddle.nn.ReLU(),
            paddle.nn.Linear(d["hidden"], d["classes"]),
        )
        opt = paddle.optimizer.SGD(0.01, parameters=model.parameters())

        @paddle.jit.to_static
        def train_step(x, y):
            loss = paddle.nn.functional.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        return train_step

    def measure(prefetch_depth):
        """(mean step s, p99/mean wait s, final loss) over d['steps'] after
        warmup, waits from the pipeline's OWN stats (the same accumulator
        the guardian reads as input_wait_s)."""
        train_step = build_step()
        loader = StreamingLoader(
            dataset, d["global_batch"], seed=d["seed"], shuffle=True,
            drop_last=True, prefetch_depth=prefetch_depth,
            donate=prefetch_depth > 0, source="bench_input",
        )
        it = iter(loader)
        steps, walls, waits, loss = d["steps"], [], [], None

        def nxt():
            nonlocal it
            try:
                return next(it)
            except StopIteration:  # epoch rolled: keep streaming
                it = iter(loader)
                return next(it)

        for _ in range(3):  # warmup: compile + ring fill
            x, y = nxt()
            float(train_step(x, y).numpy())
        instats.take_step_wait()  # drop warmup waits from the measured window
        for _ in range(steps):
            t0 = time.perf_counter()
            x, y = nxt()
            loss = float(train_step(x, y).numpy())
            walls.append(time.perf_counter() - t0)
            waits.append(instats.take_step_wait() or 0.0)
        import numpy as _np

        return (
            float(_np.mean(walls)),
            float(_np.percentile(waits, 99)),
            float(_np.mean(waits)),
            loss,
        )

    dt_on, p99_on, mean_on, loss_on = measure(2)
    verdict_on = instats.starvation_verdict()  # before the off-run pollutes the window
    dt_off, p99_off, mean_off, loss_off = measure(0)
    step_delta = dt_off - dt_on
    wait_delta = mean_off - mean_on
    res = {
        "n_samples": d["n_samples"],
        "global_batch": d["global_batch"],
        "steps": d["steps"],
        "input_dims": {k: d[k] for k in ("features", "hidden", "classes",
                                         "reader_work")},
        "prefetch_depth": 2,
        "ms_per_step": round(dt_on * 1000, 3),
        "samples_per_sec": round(d["global_batch"] / dt_on, 1),
        "p99_input_wait_ms": round(p99_on * 1000, 3),
        "mean_input_wait_ms": round(mean_on * 1000, 3),
        "final_loss": loss_on,
        "prefetch_off": {
            "ms_per_step": round(dt_off * 1000, 3),
            "samples_per_sec": round(d["global_batch"] / dt_off, 1),
            "p99_input_wait_ms": round(p99_off * 1000, 3),
            "mean_input_wait_ms": round(mean_off * 1000, 3),
            "final_loss": loss_off,
        },
        # how much of the prefetch win the pipeline's own wait metric
        # explains: ~1.0 means the step-time delta IS hidden input wait
        "wait_attribution": {
            "step_delta_ms": round(step_delta * 1000, 3),
            "wait_delta_ms": round(wait_delta * 1000, 3),
            "explained_fraction": (
                round(wait_delta / step_delta, 3) if step_delta > 0 else None
            ),
        },
        "overlap_efficiency": (
            round(max(0.0, min(1.0, 1.0 - mean_on / mean_off)), 3)
            if mean_off > 0 else None
        ),
        "verdict": verdict_on,
        "attribution": _attribution(dt_on),
    }
    return res


def _moe_dims():
    """MoE + long-context bench knobs (ROADMAP item 5 down payment), all
    BENCH_MOE_* overridable. Defaults target one TPU chip; the tier-1
    capture test shrinks seq/experts to seconds scale (moe_dims recorded)."""
    g = os.environ.get
    return {
        "seq": int(g("BENCH_MOE_SEQ", 16384)),
        "d_model": int(g("BENCH_MOE_DMODEL", 512)),
        "heads": int(g("BENCH_MOE_HEADS", 8)),
        "kv_heads": int(g("BENCH_MOE_KV_HEADS", 2)),
        "experts": int(g("BENCH_MOE_EXPERTS", 8)),
        "top_k": int(g("BENCH_MOE_TOPK", 2)),
        "capacity": float(g("BENCH_MOE_CAPACITY", 1.2)),
        "ffn": int(g("BENCH_MOE_FFN", 1024)),
        "steps": int(g("BENCH_MOE_STEPS", 6)),
    }


def _build_moe_longcontext():
    """ROADMAP item 5 operating point: a sparse long-context block —
    GQA flash attention (the r4 kernel's native head-group mapping), exact
    ring attention over the sep axis (the seq >= 16k path), and MoE
    expert-parallel routing with a REAL capacity factor (1.2 train) whose
    token drops land in the guardian telemetry counters
    (`paddle_tpu_moe_{routed,dropped}_tokens_total`).

    COMPILED by default (round 20): routing is fully jittable and the step
    RETURNS each layer's drop count as an on-device scalar read once at the
    step boundary — no host branch inside the trace — so the whole stack
    runs through to_static over the sep×ep mesh (fleet hybrid topology ->
    SpecLayout build_mesh; ep rides the dp axis, sep is the ring axis) and
    the record carries real attribution like the dense configs.
    BENCH_MOE_EAGER=1 is the escape hatch back to the eager step. The
    static-capture fusion probe records the `fuse_moe`
    dispatch->expert->combine match count perf_gate gates."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.incubate.distributed.models.moe import ExpertLayer, MoELayer
    from paddle_tpu.ops.ring_attention import ring_attention_op

    d = _moe_dims()
    hd = d["d_model"] // d["heads"]
    B, S = 1, d["seq"]
    eager = os.environ.get("BENCH_MOE_EAGER", "") == "1"
    sep = int(os.environ.get("BENCH_MOE_SEP", "1"))
    ep = int(os.environ.get("BENCH_MOE_EP", "1"))

    # the sep×ep mesh, built from SpecLayout roles: fleet.init routes the
    # hybrid dims through spec_layout.build_mesh and registers the result
    # as THE global mesh. ep rides the data axis (the reference's
    # moe_group == dp convention); on one chip both degrees are 1 (the
    # dispatch/combine einsums, ring layout, and capacity math are
    # identical, the collectives are no-ops) — dryrun_multichip runs the
    # real sep×ep decomposition on 8 devices
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": ep, "sep_degree": sep}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().mesh

    paddle.seed(0)
    q_proj = nn.Linear(d["d_model"], d["heads"] * hd)
    kv_proj = nn.Linear(d["d_model"], 2 * d["kv_heads"] * hd)
    out_proj = nn.Linear(d["heads"] * hd, d["d_model"])
    ring_qkv = nn.Linear(d["d_model"], 3 * d["heads"] * hd)
    ring_out = nn.Linear(d["heads"] * hd, d["d_model"])

    def make_moe():
        return MoELayer(
            d_model=d["d_model"],
            experts=[ExpertLayer(d["d_model"], d["ffn"])
                     for _ in range(d["experts"])],
            gate={"type": "gshard", "top_k": d["top_k"]},
            ep_axis="dp",
        )

    moe0, moe1 = make_moe(), make_moe()
    for m in (moe0, moe1):
        m.gate.capacity_factor = (d["capacity"], d["capacity"] * 2)
    params = (q_proj.parameters() + kv_proj.parameters()
              + out_proj.parameters() + ring_qkv.parameters()
              + ring_out.parameters() + moe0.parameters() + moe1.parameters())
    opt = paddle.optimizer.AdamW(1e-4, parameters=params, weight_decay=0.01)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(B, S, d["d_model"]).astype(np.float32) * 0.1
    )

    def forward(h):
        # block 0: causal GQA attention (flash kernel on TPU: S >= 512 and
        # h_kv | h_q dispatch the native head-group mapping) + MoE FFN
        q = q_proj(h).reshape([B, S, d["heads"], hd])
        kv = kv_proj(h).reshape([B, S, 2 * d["kv_heads"], hd])
        k, v = kv[:, :, : d["kv_heads"]], kv[:, :, d["kv_heads"]:]
        a = nn.functional.scaled_dot_product_attention(q, k, v, is_causal=True)
        h = h + out_proj(a.reshape([B, S, d["heads"] * hd]))
        h = h + moe0(h)
        # block 1: exact ring attention with the sequence sharded over the
        # sep axis of the SAME sep×ep mesh (the seq >= 16k long-context
        # path), recorded as one fixed-arity op (ring_attention_op)
        qkv = ring_qkv(h).reshape([B, S, 3 * d["heads"], hd])
        rq = qkv[:, :, : d["heads"]]
        rk = qkv[:, :, d["heads"]: 2 * d["heads"]]
        rv = qkv[:, :, 2 * d["heads"]:]
        r = ring_attention_op(rq, rk, rv, mesh=mesh, causal=True)
        h = h + ring_out(r.reshape([B, S, d["heads"] * hd]))
        h = h + moe1(h)
        return h

    def moe_longcontext_step(xb):
        out = forward(xb)
        loss = (out * out).mean() + 0.01 * (moe0.l_aux + moe1.l_aux)
        loss.backward()
        opt.step()
        opt.clear_grad()
        # the post-step scalar-read contract: the per-layer drop counts
        # leave the (traced) step as program OUTPUTS; the host performs
        # ONE blocking read per layer at the step boundary
        # (record_drop_telemetry(dropped=...)), never inside the trace
        return loss, moe0.last_drop_count(), moe1.last_drop_count()

    step = (moe_longcontext_step if eager
            else paddle.jit.to_static(moe_longcontext_step))
    state = {}

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            loss, d0, d1 = step(x)
        state["drops"] = (d0, d1)
        val = float(loss.numpy())
        return time.perf_counter() - t0, val

    dt_step, final_loss = _slope_measure(run, d["steps"], warm=2)
    if dt_step <= 0:
        # slope noise at CI-shrunk dims (one-step deltas): fall back to
        # a plain per-step average so the roofline (mfu/hbm_util) and
        # tokens_per_sec stay well-defined
        n_avg = max(2, d["steps"])
        t_avg, final_loss = run(n_avg)
        dt_step = t_avg / n_avg
    attribution = (_attribution(dt_step) if not eager else {
        "attribution": "unavailable",
        "why": "BENCH_MOE_EAGER=1 escape hatch (uncompiled eager step; "
               "no compiled-program cost record to attribute)",
    })

    # capacity-drop counters: ONE blocking read per layer of the LAST
    # step's returned device scalars, into the guardian telemetry +
    # the capture record (eager steps return concrete values — the
    # same read path)
    drops = {
        name: m.record_drop_telemetry(name=name, dropped=dv)
        for (name, m), dv in zip(
            (("moe0", moe0), ("moe1", moe1)), state["drops"]
        )
    }
    routed = sum(s["routed"] for s in drops.values() if s)
    dropped = sum(s["dropped"] for s in drops.values() if s)

    # fusion-coverage probe: the SAME forward, eager-converted to a static
    # Program and run through the default pass pipeline — `fuse_moe` must
    # collapse both layers' dispatch->expert->combine chains (match count
    # perf-gated like the `passes` config)
    fusion = {}
    try:
        from paddle_tpu.jit import capture_program
        from paddle_tpu.static import passes as passes_mod

        program, feed_names, fetch_list = capture_program(
            forward, x, feed_names=["h"]
        )
        fetch_vid = program.resolve_fetch(fetch_list[0])
        _work, pres = passes_mod.run_default_pipeline(
            program, fetch_vars=[fetch_vid], feed_names=feed_names
        )
        fusion = {"matches": pres.matches, "rewritten_ops": pres.rewritten_ops}
    except Exception as e:  # noqa: BLE001 — the probe must never kill the config
        fusion = {"error": str(e)[-200:]}

    from paddle_tpu.distributed.sharding import spec_layout as _slx

    res = {
        "batch": B,
        "seq": S,
        "heads": f"{d['heads']}q/{d['kv_heads']}kv",
        "experts": d["experts"],
        "top_k": d["top_k"],
        "capacity_factor": d["capacity"],
        "moe_dims": {k: d[k] for k in ("d_model", "ffn")},
        "sep_ep_dims": {"sep": sep, "ep": ep,
                        "mesh_axes": _slx.mesh_degrees(mesh)},
        "steps": d["steps"],
        "compiled": not eager,
        "ms_per_step": round(dt_step * 1000, 2),
        "tokens_per_sec": round(B * S / dt_step, 1),
        "final_loss": final_loss,
        "moe_drops": {
            "routed_per_step": routed,
            "dropped_per_step": dropped,
            "drop_fraction": round(dropped / routed, 4) if routed else None,
            "per_layer": drops,
        },
        "note": (
            "GQA flash attention + exact ring attention (sep axis) + "
            "GShard-capacity MoE EP routing in one to_static step over the "
            "sep×ep mesh; per-layer drop counts return as on-device scalars "
            "read once post-step into paddle_tpu_moe_*_tokens_total "
            "(guardian telemetry); BENCH_MOE_EAGER=1 for the eager baseline"
        ),
        "attribution": attribution,
    }
    if fusion.get("matches") is not None:
        res["matches"] = fusion["matches"]
        res["rewritten_ops"] = fusion["rewritten_ops"]
    elif fusion:
        res["fusion_probe_error"] = fusion.get("error")
    return res


def _release_device_memory():
    """Drop compiled executables + dead buffers between configs — the
    Llama-shaped config holds ~8GB of AdamW state; without this the peak
    re-measure after it can RESOURCE_EXHAUST on the 16GB chip."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def _build_resnet(steps):
    """BASELINE configs[0]: ResNet-50 ImageNet classification images/sec,
    synthetic data, bf16 AMP, Momentum+CE — measured BOTH dygraph-eager and
    @to_static (the north-star metric line names ResNet-50 images/sec).
    Batch backs off 64 -> 32 -> 16 when HBM is tight."""
    batches = [int(os.environ.get("BENCH_RESNET_BATCH", 64))]
    while batches[-1] > 16:
        batches.append(max(16, batches[-1] // 2))  # floor: never below 16
    return _oom_backoff(batches, lambda b: _build_resnet_at(steps, b))


def build_resnet_step(batch):
    """ResNet-50 train-step builder (same contract as build_train_step for
    the ERNIE configs). Returns (model, static_step, eager_step, imgs,
    labels)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    opt = paddle.optimizer.Momentum(0.1, parameters=model.parameters(), weight_decay=1e-4)
    rng = np.random.RandomState(0)
    imgs = paddle.to_tensor(rng.randn(batch, 3, 224, 224).astype(np.float32))
    labels = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int64))

    def step_body(imgs, labels):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            logits = model(imgs)
            loss = paddle.nn.functional.cross_entropy(logits, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return model, paddle.jit.to_static(step_body), step_body, imgs, labels


def _build_resnet_at(steps, batch):
    import time

    model, static_step, step_body, imgs, labels = build_resnet_step(batch)

    def measure(fn, n_steps):
        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                loss = fn(imgs, labels)
            val = float(loss.numpy())  # host fetch forces the chain
            return time.perf_counter() - t0, val

        return _slope_measure(run, n_steps)

    dt_static, loss_static = measure(static_step, steps)
    dt_eager, _ = measure(step_body, max(4, steps // 4))
    return {
        "batch": batch,
        "ms_per_step": round(dt_static * 1000, 2),
        "images_per_sec": round(batch / dt_static, 1),
        "images_per_sec_dygraph": round(batch / dt_eager, 1),
        "final_loss": loss_static,
        "attribution": _attribution(dt_static),
    }


def _build_ppocr(n_images=8, n_boxes=3):
    """BASELINE configs[2]: PP-OCR det+rec end-to-end latency on one chip.
    The weights are untrained, so DBNet's box output on a synthetic page is
    arbitrary — det and rec are therefore timed EXPLICITLY (det forward +
    postprocess on the full page; CRNN on a fixed batch of n_boxes crops +
    CTC decode) and e2e = det + rec, the pipeline models/ocr.py runs."""
    import time

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.ocr import OCRSystem, ctc_greedy_decode, db_postprocess

    paddle.seed(0)
    sys_ = OCRSystem()
    sys_.eval()
    rng = np.random.RandomState(0)
    img = paddle.to_tensor(rng.rand(1, 3, 640, 640).astype(np.float32))
    crops = paddle.to_tensor(
        rng.rand(n_boxes, *sys_.rec_image_shape).astype(np.float32)
    )

    # deployment runs the frozen (compiled) predictor, not eager dispatch —
    # eager's per-op latency would swamp the device time
    det_fwd = paddle.jit.to_static(lambda im: sys_.det(im))
    rec_fwd = paddle.jit.to_static(lambda c: sys_.rec(c))

    def det_once():
        return db_postprocess(det_fwd(img))

    def rec_once():
        return ctc_greedy_decode(rec_fwd(crops))

    def measure(fn, n_steps):
        def run(n):
            t0 = time.perf_counter()
            out = None
            for _ in range(n):
                out = fn()  # both fns end host-side (numpy postprocess)
            return time.perf_counter() - t0, out

        return _slope_measure(run, n_steps, warm=2)[0]

    det_s = measure(det_once, n_images)
    rec_s = measure(rec_once, n_images)
    e2e = det_s + rec_s
    return {
        "det_ms_per_image": round(det_s * 1000, 2),
        "rec_ms_per_batch": round(rec_s * 1000, 2),
        "rec_boxes": n_boxes,
        "ms_per_image_e2e": round(e2e * 1000, 2),
        "images_per_sec": round(1.0 / e2e, 2),
        # e2e spans BOTH compiled programs (det + rec): sum their records
        # so the roofline numerator matches the timed region
        "attribution": _attribution(e2e, combine_last=2),
    }


_CHILD = [None]  # the running child's Popen: the SIGTERM handler kills it


def _run_config_child(kind, steps):
    """Run one bench config in a child process: the chip belongs to one
    process at a time, and the parent never touches jax. Always returns a
    dict — measured stats or an explicit {"skipped": why}: a child failure
    must never abort the capture (r5 forfeited its whole record to one
    config's timeout)."""
    import subprocess

    env = dict(os.environ)
    env["BENCH_CHILD"] = kind
    env["BENCH_CHILD_STEPS"] = str(steps)
    budget = min(3600.0, _remaining())
    if budget <= _est(kind, default=30):
        return {"skipped": "deadline"}
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    _CHILD[0] = proc
    try:
        out, err = proc.communicate(timeout=budget)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"bench child {kind}: killed at the global deadline",
              file=sys.stderr)
        return {"skipped": "deadline"}
    finally:
        _CHILD[0] = None
    if proc.returncode == 0:
        try:
            return json.loads(out.strip().splitlines()[-1])
        except (ValueError, IndexError):
            # rc=0 but unparsable/empty stdout (stray atexit print, ...)
            # — record it, never abort the capture
            print(f"bench child {kind}: unparsable stdout", file=sys.stderr)
            return {"skipped": "error", "error": "unparsable child stdout"}
    if "RESOURCE_EXHAUSTED" in err:
        # distinguishable from BENCH_SKIP_*: the detail records WHY
        print(f"bench child {kind}: RESOURCE_EXHAUSTED, skipped", file=sys.stderr)
        return {"skipped": "RESOURCE_EXHAUSTED"}
    print(f"bench child {kind} failed:\n{err[-3000:]}", file=sys.stderr)
    return {"skipped": "error", "error": err[-400:]}


def _child_seq128(steps):
    """The headline: ERNIE-3.0-base at BENCH_BATCH x BENCH_SEQ."""
    seq = int(os.environ.get("BENCH_SEQ", 128))
    return _build(
        int(os.environ.get("BENCH_BATCH", 64)), seq,
        heads=int(os.environ.get("BENCH_HEADS", 12)),
        max_pos=max(512, seq), steps=steps,
    )


def _child_4096(steps):
    # batch 3 over batch 2: the fixed AdamW/copy costs amortize over 1.5x
    # tokens (r5: MFU ~0.70 vs ~0.68); fall back to batch 2 on OOM instead
    # of failing the config.
    # attn_dropout=0.1: the real pretrain regime (in-kernel dropout, r5)
    return _oom_backoff(
        (3, 2),
        lambda b: _build(batch=b, seq=4096, heads=6, max_pos=4096,
                         steps=steps, attn_dropout=0.1),
    )


class _Snapshot:
    """The un-forfeitable capture: one result dict, re-printed as a complete
    JSON line after every config resolves. The driver reads the LAST valid
    line, so the record can only GROW — a timeout mid-run costs the configs
    not yet run (which the final state marks as explicit skips), never the
    ones already measured."""

    CONFIGS = ("seq128", "passes", "seq4096", "llama3_shape", "resnet50",
               "ppocr_e2e", "serving", "fleet", "qos", "input_stream",
               "moe_longcontext")

    def __init__(self):
        self.result = {
            "metric": "ernie3.0-base tokens/sec/chip",
            "value": None,
            "unit": "tokens/s",
            "vs_baseline": None,
            "detail": {
                "configs": {k: "pending" for k in self.CONFIGS},
            },
        }

    def resolve(self, key, status):
        self.result["detail"]["configs"][key] = status
        self.emit()

    def finalize_pending(self, why="deadline", signal_safe=False):
        """Terminal emit: anything still pending (only possible if a config
        path escaped its own skip handling) becomes an explicit skip.
        signal_safe: emit via raw os.write — print() on the buffered stdout
        is not reentrant (RuntimeError if the signal landed inside another
        print, and it could splice into a half-written line); the leading
        newline guarantees the snapshot is a complete line of its own."""
        for k, st in self.result["detail"]["configs"].items():
            if st == "pending":
                self.result["detail"]["configs"][k] = f"skipped:{why}"
                self.result["detail"].setdefault(k, {"skipped": why})
        if signal_safe:
            os.write(1, b"\n" + json.dumps(self.result).encode() + b"\n")
        else:
            self.emit()

    def emit(self):
        print(json.dumps(self.result), flush=True)


def main():
    child = os.environ.get("BENCH_CHILD")
    if child:
        steps_c = int(os.environ.get("BENCH_CHILD_STEPS", 8))
        builders = {
            "peak": lambda: {"peak_flops": _measured_peak_flops()},
            "seq128": lambda: _child_seq128(steps_c),
            "passes": _measure_passes,
            "llama": lambda: _build_llama(steps=steps_c),
            "ernie4096": lambda: _child_4096(steps_c),
            "resnet": lambda: _build_resnet(steps=steps_c),
            "ocr": lambda: _build_ppocr(n_images=steps_c),
            "serving": _build_serving,
            "fleet": _build_fleet,
            "qos": _build_qos,
            "input_stream": _build_input_stream,
            "moe_longcontext": _build_moe_longcontext,
        }
        if child not in builders:
            raise ValueError(f"unknown BENCH_CHILD {child}")
        import jax

        from paddle_tpu.framework import persistent_cache

        persistent_cache.enable()
        record = builders[child]()
        devs = jax.devices()
        record["device"] = {"platform": devs[0].platform,
                            "kind": devs[0].device_kind, "count": len(devs)}
        print(json.dumps(record))
        return

    # Every config — the peak probe, the headline and the pass probe
    # included — runs in its OWN child process, one after another: a chip
    # belongs to one process at a time, and a child's HBM is released when it
    # exits. This parent only sequences them; it must never import jax.
    steps = max(10, int(os.environ.get("BENCH_STEPS", 30)))
    _DEADLINE[0] = time.monotonic() + float(os.environ.get("BENCH_DEADLINE_S", 3000))

    def skip_env(name):
        return os.environ.get(name, "").lower() in ("1", "true", "yes")

    snap = _Snapshot()

    def _on_sigterm(signum, frame):
        # The driver's timeout delivers SIGTERM (then KILL after a grace
        # period) and retains only a short stdout TAIL — r5's last snapshot
        # line was pushed out of that tail by two minutes of retry chatter,
        # so parsed=null despite four valid lines earlier in the stream.
        # Make the terminal snapshot the process's very last output, then
        # exit immediately.
        snap.finalize_pending(why="sigterm", signal_safe=True)
        if _CHILD[0] is not None:
            _CHILD[0].kill()  # leave no process behind
        os._exit(0)

    import signal

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):  # non-main thread / exotic platform
        pass

    detail = snap.result["detail"]
    fused, m2_bf16 = _fused_opt_regime()
    detail["optimizer"] = {
        "fused_pallas": fused,
        "moment2_dtype": "bfloat16" if m2_bf16 else "float32",
        "note": (
            "FLAGS_fused_optimizer=1: flat-bucket one-pass Pallas AdamW "
            "(ops/fused_optimizer.py) replaces XLA's per-tensor update "
            "fusions; moment2_dtype=bfloat16 halves second-moment HBM via "
            "stochastic rounding — unbiased, but individual loss curves "
            "diverge from the f32-moment run at matching step counts "
            "(BASELINE.md bf16-m2 A/B); disable via BENCH_FUSED_OPT=0 / "
            "BENCH_M2_BF16=0"
        ),
    }
    detail["mfu_note"] = (
        "vs_baseline = model FLOPs (matmul params + attention) / bf16 "
        "matmul peak co-measured around each run; reference publishes "
        "no number"
    )
    peaks = []

    def try_peak():
        res = _run_config_child("peak", 0)
        if "peak_flops" in res:
            peaks.append(res["peak_flops"])
        detail["all_peaks_tflops"] = [round(p / 1e12, 1) for p in peaks]

    def mfu(res, lo):
        """MFU against the mean of the peaks bracketing the config; degrades
        to one peak (or None) when the deadline ate a peak measurement."""
        pair = peaks[lo:lo + 2] or peaks[-1:]
        if not pair or "tokens_per_sec" not in res:
            return None, None
        peak = sum(pair) / len(pair)
        return res["tokens_per_sec"] * res["flops_per_token"] / peak, peak

    # ---- headline: seq-128 (first — it IS the record) ----
    try_peak()
    res_a = _run_config_child("seq128", steps)
    if "skipped" in res_a:
        detail["seq128"] = res_a
        snap.resolve("seq128", f"skipped:{res_a['skipped']}")
    else:
        try_peak()
        mfu_a, peak_a = mfu(res_a, 0)
        detail.update(
            {k: v for k, v in res_a.items() if k != "flops_per_token"}
        )
        if peak_a:
            detail["co_measured_peak_tflops"] = round(peak_a / 1e12, 1)
        snap.result["value"] = res_a["tokens_per_sec"]
        snap.result["vs_baseline"] = round(mfu_a, 4) if mfu_a else None
        snap.resolve("seq128", "measured")

    # ---- graph-pass pipeline probe (round 15; seconds-scale, CPU-capable
    # — the fusion-coverage fields perf_gate gates) ----
    res_p = _run_config_child("passes", 0)
    detail["passes"] = res_p
    snap.resolve(
        "passes",
        "measured" if "skipped" not in res_p else f"skipped:{res_p['skipped']}",
    )

    # ---- satellites, CHEAPEST-FIRST (ocr/input_stream 90s <
    # serving/resnet 180s < fleet/moe_longcontext/ernie4096 240s < llama):
    # a tight budget forfeits the expensive tail, never the whole record ----
    if skip_env("BENCH_SKIP_VISION"):
        snap.resolve("ppocr_e2e", "skipped:env")
    else:
        res_ocr = _run_config_child("ocr", 8)
        detail["ppocr_e2e"] = res_ocr if "skipped" in res_ocr else {
            **res_ocr,
            "note": "BASELINE configs[2]: DBNet det + CRNN rec end-to-end "
                    "(device inference + host box crop/CTC decode)",
        }
        snap.resolve(
            "ppocr_e2e",
            "measured" if "skipped" not in res_ocr
            else f"skipped:{res_ocr['skipped']}",
        )

    if skip_env("BENCH_SKIP_INPUT"):
        snap.resolve("input_stream", "skipped:env")
    else:
        res_in = _run_config_child("input_stream", 0)
        detail["input_stream"] = res_in if "skipped" in res_in else {
            **res_in,
            "note": "round 12: streaming data tier under an input-heavy "
                    "synthetic reader — prefetch-on vs prefetch-off on the "
                    "same seeded stream, step delta attributed to "
                    "input_wait_s by the pipeline's own stats",
        }
        snap.resolve(
            "input_stream",
            "measured" if "skipped" not in res_in
            else f"skipped:{res_in['skipped']}",
        )

    if skip_env("BENCH_SKIP_SERVING"):
        snap.resolve("serving", "skipped:env")
    else:
        res_sv = _run_config_child("serving", 0)
        detail["serving"] = res_sv if "skipped" in res_sv else {
            **res_sv,
            "note": res_sv.get("note", "") + " (BASELINE: the reference "
                    "publishes no serving number; continuous-vs-static on "
                    "the same replay is the comparison)",
        }
        snap.resolve(
            "serving",
            "measured" if "skipped" not in res_sv
            else f"skipped:{res_sv['skipped']}",
        )

    if skip_env("BENCH_SKIP_FLEET"):
        snap.resolve("fleet", "skipped:env")
    else:
        res_fl = _run_config_child("fleet", 0)
        detail["fleet"] = res_fl if "skipped" in res_fl else {
            **res_fl,
            "note": res_fl.get("note", "") + " (round 13: N engines behind "
                    "the SLO-aware router; scaling_vs_1replica and the "
                    "swap-blip p99 gate in tools/perf_gate.py)",
        }
        snap.resolve(
            "fleet",
            "measured" if "skipped" not in res_fl
            else f"skipped:{res_fl['skipped']}",
        )

    if skip_env("BENCH_SKIP_QOS"):
        snap.resolve("qos", "skipped:env")
    else:
        res_qs = _run_config_child("qos", 0)
        detail["qos"] = res_qs if "skipped" in res_qs else {
            **res_qs,
            "note": res_qs.get("note", "") + " (round 19: fairness_index, "
                    "p99_tpot_gold_ms and gold_p99_vs_uncontended gate in "
                    "tools/perf_gate.py against qos_dims)",
        }
        snap.resolve(
            "qos",
            "measured" if "skipped" not in res_qs
            else f"skipped:{res_qs['skipped']}",
        )

    if skip_env("BENCH_SKIP_VISION"):
        snap.resolve("resnet50", "skipped:env")
    else:
        res_rn = _run_config_child("resnet", max(10, steps // 2))
        detail["resnet50"] = res_rn if "skipped" in res_rn else {
            **res_rn,
            "note": "BASELINE configs[0]: synthetic ImageNet, bf16 AMP, "
                    "Momentum; images_per_sec = @to_static, *_dygraph = eager",
        }
        snap.resolve(
            "resnet50",
            "measured" if "skipped" not in res_rn
            else f"skipped:{res_rn['skipped']}",
        )

    if skip_env("BENCH_SKIP_MOE"):
        snap.resolve("moe_longcontext", "skipped:env")
    else:
        res_moe = _run_config_child("moe_longcontext", 0)
        detail["moe_longcontext"] = res_moe
        snap.resolve(
            "moe_longcontext",
            "measured" if "skipped" not in res_moe
            else f"skipped:{res_moe['skipped']}",
        )

    if skip_env("BENCH_SKIP_4096"):
        snap.resolve("seq4096", "skipped:env")
    else:
        b_lo = max(0, len(peaks) - 1)
        res_b = _run_config_child("ernie4096", max(10, steps // 2))
        if "skipped" in res_b:
            detail["seq4096"] = res_b
            snap.resolve("seq4096", f"skipped:{res_b['skipped']}")
        else:
            try_peak()
            mfu_b, peak_b = mfu(res_b, b_lo)
            detail["seq4096"] = {
                **{k: v for k, v in res_b.items() if k != "flops_per_token"},
                "mfu": round(mfu_b, 4) if mfu_b else None,
                "co_measured_peak_tflops": round(peak_b / 1e12, 1) if peak_b else None,
                "note": (
                    "heads 6x128 = TPU-native head shape (param count identical "
                    "to 12x64; MXU is 128 lanes); Pallas flash kernel dispatched "
                    "(gate S>=512) WITH in-kernel attention dropout 0.1 — the "
                    "real pretrain regime (r5)"
                ),
            }
            snap.resolve("seq4096", "measured")

    if skip_env("BENCH_SKIP_LLAMA"):
        snap.resolve("llama3_shape", "skipped:env")
    else:
        c_lo = max(0, len(peaks) - 1)
        res_c = _run_config_child("llama", max(8, steps // 4))
        if "skipped" in res_c:
            detail["llama3_shape"] = res_c
            snap.resolve("llama3_shape", f"skipped:{res_c['skipped']}")
        else:
            try_peak()
            mfu_c, peak_c = mfu(res_c, c_lo)
            detail["llama3_shape"] = {
                **{k: v for k, v in res_c.items() if k != "flops_per_token"},
                "mfu": round(mfu_c, 4) if mfu_c else None,
                "co_measured_peak_tflops": round(peak_c / 1e12, 1) if peak_c else None,
                "note": (
                    "Llama-3-8B layer dims (hidden 4096, GQA 32q/8kv, ffn "
                    "14336) on one chip; causal flash with native GQA "
                    "head-group mapping (no repeated KV); `rung` records "
                    "which OOM-ladder config produced the number"
                ),
            }
            snap.resolve("llama3_shape", "measured")

    snap.finalize_pending()


def _measured_peak_flops(n=None, iters=10):
    """Best sustained bf16 matmul rate: the chain runs inside ONE compiled
    fori_loop (no per-iter dispatch) and ends in a host-fetched scalar so
    deferred-execution backends can't skip the work. Falls back to n=8192
    if the 16k operands don't fit the HBM headroom left after a big config
    (8192^3 x 2 x iters is still ~11 TFLOP per fetch — saturating)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    if n is None:
        # BENCH_PEAK_N shrinks the operands for the tier-1 capture tests —
        # a 16k^3 chain on a CPU runner would outlive the test timeout
        n = int(os.environ.get("BENCH_PEAK_N", 16384))
    a = b = None
    try:
        a = jnp.asarray(np.random.randn(n, n), jnp.bfloat16)
        b = jnp.asarray(np.eye(n) + 1e-3, jnp.bfloat16)
        jax.block_until_ready((a, b))
    except Exception as e:
        if "RESOURCE_EXHAUSTED" not in str(e) or n <= 8192:
            raise
        del a, b  # release the failed 16k operands before the retry
        _release_device_memory()
        return _measured_peak_flops(n=8192, iters=iters * 4)

    @jax.jit
    def chain(a, b):
        c = jax.lax.fori_loop(0, iters, lambda i, c: c @ b, a)
        return jnp.sum(c.astype(jnp.float32))

    try:
        float(chain(a, b))  # warm + compile
    except Exception as e:
        if "RESOURCE_EXHAUSTED" not in str(e) or n <= 8192:
            raise
        del a, b
        _release_device_memory()
        return _measured_peak_flops(n=8192, iters=iters * 4)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(chain(a, b))
        best = min(best, time.perf_counter() - t0)
    return 2 * n**3 * iters / best


if __name__ == "__main__":
    main()
