"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            one TPU chip: the trainer, then the server
    python chip_smoke.py --chips 4  four chips: the dp=2 x mp=2 train step only
    python chip_smoke.py --tiny     sandbox rehearsal (CPU, Pallas interpreted)

Default run, through the entry points a user calls:

1. trainer — ERNIE-3.0-base at full width (12 layers, hidden 768, 12 heads,
   ffn 3072, vocab 40000), bf16 AMP, AdamW, `paddle.jit.to_static`, batch 64
   x seq 128: the first call records, the second compiles, then three
   compiled steps; loss finite, falling, and equal (tolerance below) to an
   eager model stepped on the same data. One step at seq 4096, heads 6x128,
   attention dropout 0.1 (flash forward and backward on the device) and one
   with FLAGS_fused_optimizer (the Pallas AdamW), each asserted from the
   compiled program's text to hold its kernel (`tpu_custom_call`).
2. server — LlamaForCausalLM at hidden 4096, 32q/8kv at head 128, ffn 14336,
   vocab 32000, depth cut to 4 layers (1.13 B parameters, 4.5 GB of f32
   weights; the predictor loads a second copy beside the oracle's, 9 GB of
   the chip's 16 GB, beside the KV pool), through `save_llm`
   -> `Config` -> `enable_llm_engine` -> `create_predictor` and a
   `ContinuousBatchingScheduler` replay of mixed-length requests: ids equal
   the full-forward greedy oracle token for token (float pool), the int8
   pool is deterministic run to run and requantizes exactly, every pool
   drains to zero, and the decode bucket's compiled text holds the paged
   kernel.

Every phase prints one JSON line of facts; any failed check raises, so the
exit code is non-zero and the last line is never printed. The last line is
`{"ok": true, "device": {...}}` and is printed only on a TPU at full size:
`--tiny` can pass its checks anywhere but never prints it.
"""
import argparse
import gc
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

KERNEL = "tpu_custom_call"  # how a Pallas kernel appears in compiled text

ERNIE_BASE = dict(vocab=40000, hidden=768, layers=12, ffn=3072)
FULL = dict(
    ernie=ERNIE_BASE, batch=64, seq=128, heads=12,
    long_batch=2, long_seq=4096, long_heads=6,
    serve=dict(vocab_size=32000, hidden_size=4096, num_hidden_layers=4,
               num_attention_heads=32, num_key_value_heads=8,
               intermediate_size=14336),
    serve_max_seq=256, serve_block=16, serve_batch=4,
    prompt_lens=(5, 23, 48, 11, 37, 64), max_new=8,
)
TINY = dict(
    ernie=dict(vocab=256, hidden=256, layers=2, ffn=256), batch=4, seq=16,
    heads=4, long_batch=1, long_seq=512, long_heads=2,
    serve=dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=176),
    serve_max_seq=64, serve_block=8, serve_batch=4,
    prompt_lens=(5, 11, 20, 7, 16, 24), max_new=6,
)
# bf16-AMP losses near ln(vocab) ~ 10: compiled vs eager differ by fusion
# boundaries (a fused chain keeps f32 where eager rounds to bf16 per op)
LOSS_TOL = 5e-2


def emit(phase, **facts):
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def device_facts():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_memory():
    """Device 0's bytes in use now and the process's peak so far (the peak
    never resets: a later phase reports at least an earlier one's)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {"bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def release():
    """Drop executables and dead buffers between phases (bench.py's
    _release_device_memory idiom): the trainer and the server do not fit
    the chip together."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def cache_counts():
    """This process's persistent-cache hits and misses so far."""
    from paddle_tpu.framework import persistent_cache

    stats = persistent_cache.stats()
    return {"hits": stats["hits"], "misses": stats["misses"]}


def cache_delta(before):
    now = cache_counts()
    return {k: now[k] - before[k] for k in now}


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def build_trainer(dims, batch, seq, heads, attn_dropout=0.0, compiled=True):
    """bench.py's build_train_step workload: ERNIE MLM + AdamW, bf16 AMP,
    one fixed seeded batch."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import ErnieForMaskedLM, ErnieModel

    paddle.seed(0)
    model = ErnieForMaskedLM(ErnieModel(
        vocab_size=dims["vocab"], hidden_size=dims["hidden"],
        num_hidden_layers=dims["layers"], num_attention_heads=heads,
        intermediate_size=dims["ffn"], hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=attn_dropout,
        max_position_embeddings=max(512, seq),
    ))
    opt = paddle.optimizer.AdamW(
        1e-4, parameters=model.parameters(), weight_decay=0.01)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, dims["vocab"], (batch, seq)).astype(np.int64))
    labels = paddle.to_tensor(rng.randint(0, dims["vocab"], (batch, seq)).astype(np.int64))

    def train_step(ids, labels):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            loss, _ = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step) if compiled else train_step
    return model, opt, step, ids, labels


def run_steps(step, ids, labels, n):
    """n calls, each ending in a host fetch; (losses, seconds per call)."""
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels).numpy()))
        secs.append(round(time.perf_counter() - t0, 3))
    return losses, secs


def compiled_texts(step):
    """Compiled-program text of every to_static entry of `step`."""
    return [e.jitted.as_text() for e in step.concrete_program().values()
            if e.jitted is not None]


def check_losses(losses, what):
    import math

    check(all(math.isfinite(x) for x in losses), f"{what}: non-finite loss {losses}")


def phase_train(cfg):
    dims = cfg["ernie"]
    before = cache_counts()
    model, opt, step, ids, labels = build_trainer(dims, cfg["batch"], cfg["seq"], cfg["heads"])
    losses, secs = run_steps(step, ids, labels, 5)
    check_losses(losses, "train_seq128")
    check(losses[-1] < losses[0], f"train_seq128: loss not falling {losses}")
    texts = compiled_texts(step)
    check(len(texts) == 1, f"train_seq128: {len(texts)} compiled programs, want 1")
    del model, opt, step

    _, _, eager, ids, labels = build_trainer(
        dims, cfg["batch"], cfg["seq"], cfg["heads"], compiled=False)
    eager_losses, _ = run_steps(eager, ids, labels, 3)
    diff = max(abs(a - b) for a, b in zip(losses, eager_losses))
    check(diff <= LOSS_TOL,
          f"train_seq128: compiled {losses[:3]} vs eager {eager_losses} "
          f"differ by {diff} > {LOSS_TOL}")
    del eager
    emit("train_seq128", model="ernie-3.0-base", dims=dims, batch=cfg["batch"],
         seq=cfg["seq"], heads=cfg["heads"], calls="record, compile, 3 compiled",
         losses=losses, call_seconds=secs, eager_losses=eager_losses,
         max_abs_diff_vs_eager=diff, tolerance=LOSS_TOL,
         kernels_in_step_program=texts[0].count(KERNEL),
         persistent_cache=cache_delta(before), memory=device_memory())
    release()
    return losses


def phase_train_long(cfg, on_tpu):
    before = cache_counts()
    model, opt, step, ids, labels = build_trainer(
        cfg["ernie"], cfg["long_batch"], cfg["long_seq"], cfg["long_heads"],
        attn_dropout=0.1)
    losses, secs = run_steps(step, ids, labels, 3)
    check_losses(losses, "train_seq4096")
    n_kernels = compiled_texts(step)[0].count(KERNEL)
    if on_tpu:
        check(n_kernels >= 3, "train_seq4096: flash fwd/dq/dkdv kernels not in "
              f"the compiled step ({n_kernels} {KERNEL})")
    emit("train_seq4096", batch=cfg["long_batch"], seq=cfg["long_seq"],
         heads=cfg["long_heads"], attn_dropout=0.1,
         calls="record, compile, 1 compiled", losses=losses, call_seconds=secs,
         kernels_in_step_program=n_kernels, kernel="flash attention fwd+bwd",
         persistent_cache=cache_delta(before), memory=device_memory())
    del model, opt, step
    release()


def phase_train_fused(cfg, on_tpu, ref_losses):
    import paddle_tpu as paddle

    before = cache_counts()
    paddle.set_flags({"FLAGS_fused_optimizer": True})
    try:
        model, opt, step, ids, labels = build_trainer(
            cfg["ernie"], cfg["batch"], cfg["seq"], cfg["heads"])
        losses, secs = run_steps(step, ids, labels, 3)
    finally:
        paddle.set_flags({"FLAGS_fused_optimizer": False})
    check_losses(losses, "train_fused_adamw")
    # same seed, same batch: the one-pass kernel must step like the
    # per-tensor update it replaces
    diff = max(abs(a - b) for a, b in zip(losses, ref_losses))
    check(diff <= LOSS_TOL, f"train_fused_adamw: {losses} vs per-tensor "
          f"{ref_losses[:3]} differ by {diff} > {LOSS_TOL}")
    n_kernels = compiled_texts(step)[0].count(KERNEL)
    if on_tpu:
        check(n_kernels >= 1, "train_fused_adamw: Pallas AdamW not in the "
              "compiled step (jnp reference ran instead)")
    emit("train_fused_adamw", calls="record, compile, 1 compiled", losses=losses,
         call_seconds=secs, max_abs_diff_vs_per_tensor=diff, tolerance=LOSS_TOL,
         kernels_in_step_program=n_kernels, kernel="fused AdamW",
         persistent_cache=cache_delta(before), memory=device_memory())
    del model, opt, step
    release()


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def greedy_oracle(model, prompts, max_new, pad_to):
    """Full-forward greedy continuation of every prompt, no cache: all rows
    ride one right-padded [R, pad_to] batch (a causal model's logits at a
    position ignore what follows it), so every step reuses one shape."""
    import numpy as np

    import paddle_tpu as paddle

    rows = np.zeros((len(prompts), pad_to), np.int64)
    lens = [len(p) for p in prompts]
    for r, p in enumerate(prompts):
        rows[r, :len(p)] = p
    out = [[] for _ in prompts]
    for _ in range(max_new):
        with paddle.no_grad():
            logits = model(paddle.to_tensor(rows)).numpy()
        for r, n in enumerate(lens):
            tok = int(logits[r, n - 1].argmax())
            out[r].append(tok)
            rows[r, n] = tok
            lens[r] = n + 1
    return out


def serve_once(inf, prefix, cfg, prompts, kv_dtype=None, on_tpu=False):
    """One predictor over the saved artifact: a predictor.run() of the whole
    batch, then a continuous-batching replay of the same prompts with
    staggered arrivals on the same engine. Returns (run ids, replay ids,
    facts); the predictor and its copy of the weights die with the call."""
    import numpy as np

    from paddle_tpu.inference.scheduler import (
        ContinuousBatchingScheduler, Request, replay)

    max_new = cfg["max_new"]
    config = inf.Config(prefix)
    opts = dict(max_new_tokens=max_new, max_seq_len=cfg["serve_max_seq"],
                block_size=cfg["serve_block"], max_batch=cfg["serve_batch"])
    if kv_dtype is not None:
        opts["kv_dtype"] = kv_dtype
    config.enable_llm_engine(**opts)
    t0 = time.perf_counter()
    pred = inf.create_predictor(config)
    load_s = time.perf_counter() - t0
    check(isinstance(pred, inf.LLMPredictor), "create_predictor: not an LLMPredictor")

    width = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), width), np.int64)
    for r, p in enumerate(prompts):
        ids[r, :len(p)] = p
    t0 = time.perf_counter()
    (out,) = pred.run([ids, np.asarray([len(p) for p in prompts])])
    run_s = time.perf_counter() - t0
    run_ids = [list(map(int, row)) for row in out]

    engine = pred._engine
    check(engine.pool.used() == 0, f"pool holds {engine.pool.used()} pages after run()")
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=max_new,
                    arrival_time=0.02 * i) for i, p in enumerate(prompts)]
    stats = replay(ContinuousBatchingScheduler(engine), reqs)
    replay_ids = [r.prompt[r.prompt_len:] + list(r.generated) for r in reqs]
    check(engine.pool.used() == 0, f"pool holds {engine.pool.used()} pages after drain")
    # the paged kernel, per compiled decode bucket
    kernels = {f"{kind}_{size}": ex.as_text().count(KERNEL)
               for (kind, size), ex in engine._compiled.items() if kind == "decode"}
    if on_tpu:
        check(kernels and all(n >= 1 for n in kernels.values()),
              f"paged kernel missing from a decode bucket: {kernels}")
    facts = dict(load_seconds=round(load_s, 2), run_seconds=round(run_s, 2),
                 replay=stats, bucket_stats=dict(engine.bucket_stats),
                 paged_kernels_in_decode_buckets=kernels,
                 pool_bytes=engine.pool.pool_bytes(), pool_used_after_drain=0,
                 memory=device_memory())  # predictor + oracle weights + pool
    if kv_dtype is None:
        facts["requant_exact_pages"] = requant_exact(engine, prompts[2])
    return run_ids, replay_ids, facts


def requant_exact(engine, prompt):
    """An int8 pool's own write math equals convert_payload: prefill one
    prompt into the float pool, export its pages, and (a) convert the
    payload to int8, (b) write the same K/V through a fresh int8 pool's
    view; the two must agree byte for byte."""
    import numpy as np
    from jax import numpy as jnp

    from paddle_tpu.inference.kv_cache import (
        BlockPool, convert_payload, export_pages)

    pool = engine.pool
    pages = pool.alloc(pool.blocks_for_tokens(len(prompt)))
    engine.prefill(prompt, pages)
    payload = export_pages(pool, pages)
    pool.free(pages, retain=False)
    converted = convert_payload(payload, "int8")

    q = BlockPool(len(pages) + 1, pool.block_size, pool.num_layers,
                  pool.num_kv_heads, pool.head_dim, kv_dtype="int8")
    qpages = q.alloc(len(pages))
    n_tok = len(pages) * pool.block_size
    view = q.view(np.asarray([q.padded_table(qpages, len(qpages))], np.int32),
                  np.asarray([n_tok], np.int32))
    pos = np.arange(n_tok, dtype=np.int32)[None]
    for layer in range(pool.num_layers):
        # [n, Hkv, bs, D] pages -> the [1, S, Hkv, D] token stream a step writes
        k, v = (jnp.asarray(payload[key][layer]).transpose(0, 2, 1, 3)
                .reshape(1, n_tok, pool.num_kv_heads, pool.head_dim)
                for key in ("k", "v"))
        view.write(layer, k, v, pos)
    q.adopt_state({"k": view.k_pages, "v": view.v_pages,
                   "k_scale": view.k_scales, "v_scale": view.v_scales})
    written = export_pages(q, qpages)
    for key in ("k", "v", "k_scale", "v_scale"):
        for a, b in zip(converted[key], written[key]):
            check(np.array_equal(a, b), f"requant mismatch in {key}")
    return len(pages)


def phase_serve(cfg, on_tpu):
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.inference as inf
    from paddle_tpu.models.llama import LlamaForCausalLM

    before = cache_counts()
    paddle.seed(0)
    model = LlamaForCausalLM(**cfg["serve"])
    model.eval()
    n_params = sum(p.size for p in model.parameters())
    weight_bytes = sum(p.size * p._value.dtype.itemsize for p in model.parameters())
    rng = np.random.RandomState(1)
    vocab = cfg["serve"]["vocab_size"]
    prompts = [rng.randint(1, vocab, (n,)).tolist() for n in cfg["prompt_lens"]]
    max_new = cfg["max_new"]

    with tempfile.TemporaryDirectory(prefix="chip_smoke_llm_") as tmp, \
            jax.default_matmul_precision("highest"):
        # f32 weights with every matmul at full f32 precision: the paged
        # path and the oracle then agree to rounding, so token-for-token
        # equality is a fair demand (the TPU's default f32 matmul is a
        # single bf16 pass, whose error flips near-tied argmaxes)
        prefix = os.path.join(tmp, "llm")
        inf.save_llm(model, prefix)
        want = greedy_oracle(model, prompts, max_new,
                             pad_to=max(cfg["prompt_lens"]) + max_new)

        run_ids, replay_ids, facts = serve_once(
            inf, prefix, cfg, prompts, on_tpu=on_tpu)
        check(run_ids == want, f"float pool: predictor ids {run_ids} != oracle {want}")
        check(replay_ids == want, f"float pool: replay ids {replay_ids} != oracle {want}")
        emit("serve_float_pool", model="LlamaForCausalLM", dims=cfg["serve"],
             depth=cfg["serve"]["num_hidden_layers"], params=int(n_params),
             weight_bytes=int(weight_bytes), weights="float32",
             matmul_precision="highest", requests=len(prompts),
             prompt_lens=list(cfg["prompt_lens"]), max_new_tokens=max_new,
             ids_matched=f"{sum(len(w) for w in want)}/{sum(len(w) for w in want)}",
             oracle="full-forward greedy, token for token", **facts,
             persistent_cache=cache_delta(before))
        release()

        before = cache_counts()
        first = serve_once(inf, prefix, cfg, prompts, kv_dtype="int8", on_tpu=on_tpu)
        release()
        second = serve_once(inf, prefix, cfg, prompts, kv_dtype="int8", on_tpu=on_tpu)
        check(first[:2] == second[:2], "int8 pool: two identical runs disagree")
        agree = sum(a == b for g, w in zip(first[0], want) for a, b in zip(g, w))
        emit("serve_int8_pool", kv_dtype="int8", deterministic=True,
             ids_agree_with_f32_oracle=f"{agree}/{sum(len(w) for w in want)}",
             note="an int8 pool is held to run-to-run determinism and exact "
                  "requantization (checked on the float pool's pages), not "
                  "to the f32 oracle", **second[2],
             persistent_cache=cache_delta(before))
    del model
    release()


# ---------------------------------------------------------------------------
# four chips: dp=2 x mp=2
# ---------------------------------------------------------------------------

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def phase_four_chips(cfg):
    """The ERNIE train step under fleet.init(dp=2, mp=2): parameters placed
    by the SpecLayout table, batch split over dp; loss equal to the
    single-device loss taken first, shards on four distinct devices, the
    expected collectives in the compiled step."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.sharding import spec_layout as sl

    check(len(jax.devices()) >= 4, f"--chips 4 needs four devices, found {len(jax.devices())}")
    dims = cfg["ernie"]
    args = (dims, cfg["batch"], cfg["seq"], cfg["heads"])
    model, opt, step, ids, labels = build_trainer(*args)
    single, single_secs = run_steps(step, ids, labels, 4)
    check_losses(single, "single device")
    del model, opt, step
    release()

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = sl.global_mesh()
    model, opt, step, ids, labels = build_trainer(*args)
    table = sl.transformer_layout_table(dp=2)
    specs = {}
    for name, p in model.named_parameters():
        specs[name] = table.spec_for(name, p.shape)
        sl.place(p, specs[name])
    batch_sharding = NamedSharding(mesh, P(sl.layout().data_axis, None))
    for t in (ids, labels):
        t._replace_value(jax.device_put(t._raw(), batch_sharding))
    losses, secs = run_steps(step, ids, labels, 4)
    check_losses(losses, "dp2 x mp2")
    diff = max(abs(a - b) for a, b in zip(losses, single))
    check(diff <= LOSS_TOL, f"dp2 x mp2 {losses} vs single device {single}: "
          f"differ by {diff} > {LOSS_TOL}")

    def placed_as(arr, spec, what):
        devs = {s.device for s in arr.addressable_shards}
        check(len(devs) == 4, f"{what}: lives on {len(devs)} devices, want 4")
        want = NamedSharding(mesh, spec)
        check(arr.sharding.is_equivalent_to(want, arr.ndim),
              f"{what}: sharding {arr.sharding.spec} is not the table's {spec}")
        return arr.addressable_shards[0].data.shape != arr.shape

    n_split = 0
    params = list(model.named_parameters())
    for name, p in params:
        n_split += placed_as(p._raw(), specs[name], name)
    state = opt.state_dict()
    n_moments = 0
    for i, (name, p) in enumerate(params):
        for acc in ("moment1", "moment2"):
            t = state.get(f"{acc}_{i}")
            if t is not None:
                placed_as(t._raw(), specs[name], f"{acc} of {name}")
                n_moments += 1
    # (the MLM loss never reads the pooler, so two params have no state)
    check(n_moments >= len(params), f"found only {n_moments} AdamW moments "
          f"for {len(params)} parameters")
    text = compiled_texts(step)[0]
    counts = {c: text.count(c) for c in COLLECTIVES}
    check(counts["all-reduce"] >= 1, f"no all-reduce in the dp x mp step: {counts}")
    emit("train_dp2_mp2", mesh={k: int(v) for k, v in mesh.shape.items() if v > 1},
         batch=cfg["batch"], seq=cfg["seq"], single_device_losses=single,
         losses=losses, max_abs_diff=diff, tolerance=LOSS_TOL,
         call_seconds=secs, single_device_call_seconds=single_secs,
         params=len(params), params_split=int(n_split), moments_checked=n_moments,
         devices_per_array=4, collectives=counts, memory=device_memory())


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp=2 x mp=2 train step, on four chips")
    ap.add_argument("--tiny", action="store_true",
                    help="sandbox rehearsal at toy size; never prints the ok line")
    args = ap.parse_args()

    import jax

    from paddle_tpu.framework import persistent_cache

    cache_dir = persistent_cache.enable()
    device = device_facts()
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.tiny:
        sys.exit(f"chip_smoke: needs a TPU, jax found {device}")
    if args.tiny and not on_tpu:
        from paddle_tpu.ops import pallas

        pallas._INTERPRET = True  # kernels run interpreted on the CPU
    cfg = TINY if args.tiny else FULL
    emit("start", device=device, jax=jax.__version__, cache_dir=cache_dir,
         size="tiny" if args.tiny else "full", chips=args.chips)
    check(args.tiny or device["count"] == args.chips,
          f"asked for {args.chips} chip(s), jax sees {device['count']}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(cfg)
    else:
        ref_losses = phase_train(cfg)
        phase_train_long(cfg, on_tpu)
        phase_train_fused(cfg, on_tpu, ref_losses)
        phase_serve(cfg, on_tpu)
    emit("done", wall_seconds=round(time.perf_counter() - t0, 1),
         persistent_cache=persistent_cache.stats())
    if args.tiny or not on_tpu:
        sys.exit("chip_smoke: rehearsal only — every check passed, but this "
                 "is not a full-size run on a TPU")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
