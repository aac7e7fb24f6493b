"""The long-context configuration's program against its plain reference on ONE
sequence, logit by logit, on the chip at the published widths: a bucketed
prefill or chunks, then a few decode steps; and the two kernels against their
jnp oracles at the cell's shapes. Says how far the served logits lie from the
reference's and how much of that the index scores' bfloat16 operands explain
(the reference run again with its index queries and keys rounded to bfloat16).

    chiprun --chips 1 --timeout 1500 -- python3 benchmarks/dsa_engine_check.py [prompt_len] [how]
"""
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import run, weights
from paddle_tpu.framework import persistent_cache
from paddle_tpu.ops import pallas as pk


def log(**kw):
    print(json.dumps(kw, default=float), flush=True)


def kernels():
    n, bs, m, k = 8705, 16, 1088, 2048
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    lat = jax.random.normal(ks[0], (n, bs, 640), jnp.bfloat16).at[..., 576:].set(0)
    idx = jax.random.normal(ks[1], (n, bs, 128), jnp.bfloat16)
    for b, q, ctx in ((1, 128, 6144), (8, 1, 6144)):
        rng = np.random.RandomState(b)
        bt = np.zeros((b, m), np.int32)
        for r in range(b):
            bt[r, :ctx // bs] = rng.permutation(n - 1)[:ctx // bs] + 1
        pos = jnp.asarray(np.zeros((b, q), np.int32) + (ctx - q) + np.arange(q)[None])
        qi = jax.random.normal(ks[2], (b, q, 64, 128), jnp.bfloat16)
        w = jax.random.normal(ks[3], (b, q, 64), jnp.float32)
        qa = (jax.random.normal(ks[4], (b, q, 128, 576), jnp.float32) * 0.3).astype(jnp.bfloat16)
        got = pk.dsa_index_scores(qi, w, idx, bt, pos)
        want = jax.jit(pk.dsa_index_reference)(qi, w, idx, jnp.asarray(bt), pos)
        fin = np.isfinite(np.asarray(want))
        same_inf = bool((np.isfinite(np.asarray(got)) == fin).all())
        err = float(np.abs(np.asarray(got)[fin] - np.asarray(want)[fin]).max())
        chosen = pk.dsa_select(got, k)
        chosen_ref = pk.dsa_select(want, k)
        overlap = np.mean([len(set(a) & set(c)) / k for a, c in zip(np.asarray(chosen).reshape(-1, k),
                                                                    np.asarray(chosen_ref).reshape(-1, k))])
        counts = jnp.minimum(k, pos + 1)
        rows = pk.dsa_select(got, k, carry=pk.pool_rows(bt, bs), frontier=jnp.max(pos) + 1)
        assert (np.asarray(rows) == np.take_along_axis(np.asarray(pk.pool_rows(bt, bs))[:, None], np.asarray(chosen), -1)).all()
        a = pk.mla_sparse_attention(qa, lat, rows, counts, 512, 0.135)
        r = jax.jit(lambda *x: pk.mla_sparse_reference(*x, 512, 0.135))(qa, lat, rows, counts)
        log(check="kernels", rows=b, queries=q, index_same_inf=same_inf, index_max_err=err,
            index_scale=float(np.abs(np.asarray(want)[fin]).mean()), chosen_overlap=float(overlap),
            sparse_max_err=float(np.abs(np.asarray(a, np.float32) - np.asarray(r, np.float32)).max()),
            sparse_scale=float(np.abs(np.asarray(r, np.float32)).mean()))


def main():
    prompt_len = int(sys.argv[1]) if len(sys.argv) > 1 else 4500
    how = sys.argv[2] if len(sys.argv) > 2 else "prefill"
    persistent_cache.enable()
    kernels()
    _, cell, cfg, mix, ref = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                                            "deepseek-v32-ep16-l5.longctx-open", os.path.join(ROOT, "chipbench"))
    from paddle_tpu.inference.engine import InferenceEngine

    seed, eng = 4000000123, mix["engine"]
    model = run.load_module("builders", cfg["builder"]).build(cfg)
    vals = weights.make(ref.leaf_specs(cfg), seed, jnp.bfloat16)
    for name, t in model.state_dict().items():
        t._value = vals[name]
    del vals
    engine = InferenceEngine(model, max_seq_len=eng["max_seq_len"], block_size=eng["block_size"],
                             num_blocks=eng["num_blocks"], max_batch=eng["max_batch"],
                             prefill_buckets=eng["prefill_buckets"], decode_batch_buckets=eng["decode_batch_buckets"])
    ids = np.random.RandomState(seed % 2 ** 32).randint(1, cfg["vocab_size"], prompt_len).tolist()
    pages = engine.pool.alloc(engine.pool.blocks_for_tokens(prompt_len + 16))
    got = []
    if how == "prefill":
        got.append(engine.prefill(ids, pages))
    else:
        for start in range(0, prompt_len, engine.chunk_width):
            part = ids[start:start + engine.chunk_width]
            _, last = engine.decode_with_chunk([], [], [], [], part, start, pages)
        got.append(last)
    seq = list(ids)
    for _ in range(6):
        seq.append(int(np.argmax(got[-1])))
        got.append(engine.decode([seq[-1]], [len(seq) - 1], [len(seq)], [pages])[0])
    seq.append(int(np.argmax(got[-1])))  # 7 served tokens, each predicted by one row of `got`
    got = np.asarray(got, np.float32)
    del model, engine
    gc.collect()
    jax.clear_caches()
    for name, patch in (("f32", None), ("index_operands_bf16", jnp.bfloat16), ("no_select", "no_select")):
        orig = ref.index_scores
        if patch is jnp.bfloat16:
            lin = ref._linear
            # the indexer's three products give bfloat16 results, as the program's do
            def index_scores(a, c_q, w, cfg, int8=False):
                ref._linear = lambda x, m, i8: lin(x, m, i8).astype(jnp.bfloat16).astype(jnp.float32) \
                    if m.shape[-1] in (cfg["index_n_heads"] * cfg["index_head_dim"], cfg["index_head_dim"]) else lin(x, m, i8)
                try:
                    return orig(a, c_q, w, cfg, int8)
                finally:
                    ref._linear = lin
            ref.index_scores = index_scores
        prec = ("no_select",) if patch == "no_select" else ("f32",)
        logits, served = ref.token_gaps(cfg, seed, [seq[:prompt_len + 7]], [prompt_len], prec)
        ref.index_scores = orig
        want = logits[prec[0]]
        d = np.abs(got - want)
        log(check="engine_vs_reference", reference=name, how=how, prompt_len=prompt_len,
            logit_std=float(want.std()), max_abs_err=float(d.max()), mean_abs_err=float(d.mean()),
            err_by_step=[float(x) for x in d.max(-1)], gap=[float(x) for x in ref.gaps(want, served)],
            top1_same=int((got.argmax(-1) == want.argmax(-1)).sum()))


if __name__ == "__main__":
    main()
