"""Device time of the learned-sparse-attention pieces at the long-context
cell's shapes (one layer): `dsa_index`, the selection, the gather of chosen
entries, `mla_sparse_paged_attn`, and the dense `mla_paged_attn` beside them.

    chiprun --chips 1 -- python3 benchmarks/dsa_micro.py

Each piece runs 8 times inside one jitted scan; the host clock is taken
around 3 such calls after a warm one. Prints one JSON line a piece.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import paddle_tpu  # noqa: F401
from paddle_tpu.ops import pallas as pk

N, BS, M, H, J, K = 8705, 16, 1088, 128, 64, 2048
REPS = 8


def timed(name, fn, *args, **facts):
    """`fn(bump, *args)`: `bump` is an int32 zero the compiler cannot see through, to be added to a small
    operand, so that no repetition's work can be hoisted out of the loop."""
    def many(*a):
        def body(c, _):
            out = fn(jnp.minimum(c, 0), *a)
            return jnp.maximum(c, jnp.abs(out.reshape(-1)[0]).astype(jnp.int32) % 2), None
        return lax.scan(body, jnp.int32(0), None, length=REPS)[0]

    with jax.enable_x64(False):
        f = jax.jit(many)
        f(*args).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            f(*args).block_until_ready()
        ms = (time.perf_counter() - t0) / (3 * REPS) * 1e3
    print(json.dumps({"piece": name, "ms": round(ms, 4), **facts}), flush=True)
    return ms


def main():
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}), flush=True)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    lat = jax.random.normal(ks[0], (N, BS, 640), jnp.bfloat16)
    idx = jax.random.normal(ks[1], (N, BS, 128), jnp.bfloat16)
    for b, q, ctx in ((1, 128, 8192), (1, 128, 16384), (8, 1, 8192), (8, 1, 16384)):
        rng = np.random.RandomState(ctx + b)
        bt = np.zeros((b, M), np.int32)
        for r in range(b):
            bt[r, :ctx // BS] = rng.permutation(N - 1)[:ctx // BS] + 1
        pos = np.zeros((b, q), np.int32) + (ctx - q) + np.arange(q)[None]
        bt, pos = jnp.asarray(bt), jnp.asarray(pos)
        qi = jax.random.normal(ks[2], (b, q, J, 128), jnp.bfloat16)
        w = jax.random.normal(ks[3], (b, q, J), jnp.float32)
        qa = jax.random.normal(ks[4], (b, q, H, 576), jnp.bfloat16)
        facts = {"rows": b, "queries": q, "context": ctx}
        timed("dsa_index", lambda z, qi, w, idx, bt, pos: pk.dsa_index_scores(qi, w, idx, bt, pos + z),
              qi, w, idx, bt, pos, **facts)
        scores = pk.dsa_index_scores(qi, w, idx, bt, pos)
        carry = pk.pool_rows(bt, BS)
        front = jnp.max(pos) + 1
        timed("select_sort_whole", lambda z, s, c: pk.dsa_select(s + z.astype(jnp.float32), K, carry=c),
              scores, carry, **facts)
        timed("select_sort_to_frontier", lambda z, s, c, f: pk.dsa_select(s + z.astype(jnp.float32), K, carry=c,
                                                                           frontier=f), scores, carry, front, **facts)
        rows = pk.dsa_select(scores, K, carry=carry, frontier=front)
        counts = jnp.minimum(K, pos + 1)
        timed("gather_entries", lambda z, p, i: jnp.take(p.reshape(N * BS, 640), i.reshape(-1) + z, axis=0),
              lat, rows, **facts)
        timed("sparse_attention_whole", lambda z, qa, lat, rows, counts: pk.mla_sparse_attention(
            qa, lat, rows + z, counts, 512, 0.1), qa, lat, rows, counts, **facts)
        timed("mla_paged_attn_dense", lambda z, *a: pk.mla_paged_attention(*a[:3], a[3] + z, 512, 0.1),
              qa, lat, bt, pos, **facts)


if __name__ == "__main__":
    main()
