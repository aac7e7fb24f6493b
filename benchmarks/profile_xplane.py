"""Per-op device-time breakdown of the headline train step via the XLA
profiler (device_duration_ps per op).

Prints total device time per HLO category and the top-N individual ops,
so every millisecond of the step has a name (VERDICT r2 Weak #1).

Run: python benchmarks/profile_xplane.py
"""
import glob
import gzip
import json
import os
import sys
import tempfile
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax

import paddle_tpu as paddle


def main():
    from bench import build_train_step

    batch = int(os.environ.get("BENCH_BATCH", 64))
    seq = int(os.environ.get("BENCH_SEQ", 128))
    heads = int(os.environ.get("BENCH_HEADS", 12))
    # same builder as bench.py: the profiled model IS the benchmarked model.
    # BENCH_ATTN_DROPOUT=0.1 matches bench.py's seq-4096 operating point
    # (in-kernel attention dropout — r5); default 0 matches seq-128.
    drop = float(os.environ.get("BENCH_ATTN_DROPOUT", "0"))
    model, train_step, ids, labels = build_train_step(
        batch, seq, heads, attn_dropout=drop
    )

    # warm + compile
    for _ in range(4):
        loss = train_step(ids, labels)
    float(loss.numpy())

    tdir = tempfile.mkdtemp(prefix="xplane_")
    jax.profiler.start_trace(tdir)
    NSTEP = 3
    for _ in range(NSTEP):
        loss = train_step(ids, labels)
    float(loss.numpy())  # force execution inside the trace window
    jax.profiler.stop_trace()

    traces = glob.glob(f"{tdir}/**/*.trace.json.gz", recursive=True)
    d = json.load(gzip.open(traces[0]))
    evs = d["traceEvents"]

    # find the device pid and its "XLA Ops" tid
    dev_pid = next(e["pid"] for e in evs
                   if e.get("ph") == "M" and e.get("name") == "process_name"
                   and "TPU" in e["args"]["name"])
    ops_tid = next(e["tid"] for e in evs
                   if e.get("ph") == "M" and e.get("name") == "thread_name"
                   and e["pid"] == dev_pid and e["args"]["name"] == "XLA Ops")

    cat_time = defaultdict(float)
    op_time = defaultdict(float)
    op_src = {}
    total = 0.0
    for e in evs:
        if e.get("ph") != "X" or e.get("pid") != dev_pid or e.get("tid") != ops_tid:
            continue
        a = e.get("args", {})
        dur_ms = int(a.get("device_duration_ps", 0)) / 1e9
        cat = a.get("hlo_category", "?")
        cat_time[cat] += dur_ms
        op_time[e["name"]] += dur_ms
        if e["name"] not in op_src:
            op_src[e["name"]] = (a.get("tf_op", ""), (a.get("source_stack", "").splitlines() or [""])[0],
                                 a.get("shape_with_layout", ""), int(a.get("bytes_accessed", 0)),
                                 a.get("long_name", "")[:200])
        total += dur_ms

    print(f"== device time over {NSTEP} steps: {total:.2f} ms ({total/NSTEP:.2f} ms/step) ==")
    print("\n-- by HLO category --")
    for cat, t in sorted(cat_time.items(), key=lambda kv: -kv[1]):
        print(f"{t/NSTEP:9.3f} ms/step  {cat}")
    print("\n-- top 20 ops --")
    for name, t in sorted(op_time.items(), key=lambda kv: -kv[1])[:20]:
        tf_op, src, shape, nbytes, long = op_src[name]
        print(f"{t/NSTEP:9.3f} ms/step  {name[:40]:40s} {nbytes/1e6:9.1f} MB  {tf_op[:44]:44s} {src[:50]}")
        print(f"           shape={shape[:110]}")
        print(f"           {long[:160]}")


if __name__ == "__main__":
    main()
