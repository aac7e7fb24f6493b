"""chipbench — one run of one cell.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`configs/<config>.json`, with its plain reference `reference/<config>.py`
and its builder `builders/<builder>.py`) under a traffic mix
(`traffic/<mix>.json`, whose `loop` picks `loops/<loop>.py`). Per-layer
metrics are read by `layer_metrics/<metric>.py`. Adding any of these is
adding a file and an entry; nothing here names one.

Earlier lines are free; the last line of stdout is the result. The run
refuses (exit 2, no result) on anything but a TPU of the table of peaks.
"""
import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse
import importlib
import importlib.util
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

LOOPS = {"train": "train", "open": "serve", "closed": "serve"}


def load_module(kind: str, name: str):
    """A file of the benchmark found by a name from BENCHMARK.json (names
    may hold `-` and `.`, so not by `import`)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(benchmark: str, workload: str, data_root: str):
    """(the benchmark's table, the cell's entry, its configuration, its
    traffic mix, its plain reference), by the names in BENCHMARK.json."""
    with open(benchmark) as f:
        bench = json.load(f)
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if not cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in {benchmark}")
    cell = cells[0]
    with open(os.path.join(data_root, "configs", cell["config"] + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(data_root, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return bench, cell, cfg, mix, load_module("reference", cfg.get("reference", cell["config"]))


def main(argv=None, allow_cpu=False, data_root=HERE):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control (the lower precision) after the window; "
                         "never set by the driver")
    ap.add_argument("--override", action="append", default=[],
                    help="key=json: replaces a top-level parameter of the traffic mix, for the "
                         "sweep that finds a rate; never set by the driver")
    ap.add_argument("--save-ir", default=None,
                    help="with --trace 1: also keep the reduced trace here (the tests' recorded trace)")
    ap.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="for the tests: another cell table")
    args = ap.parse_args(argv)

    bench, cell, cfg, mix, reference = load_cell(args.benchmark, args.workload, data_root)

    # the program: a checkout that holds only the benchmark has none, and
    # the import fails before anything is printed
    from paddle_tpu.framework import persistent_cache

    from chipbench import harness, peaks

    t_imported = time.perf_counter()
    cache_dir = persistent_cache.enable()  # fixed path inside the checkout
    device = harness.device_facts(cell["chips"], allow_cpu=allow_cpu)
    t_device = time.perf_counter()
    if allow_cpu and device["platform"] != "tpu":
        from paddle_tpu.ops import pallas

        pallas._INTERPRET = True

    for item in args.override:
        key, _, value = item.partition("=")
        mix[key] = json.loads(value)
    ctx = types.SimpleNamespace(
        t0=T0, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        control=bool(args.control), chips=cell["chips"], cell=cell, cfg=cfg, mix=mix,
        device=device, peak=None if allow_cpu and device["platform"] != "tpu" else peaks.peak_for(device["kind"]),
        spans=harness.Spans(), compiles=harness.CompileCounter(), events=[], admitted={},
        reference=reference,
        builder=load_module("builders", cfg["builder"]), facts={}, ir=None)
    harness.log("start", workload=cell["name"], seed=args.seed, seconds=args.seconds,
                trace=args.trace, device=device, cache_dir=cache_dir,
                import_s=round(t_imported - T0, 3), device_init_s=round(t_device - t_imported, 3))

    loop = importlib.import_module("chipbench.loops." + LOOPS[mix["loop"]])
    correct, attempted, failed, end_to_end, compared, peak_bytes = loop.run(ctx)
    harness.log("persistent cache", **ctx.compiles.cache)

    dev = dict(device, memory_peak_bytes=int(peak_bytes))
    metrics, breakdown = {}, None
    if not ctx.trace:
        for m in bench["end_to_end"]:
            if m["name"] in end_to_end and ("workloads" not in m or cell["name"] in m["workloads"]):
                metrics[m["name"]] = {"value": end_to_end[m["name"]], "unit": m["unit"]}
    else:
        from chipbench import xplane

        if args.save_ir:
            xplane.save_ir(ctx.ir, args.save_ir)
        harness.log("trace", planes={k: len(v) for k, v in ctx.ir["devices"].items()},
                    spans=len(ctx.ir["spans"]), top_ops=xplane.top_ops(ctx.ir, 40))
        busy = xplane.busy_seconds(ctx.ir)
        dev["busy_s"], dev["window_s"] = busy["busy_s"], busy["window_s"]
        for m in bench["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            value = load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        gaps = sorted(xplane.gaps_by_span(ctx.ir).items(), key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": xplane.top_ops(ctx.ir, 10),
                     "idle_gaps": [[k, v] for k, v in gaps]}
        harness.log("end to end in the traced run (not reported)", **end_to_end)
    harness.emit_result(correct, attempted, failed, metrics, dev, compared, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
