"""The control and the planted faults of a TRAINING cell, read on the chip
at the cell's own size. Not a benchmark run: no window, no result line.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3

For each seed: the plain reference's three steps; the control (the same
reference one precision down, see the configuration's `precision.control`)
put in the program's place; and the half-batch fault planted in the
reference (the mean taken over half of the rows). Prints, for each, the
numbers a run compares, so that a limit can be set between the program's
readings and these. A state left unchanged reads 1 by the measure and needs
no run. (A served cell's control rides a run: `run.py --control 1`.)
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None, allow_cpu=False, data_root=HERE):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--benchmark", default=os.path.join(os.path.dirname(HERE), "BENCHMARK.json"))
    args = ap.parse_args(argv)
    import numpy as np

    from paddle_tpu.framework import persistent_cache

    from chipbench import harness, run, traffic, weights
    from chipbench.loops import train

    persistent_cache.enable()
    _, _, cfg, mix, ref_mod = run.load_cell(args.benchmark, args.workload, data_root)
    harness.device_facts(1, allow_cpu=allow_cpu)
    replicas = int((mix.get("mesh") or {}).get("dp", 1))
    block = int(mix.get("reference_row_block", 4))
    out = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        ids, labels = traffic.train_batch(mix, seed, cfg["vocab_size"], replicas)
        params = weights.make(ref_mod.leaf_specs(cfg), seed, np.float32)
        kw = dict(steps=3, row_block=block)
        ref = ref_mod.train_steps(params, ids, labels, cfg, cfg["optimizer"], **kw)
        readings = {}
        for name, extra in (("control_bf16", {"precision": "bf16"}),
                            ("fault_half_batch", {"rows": slice(0, ids.shape[0] // 2)})):
            got = ref_mod.train_steps(params, ids, labels, cfg, cfg["optimizer"], **kw, **extra)
            compared = {}
            ok, where = train.judge(got, ref, mix["limits"], compared)
            readings[name] = {"correct": bool(ok), **{k: v["value"] for k, v in compared.items()},
                              "grad_leaf": where["grad_leaf"], "change_leaf": where["change_leaf"]}
        line = {"seed": seed, "ref_losses": ref["losses"], **readings}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


if __name__ == "__main__":
    main()
