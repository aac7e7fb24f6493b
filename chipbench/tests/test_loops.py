"""Each loop, at a tiny size on the CPU, ends in a last line with the
contract's keys; the command itself refuses a CPU."""
import contextlib
import io
import json
import os
import subprocess
import sys

import pytest

from chipbench import run

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
ROOT = os.path.dirname(os.path.dirname(HERE))


def drive(workload, seed=7, seconds=1.5, extra=()):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        run.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                  "--trace", "0", "--benchmark", os.path.join(DATA, "BENCHMARK.json"), *extra],
                 allow_cpu=True, data_root=DATA)
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


@pytest.mark.parametrize("workload,metrics", [
    ("tiny-ernie.tiny-train", {"train_tokens_per_s", "setup_s"}),
    ("tiny-mistral.tiny-open", {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"}),
    ("tiny-mistral.tiny-closed", {"serve_tokens_per_s", "setup_s"}),
    ("tiny-ernie.tiny-train-dp2mp2", {"train_tokens_per_s", "setup_s"}),
])
def test_loop_ends_in_the_contracts_line(workload, metrics):
    line, err = drive(workload, seed=2 ** 31 + 12345)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"          # the numbers compared come last
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 and m["unit"] for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for c in line["compared"].values():
        assert set(c) == {"value", "limit", "ok"}
    last = err.strip().splitlines()
    assert last[-1] == "correct: True" and last[-2].startswith("compared ")


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
                        "--workload", "ernie3-base-mlm.s512-1chip", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cb = os.path.join(ROOT, "chipbench")
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.join(cb, "reference", c["name"] + ".py"))
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for w in b["workloads"]:
        assert os.path.exists(os.path.join(cb, "traffic", w["traffic"] + ".json"))
        assert w["name"] == w["config"] + "." + w["traffic"]
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(cb, "layer_metrics", m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
