"""The one cache of compiled programs that outlives a process: JAX's
persistent compilation cache, switched on by
`paddle_tpu.framework.persistent_cache.enable()`.

Every case runs in child processes: a cache hit needs a fresh process (the
in-process jit cache would hide it), and `enable()` changes jax.config for
the whole process. A child is this file run as a script; it prints one JSON
line: the cache's directory, this process's hits and misses, and what the
entry point computed. The cache is never stubbed.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the children
# ---------------------------------------------------------------------------

def _run_engine():
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import InferenceEngine
    from paddle_tpu.models.llama import llama_tiny

    paddle.seed(0)
    model = llama_tiny(num_key_value_heads=2)
    model.eval()
    eng = InferenceEngine(model, max_seq_len=32, block_size=8, max_batch=2,
                          decode_batch_buckets=(2,))
    stats = eng.prewarm()
    ids = eng.generate([list(range(1, 7))], max_new_tokens=4)
    assert stats["compiles"] >= 2 and eng.bucket_stats["compiles"] == stats["compiles"]
    return ids


def _run_to_static():
    import numpy as np

    import paddle_tpu as paddle

    paddle.seed(0)
    lin = paddle.nn.Linear(4, 2)
    opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=lin.parameters())

    @paddle.jit.to_static
    def train_step(x, y):
        loss = ((lin(x) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.ones((8, 4), "float32"))
    y = paddle.to_tensor(np.zeros((8, 2), "float32"))
    return [float(train_step(x, y).numpy()) for _ in range(3)]


def _run_executor():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import static

    main = static.Program()
    with static.program_guard(main, static.Program()):
        x = static.data("x", [2, 4], "float32")
        y = paddle.matmul(x, paddle.ones([4, 2])) + 1.0
    feed = np.arange(8, dtype="float32").reshape(2, 4)
    (out,) = static.Executor().run(main, feed={"x": feed}, fetch_list=[y])
    return np.asarray(out).tolist()


def _run_enable():
    import jax

    from paddle_tpu.framework import persistent_cache

    again = persistent_cache.enable()  # the child's main has called it once
    # one program: a listener registered twice would count it twice
    jax.jit(lambda x: x + 1).lower(jax.ShapeDtypeStruct((), "float32")).compile()
    return {
        "again": again,
        "checkout_dir": persistent_cache.CHECKOUT_CACHE_DIR,
        "min_compile_time_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
        "min_entry_size_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes,
    }


_ENTRIES = {"engine": _run_engine, "to_static": _run_to_static,
            "executor": _run_executor, "enable": _run_enable}


def _child_main(entry: str) -> None:
    sys.path.insert(0, REPO)
    from paddle_tpu.framework import persistent_cache

    persistent_cache.enable()
    out = _ENTRIES[entry]()
    print(json.dumps({**persistent_cache.stats(), "out": out}))


# ---------------------------------------------------------------------------
# the parent's side
# ---------------------------------------------------------------------------

def _child(entry, cache_dir, devices=1):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PADDLE_TPU_TELEMETRY="1")
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    r = subprocess.run([sys.executable, os.path.abspath(__file__), entry], env=env,
                       capture_output=True, text=True, timeout=240, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _cold_then_warm(entry, cache_dir, devices=1):
    cold = _child(entry, cache_dir, devices)
    assert cold["dir"] == str(cache_dir)
    assert cold["hits"] == 0 and cold["misses"] > 0, cold
    assert os.listdir(cache_dir), "the cold process kept nothing"
    warm = _child(entry, cache_dir, devices)
    assert warm["misses"] == 0 and warm["hits"] == cold["misses"], (cold, warm)
    assert warm["out"] == cold["out"]
    return cold


@pytest.mark.parametrize("env_dir", [True, False], ids=["env-dir", "checkout-dir"])
def test_enable_is_idempotent_and_places_the_cache(tmp_path, env_dir):
    """`enable()` leaves a set JAX_COMPILATION_CACHE_DIR alone and otherwise
    names the one fixed path inside the checkout; it keeps every program
    (both thresholds lowered) and counts each of JAX's events once."""
    got = _child("enable", tmp_path if env_dir else None)
    out = got["out"]
    want = str(tmp_path) if env_dir else out["checkout_dir"]
    assert out["again"] == got["dir"] == want
    assert out["checkout_dir"] == os.path.join(REPO, ".jax_cache")
    assert out["min_compile_time_secs"] == 0.0
    assert out["min_entry_size_bytes"] == -1
    assert got["hits"] + got["misses"] == 1  # one program, counted once


@pytest.mark.parametrize("entry", ["engine", "to_static", "executor"])
def test_cold_process_compiles_warm_process_restores(tmp_path, entry):
    """Each compile entry point: a first process compiles and keeps every
    program, a second restores all of them and computes the same."""
    cold = _cold_then_warm(entry, tmp_path)
    if entry == "to_static":
        assert cold["out"][2] < cold["out"][1] < cold["out"][0]  # it trains


def test_process_with_eight_devices_restores_a_one_device_program(tmp_path):
    """The case the repo's own executable store failed (removed in PR 29):
    a process that sees 8 devices restores the engine's one-device bucket
    programs and generates the same ids."""
    _cold_then_warm("engine", tmp_path, devices=8)


def test_damaged_entries_fall_back_to_a_compile(tmp_path):
    """Every file of the cache cut to half its length: the next process
    compiles again, does not crash, and generates the same ids."""
    cold = _child("engine", tmp_path)
    names = os.listdir(tmp_path)
    assert names
    for name in names:
        path = os.path.join(tmp_path, name)
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
    again = _child("engine", tmp_path)
    assert again["hits"] == 0 and again["misses"] == cold["misses"], (cold, again)
    assert again["out"] == cold["out"]


if __name__ == "__main__":
    _child_main(sys.argv[1])
