"""Step-level performance attribution: XLA cost/memory capture + HBM census.

Reference parity: python/paddle/profiler/profiler_statistic.py's per-op
FLOPs/memory tables are fed by CUPTI on GPU; a TPU-native rebuild gets the
same answer from XLA itself — `compiled.cost_analysis()` (FLOPs, HBM bytes
accessed) and `compiled.memory_analysis()` (argument/output/temp/peak
memory) captured AT COMPILE TIME for every compiled program. The XProf
"where did the step go" roles covered here:

1. **Per-program cost records** — the static `Executor` compile path, the
   `to_static` trace, and the fused-optimizer bucket kernels call
   `record_compiled(origin, name, ...)` when a program finishes compiling;
   each record carries FLOPs, bytes accessed, the memory breakdown, and the
   compile wall time, and the latest numbers per origin land in the
   telemetry registry (`paddle_tpu_program_*` gauges).

2. **Live-HBM accounting** — `live_array_census()` walks
   `jax.live_arrays()` into count/bytes by dtype (and by annotated module,
   see `annotate_module`); `sample_watermark()` is the cheap step-boundary
   probe that tracks the process-lifetime high-water mark (sampled by
   `Optimizer.step`, by guardian anomalies, and included in flight-recorder
   crash dumps).

3. **Roofline** — `roofline(flops, bytes, seconds)` reports achieved vs
   peak FLOP/s and HBM bytes/s against a per-platform peak table
   (`DEFAULT_PEAK_TABLE`, CPU fallback included) so `bench.py` can emit
   `detail.attribution` (mfu, bandwidth utilization, compute/memory bound)
   alongside every timing.

4. **`perf_report()`** — the queryable JSON summary
   (`paddle.profiler.perf_report()`): programs + census + watermark.

Gating: collection sites check `telemetry.enabled()` (the
`PADDLE_TPU_TELEMETRY` flag) — disabled means record nothing and pay one
cached-bool read. Explicit queries (`perf_report`, `live_array_census`)
always work; they read what was collected.
"""
from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

from .. import telemetry as _tm

# bounded record store: old programs age out instead of growing without
# limit under long guard-cache-thrashing runs
_MAX_RECORDS = 256

_lock = threading.Lock()
_records: deque = deque(maxlen=_MAX_RECORDS)
_serial = [0]
_watermark: Dict[str, object] = {
    "peak_hbm_bytes": 0,
    "peak_at": None,
    "peak_tag": None,
    "live_bytes": 0,
    "live_count": 0,
    "samples": 0,
}
# module annotation registry: name -> [weakref to framework Tensor]
_module_tensors: Dict[str, list] = {}


# ---------------------------------------------------------------------------
# per-program cost/memory records
# ---------------------------------------------------------------------------

def _as_cost_dict(ca) -> dict:
    """Normalize cost_analysis() across jax versions: older jax returns a
    one-element list of dicts, newer returns the dict directly."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return {}
    return dict(ca)


_MEM_FIELDS = (
    ("generated_code_size_in_bytes", "generated_code_bytes"),
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
)


def _as_memory_dict(ma) -> dict:
    """Normalize memory_analysis(): a CompiledMemoryStats object (attrs) or
    a mapping, depending on backend/version."""
    if ma is None:
        return {}
    out = {}
    for attr, name in _MEM_FIELDS:
        v = getattr(ma, attr, None)
        if v is None and isinstance(ma, dict):
            v = ma.get(attr)
        if v is not None:
            out[name] = int(v)
    if out:
        # aliased (donated) argument bytes are reused by outputs, so they
        # count once; this is the program's device-memory footprint, not the
        # process high-water mark (that's the live-array watermark)
        out["peak_bytes"] = (
            out.get("argument_bytes", 0)
            + out.get("output_bytes", 0)
            + out.get("temp_bytes", 0)
            + out.get("generated_code_bytes", 0)
            - out.get("alias_bytes", 0)
        )
    return out


def record_compiled(
    origin: str,
    name: str,
    lowered=None,
    compiled=None,
    compile_seconds: Optional[float] = None,
    extra: Optional[dict] = None,
) -> Optional[dict]:
    """Capture one compiled program's XLA cost + memory analysis.

    Call sites are compile paths (static Executor, to_static, fused bucket
    build) — this must never break them: every analysis read is fenced, and
    a platform without cost analysis still yields a record (marked
    ``available: False``) so the caller can report "attribution
    unavailable" instead of silently dropping the program.

    Returns the record, or None when telemetry is disabled.
    """
    if not _tm.enabled():
        return None
    cost: dict = {}
    mem: dict = {}
    for src in (compiled, lowered):
        if src is None or cost:
            continue
        try:
            cost = _as_cost_dict(src.cost_analysis())
        except Exception:
            cost = {}
    if compiled is not None:
        try:
            mem = _as_memory_dict(compiled.memory_analysis())
        except Exception:
            mem = {}
    flops = float(cost.get("flops", 0.0) or 0.0)
    nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    with _lock:
        _serial[0] += 1
        serial = _serial[0]
    rec = {
        "serial": serial,
        "origin": str(origin),
        "name": str(name),
        "platform": platform_name(),
        "flops": flops,
        "bytes_accessed": nbytes,
        "transcendentals": float(cost.get("transcendentals", 0.0) or 0.0),
        "memory": mem,
        "peak_memory_bytes": int(mem.get("peak_bytes", 0)),
        "compile_seconds": (
            float(compile_seconds) if compile_seconds is not None else None
        ),
        "recorded_at": time.time(),
        "available": bool(cost) or bool(mem),
    }
    if extra:
        rec.update(extra)
    with _lock:
        _records.append(rec)
    try:
        _tm.counter(
            "paddle_tpu_perf_programs_recorded_total",
            "compiled programs captured by the attribution layer", ("origin",),
        ).labels(origin=rec["origin"]).inc()
        # latest-per-origin gauges: bounded cardinality (origins are the few
        # compile paths, not per-program names) — per-program detail lives
        # in perf_report()
        _tm.gauge(
            "paddle_tpu_program_flops",
            "FLOPs of the most recently compiled program", ("origin",),
        ).labels(origin=rec["origin"]).set(flops)
        _tm.gauge(
            "paddle_tpu_program_hbm_bytes",
            "HBM bytes accessed by the most recently compiled program",
            ("origin",),
        ).labels(origin=rec["origin"]).set(nbytes)
        _tm.gauge(
            "paddle_tpu_program_peak_memory_bytes",
            "XLA memory-analysis footprint of the most recently compiled "
            "program", ("origin",),
        ).labels(origin=rec["origin"]).set(rec["peak_memory_bytes"])
    except Exception:
        pass  # a telemetry schema clash must never break a compile path
    return rec


def program_records(origin: Optional[str] = None,
                    name: Optional[str] = None) -> List[dict]:
    """Recorded programs in compile order (oldest first), optionally
    filtered by origin and/or name. Returns copies."""
    with _lock:
        recs = list(_records)
    if origin is not None:
        recs = [r for r in recs if r["origin"] == origin]
    if name is not None:
        recs = [r for r in recs if r["name"] == name]
    return [dict(r) for r in recs]


# ---------------------------------------------------------------------------
# live-HBM accounting
# ---------------------------------------------------------------------------

def annotate_module(name: str, module) -> None:
    """Tag a Layer (or an iterable of Tensors) so the census reports its
    live bytes under `by_module[name]`. Weak references: annotation never
    extends tensor lifetime, and dead entries are pruned at census time."""
    if hasattr(module, "state_dict"):
        tensors = list(module.state_dict().values())
    else:
        tensors = list(module)
    refs = []
    for t in tensors:
        try:
            refs.append(weakref.ref(t))
        except TypeError:
            pass
    with _lock:
        _module_tensors[str(name)] = refs


def _live_totals() -> Tuple[int, int, Dict[str, dict]]:
    """(count, bytes, by_dtype) over jax.live_arrays(). Metadata-only: no
    device sync — nbytes/dtype are host-side attributes."""
    import jax

    by_dtype: Dict[str, dict] = {}
    total = 0
    count = 0
    for a in jax.live_arrays():
        try:
            nb = int(a.nbytes)
            dt = str(a.dtype)
        except Exception:
            continue  # a buffer deleted mid-walk
        total += nb
        count += 1
        st = by_dtype.setdefault(dt, {"count": 0, "bytes": 0})
        st["count"] += 1
        st["bytes"] += nb
    return count, total, by_dtype


def _module_census() -> Dict[str, dict]:
    import jax

    out: Dict[str, dict] = {}
    with _lock:
        items = list(_module_tensors.items())
    for name, refs in items:
        live = []
        cnt, nb = 0, 0
        for r in refs:
            t = r()
            if t is None:
                continue
            live.append(r)
            v = getattr(t, "_value", None)
            if v is None or isinstance(v, jax.core.Tracer):
                continue
            deleted = getattr(v, "is_deleted", None)
            if deleted is not None and deleted():
                continue  # donated-away buffer
            try:
                nb += int(v.nbytes)
                cnt += 1
            except Exception:
                continue
        with _lock:
            if name in _module_tensors:
                _module_tensors[name] = live  # prune dead weakrefs
        if cnt:
            out[name] = {"count": cnt, "bytes": nb}
    return out


def live_array_census(set_gauges: bool = True) -> dict:
    """Full census of live device arrays: count/bytes by dtype and by
    annotated module. Explicit query — works with telemetry disabled; the
    gauges only publish when it is enabled."""
    count, total, by_dtype = _live_totals()
    by_module = _module_census()
    census = {
        "count": count,
        "bytes": total,
        "by_dtype": by_dtype,
        "by_module": by_module,
    }
    if set_gauges and _tm.enabled():
        try:
            _tm.gauge(
                "paddle_tpu_hbm_live_arrays", "live device arrays"
            ).set(count)
            _tm.gauge(
                "paddle_tpu_hbm_live_bytes_total", "live device bytes"
            ).set(total)
            g = _tm.gauge(
                "paddle_tpu_hbm_live_bytes",
                "live device bytes by dtype", ("dtype",),
            )
            for dt, st in by_dtype.items():
                g.labels(dtype=dt).set(st["bytes"])
            gm = _tm.gauge(
                "paddle_tpu_hbm_module_bytes",
                "live device bytes by annotated module", ("module",),
            )
            for m, st in by_module.items():
                gm.labels(module=m).set(st["bytes"])
        except Exception:
            pass
    return census


# step-boundary sampling throttle: jax.live_arrays() costs O(live buffers)
# in Python wrapper construction (~20 us/array), so per-step sampling at
# thousands of live arrays would dominate a fast step. The probe
# self-throttles to >= max(_MIN_SAMPLE_GAP_S, 50x its own last cost) between
# samples, bounding steady-state overhead at ~2% while still catching the
# high-water mark's growth; rare/explicit callers (guardian anomalies,
# bench, tests) pass force=True.
_MIN_SAMPLE_GAP_S = 0.25
_sample_state = {"next_at": 0.0}


def sample_watermark(tag: str = "step", force: bool = False) -> Optional[dict]:
    """Step-boundary probe: total live bytes + high-water mark.

    Called per optimizer step and on guardian anomalies — it skips the
    by-dtype/by-module breakdown (that's the full census) and is a no-op
    when telemetry is disabled. Throttled (see _MIN_SAMPLE_GAP_S) unless
    `force`. Returns the watermark snapshot (the last one when throttled).
    """
    if not _tm.enabled():
        return None
    now = time.monotonic()
    if not force and now < _sample_state["next_at"]:
        return watermark()
    t0 = time.perf_counter()
    count, total, _ = _live_totals()
    _sample_state["next_at"] = now + max(
        _MIN_SAMPLE_GAP_S, 50.0 * (time.perf_counter() - t0)
    )
    with _lock:
        _watermark["live_bytes"] = total
        _watermark["live_count"] = count
        _watermark["samples"] = int(_watermark["samples"]) + 1
        if total > int(_watermark["peak_hbm_bytes"]):
            _watermark["peak_hbm_bytes"] = total
            _watermark["peak_at"] = time.time()
            _watermark["peak_tag"] = str(tag)
        snap = dict(_watermark)
    try:
        _tm.gauge(
            "paddle_tpu_hbm_live_bytes_total", "live device bytes"
        ).set(total)
        _tm.gauge(
            "paddle_tpu_hbm_watermark_bytes",
            "high-water mark of live device bytes (sampled at step "
            "boundaries and on guardian anomalies)",
        ).set(snap["peak_hbm_bytes"])
    except Exception:
        pass
    return snap


def watermark() -> dict:
    with _lock:
        return dict(_watermark)


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

# per-chip bf16 matmul peak FLOP/s and HBM bandwidth, keyed by the lowercased
# `jax.devices()[0].device_kind` (published numbers: Google Cloud TPU system
# architecture pages; v5e = 197 TFLOP/s bf16, 819 GB/s). A device that is not
# in the table is an error, not a default: a utilization against the wrong
# peak is off by orders of magnitude, silently.
DEFAULT_PEAK_TABLE = {
    "tpu v4": {"flops_per_s": 275e12, "bytes_per_s": 1.2e12},
    "tpu v5e": {"flops_per_s": 197e12, "bytes_per_s": 0.82e12},
    "tpu v5p": {"flops_per_s": 459e12, "bytes_per_s": 2.77e12},
    "tpu v6e": {"flops_per_s": 918e12, "bytes_per_s": 1.64e12},
}
# device_kind spellings jax reports for a row of the table
_DEVICE_KIND_ALIASES = {
    "tpu v5 lite": "tpu v5e",
    "tpu v5": "tpu v5p",
    "tpu v6 lite": "tpu v6e",
}


class UnknownDeviceError(LookupError):
    """No published peak for this device kind — roofline refuses to guess."""


def platform_name() -> str:
    """Lowercased device kind ('tpu v5 lite', 'cpu', ...)."""
    import jax

    return str(jax.devices()[0].device_kind).lower()


def peak_for(platform: Optional[str] = None,
             peak_table: Optional[dict] = None) -> Tuple[str, dict]:
    """(table key, {flops_per_s, bytes_per_s}) for a device kind: an exact
    row, or the row a known `device_kind` spelling names ('TPU v5 lite' is
    the v5e). Anything else raises UnknownDeviceError."""
    table = peak_table if peak_table is not None else DEFAULT_PEAK_TABLE
    p = (platform or platform_name()).lower()
    key = p if p in table else _DEVICE_KIND_ALIASES.get(p)
    if key not in table:
        raise UnknownDeviceError(
            f"no published peak for device kind {p!r} (known: {sorted(table)})"
        )
    return key, dict(table[key])


def roofline(flops, bytes_accessed, seconds, platform: Optional[str] = None,
             peak_table: Optional[dict] = None) -> dict:
    """Achieved-vs-peak utilization for one measured region.

    `flops`/`bytes_accessed` come from the program's cost record, `seconds`
    from a real measurement (slope-timed step, profiled span). `mfu` is
    achieved FLOP/s over peak FLOP/s; `hbm_util` likewise for bandwidth;
    `bound` names the roofline regime the measurement sits in.
    """
    seconds = float(seconds)
    if seconds <= 0:
        raise ValueError(f"roofline needs a positive duration, got {seconds}")
    plat, peak = peak_for(platform, peak_table)
    achieved_f = float(flops) / seconds
    achieved_b = float(bytes_accessed) / seconds
    mfu = achieved_f / peak["flops_per_s"]
    hbm_util = achieved_b / peak["bytes_per_s"]
    return {
        "platform": plat,
        "seconds": seconds,
        "flops": float(flops),
        "bytes": float(bytes_accessed),
        "achieved_flops_per_s": achieved_f,
        "achieved_bytes_per_s": achieved_b,
        "peak_flops_per_s": float(peak["flops_per_s"]),
        "peak_bytes_per_s": float(peak["bytes_per_s"]),
        "mfu": mfu,
        "hbm_util": hbm_util,
        "bound": "compute" if mfu >= hbm_util else "memory",
    }


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

_REPORT_KEYS = (
    "version", "generated_at", "platform", "telemetry_enabled",
    "programs", "live_arrays", "hbm_watermark", "input_pipeline",
    "serving", "compilation",
)
_PROGRAM_KEYS = (
    "serial", "origin", "name", "platform", "flops", "bytes_accessed",
    "memory", "peak_memory_bytes", "compile_seconds", "recorded_at",
    "available",
)


def _input_pipeline_section() -> dict:
    """The starved-vs-slow join (round 12): the streaming tier's wait
    totals + rolling-window verdict, annotated against the device-side
    story this report carries. A 'starved' step is one the roofline records
    CANNOT explain — the device was idle waiting for the host — which is
    exactly the case where chasing `programs[]` mfu would mislead."""
    try:
        from ..io.streaming import stats as _instats

        section = _instats.summary()
    except Exception as e:  # the report must not die on a partial install
        return {"verdict": "unavailable", "error": str(e)[-200:]}
    hints = {
        "starved": "host input pipeline bounds the step; device attribution "
                   "(programs[]) cannot explain the step time — fix the "
                   "reader/prefetch, not the kernels",
        "input_limited": "input wait is a visible slice of the step; both "
                         "host and device stories apply",
        "compute": "device-bound: see programs[] cost records + roofline",
    }
    section["attribution_hint"] = hints.get(section.get("verdict"))
    return section


def _serving_section() -> dict:
    """The request-trace SLO decomposition (round 16): per-component
    TTFT/TPOT attribution over sampled serving requests, or an explicit
    unavailable marker. The component sums equal the measured request wall
    time by construction (contiguous phase spans), so the `consistency`
    field doubles as a tracing-health check perf_gate enforces."""
    try:
        from ..telemetry import request_trace as _rt

        return _rt.serving_section()
    except Exception as e:  # the report must render without the serving tier
        return {"available": False, "reason": f"request_trace failed: {e}"}


def _compilation_section() -> dict:
    """The compile-lifecycle ledger rollup: event/hit/miss counts and
    compile seconds by origin. Answers 'what did cold start cost' from the
    same report that already attributes steady-state FLOPs."""
    try:
        from .. import compile_cache as _cc

        return _cc.summary()
    except Exception as e:  # the report must render without the ledger
        return {"available": False, "reason": f"compile ledger failed: {e}"}


def perf_report(origin: Optional[str] = None) -> dict:
    """The queryable attribution summary (exported as
    `paddle.profiler.perf_report`): every recorded program's FLOPs / bytes /
    memory / compile time, the live-array census, the HBM watermark, and
    the input-pipeline starved-vs-slow verdict. Plain JSON-serializable
    dict."""
    return {
        "version": 1,
        "generated_at": time.time(),
        "platform": platform_name(),
        "telemetry_enabled": _tm.enabled(),
        "programs": program_records(origin),
        "live_arrays": live_array_census(set_gauges=False),
        "hbm_watermark": watermark(),
        "input_pipeline": _input_pipeline_section(),
        "serving": _serving_section(),
        "compilation": _compilation_section(),
    }


def validate_report(report: dict) -> dict:
    """Schema check for perf_report() output (used by tests and by consumers
    reading a report back from JSON). Raises ValueError on a malformed
    report; returns it unchanged otherwise."""
    missing = [k for k in _REPORT_KEYS if k not in report]
    if missing:
        raise ValueError(f"perf report missing keys: {missing}")
    for i, rec in enumerate(report["programs"]):
        bad = [k for k in _PROGRAM_KEYS if k not in rec]
        if bad:
            raise ValueError(f"program record {i} missing keys: {bad}")
    census = report["live_arrays"]
    for k in ("count", "bytes", "by_dtype", "by_module"):
        if k not in census:
            raise ValueError(f"live_arrays census missing {k!r}")
    if "peak_hbm_bytes" not in report["hbm_watermark"]:
        raise ValueError("hbm_watermark missing peak_hbm_bytes")
    if "verdict" not in report["input_pipeline"]:
        raise ValueError("input_pipeline missing verdict")
    if "available" not in report["serving"]:
        raise ValueError("serving section missing 'available'")
    if report["serving"].get("available") and report["serving"].get("n_traced"):
        # round 17: a populated serving section must attribute where the
        # latency wins come from (prefix reuse + speculative decoding) —
        # zeros are fine, absence means the breakdown regressed
        for k in ("cached_tokens", "spec"):
            if k not in report["serving"]:
                raise ValueError(f"serving section missing {k!r}")
    comp = report["compilation"]
    if "available" not in comp:
        raise ValueError("compilation section missing 'available'")
    if comp.get("available"):
        # round 18: a live ledger must carry the cold-start accounting —
        # zero counts are fine, absent keys mean the rollup regressed
        for k in ("hits", "misses", "hit_rate", "total_compile_seconds",
                  "by_origin"):
            if k not in comp:
                raise ValueError(f"compilation section missing {k!r}")
    return report


def snapshot_for_crash(max_programs: int = 8) -> dict:
    """Compact attribution snapshot for flight-recorder crash dumps: the
    watermark plus the newest programs' headline numbers — enough to answer
    'was this an OOM-adjacent step' without the full report."""
    recs = program_records()[-max_programs:]
    return {
        "platform": platform_name(),
        "hbm_watermark": watermark(),
        "programs": [
            {
                "origin": r["origin"],
                "name": r["name"],
                "flops": r["flops"],
                "bytes_accessed": r["bytes_accessed"],
                "peak_memory_bytes": r["peak_memory_bytes"],
                "compile_seconds": r["compile_seconds"],
            }
            for r in recs
        ],
    }


def reset() -> None:
    """Clear records, watermark, and module annotations (tests)."""
    with _lock:
        _records.clear()
        _module_tensors.clear()
        _watermark.update(
            peak_hbm_bytes=0, peak_at=None, peak_tag=None,
            live_bytes=0, live_count=0, samples=0,
        )
        _sample_state["next_at"] = 0.0
