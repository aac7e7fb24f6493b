"""Compilation-lifecycle observability: the compile ledger and the engine's
bucket key.

- `ledger`: every `lower()`/`compile()` across the four compile entry
  points (static `Executor`, `to_static`, the `InferenceEngine` shape
  buckets, the fused-optimizer engine) emits a structured event (origin,
  name, signature, wall seconds, miss|hit|shared outcome) into a bounded
  store with `paddle_tpu_compile_*` telemetry, compile spans in the request
  trace's chrome lanes, and a cold-start timeline report
  (`python -m paddle_tpu.compile_cache report`) decomposing the
  engine-load -> first-token wall.
- `fingerprint`: the key under which same-signature engines of one process
  share a bucket's executable (`inference/engine.py`).

The cache itself is JAX's: compiled programs persist across processes
through JAX's persistent compilation cache, placed by
`paddle_tpu.framework.persistent_cache.enable()`, and through nothing else.
"""
from . import fingerprint, ledger, report  # noqa: F401
from .fingerprint import (  # noqa: F401
    aval_signature,
    entry_key,
    fingerprint_text,
    topology_meta,
)
from .ledger import (  # noqa: F401
    events,
    record,
    reset,
    reset_timeline,
    summary,
)
from .report import cold_start_report, format_report  # noqa: F401

__all__ = [
    "fingerprint",
    "ledger",
    "report",
    "aval_signature",
    "entry_key",
    "fingerprint_text",
    "topology_meta",
    "events",
    "record",
    "reset",
    "reset_timeline",
    "summary",
    "cold_start_report",
    "format_report",
]
