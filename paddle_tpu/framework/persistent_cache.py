"""JAX's persistent compilation cache, placed from outside.

Entry points (`chip_smoke.py`, `bench.py` and its children) call
`enable()` once, before their first compile. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX already keeps its cache there and
this module sets no directory. Where it is not, the cache goes to ONE fixed
path inside the checkout (`.jax_cache/`, ignored by git): the path is part
of every entry's key, so a temp name, a pid or a time would never hit.

The cache's thresholds are lowered so that every program is kept, the
step and bucket programs included. `stats()` counts this process's
persistent-cache hits and misses from JAX's own monitoring events.

This is the one cache of compiled programs that outlives a process: the
repo keeps no executable store of its own.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_counts = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kwargs) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _counts[key] += 1


def enable() -> str:
    """Switch the persistent cache on for this process; returns its
    directory. Idempotent. Touches only jax.config — no backend starts."""
    global _listening
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return jax.config.jax_compilation_cache_dir


def stats() -> dict:
    """{"dir", "hits", "misses"} for this process so far."""
    return {"dir": jax.config.jax_compilation_cache_dir, **_counts}
