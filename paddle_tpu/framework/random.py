"""RNG state management.

Reference parity: paddle/phi/core/generator.h + python/paddle/framework/random.py.
TPU-native design: jax threaded PRNG keys instead of stateful Philox counters.
A global Generator owns a key and splits per draw. Under program capture
(to_static), a trace scope substitutes a traced base key and derives per-draw
keys via fold_in(counter) so randomness varies per step instead of being baked
into the compiled program as a constant.
"""
from __future__ import annotations

import threading

import jax
import numpy as np


class Generator:
    """Analog of phi::Generator (paddle/phi/core/generator.h)."""

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self._seed = seed
        # built on first use: a PRNGKey is a device array, and importing the
        # framework must not initialise a backend (a spawned DataLoader
        # worker or a launcher parent imports it on a host whose chip
        # belongs to another process)
        self._lazy_key = None
        # trace-scope state: (base_key_tracer, counter) or None
        self._trace_base = None
        self._trace_counter = 0

    @property
    def _key(self):
        if self._lazy_key is None:
            self._lazy_key = jax.random.PRNGKey(self._seed)
        return self._lazy_key

    @_key.setter
    def _key(self, key):
        self._lazy_key = key

    def manual_seed(self, seed: int):
        with self._lock:
            self._seed = int(seed)
            self._lazy_key = None
        return self

    def initial_seed(self) -> int:
        return self._seed

    def get_state(self):
        return np.asarray(self._key)

    def set_state(self, state):
        import jax.numpy as jnp

        self._key = jnp.asarray(state, dtype=jnp.uint32)

    def fold_in(self, data: int):
        """Deterministically derive a new base key from (current key, data).

        Used by the training guardian's rollback: restoring a snapshot key
        then folding in the rollback count makes the retried steps draw
        fresh dropout/noise deterministically instead of replaying the
        exact randomness of the diverged attempt."""
        with self._lock:
            self._key = jax.random.fold_in(self._key, int(data))
        return self

    def next_key(self):
        """Return a fresh PRNG key. Thread-safe; trace-aware."""
        with self._lock:
            if self._trace_base is not None:
                k = jax.random.fold_in(self._trace_base, self._trace_counter)
                self._trace_counter += 1
                return k
            self._key, sub = jax.random.split(self._key)
            return sub

    class _TraceScope:
        def __init__(self, gen, base_key):
            self.gen = gen
            self.base = base_key

        def __enter__(self):
            self.prev = (self.gen._trace_base, self.gen._trace_counter)
            self.gen._trace_base = self.base
            self.gen._trace_counter = 0
            return self

        def __exit__(self, *exc):
            self.gen._trace_base, self.gen._trace_counter = self.prev
            return False

    def trace_scope(self, base_key):
        return Generator._TraceScope(self, base_key)


_default_generator = Generator(0)


def default_generator() -> Generator:
    return _default_generator


def seed(value: int) -> Generator:
    """paddle.seed analog (python/paddle/framework/random.py)."""
    return _default_generator.manual_seed(value)


def get_rng_state():
    return _default_generator.get_state()


def set_rng_state(state):
    _default_generator.set_state(state)


def next_key():
    return _default_generator.next_key()
