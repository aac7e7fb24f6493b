"""Thread-local framework state: grad mode + trace recording hooks.

Reference parity: grad mode ≈ paddle.no_grad (python/paddle/base/dygraph/base.py);
trace recording is the substrate for to_static program capture (the analog of
run_program_op state capture, python/paddle/jit/dy2static/partial_program.py).
"""
from __future__ import annotations

import functools
import threading

import jax


class _TLS(threading.local):
    def __init__(self):
        self.grad_enabled = True
        self.recorder = None  # active StateRecorder during to_static capture
        self.amp_state = None  # active AMP context (paddle_tpu.amp)
        self.scope = ""  # path of the named scopes around the running op


_tls = _TLS()


def is_grad_enabled() -> bool:
    return _tls.grad_enabled


def set_grad_enabled(mode: bool):
    _tls.grad_enabled = bool(mode)


class named_scope:
    """`jax.named_scope(name)` that the autograd tape can follow: every HLO
    op traced inside carries the path of the scopes around it
    (`.../encoder/layers.3/self_attn/...`), and so does its pullback, which
    runs later and elsewhere: a GradNode keeps `scope_path()` and the
    backward re-enters it. Compile-time metadata only."""

    __slots__ = ("name", "_prev", "_ctx")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._prev = _tls.scope
        _tls.scope = f"{self._prev}/{self.name}" if self._prev else self.name
        self._ctx = jax.named_scope(self.name)
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        self._ctx.__exit__(*exc)
        _tls.scope = self._prev
        return False


def scope_path() -> str:
    return _tls.scope


class no_grad:
    """paddle.no_grad analog: context manager AND decorator."""

    def __enter__(self):
        self._prev = _tls.grad_enabled
        _tls.grad_enabled = False
        return self

    def __exit__(self, *exc):
        _tls.grad_enabled = self._prev
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with no_grad():
                return fn(*args, **kwargs)

        return wrapper


class enable_grad:
    def __enter__(self):
        self._prev = _tls.grad_enabled
        _tls.grad_enabled = True
        return self

    def __exit__(self, *exc):
        _tls.grad_enabled = self._prev
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with enable_grad():
                return fn(*args, **kwargs)

        return wrapper


class set_grad_enabled_ctx:
    def __init__(self, mode: bool):
        self.mode = bool(mode)

    def __enter__(self):
        self._prev = _tls.grad_enabled
        _tls.grad_enabled = self.mode
        return self

    def __exit__(self, *exc):
        _tls.grad_enabled = self._prev
        return False


# ---- trace recording (used by paddle_tpu.jit) ----

def get_recorder():
    return _tls.recorder


def set_recorder(rec):
    prev = _tls.recorder
    _tls.recorder = rec
    return prev


def record_read(tensor):
    rec = _tls.recorder
    if rec is not None:
        rec.on_read(tensor)


def record_write(tensor):
    rec = _tls.recorder
    if rec is not None:
        rec.on_write(tensor)


def record_create(tensor):
    rec = _tls.recorder
    if rec is not None:
        rec.on_create(tensor)


def record_grad_write(tensor):
    rec = _tls.recorder
    if rec is not None:
        rec.on_grad_write(tensor)


# ---- AMP state (set by paddle_tpu.amp.auto_cast) ----

def get_amp_state():
    return _tls.amp_state


def set_amp_state(st):
    prev = _tls.amp_state
    _tls.amp_state = st
    return prev


# ---- static program capture (set by paddle_tpu.static.program_guard) ----

def get_program_capture():
    return getattr(_tls, "program_capture", None)


def set_program_capture(prog):
    prev = getattr(_tls, "program_capture", None)
    _tls.program_capture = prog
    return prev
