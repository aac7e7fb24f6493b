"""Native runtime core (C++), loaded via ctypes.

The shared library is built on first import with g++ (no pybind11 in the
image; plain C ABI). Build artifacts live next to the source under _build/
keyed by a hash of the source, so a source change rebuilds automatically and
a copied checkout (whose mtimes mean nothing) can never load a library built
from other source.
Set PADDLE_TPU_NO_NATIVE=1 to disable (pure-Python fallbacks are used).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_here = os.path.dirname(os.path.abspath(__file__))
_src = os.path.join(_here, "src", "core.cc")
_build_dir = os.path.join(_here, "_build")
_lib = None
_lib_lock = threading.Lock()


class NativeUnavailable(RuntimeError):
    pass


def _build() -> str:
    os.makedirs(_build_dir, exist_ok=True)
    with open(_src, "rb") as f:
        stamp = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_build_dir, f"libpaddle_tpu_core.{stamp}.so")
    if os.path.exists(so_path):
        return so_path
    cmd = [
        "g++",
        "-O2",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-pthread",
        _src,
        "-o",
        so_path + ".tmp",
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        msg = getattr(e, "stderr", str(e))
        raise NativeUnavailable(f"native core build failed: {msg}") from e
    os.replace(so_path + ".tmp", so_path)
    # drop stale builds
    for f in os.listdir(_build_dir):
        if f.startswith("libpaddle_tpu_core.") and f != os.path.basename(so_path):
            try:
                os.remove(os.path.join(_build_dir, f))
            except OSError:
                pass
    return so_path


def _declare(lib):
    c = ctypes
    lib.pt_ring_create.restype = c.c_void_p
    lib.pt_ring_create.argtypes = [c.c_int, c.c_long]
    lib.pt_ring_destroy.argtypes = [c.c_void_p]
    lib.pt_ring_buffer_bytes.restype = c.c_long
    lib.pt_ring_buffer_bytes.argtypes = [c.c_void_p]
    lib.pt_ring_acquire_fill.restype = c.c_void_p
    lib.pt_ring_acquire_fill.argtypes = [c.c_void_p]
    lib.pt_ring_commit.argtypes = [c.c_void_p, c.c_void_p, c.c_long]
    lib.pt_ring_abort_fill.argtypes = [c.c_void_p, c.c_void_p]
    lib.pt_ring_acquire_batch.restype = c.c_void_p
    lib.pt_ring_acquire_batch.argtypes = [c.c_void_p, c.POINTER(c.c_long)]
    lib.pt_ring_release.argtypes = [c.c_void_p, c.c_void_p]
    lib.pt_ring_close.argtypes = [c.c_void_p]
    lib.pt_ring_ready_count.restype = c.c_int
    lib.pt_ring_ready_count.argtypes = [c.c_void_p]
    lib.pt_collate.argtypes = [
        c.c_void_p,
        c.POINTER(c.c_void_p),
        c.POINTER(c.c_long),
        c.POINTER(c.c_long),
        c.c_int,
        c.c_int,
    ]
    lib.pt_store_server_start.restype = c.c_void_p
    lib.pt_store_server_start.argtypes = [c.c_int]
    lib.pt_store_server_port.restype = c.c_int
    lib.pt_store_server_port.argtypes = [c.c_void_p]
    lib.pt_store_server_stop.argtypes = [c.c_void_p]
    lib.pt_store_client_connect.restype = c.c_void_p
    lib.pt_store_client_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.pt_store_set.restype = c.c_int
    lib.pt_store_set.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_int]
    lib.pt_store_get.restype = c.c_int
    lib.pt_store_get.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_int]
    lib.pt_store_add.restype = c.c_long
    lib.pt_store_add.argtypes = [c.c_void_p, c.c_char_p, c.c_long]
    lib.pt_store_wait.restype = c.c_int
    lib.pt_store_wait.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.pt_store_del.restype = c.c_int
    lib.pt_store_del.argtypes = [c.c_void_p, c.c_char_p]
    lib.pt_store_client_close.argtypes = [c.c_void_p]
    lib.pt_store_client_shutdown.argtypes = [c.c_void_p]
    return lib


def get_lib():
    """Load (building if needed) the native core; raises NativeUnavailable."""
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if os.environ.get("PADDLE_TPU_NO_NATIVE"):
            raise NativeUnavailable("disabled via PADDLE_TPU_NO_NATIVE")
        so = _build()
        _lib = _declare(ctypes.CDLL(so))
        return _lib


def available() -> bool:
    try:
        get_lib()
        return True
    except NativeUnavailable:
        return False
