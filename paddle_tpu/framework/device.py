"""Device / Place abstraction.

Reference parity: paddle/phi/common/place.h (Place, AllocationType) and
python/paddle/device/__init__.py (set_device/get_device). TPU-native design:
a Place is a thin view over a jax.Device; "tpu" is the first-class device
type, "cpu" is the host fallback. There is no allocator facade — XLA/TPU
runtime owns HBM; what we expose is device selection + placement.
"""
from __future__ import annotations

import threading

import jax

_lock = threading.Lock()
_current_place = None


def _device_kind(d: "jax.Device") -> str:
    return d.platform


class Place:
    """Analog of phi::Place (paddle/phi/common/place.h:57): (device_type, device_id).

    Wraps a concrete jax.Device.
    """

    __slots__ = ("_device",)

    def __init__(self, device):
        if isinstance(device, Place):
            device = device._device
        self._device = device

    @property
    def jax_device(self):
        return self._device

    @property
    def device_type(self) -> str:
        return _device_kind(self._device)

    @property
    def device_id(self) -> int:
        return self._device.id

    def is_tpu_place(self) -> bool:
        return self.device_type == "tpu"

    def is_cpu_place(self) -> bool:
        return self.device_type == "cpu"

    def __eq__(self, other):
        if isinstance(other, str):
            try:
                other = _parse_device(other)
            except ValueError:
                return NotImplemented
            return self._device == other._device
        if isinstance(other, Place):
            return self._device == other._device
        return NotImplemented

    def __hash__(self):
        return hash(self._device)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        devs = [d for d in jax.devices() if _device_kind(d) == "tpu"]
        if not devs:
            raise RuntimeError("No TPU devices visible to jax")
        super().__init__(devs[device_id])


class CPUPlace(Place):
    def __init__(self, device_id: int = 0):
        devs = jax.devices("cpu") if jax.default_backend() != "cpu" else jax.devices()
        super().__init__(devs[device_id])


def _parse_device(device: str) -> Place:
    device = device.lower()
    if ":" in device:
        kind, _, idx = device.partition(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind in ("tpu", "gpu", "xpu"):
        # gpu/xpu requests map to the accelerator present (tpu-native framework)
        devs = [d for d in jax.devices() if _device_kind(d) == "tpu"]
        if not devs:
            raise ValueError(f"no accelerator device for '{device}'")
        return Place(devs[idx])
    if kind == "cpu":
        return CPUPlace(idx)
    raise ValueError(f"unknown device '{device}'")


def set_device(device: str) -> Place:
    """paddle.device.set_device analog (python/paddle/device/__init__.py:265)."""
    global _current_place
    place = _parse_device(device) if isinstance(device, str) else Place(device)
    with _lock:
        _current_place = place
    return place


def get_device() -> str:
    """paddle.device.get_device analog (python/paddle/device/__init__.py:297)."""
    p = _get_current_place()
    return f"{p.device_type}:{p.device_id}"


def _get_current_place() -> Place:
    global _current_place
    if _current_place is None:
        with _lock:
            if _current_place is None:
                _current_place = Place(jax.devices()[0])
    return _current_place


def is_compiled_with_tpu() -> bool:
    try:
        return any(_device_kind(d) == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def device_count(kind: str = None) -> int:
    if kind is None:
        return len(jax.devices())
    return len([d for d in jax.devices() if _device_kind(d) == kind])


class CUDAPinnedPlace(CPUPlace):
    """CUDA-compat pinned-host-memory place: on TPU the host staging role is
    played by the native prefetch ring / XLA host memory kinds, so this is
    the host place (reference phi/common/place.h CUDAPinnedPlace)."""
