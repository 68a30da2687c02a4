"""Device / place management.

Reference analog: paddle/phi/backends/device_manager.h (DeviceManager),
paddle/fluid/platform Place types, python/paddle/device/__init__.py
(`paddle.set_device('gpu:0')`). On TPU the device set is owned by the PJRT
client; a "place" is a jax.Device. We keep the `set_device`/`get_device`
string UX ('tpu', 'tpu:0', 'cpu') and let it steer jax's default device.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

_LOCK = threading.RLock()
_CURRENT: Optional[str] = None  # normalized "plat:idx"


class Place:
    """A concrete device (≈ phi::Place). Wraps a jax.Device."""

    def __init__(self, device: "jax.Device"):
        self._device = device

    @property
    def jax_device(self):
        return self._device

    @property
    def platform(self) -> str:
        return self._device.platform

    @property
    def index(self) -> int:
        return self._device.id

    def is_tpu_place(self) -> bool:
        return self._device.platform == "tpu"

    def is_cpu_place(self) -> bool:
        return self._device.platform == "cpu"

    def __repr__(self):
        return f"Place({self._device.platform}:{self._device.id})"

    def __eq__(self, other):
        return isinstance(other, Place) and self._device == other._device

    def __hash__(self):
        return hash(self._device)


def _parse(device: str):
    device = device.lower().strip()
    if ":" in device:
        plat, idx = device.split(":", 1)
        return plat, int(idx)
    return device, 0


_PLAT_ALIASES = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu", "npu": "tpu"}


def set_device(device: str) -> Place:
    """paddle.set_device analog. Accepts 'tpu', 'tpu:0', 'cpu'.

    Accelerator aliases from the reference ('gpu', 'xpu', 'npu') map to 'tpu'
    so ported scripts run unchanged. A platform with no visible device
    raises: asking for the TPU never lands on the CPU.
    """
    global _CURRENT
    plat, idx = _parse(device)
    plat = _PLAT_ALIASES.get(plat, plat)
    try:
        devs = jax.devices(plat)
    except RuntimeError as e:
        raise RuntimeError(
            f"set_device({device!r}): no {plat} device is visible "
            f"(default backend: {jax.default_backend()})") from e
    if idx >= len(devs):
        raise ValueError(f"Device index {idx} out of range for {plat} "
                         f"({len(devs)} visible)")
    with _LOCK:
        _CURRENT = f"{plat}:{idx}"
        jax.config.update("jax_default_device", devs[idx])
    return Place(devs[idx])


def get_device() -> str:
    with _LOCK:
        if _CURRENT is not None:
            return _CURRENT
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def current_place() -> Place:
    plat, idx = _parse(get_device())
    devs = [d for d in jax.devices() if d.platform == plat]
    return Place(devs[idx] if idx < len(devs) else jax.devices()[0])


def device_count(plat: Optional[str] = None) -> int:
    if plat is None:
        plat = _parse(get_device())[0]
    return len([d for d in jax.devices() if d.platform == plat])


def is_compiled_with_tpu() -> bool:
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def synchronize():
    """Block until all queued device work completes (≈ device_synchronize)."""
    (jax.device_put(0.0) + 0).block_until_ready()
