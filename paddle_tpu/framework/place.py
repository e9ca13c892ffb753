"""Place / device abstraction.

Reference parity: paddle/fluid/platform/place.h:137 (CPUPlace/CUDAPlace/... as a
tagged variant) and DeviceContextPool (device_context.h:614). TPU-first: a Place
is a thin tag over a PJRT device obtained from jax; TPUPlace is the peer of
CUDAPlace. There are no streams to manage -- XLA/PJRT owns ordering -- so the
DeviceContext collapses to "which jax.Device do I put buffers on".
"""
from __future__ import annotations

import jax


class Place:
    _kind = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((self._kind, self.device_id))

    def __repr__(self):
        return f"Place({self._kind}:{self.device_id})"

    def jax_device(self) -> jax.Device:
        platform = self._platform()
        devs = [d for d in jax.devices() if d.platform == platform]
        if self.device_id >= len(devs):
            raise RuntimeError(
                f"{self!r}: this process has {len(devs)} {platform!r} "
                f"device(s) (jax.devices(): "
                f"{sorted({d.platform for d in jax.devices()})})")
        return devs[self.device_id]

    def _platform(self) -> str:
        return "cpu"


class CPUPlace(Place):
    _kind = "cpu"

    def _platform(self):
        return "cpu"


class TPUPlace(Place):
    """The north-star device: peer of CUDAPlace, lowers through XLA:TPU."""
    _kind = "tpu"

    def _platform(self):
        return "tpu"


class CUDAPlace(Place):
    _kind = "gpu"

    def _platform(self):
        return "gpu"


class CUDAPinnedPlace(CPUPlace):
    _kind = "cuda_pinned"


class XPUPlace(TPUPlace):
    _kind = "xpu"


_CURRENT: list = []


def _detect_default() -> Place:
    plats = {d.platform for d in jax.devices()}
    if "tpu" in plats:
        return TPUPlace(0)
    if "gpu" in plats:
        return CUDAPlace(0)
    return CPUPlace(0)


def get_device() -> str:
    p = current_place()
    return f"{p._kind}:{p.device_id}" if p._kind != "cpu" else "cpu"


def set_device(device: str) -> Place:
    """paddle.set_device parity (python/paddle/device/__init__.py)."""
    device = device.lower()
    if ":" in device:
        kind, idx = device.split(":", 1)
        idx = int(idx)
    else:
        kind, idx = device, 0
    table = {"cpu": CPUPlace, "tpu": TPUPlace, "gpu": CUDAPlace, "xpu": XPUPlace}
    if kind not in table:
        raise ValueError(f"unknown device {device!r}")
    place = table[kind](idx)
    _CURRENT.clear()
    _CURRENT.append(place)
    jax.config.update("jax_default_device", place.jax_device())
    return place


def current_place() -> Place:
    if not _CURRENT:
        _CURRENT.append(_detect_default())
    return _CURRENT[0]


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return len(jax.devices())
