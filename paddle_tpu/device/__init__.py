"""paddle.device parity: device query/selection over PJRT.

Reference parity: paddle/fluid/platform/init.cc InitDevices + Python
paddle.device package. Device discovery is PJRT's; these are thin queries.
"""
from __future__ import annotations

import jax

from ..framework.place import (  # noqa: F401
    CPUPlace, TPUPlace, CUDAPlace, set_device, get_device, current_place,
)


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return []


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


def device_count():
    return len(jax.devices())


def is_compiled_with_cinn():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def synchronize(device=None):
    """cudaDeviceSynchronize parity: drain pending async work — a device
    runs its programs in order, so blocking on one enqueued now waits for
    everything before it (chip_smoke.py's train phase checks on the chip
    that block_until_ready does wait).  The Profiler closes its record
    windows with it."""
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()


class cuda:
    """paddle.device.cuda namespace stub (queries return TPU equivalents)."""

    @staticmethod
    def device_count():
        return len([d for d in jax.devices() if d.platform != "cpu"])

    @staticmethod
    def is_available():
        return False

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def empty_cache():
        pass
