"""paddle.device namespace parity (reference: python/paddle/device/).

Streams/events are explicit CUDA concepts; under XLA execution they are
compiler-scheduled, so the stream API here is a documented no-op that keeps
call sites working (SURVEY.md B14).
"""
from __future__ import annotations

import jax

from ..framework.device import (  # noqa: F401
    get_device,
    set_device,
    device_count,
    synchronize,
)

__all__ = [
    "get_device", "set_device", "device_count", "get_all_device_type",
    "get_available_device", "is_compiled_with_cuda", "is_compiled_with_rocm",
    "is_compiled_with_xpu", "is_compiled_with_custom_device", "synchronize",
    "Stream", "Event", "current_stream", "stream_guard", "cuda",
]


def get_all_device_type():
    kinds = []
    for d in jax.devices():
        p = d.platform
        if p not in kinds:
            kinds.append(p)
    return kinds


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_custom_device(device_type: str):
    return device_type in ("tpu",) or any(
        d.platform == device_type for d in jax.devices()
    )


class Stream:
    """No-op stream: XLA owns scheduling. Kept for API parity."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


class stream_guard:
    def __init__(self, stream):
        self.stream = stream

    def __enter__(self):
        return self.stream

    def __exit__(self, *exc):
        return False


class _CudaNS:
    """paddle.device.cuda shim — empty on TPU but importable."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def is_available():
        return False

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def max_memory_allocated(device=None):
        return max_memory_allocated(device if device is not None else 0)

    @staticmethod
    def memory_allocated(device=None):
        return memory_allocated(device if device is not None else 0)

    @staticmethod
    def empty_cache():
        pass


cuda = _CudaNS()


# ---------------------------------------------------------- memory stats ---
# Reference: paddle.device.cuda.max_memory_allocated / memory_allocated etc.
# (paddle/fluid/memory/stats.cc). TPU equivalent: PJRT device memory_stats —
# SURVEY.md A12: "Surface: memory stats API reading PJRT memory_stats()".


def _mem_stats(device_id=0):
    if isinstance(device_id, str):  # "tpu:1" / "gpu:0" / bare "tpu" (dev 0)
        if ":" in device_id:
            device_id = int(device_id.rsplit(":", 1)[-1])
        else:
            device_id = int(device_id) if device_id.isdigit() else 0
    elif not isinstance(device_id, int):
        device_id = int(getattr(device_id, "id", device_id))
    d = jax.devices()[device_id]
    stats = getattr(d, "memory_stats", lambda: None)()
    return stats or {}


def memory_allocated(device_id=0) -> int:
    """Bytes currently allocated on the device (PJRT bytes_in_use)."""
    return int(_mem_stats(device_id).get("bytes_in_use", 0))


def max_memory_allocated(device_id=0) -> int:
    """High-water allocation mark (PJRT peak_bytes_in_use)."""
    return int(_mem_stats(device_id).get("peak_bytes_in_use", 0))


def memory_reserved(device_id=0) -> int:
    """Bytes reserved by the allocator pool (0 when the backend does not
    report it — bytes_limit is CAPACITY, not a reservation)."""
    return int(_mem_stats(device_id).get("bytes_reserved", 0))


def max_memory_reserved(device_id=0) -> int:
    return int(_mem_stats(device_id).get("peak_bytes_reserved", 0))


def memory_stats(device_id=0) -> dict:
    """Raw PJRT stats dict (superset of the reference's counters)."""
    return dict(_mem_stats(device_id))


__all__ += ["memory_allocated", "max_memory_allocated", "memory_reserved",
            "max_memory_reserved", "memory_stats"]
