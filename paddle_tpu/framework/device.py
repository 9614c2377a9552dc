"""Device/place API (reference: python/paddle/device/__init__.py set_device,
phi::Place in paddle/phi/common/place.h).

On this framework the device roster is whatever PJRT exposes (TPU chips, or
virtual CPU devices in tests). ``set_device`` selects the default device used
for new tensors; streams are XLA's concern (async dispatch), so the stream
API surfaces are documented no-ops.
"""
from __future__ import annotations

import jax

_current = None


class Place:
    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return isinstance(other, Place) and (self.kind, self.index) == (other.kind, other.index)


def TPUPlace(idx=0):
    return Place("tpu", idx)


def CPUPlace():
    return Place("cpu", 0)


CustomPlace = Place


def set_device(device: str):
    """Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0' (mapped to the default backend)."""
    global _current
    kind, _, idx = device.partition(":")
    _current = Place(kind, int(idx) if idx else 0)
    return _current


def get_device() -> str:
    if _current is not None:
        return f"{_current.kind}:{_current.index}"
    backend = jax.default_backend()
    return f"{backend}:0"


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_custom_device(name: str) -> bool:
    # TPU is the first-class "custom device" here (the reference's
    # CustomDevice plugin seam, paddle/phi/backends/custom/custom_device.cc,
    # is played by PJRT/libtpu in this framework).
    return name in ("tpu", "npu")


def cuda_device_count() -> int:
    return 0


def synchronize(device=None):
    """Block until all dispatched work is done (paddle.device.synchronize):
    wait on what was dispatched, every live array. ``device`` is accepted
    for API parity; XLA owns the streams."""
    for d in jax.live_arrays():
        d.block_until_ready()
