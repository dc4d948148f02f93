"""Device / Place abstraction over jax devices.

Reference parity: paddle/common/place.h (phi::Place, CPUPlace/GPUPlace/...) and
python/paddle/device/__init__.py (set_device/get_device). Upstream-canonical
paths, unverified (SURVEY.md §0).

TPU-first design: a Place is a thin named handle onto a `jax.Device`. The
paddle device strings ("cpu", "gpu:0", ...) map onto jax platforms; "tpu" is
the first-class accelerator, and "gpu"/"cuda" aliases resolve to whatever
accelerator backend jax exposes so that reference scripts run with only a
device-string change (BASELINE.json north_star).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

_ACCEL_ALIASES = ("tpu", "gpu", "cuda")


@functools.lru_cache(maxsize=None)
def _platforms() -> dict:
    out = {}
    for d in jax.devices():
        out.setdefault(d.platform, []).append(d)
    # CPU devices are always constructible even when an accelerator is default.
    if "cpu" not in out:
        try:
            out["cpu"] = jax.devices("cpu")
        except RuntimeError:
            pass
    return out


def _accelerator_platform() -> Optional[str]:
    plats = _platforms()
    for p in plats:
        if p != "cpu":
            return p
    return None


class Place:
    """A device handle. Compares by (platform, index) like phi::Place."""

    __slots__ = ("_device",)

    def __init__(self, device: jax.Device):
        self._device = device

    @property
    def jax_device(self) -> jax.Device:
        return self._device

    @property
    def platform(self) -> str:
        return self._device.platform

    @property
    def index(self) -> int:
        return self._device.id

    def is_cpu_place(self) -> bool:
        return self.platform == "cpu"

    def is_gpu_place(self) -> bool:  # paddle API name; true for any accelerator
        return self.platform != "cpu"

    is_tpu_place = is_gpu_place
    is_accelerator_place = is_gpu_place

    def __eq__(self, other):
        return isinstance(other, Place) and self._device == other._device

    def __hash__(self):
        return hash(self._device)

    def __repr__(self):
        return f"Place({self.platform}:{self.index})"


def CPUPlace(idx: int = 0) -> Place:
    return Place(_platforms()["cpu"][idx])


def TPUPlace(idx: int = 0) -> Place:
    plat = _accelerator_platform()
    if plat is None:
        raise RuntimeError("no TPU/accelerator devices visible to jax")
    return Place(_platforms()[plat][idx])


# Reference scripts say CUDAPlace/GPUPlace/XPUPlace; on this framework they
# name the accelerator backend (TPU) and, like set_device("gpu"), raise
# when there is none — an accelerator request never yields a CPU place.
CUDAPlace = GPUPlace = XPUPlace = TPUPlace

_current_place: Optional[Place] = None


def set_device(device) -> Place:
    """paddle.device.set_device — accepts 'cpu', 'tpu', 'tpu:1', 'gpu:0', ..."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return device
    name, _, idx = str(device).partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name == "cpu":
        _current_place = CPUPlace(idx)
    elif name in _ACCEL_ALIASES:
        _current_place = TPUPlace(idx)
    else:
        raise ValueError(f"unknown device {device!r}")
    return _current_place


def get_device() -> str:
    p = _default_place()
    return f"{p.platform}:{p.index}" if not p.is_cpu_place() else "cpu"


def _default_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = Place(jax.devices()[0])
    return _current_place


def is_compiled_with_cuda() -> bool:
    return False  # CUDA-free build by design (BASELINE.json north_star)


def is_compiled_with_tpu() -> bool:
    return _accelerator_platform() is not None


def device_count() -> int:
    plat = _accelerator_platform()
    return len(_platforms()[plat]) if plat else len(jax.devices())


# ---------------------------------------------------------------------------
# Memory introspection (reference: paddle.device.cuda.memory_allocated /
# max_memory_allocated / memory_reserved and friends — SURVEY.md §5
# metrics row: 'memory via jax.local_devices()[0].memory_stats()').
# On TPU the PJRT allocator owns HBM; these read its live statistics.
# Backends that expose no memory_stats (CPU) degrade to 0 rather than
# raising — recipes keep running.
# ---------------------------------------------------------------------------

def _memory_stats(device_id: int = 0) -> dict:
    devs = jax.local_devices()
    if not 0 <= device_id < len(devs):
        return {}
    return devs[device_id].memory_stats() or {}


def _dev_idx(device) -> int:
    """Resolve a device argument to a local_devices() position. None means
    the CURRENT device (set_device), not device 0."""
    if device is None:
        place = _default_place()
        device = place.index if place.index is not None else 0
    if isinstance(device, Place):
        device = device.index or 0
    if not isinstance(device, int):
        sdev = str(device)
        device = int(sdev.rsplit(":", 1)[-1]) if ":" in sdev else 0
    # Place.index / ids are global device ids; map to a local position
    for pos, d in enumerate(jax.local_devices()):
        if d.id == device:
            return pos
    return device


def memory_allocated(device=None) -> int:
    """Bytes currently held by live buffers on the device."""
    return int(_memory_stats(_dev_idx(device)).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """High-water mark of live buffer bytes."""
    s = _memory_stats(_dev_idx(device))
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def memory_reserved(device=None) -> int:
    """Bytes the allocator arena holds. PJRT reports bytes_reserved when
    it runs a pool; otherwise bytes_limit (the whole managed HBM arena)
    is the closest analog; bytes_in_use is the last resort."""
    s = _memory_stats(_dev_idx(device))
    return int(s.get("bytes_reserved",
                     s.get("bytes_limit", s.get("bytes_in_use", 0))))


def max_memory_reserved(device=None) -> int:
    s = _memory_stats(_dev_idx(device))
    return int(s.get("peak_bytes_reserved",
                     s.get("bytes_limit",
                           s.get("peak_bytes_in_use",
                                 s.get("bytes_in_use", 0)))))


def empty_cache() -> None:
    """XLA/PJRT owns the allocator: there is no user-facing cache to
    drop; provided for recipe parity (reference empties the CUDA caching
    allocator)."""


def synchronize(device=None) -> None:
    """Block until queued work on THE GIVEN device finishes (reference
    cuda.synchronize): an empty computation placed there as a barrier."""
    import jax.numpy as jnp
    devs = jax.local_devices()
    idx = _dev_idx(device)
    target = devs[idx] if 0 <= idx < len(devs) else devs[0]
    jax.device_put(jnp.zeros(()), target).block_until_ready()


def get_device_properties(device=None):
    import types
    devs = jax.local_devices()
    idx = _dev_idx(device)
    if not 0 <= idx < len(devs):  # degrade like the memory_* getters
        return types.SimpleNamespace(name="unknown", total_memory=0,
                                     multi_processor_count=0,
                                     major=0, minor=0)
    d = devs[idx]
    stats = _memory_stats(idx)
    return types.SimpleNamespace(
        name=getattr(d, "device_kind", str(d)),
        total_memory=int(stats.get("bytes_limit", 0)),
        multi_processor_count=getattr(d, "core_count", 1),
        major=0, minor=0)


import types as _t
# paddle.device.cuda namespace: recipes call cuda.* regardless of backend
cuda = _t.SimpleNamespace(
    memory_allocated=memory_allocated,
    max_memory_allocated=max_memory_allocated,
    memory_reserved=memory_reserved,
    max_memory_reserved=max_memory_reserved,
    empty_cache=empty_cache,
    synchronize=synchronize,
    device_count=device_count,
    get_device_properties=get_device_properties,
)
