"""Device memory telemetry.

Reference: paddle/fluid/memory/stats.cc (peak/current allocation stats) →
paddle.device.cuda.max_memory_allocated etc. TPU-native: XLA owns the
allocator, so stats come from the PJRT device (`memory_stats()`); where
the runtime doesn't expose them (the CPU backend), usage is
computed from the live jax.Array set and the peak is maintained as the
max observed across queries (exact current usage, observed peak).
"""
from __future__ import annotations

from typing import Optional

import jax

_peak = {}
_reserved_peak = {}
_reset_floor = {}  # device -> PJRT peak_bytes_in_use at last reset


def _device(device=None):
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    return device


def _live_bytes(dev) -> int:
    total = 0
    for a in jax.live_arrays():
        try:
            if dev in a.devices():
                total += a.nbytes // len(a.devices())
        except Exception:  # pragma: no cover — deleted arrays
            pass
    return total


def memory_allocated(device=None) -> int:
    """Bytes currently allocated on the device
    (paddle.device.cuda.memory_allocated parity)."""
    dev = _device(device)
    stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    cur = stats["bytes_in_use"] if stats else _live_bytes(dev)
    key = id(dev)
    _peak[key] = max(_peak.get(key, 0), cur)
    return int(cur)


def max_memory_allocated(device=None) -> int:
    dev = _device(device)
    stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    if stats and "peak_bytes_in_use" in stats:
        dev_peak = int(stats["peak_bytes_in_use"])
        floor = _reset_floor.get(id(dev))
        if floor is None:
            return dev_peak
        # PJRT's peak is monotonic; after a reset, report the device peak
        # only once it exceeds the value at reset time, else the observed
        # current-usage peak since the reset
        if dev_peak > floor:
            return dev_peak
        memory_allocated(device)
        return int(_peak.get(id(dev), 0))
    memory_allocated(device)  # refresh observed peak
    return int(_peak.get(id(dev), 0))


def memory_reserved(device=None) -> int:
    dev = _device(device)
    stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    if stats:
        return int(stats.get("bytes_reserved",
                             stats.get("bytes_in_use", 0)))
    return memory_allocated(device)


def max_memory_reserved(device=None) -> int:
    return max_memory_allocated(device)


def reset_peak_memory_stats(device=None):
    dev = _device(device)
    _peak.pop(id(dev), None)
    stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    if stats and "peak_bytes_in_use" in stats:
        _reset_floor[id(dev)] = int(stats["peak_bytes_in_use"])


def empty_cache():
    """paddle.device.cuda.empty_cache parity — XLA frees buffers when the
    owning jax.Array dies; nothing to flush beyond a GC pass."""
    import gc
    gc.collect()
