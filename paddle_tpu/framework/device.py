"""Device management: paddle.device.set_device / get_device equivalents.

Reference: /root/reference/python/paddle/device/__init__.py (set_device /
get_device / is_compiled_with_*). Here devices are jax devices; the "current
device" determines where new tensors materialize (jax.default_device).
"""
from __future__ import annotations

import contextlib
import threading

import jax

from .place import CPUPlace, Place, TPUPlace, default_place

_state = threading.local()


def _current() -> Place:
    p = getattr(_state, "place", None)
    if p is None:
        p = default_place()
        _state.place = p
    return p


def set_device(device: str) -> Place:
    """Accepts 'cpu', 'tpu', 'tpu:0', and (compat) 'gpu'/'gpu:0' → tpu."""
    if isinstance(device, Place):
        _state.place = device
        return device
    dev = device.lower()
    idx = 0
    if ":" in dev:
        dev, idx_s = dev.split(":", 1)
        idx = int(idx_s)
    if dev == "cpu":
        place = CPUPlace()
    elif dev in ("tpu", "gpu", "cuda", "xpu", "npu"):
        place = TPUPlace(idx)
    else:
        raise ValueError(f"Unknown device {device!r}; expected cpu/tpu[:i]")
    _state.place = place
    return place


def get_device() -> str:
    p = _current()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"tpu:{p.get_device_id()}"


def get_current_place() -> Place:
    return _current()


def current_jax_device():
    return _current().jax_device()


def device_count(device_type: str = "tpu") -> int:
    if device_type == "cpu":
        return len(jax.devices("cpu"))
    return len([d for d in jax.devices() if d.platform == "tpu"])


def is_compiled_with_cuda() -> bool:  # compat shim
    return False


def is_compiled_with_tpu() -> bool:
    return device_count("tpu") > 0


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


@contextlib.contextmanager
def device_guard(device: str):
    prev = _current()
    set_device(device)
    try:
        yield
    finally:
        _state.place = prev


def synchronize():
    """Block until all queued device work completes.

    XLA/jax dispatch is async; this is the analog of the reference's
    DeviceContext::Wait (/root/reference/paddle/phi/core/device_context.h).
    """
    try:
        (jax.device_put(0.0) + 0).block_until_ready()
    except Exception:  # pragma: no cover
        pass
