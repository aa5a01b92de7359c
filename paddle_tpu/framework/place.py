"""Places — logical device locations.

Mirrors the reference's ``phi::Place`` hierarchy
(/root/reference/paddle/phi/common/place.h:58) with the device set that makes
sense on a TPU-native stack: CPUPlace and TPUPlace (CUDAPlace is accepted as an
alias for TPUPlace so reference scripts keep running, with a warning).
"""
from __future__ import annotations

import functools
import logging

import jax

logger = logging.getLogger(__name__)


@functools.cache
def on_tpu() -> bool:
    """Whether this process computes on a TPU — the one platform
    question the framework asks, once. The default place and kernel
    selection (compiled Pallas or interpret mode, flash or dense
    attention, autotuning) all follow the answer; what jax raises while
    it starts the backend is raised here, never read as "no TPU"."""
    backend = jax.default_backend()
    tpu = backend == "tpu"
    logger.info("default backend %s: Pallas kernels %s", backend,
                "compile for the chip" if tpu else "run in interpret mode")
    return tpu


class Place:
    """Base place. A place maps onto a jax.Device."""

    device_type = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self._device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.device_type, self._device_id))

    def jax_device(self):
        """Resolve to a concrete jax.Device — PROCESS-LOCAL ones only: in
        multi-controller SPMD jax.devices() lists every process's devices,
        and host data committed to another process's device cannot feed
        compiled steps (cross-host reshard is unsupported)."""
        devs = [d for d in jax.local_devices()
                if d.platform == self.device_type]
        if not devs:
            raise RuntimeError(
                f"{self!r} needs a {self.device_type} device and this "
                f"process has none (local devices: {jax.local_devices()})")
        return devs[self._device_id % len(devs)]


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "Place(cpu)"


class TPUPlace(Place):
    device_type = "tpu"

    def __repr__(self):
        return f"Place(tpu:{self._device_id})"


class CUDAPlace(TPUPlace):
    """Compat alias: reference scripts constructing CUDAPlace land on TPU."""

    def __repr__(self):
        return f"Place(tpu:{self._device_id})  # CUDAPlace compat"


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


class IPUPlace(TPUPlace):
    """Compat alias: lands on TPU like CUDAPlace/XPUPlace."""


class MLUPlace(TPUPlace):
    """Compat alias: lands on TPU like CUDAPlace/XPUPlace."""


class NPUPlace(TPUPlace):
    pass


def default_place() -> Place:
    return TPUPlace(0) if on_tpu() else CPUPlace()
