"""Device / place management.

Reference parity: `paddle.set_device` / `paddle.get_device` and the Place
hierarchy (paddle/phi/common/place.h; python/paddle/device/__init__.py).
TPU-first design: a "place" names a jax.Device; `set_device('tpu')` selects the
PJRT TPU client. There are no streams — XLA's async dispatch plays that role
(SURVEY.md §7 stage 1).
"""
from __future__ import annotations

import threading

import jax

_state = threading.local()


class Place:
    """A device place: ('tpu', 0) / ('cpu', 0)."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def jax_device(self):
        return _jax_device_for(self.device_type, self.device_id)


class CPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("cpu", device_id)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPlace(TPUPlace):
    """Compat shim: reference code constructing CUDAPlace(i) gets the
    accelerator (TPU) place — device_id semantics carry over."""


class CUDAPinnedPlace(CPUPlace):
    """Compat shim: pinned host memory is plain host memory under PJRT."""


def _tpu_devices():
    """The TPU devices of the default backend ([] when it is not TPU)."""
    return [d for d in jax.devices() if d.platform == "tpu"]


def _jax_device_for(device_type: str, device_id: int = 0):
    if device_type == "tpu":
        devs = _tpu_devices()
        if not devs:
            raise RuntimeError(
                "no TPU: 'tpu' was asked for but jax.devices() is "
                f"{jax.devices()} — run on the chip, or use 'cpu'")
    elif device_type == "cpu":
        devs = jax.devices("cpu")
    else:
        raise ValueError(f"unknown device type {device_type!r}")
    if device_id >= len(devs):
        raise ValueError(
            f"{device_type}:{device_id} asked for, {len(devs)} present")
    return devs[device_id]


def set_device(device: str) -> Place:
    """paddle.set_device parity: 'tpu', 'tpu:0', 'cpu'."""
    if ":" in device:
        dev_type, _, idx = device.partition(":")
        device_id = int(idx)
    else:
        dev_type, device_id = device, 0
    if dev_type == "gpu":
        # the reference's CUDA place; on this framework it aliases tpu
        dev_type = "tpu"
    if dev_type not in ("tpu", "cpu"):
        raise ValueError(
            f"device must be 'tpu' or 'cpu', got {device!r}"
        )
    place = TPUPlace(device_id) if dev_type == "tpu" else CPUPlace(device_id)
    # raises when there is no such device; new tensors and the kernel
    # routing (ops/pallas/routing.on_tpu) follow JAX's default device
    jax.config.update("jax_default_device", place.jax_device())
    _state.place = place
    return place


def get_device() -> str:
    place = current_place()
    return f"{place.device_type}:{place.device_id}"


def current_place() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        # default: tpu if present, else cpu — decided once per thread
        place = TPUPlace(0) if _tpu_devices() else CPUPlace(0)
        _state.place = place
    return place


def default_jax_device():
    return current_place().jax_device()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return bool(_tpu_devices())


def device_count() -> int:
    return len(jax.devices())
