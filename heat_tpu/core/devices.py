"""Device abstraction (reference ``heat/core/devices.py``).

The reference pins one CUDA device per MPI rank round-robin
(``devices.py:98-102``). Under single-controller JAX the mesh owns device
placement, so :class:`Device` is a light label selecting the JAX platform
("cpu" or "tpu"); all arrays on a given platform are sharded across that
platform's devices via the mesh.
"""
from __future__ import annotations

from typing import Optional, Union

import jax

__all__ = ["Device", "cpu", "get_device", "use_device", "sanitize_device"]


class Device:
    """A compute platform label (reference ``devices.py:17``).

    Parameters
    ----------
    device_type : str
        "cpu", "tpu" (or "gpu" where available).
    device_id : int
        Kept for reference-API parity; under a mesh, placement is collective
        so this is informational only.
    """

    def __init__(self, device_type: str, device_id: int = 0):
        self.__device_type = str(device_type)
        self.__device_id = int(device_id)

    @property
    def device_type(self) -> str:
        return self.__device_type

    @property
    def device_id(self) -> int:
        return self.__device_id

    @property
    def jax_platform(self) -> str:
        return self.__device_type

    def __repr__(self) -> str:
        return f"device({self.__str__()!r})"

    def __str__(self) -> str:
        return f"{self.device_type}:{self.device_id}"

    def __eq__(self, other) -> bool:
        if isinstance(other, Device):
            return self.device_type == other.device_type and self.device_id == other.device_id
        if isinstance(other, str):
            return str(self) == other or self.device_type == other
        return NotImplemented

    def __hash__(self):
        return hash(str(self))


cpu = Device("cpu")
"""The CPU device singleton (reference ``devices.py:79``)."""

# Accelerator detection is lazy: probing the platform initializes the XLA
# backend, which must not happen at import time (init_distributed must be
# callable first — see communication.init_distributed).
_accel: Optional[Device] = None
_accel_probed = False
__default_device: Optional[Device] = None


def _detect_accel() -> Optional[Device]:
    global _accel, _accel_probed
    if not _accel_probed:
        # a probe that raises is an error of the installation (JAX fails
        # at start-up when the platform it was told to use is missing),
        # not "no accelerator": it propagates, and the next call probes
        # again
        platform = jax.default_backend()
        _accel_probed = True
        if platform != "cpu":
            _accel = Device(platform)
    return _accel


# names that may lazily probe the backend (shared by the package-level
# __getattr__ forwarders and sanitize_device); cuda/rocm alias 'gpu'
ACCEL_NAMES = ("tpu", "gpu", "cuda", "rocm")
_GPU_ALIASES = ("gpu", "cuda", "rocm")


def _accel_matches(name: str, accel: Optional[Device], strict: bool = False) -> bool:
    """Single source of truth for accelerator-name matching.

    ``strict`` (attribute access, e.g. ``ht.gpu``): exact platform name or
    a cuda/rocm<->gpu alias — hasattr-based feature detection must not see
    a TPU as a GPU. Non-strict (``sanitize_device``): additionally accepts
    'gpu' as a generic accelerator request."""
    if accel is None:
        return False
    if name == accel.device_type or (
        name in _GPU_ALIASES and accel.device_type in _GPU_ALIASES
    ):
        return True
    if strict:
        return False
    return name == "gpu"


def __getattr__(name: str):
    # expose the accelerator singleton by platform name (ht.tpu / ht.gpu);
    # only ACCEL_NAMES may probe the backend — anything else must raise
    # without initializing XLA (import machinery getattrs freely)
    if name in ACCEL_NAMES:
        accel = _detect_accel()
        if _accel_matches(name, accel, strict=True):
            return accel
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def get_device() -> Device:
    """The currently globally-set default device (reference ``devices.py:121``)."""
    global __default_device
    if __default_device is None:
        accel = _detect_accel()
        __default_device = accel if accel is not None else cpu
    return __default_device


def use_device(device: Optional[Union[str, Device]] = None) -> None:
    """Set the global default device (reference ``devices.py:135``)."""
    global __default_device
    __default_device = sanitize_device(device)


def sanitize_device(device: Optional[Union[str, Device]]) -> Device:
    """Default-or-validate a device argument (reference ``devices.py:157``)."""
    if device is None:
        return get_device()
    if isinstance(device, Device):
        return device
    if isinstance(device, str):
        name = device.lower().split(":")[0]
        if name == "cpu":
            # must not probe the backend: sanitizing "cpu" is valid before
            # init_distributed()
            return cpu
        if name in ACCEL_NAMES:
            accel = _detect_accel()
            if _accel_matches(name, accel):
                return accel
    raise ValueError(f"Unknown device, must be 'cpu' or an available accelerator, got {device}")
