"""Device model for the TPU-native stack.

Re-design of reference thunder/core/devices.py:13 — DeviceType there is
{CPU, CUDA, META}; here the accelerator is TPU and META supports deferred
initialization. Devices map onto ``jax.devices()`` entries.
"""
from __future__ import annotations

from enum import Enum
from functools import lru_cache


class DeviceType(Enum):
    CPU = "cpu"
    TPU = "tpu"
    META = "meta"


class Device:
    def __init__(self, devtype: "DeviceType | str" = DeviceType.TPU, index: int = 0):
        if isinstance(devtype, str):
            devtype, _, idx = devtype.partition(":")
            devtype = DeviceType(devtype)
            if idx:
                index = int(idx)
        self.devicetype = devtype
        self.index = index

    @property
    def type(self) -> str:
        return self.devicetype.value

    def __repr__(self) -> str:
        return f"Device(type='{self.devicetype.value}:{self.index}')"

    def __str__(self) -> str:
        return f"{self.devicetype.value}:{self.index}"

    def __hash__(self) -> int:
        return hash((self.devicetype, self.index))

    def __eq__(self, other) -> bool:
        return isinstance(other, Device) and other.devicetype == self.devicetype and other.index == self.index

    def jax_device(self):
        """Resolve to the concrete jax device; raises when this process has
        no device of that kind at that index."""
        if self.devicetype == DeviceType.META:
            return None
        devs = _jax_devices_by_kind(self.devicetype.value)
        if self.index >= len(devs):
            raise RuntimeError(
                f"{self}: this process has {len(devs)} jax device(s) of kind "
                f"{self.devicetype.value!r}")
        return devs[self.index]


@lru_cache(maxsize=None)
def _jax_devices_by_kind(kind: str):
    import jax

    if kind == "cpu":
        try:
            return tuple(jax.devices("cpu"))
        except RuntimeError:  # JAX_PLATFORMS leaves the host backend out
            return ()
    return tuple(d for d in jax.devices() if d.platform == kind)


cpu = Device(DeviceType.CPU, 0)
meta = Device(DeviceType.META, 0)


def to_device(x, default_type: DeviceType = DeviceType.TPU) -> Device:
    if x is None:
        return default_device()
    if isinstance(x, Device):
        return x
    if isinstance(x, str):
        return Device(x)
    # jax device object
    plat = getattr(x, "platform", None)
    if plat is not None:
        dt = DeviceType.CPU if plat == "cpu" else DeviceType.TPU
        return Device(dt, getattr(x, "id", 0))
    raise ValueError(f"cannot canonicalize device {x!r}")


@lru_cache(maxsize=1)
def default_device() -> Device:
    if _jax_devices_by_kind("tpu"):
        return Device(DeviceType.TPU, 0)
    return Device(DeviceType.CPU, 0)
