"""Device / Place abstraction.

Reference analog: phi::Place (paddle/phi/common/place.h:28) and
python/paddle/device/__init__.py (set_device / get_device). On TPU the device
runtime is PJRT via jax; a Place is a thin, hashable handle that resolves to a
jax.Device.
"""
from __future__ import annotations

import jax


class Place:
    """Base place. Resolves to a concrete jax.Device via .device."""

    _kind = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    # -- resolution -------------------------------------------------------
    def _platforms(self):
        raise NotImplementedError

    @property
    def device(self) -> jax.Device:
        for plat in self._platforms():
            try:
                devs = jax.devices(plat)
            except RuntimeError:
                continue
            if devs:
                return devs[self.device_id % len(devs)]
        raise RuntimeError(f"No device available for place {self!r}")

    # -- identity ---------------------------------------------------------
    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self._kind, self.device_id))

    def __repr__(self):
        return f"Place({self._kind}:{self.device_id})"

    def is_cpu_place(self):
        return self._kind == "cpu"

    def is_tpu_place(self):
        return self._kind == "tpu"


class CPUPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)

    def _platforms(self):
        return ("cpu",)

    def __repr__(self):
        return "Place(cpu)"


class TPUPlace(Place):
    """The accelerator place (jax platform 'tpu')."""

    _kind = "tpu"

    def _platforms(self):
        return ("tpu",)

    def __repr__(self):
        return f"Place(tpu:{self.device_id})"


# CustomPlace parity (phi::CustomPlace) -- any other jax platform.
class CustomPlace(Place):
    _kind = "custom"

    def __init__(self, platform: str, device_id: int = 0):
        super().__init__(device_id)
        self.platform = platform

    def _platforms(self):
        return (self.platform,)

    def __repr__(self):
        return f"Place({self.platform}:{self.device_id})"


_CURRENT_DEVICE = [None]  # lazily resolved


def _default_place() -> Place:
    plat = jax.default_backend()
    if plat == "cpu":
        return CPUPlace()
    if plat == "tpu":
        return TPUPlace(0)
    return CustomPlace(plat, 0)


def set_device(device) -> Place:
    """paddle.device.set_device('tpu:0' | 'cpu') parity."""
    place = _parse_device(device)
    _CURRENT_DEVICE[0] = place
    return place


def get_device() -> str:
    p = _current_place()
    if p.is_cpu_place():
        return "cpu"
    return f"{p._kind}:{p.device_id}"


def _current_place() -> Place:
    if _CURRENT_DEVICE[0] is None:
        _CURRENT_DEVICE[0] = _default_place()
    return _CURRENT_DEVICE[0]


def _parse_device(device) -> Place:
    if isinstance(device, Place):
        return device
    if isinstance(device, jax.Device):
        plat = device.platform
        if plat == "cpu":
            return CPUPlace()
        if plat == "tpu":
            return TPUPlace(device.id)
        return CustomPlace(plat, device.id)
    if isinstance(device, str):
        name = device.lower()
        if name == "cpu":
            return CPUPlace()
        idx = 0
        if ":" in name:
            name, idx_s = name.split(":", 1)
            idx = int(idx_s)
        if name in ("tpu", "gpu", "xpu"):  # gpu/xpu aliases map to the accelerator
            return TPUPlace(idx)
        return CustomPlace(name, idx)
    raise ValueError(f"Cannot parse device: {device!r}")


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    try:
        return bool(jax.devices("tpu"))
    except RuntimeError:
        return False


def device_count() -> int:
    return jax.device_count()


# -- vendor-compat place classes + build predicates -------------------------
# reference: paddle.device exports every vendor's Place and an
# is_compiled_with_* predicate; a TPU-native build answers False for
# the others and maps foreign places to the accelerator that exists.

def _mapped_vendor_place(kind, device_id=0):
    """THE shim behind every foreign vendor place — NPU/XPU/MLU here and
    paddle_tpu.compat's CUDA places delegate to it — so the mapping
    behaves one way everywhere: warn, then return the place this build
    actually computes on, preserving device_id when the accelerator
    place carries one (the old compat.py/core.place copies diverged on
    exactly that)."""
    import warnings
    warnings.warn(
        f"{kind}({device_id}) requested on a TPU-native build: mapping "
        "to the available accelerator place", stacklevel=3)
    p = _default_place()
    return TPUPlace(device_id) if isinstance(p, TPUPlace) else p


class XPUPlace:
    def __new__(cls, device_id=0):
        return _mapped_vendor_place("XPUPlace", device_id)


class IPUPlace:
    def __new__(cls, device_id=0):
        return _mapped_vendor_place("IPUPlace", device_id)


class MLUPlace:
    def __new__(cls, device_id=0):
        return _mapped_vendor_place("MLUPlace", device_id)


class NPUPlace:
    def __new__(cls, device_id=0):
        return _mapped_vendor_place("NPUPlace", device_id)


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_mlu():
    return False


def get_cudnn_version():
    """reference: returns the cudnn version int or None when absent —
    None here, there is no cudnn in the build."""
    return None
