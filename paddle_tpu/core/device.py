"""Device / Place abstraction.

TPU-native equivalent of the reference Place system (reference:
paddle/fluid/platform/place.h, device_context.h DeviceContextPool,
python/paddle/device/__init__.py:181 set_device). On TPU there is no
per-device stream/handle bundle to manage — PjRt owns the device runtime —
so a Place is simply an identity wrapping a jax.Device.
"""
import jax


class Place:
    """Device identity, paddle-style (CPUPlace / TPUPlace analogues)."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type, device_id=0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        if self.device_type == "cpu":
            return "Place(cpu)"
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def jax_device(self):
        """Resolve to the backing jax.Device."""
        devs = _devices_for(self.device_type)
        return devs[self.device_id]


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id=0):
        super().__init__(_accelerator_platform() or "cpu", device_id)


def _accelerator_platform():
    """Name of the non-cpu platform if one exists."""
    platform = jax.default_backend()
    return platform if platform != "cpu" else None


def _devices_for(device_type):
    if device_type == "cpu":
        return jax.devices("cpu") if jax.default_backend() == "cpu" else jax.local_devices(backend="cpu")
    return jax.devices()


_current_place = None


def set_device(device):
    """paddle.device.set_device equivalent. Accepts 'cpu', 'tpu', 'tpu:0',
    and for compat 'gpu'/'gpu:0' (mapped to the accelerator, or the CPU
    when there is none). Asking for 'tpu' by name with no TPU present
    is an error, never a quiet CPU place."""
    global _current_place
    dev = device.lower()
    if ":" in dev:
        kind, _, idx = dev.partition(":")
        idx = int(idx)
    else:
        kind, idx = dev, 0
    if kind == "cpu":
        _current_place = CPUPlace()
    elif kind == "tpu" and _accelerator_platform() is None:
        raise RuntimeError(
            f"set_device({device!r}): no TPU present (jax default "
            f"backend is {jax.default_backend()!r})")
    elif kind in ("tpu", "gpu", "xpu", "npu"):
        _current_place = TPUPlace(idx)
    else:
        raise ValueError(f"unsupported device {device!r}")
    return _current_place


def get_device():
    p = get_place()
    if p.is_cpu_place():
        return "cpu"
    return f"tpu:{p.device_id}"


def get_place():
    global _current_place
    if _current_place is None:
        # Default to the accelerator when present, like paddle defaults to GPU.
        _current_place = TPUPlace(0) if _accelerator_platform() else CPUPlace()
    return _current_place


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return _accelerator_platform() is not None


def device_count():
    return jax.device_count()


class CUDAPlace(Place):
    """Shim: maps to the accelerator (TPU) device for API parity with the
    reference's CUDAPlace (paddle/fluid/platform/place.h)."""

    def __init__(self, device_id=0):
        super().__init__(_accelerator_platform() or "cpu", device_id)


class CUDAPinnedPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class XPUPlace(Place):
    def __init__(self, device_id=0):
        super().__init__(_accelerator_platform() or "cpu", device_id)


class NPUPlace(Place):
    def __init__(self, device_id=0):
        super().__init__(_accelerator_platform() or "cpu", device_id)


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_rocm():
    return False
