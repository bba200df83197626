"""Device / Place abstraction over the XLA (PjRt) client.

TPU-native replacement for the reference platform layer
(reference: paddle/fluid/platform/place.h `Place` variant and
platform/device_context.h:796 `DeviceContextPool`). Streams, events and
communicator handles are owned by XLA — the framework only names devices.

`Place` mirrors paddle's CPUPlace/CUDAPlace API shape with TPUPlace first-class.
`set_device`/`get_device` mirror python/paddle/device/__init__.py.
"""
from __future__ import annotations

import jax


class Place:
    """A named device slot: device_type + device_id."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = int(device_id)

    def __repr__(self):
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
        """Resolve to the backing jax.Device; raises when this process has
        no such platform or no device with that index."""
        try:
            devs = jax.devices(self.device_type)
        except RuntimeError as e:
            raise RuntimeError(
                f"{self!r}: no {self.device_type!r} device in this process "
                f"(jax runs on {jax.default_backend()!r})") from e
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r}: device index out of range — "
                f"{len(devs)} {self.device_type!r} device(s) visible")
        return devs[self.device_id]


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(dev_id: int = 0):
    return Place("tpu", dev_id)


# CUDAPlace exists for API-compat of ported scripts; it resolves to the default
# accelerator (reference code that says CUDAPlace(i) means "accelerator i").
def CUDAPlace(dev_id: int = 0):
    return Place(_default_backend(), dev_id)


_CURRENT = [None]


def _default_backend() -> str:
    return jax.default_backend()


def set_device(device: str) -> Place:
    """paddle.set_device parity. Accepts 'cpu', 'tpu', 'tpu:0', 'gpu:0'
    (→ accelerator). The named device must exist (a missing platform or
    an out-of-range index raises) and becomes jax's default device, so
    :func:`get_device` names the device that actually runs."""
    if ":" in device:
        kind, _, idx = device.partition(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind == "gpu":  # ported-script compat: gpu means "the accelerator"
        kind = _default_backend()
    place = Place(kind, idx)
    jax.config.update("jax_default_device", place.jax_device())
    _CURRENT[0] = place
    return place


def get_device() -> str:
    p = _expected_place()
    return f"{p.device_type}:{p.device_id}"


def _expected_place() -> Place:
    if _CURRENT[0] is None:
        _CURRENT[0] = Place(_default_backend(), 0)
    return _CURRENT[0]


def device_count(kind: str = None) -> int:
    try:
        return len(jax.devices(kind)) if kind else len(jax.devices())
    except RuntimeError:
        return 0


def is_compiled_with_cuda() -> bool:
    """API parity helper; always False — zero CUDA symbols linked."""
    return False


def is_compiled_with_tpu() -> bool:
    return True


def synchronize(place: Place = None):
    """Block until all dispatched work on the device is done
    (reference: DeviceContext::Wait). XLA: realized via blocking on arrays;
    here we use the effects barrier."""
    (jax.device_put(0) + 0).block_until_ready()
