"""Device API (reference: ``python/paddle/device/``).

TPU-native: a "place" is a jax device. ``set_device`` selects the default jax
device for eager op placement; under jit/pjit, placement is owned by XLA and
shardings, so this is mostly an eager/debug affordance.
"""
from __future__ import annotations

import jax

_CURRENT = {"device": None}


class Place:
    def __init__(self, device):
        self._device = device

    @property
    def jax_device(self):
        return self._device

    def __repr__(self):
        return f"Place({self._device})"

    def __eq__(self, other):
        return isinstance(other, Place) and self._device == other._device


def get_all_devices():
    return jax.devices()


def device_summary() -> dict:
    """The device as JAX reports it, in the shape every result line and
    banner names it: ``{"platform", "kind", "count"}``."""
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def device_count():
    return jax.device_count()


def local_device_count():
    return jax.local_device_count()


def set_device(device: str):
    """Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0' (mapped to whatever backend runs)."""
    if isinstance(device, Place):
        _CURRENT["device"] = device.jax_device
        return device
    name = device.lower()
    idx = 0
    if ":" in name:
        name, idx_s = name.split(":")
        idx = int(idx_s)
    if name in ("tpu", "gpu", "xpu", "npu", "custom"):
        devs = jax.devices()
    elif name == "cpu":
        try:
            devs = jax.devices("cpu")
        except RuntimeError:
            devs = jax.devices()
    else:
        raise ValueError(f"unknown device {device!r}")
    dev = devs[idx % len(devs)]
    _CURRENT["device"] = dev
    return Place(dev)


def get_device():
    if _CURRENT["device"] is None:
        _CURRENT["device"] = jax.devices()[0]
    return Place(_CURRENT["device"])


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    return True


def synchronize():
    """Block until all dispatched work completes (cuda.synchronize analog)."""
    (jax.device_put(0) + 0).block_until_ready()
