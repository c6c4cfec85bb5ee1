"""The chip this run is on: the check that refuses anything else, and
what the result line says about it."""
from __future__ import annotations


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The devices, or NoChip with a one-line reason."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no usable jax backend: {str(e).splitlines()[0]}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, found {len(devices)}")
    return devices


def describe(chips: int) -> dict:
    """platform, kind, count and the peak memory in use on the fullest
    of the chips this cell uses."""
    import jax

    devices = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }
