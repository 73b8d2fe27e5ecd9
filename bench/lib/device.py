"""The chip the cell runs on: found, named and read; never a fallback."""
from __future__ import annotations


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoChip`."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:                      # no backend at all
        raise NoChip(f"JAX found no devices: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"platform {devs[0].platform!r}: a TPU is required")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} TPU devices, the cell needs {chips}")
    return devs[:chips]


def record(devs) -> dict:
    """Platform, kind and count as JAX reports them."""
    import jax
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices())}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of ``devs``."""
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devs)
