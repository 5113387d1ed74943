"""The devices a run may use, and their published peaks."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(chips: int, *, platform: str = "tpu"):
    """The first ``chips`` devices, which must be of ``platform``. A run
    never falls back to another platform."""
    import jax

    devices = jax.devices()
    found = devices[0].platform
    if found != platform:
        raise NoChip(f"needs a {platform.upper()}; JAX found platform "
                     f"{found!r} ({len(devices)} device(s))")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``. An unknown kind is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; add them with their source")
    return table[device_kind]


def describe(devices) -> dict:
    """``platform``, ``kind`` and ``count`` as JAX reports them."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    reports nothing)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
