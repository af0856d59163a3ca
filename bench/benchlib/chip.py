"""The chips a run uses: the check that they are TPUs, their description,
their peak memory and the table of peaks."""
from __future__ import annotations

from typing import List

from benchlib.cells import BENCH, load_json


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require(chips: int) -> List:
    """The first ``chips`` TPU devices, or ``NoChip``. There is no fallback
    to another platform: a number from the CPU is not a device number."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform {devices[0].platform!r} "
                     f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``, as the runtime
    reports it (0 where it reports nothing)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip, by JAX's ``device_kind``. A device
    missing from ``peaks.json`` is an error, not a default."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]
