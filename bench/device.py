"""The chip: its presence, its identity, its memory and its peaks."""
from __future__ import annotations

import json

from bench.spec import BENCH


class NoChip(RuntimeError):
    pass


def require(chips: int):
    """The TPU devices a cell may use; raises where JAX finds no TPU or
    fewer chips than the cell asks for.  Never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform if devices else 'no'} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use") for dev in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind`` (``bench/peaks.json``).  A
    kind that is not in the table is an error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["chips"][kind]
