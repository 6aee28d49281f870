"""The program's own spans and counters (``repro.telemetry``), as the
per-layer readers of ``source`` ``program_span`` and ``program_counter``
read them.

Readers run in the benchmark's process after the window.  The program
records only while a profiler session records, and a traced run's session
holds exactly the window (``trace.capture`` around ``bench.window``), so
the records are the window's work: no set-up, no warm-up, no compile.  A
program without ``repro.telemetry``, a name that recorded nothing, and a
ring that wrapped (so that it no longer holds the whole window) read
``None``.
"""
from __future__ import annotations


def stats(name: str) -> dict | None:
    """``count``, ``mean``, ``p50`` and ``p95`` of one name's records."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    s = telemetry.summary().get(name)
    if s is None or s["wrapped"]:
        return None
    return s


def mean(name: str, scale: float) -> float | None:
    """Mean of the records of ``name`` times ``scale`` (spans are in ns)."""
    s = stats(name)
    return None if s is None else s["mean"] * scale
