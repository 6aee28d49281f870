"""The numbers that decide ``correct``, each against its limit.

Every function returns one number: how far what the timed path produced
lies from the plain reference.  ``verdict`` pairs the numbers with the
cell's limits (``bench/workloads/<cell>.json``); a number without a limit
is not correct, so a cell cannot pass on a limit nobody set.
"""
from __future__ import annotations

import numpy as np

# A leaf whose change in the reference is below this share of the median
# leaf's moves by round-off alone, and is left out of the change gap.
QUIET_LEAF = 1e-3


def max_rel(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1e-30)))


def max_abs(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref))) if got.size else 0.0


def change_gap(final, ref_final, ref_init) -> float:
    """Worst leaf's gap between the norm of the program's change of the
    parameters and the reference's, over the larger of that leaf's
    reference change and the median leaf's.  Leaves come as matching lists
    of arrays; a leaf whose reference change is below ``QUIET_LEAF`` of
    the median leaf's is left out."""
    got = [np.linalg.norm(np.asarray(f, np.float64) - np.asarray(i, np.float64))
           for f, i in zip(final, ref_init)]
    ref = [np.linalg.norm(np.asarray(f, np.float64) - np.asarray(i, np.float64))
           for f, i in zip(ref_final, ref_init)]
    med = float(np.median(ref))
    gaps = [abs(g - r) / max(r, med) for g, r in zip(got, ref)
            if r >= QUIET_LEAF * med]
    return float(max(gaps))


def verdict(numbers: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): correct when every number is
    finite and at most its limit."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        out[name] = {"value": value, "limit": limit}
        if limit is None or not np.isfinite(value) or value > limit:
            ok = False
    return ok, out
