"""The program's own spans and counters, recorded only while a profiler
session records.

``span(name)`` wraps a piece of host work.  Outside a profiler session
(``jax.profiler.trace`` or a TensorBoard capture) it only takes the two
``time.perf_counter_ns`` stamps its caller may read (``seconds``).  Inside
one it also opens a ``jax.profiler.TraceAnnotation``, so the span lands on
the host plane of the profile beside the device's operations, and appends
its duration in nanoseconds to a ring kept per name.  ``observe(name,
values)`` appends numbers (per-request queue waits, say) to such a ring,
also only inside a session.  ``records``, ``summary`` and ``clear`` read
and reset the rings in the same process.

Whether a session records is the profiler's own ``TraceMe.is_enabled``
(a few tens of nanoseconds); where that private hook is missing the
module never records.  Rings are preallocated float64 arrays written in
place, so a record adds no object for the garbage collector.  They are
not locked: record from one thread.

| Name | Kind | Where |
| --- | --- | --- |
| ``serve.submit`` | span, ns | ``ScoringService.submit`` |
| ``serve.assemble`` | span, ns | ``ScoringService.step``: thresholds and batch fill |
| ``serve.transfer`` | span, ns | ``step``: upload, launch, device, download |
| ``serve.complete`` | span, ns | ``step``: per-request results and stats |
| ``serve.queue_wait_s`` | counter, s | ``step``: its start less each finished request's submit |
| ``engine.prepare`` | span, ns | ``Engine.run``: config, data, keys, placement |
| ``engine.execute`` | span, ns | ``Engine._timed_call``: launch and wait |
| ``engine.publish`` | span, ns | ``Engine.run``: hand-off of trial (0, 0) to a store |
| ``engine.local_train_pack`` | counter | ``Engine.run``, on the Pallas local-train path: clients per kernel tile |
| ``engine.compress_tiles_per_step`` | counter | ``Engine.run``, on the Pallas compress path: tiles each grid step of the job's compress kernel bisects together |
| ``engine.local_train_packs_per_step`` | counter | ``Engine.run``, on the Pallas local-train path: packs each grid step of the job's local-train kernel trains together |
| ``engine.detector_params`` | counter | ``Engine.run``, once a job: the detector's d |
| ``engine.local_windows`` | counter | ``Engine.run``, once a job: samples a sensor trains a round |
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

try:
    from jax._src.lib import _profiler

    recording = _profiler.TraceMe.is_enabled
except (ImportError, AttributeError):
    def recording() -> bool:
        return False

RING = 1 << 18

_perf_ns = time.perf_counter_ns


class _Ring:
    __slots__ = ("buf", "n")

    def __init__(self):
        self.buf = np.zeros(RING, np.float64)
        self.n = 0  # values ever appended


_rings: dict[str, _Ring] = {}


def _ring(name: str) -> _Ring:
    r = _rings.get(name)
    if r is None:
        r = _rings[name] = _Ring()
    return r


class span:
    """``with span(name) as s: ...``; ``s.seconds`` is the work's length."""

    __slots__ = ("name", "_ann", "t0", "t1")

    def __init__(self, name: str):
        self.name = name
        self._ann = None

    def __enter__(self) -> "span":
        if recording():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = _perf_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = _perf_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            r = _ring(self.name)
            r.buf[r.n % RING] = self.t1 - self.t0
            r.n += 1

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9


def observe(name: str, values) -> None:
    """Append ``values`` to the ring ``name`` while a session records."""
    if not recording():
        return
    v = np.ravel(np.asarray(values, np.float64))
    r = _ring(name)
    total = v.size
    v = v[-RING:]
    i = (r.n + total - v.size) % RING
    head = min(v.size, RING - i)
    r.buf[i:i + head] = v[:head]
    r.buf[:v.size - head] = v[head:]
    r.n += total


def records(name: str) -> np.ndarray:
    """The newest values of ``name`` (at most ``RING``, in ring order once
    it has wrapped); empty when nothing was recorded."""
    r = _rings.get(name)
    if r is None:
        return np.zeros(0, np.float64)
    return r.buf if r.n > RING else r.buf[:r.n]


def summary() -> dict[str, dict]:
    """Per name: ``count`` (values ever recorded), ``mean``, ``p50`` and
    ``p95`` of the values kept, and ``wrapped`` (older values were
    overwritten, so the statistics cover only the newest ``RING``)."""
    out = {}
    for name, r in _rings.items():
        if r.n == 0:
            continue
        v = records(name)
        p50, p95 = np.percentile(v, (50, 95))
        out[name] = {"count": r.n, "mean": float(np.mean(v)), "p50": float(p50),
                     "p95": float(p95), "wrapped": r.n > RING}
    return out


def clear() -> None:
    for r in _rings.values():
        r.n = 0
