"""Profiler trace of a run's window, reduced to the numbers the per-layer
readers need.

The window runs under ``jax.profiler.trace`` with the Python tracer off;
the benchmark's own spans (``jax.profiler.TraceAnnotation`` named
``bench.*``) land on the host plane, the device's operations on the
``/device:TPU:<n>`` planes.  ``events`` flattens the newest ``.xplane.pb``
into plain tuples; everything after that is arithmetic on those tuples,
so the tests can check it on a small recorded trace.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# The line of a device plane that holds one event per executed operation
# (the lines beside it hold whole programs and steps, which overlap it).
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


class Event(NamedTuple):
    plane: str
    line: str
    name: str          # HLO instruction name of a device op, else the span's
    start_ns: float
    dur_ns: float
    call: bool = False  # the device op is a custom call (a Pallas kernel is one)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@contextlib.contextmanager
def capture(logdir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(logdir, profiler_options=opts):
        yield


def events(logdir: str) -> list[Event]:
    """Device operations and ``bench.*`` host spans of the newest trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name != OP_LINE:
                continue
            for e in line.events:
                if device:
                    out.append(Event(plane.name, line.name, instruction(e.name),
                                     float(e.start_ns), float(e.duration_ns),
                                     " custom-call(" in e.name))
                elif e.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def instruction(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


# --- reductions ----------------------------------------------------------

def spans(evs: list[Event], name: str) -> list[Event]:
    return [e for e in evs if e.name == name and not DEVICE_PLANE.match(e.plane)]


def window(evs: list[Event]) -> tuple[float, float] | None:
    """(start, end) of the ``bench.window`` span."""
    w = spans(evs, SPAN_PREFIX + "window")
    return (w[0].start_ns, w[0].end_ns) if w else None


def device_planes(evs: list[Event]) -> list[str]:
    return sorted({e.plane for e in evs if DEVICE_PLANE.match(e.plane)})


def union(intervals: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """Disjoint, sorted union of the intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def ops(evs: list[Event], plane: str | None = None) -> list[Event]:
    return [e for e in evs if DEVICE_PLANE.match(e.plane) and (plane is None or e.plane == plane)]


def busy_ns(evs: list[Event], win: tuple[float, float]) -> float:
    """Time in which some operation ran, averaged over the chips traced."""
    planes = device_planes(evs)
    if not planes:
        return 0.0
    total = 0.0
    for p in planes:
        total += sum(e - s for s, e in union(
            [(o.start_ns, o.end_ns) for o in ops(evs, p)], *win))
    return total / len(planes)


_JIT = re.compile(r"^jit\(([A-Za-z_]\w*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def kernel_names(hlo_texts) -> dict[str, str]:
    """{custom-call instruction: Pallas kernel} from compiled HLO text.  The
    trace names a device op by its instruction only; the instruction's
    op_name metadata names the kernel: the innermost ``jit(<kernel>)``
    before ``pallas_call``, which the kernel's wrapper sets."""
    out = {}
    for text in hlo_texts:
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            m = _OP_NAME.search(line)
            parts = m.group(1).split("/") if m else []
            if "pallas_call" not in parts:
                continue
            end = len(parts) - 1 - parts[::-1].index("pallas_call")
            for part in reversed(parts[:end]):
                jm = _JIT.match(part)
                if jm:
                    out[instruction(line.strip())] = jm.group(1)
                    break
    return out


def label(e: Event, kernels: dict[str, str]) -> str:
    """What an operation is: the Pallas kernel a custom call runs, else
    its instruction name without the ``.<n>`` suffix."""
    if e.call and e.name in kernels:
        return kernels[e.name]
    return e.name.split(".")[0]


def leaf_ops(ops_: list[Event]) -> list[Event]:
    """Operations that contain no other operation: a loop or a call that
    wraps kernels shows on the same line as the kernels inside it."""
    s = sorted(ops_, key=lambda o: (o.plane, o.start_ns, -o.dur_ns))
    parent, stack = set(), []
    for i, o in enumerate(s):
        while stack and (s[stack[-1]].plane != o.plane or s[stack[-1]].end_ns <= o.start_ns):
            stack.pop()
        if stack and o.end_ns <= s[stack[-1]].end_ns:
            parent.add(stack[-1])
        stack.append(i)
    return [o for i, o in enumerate(s) if i not in parent]


def kernel_ns(evs: list[Event], win: tuple[float, float], names, kernels: dict) -> float:
    """Device time of the operations labelled with one of ``names``,
    clipped to the window and averaged over the chips traced."""
    planes = device_planes(evs)
    if not planes:
        return 0.0
    total = 0.0
    for o in leaf_ops(ops(evs)):
        if label(o, kernels) in names:
            total += max(0.0, min(o.end_ns, win[1]) - max(o.start_ns, win[0]))
    return total / len(planes)


def top_ops(evs: list[Event], win: tuple[float, float], kernels: dict,
            n: int = 10) -> list[list]:
    """The device operations that took most time, by ``label``, loops and
    calls around them left out: [[name, seconds], ...]."""
    acc: dict[str, float] = {}
    for o in leaf_ops(ops(evs)):
        t = max(0.0, min(o.end_ns, win[1]) - max(o.start_ns, win[0]))
        if t > 0:
            key = label(o, kernels)
            acc[key] = acc.get(key, 0.0) + t
    planes = max(1, len(device_planes(evs)))
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / planes / 1e9] for k, v in top]


def idle_gaps(evs: list[Event], win: tuple[float, float], n: int = 10) -> list[list]:
    """The longest stretches in which the first chip ran nothing, each
    named by the innermost ``bench.*`` host span around its middle:
    [[name, seconds], ...]."""
    planes = device_planes(evs)
    if not planes:
        return []
    busy = union([(o.start_ns, o.end_ns) for o in ops(evs, planes[0])], *win)
    gaps, at = [], win[0]
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < win[1]:
        gaps.append((at, win[1]))
    host = [e for e in evs if not DEVICE_PLANE.match(e.plane)
            and e.name != SPAN_PREFIX + "window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + e)
        around = [h for h in host if h.start_ns <= mid <= h.end_ns]
        name = min(around, key=lambda h: h.dur_ns).name if around else "bench.window"
        out.append([name, (e - s) / 1e9])
    return out


def idle_percent(evs: list[Event], win: tuple[float, float]) -> float | None:
    """Share of the window in which the device ran nothing, in percent;
    None where the trace holds no device operation."""
    if not win or not device_planes(evs):
        return None
    return 100.0 * (1.0 - busy_ns(evs, win) / (win[1] - win[0]))


def roofline_percent(flops: float, nbytes: float, seconds: float, peaks: dict,
                     chips: int) -> float | None:
    """Least time the chips could take for the work, over the time taken,
    in percent: the larger of operations over peak FLOP/s and bytes over
    peak bandwidth.  None where no time was measured."""
    if seconds <= 0:
        return None
    least = max(flops / (chips * peaks["bf16_flops_per_s"]),
                nbytes / (chips * peaks["hbm_bytes_per_s"]))
    return 100.0 * least / seconds
