"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What the trace looks like on a v5e (read by hand before this was written):
plane ``/device:TPU:<n>`` has the line ``XLA Ops`` — every device operation
with start and duration in ns, NESTED: a ``%while`` event spans the events
of its body — and ``XLA Modules`` (one event per executed program).  Plane
``/host:CPU`` has one line per thread; the main thread's line (named after
the executable, ``python`` or ``python3``) holds the Python calls, named
``$file.py:line function``.  All planes share one clock.

Everything here works on plain tuples ``(name, start_ns, duration_ns)`` so
the self-check can feed it a small recorded trace.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
PYTHON_CALL = "$"  # the first character of a Python call in the profiler
#: device events of the histogram kernels, by the names the trace prints
#: today (three unnamed ``pallas_call``s inside ``_build_histogram_pallas_jit``)
HIST_KERNEL = ("_build_histogram_pallas_jit", "custom-call")


def union_seconds(events: Iterable[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events)
    total, end = 0.0, lo
    for s, e in spans:
        if e <= s:
            continue
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def gaps(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Idle intervals (start_ns, end_ns) inside [lo, hi], longest first."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events)
    out, end = [], lo
    for s, e in spans:
        if e <= s:
            continue
        if s > end:
            out.append((end, s))
        end = max(end, e)
    if hi > end:
        out.append((end, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def self_seconds(events: Sequence[Event]) -> Dict[str, float]:
    """Per name, time inside an event but outside the events nested in it
    (a ``%while`` keeps only what its body does not account for)."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0) / 1e9

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    close(float("inf"))
    return out


def matching_seconds(events: Iterable[Event], needles: Sequence[str],
                     lo: float, hi: float) -> Tuple[float, int]:
    """Sum of durations (s) and count of events inside [lo, hi] whose name
    holds every needle."""
    total, n = 0.0, 0
    for name, s, d in events:
        if s >= lo and s + d <= hi and all(x in name for x in needles):
            total += d
            n += 1
    return total / 1e9, n


def short_name(name: str) -> str:
    """``%fusion.928 = s32[...]{...} fusion(...)`` -> ``fusion.928 s32[..]``:
    enough to find the operation in the program text, short enough for the
    ledger."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    label = head.lstrip("%")
    return f"{label} {shape}".strip()[:120]


def attribute_gap(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """What the host was doing in an idle gap: the innermost host span that
    covers at least half of it (spans are nested calls, so innermost = the
    shortest that still covers)."""
    s, e = gap
    need = (e - s) / 2.0
    best: Optional[Event] = None
    for ev in host:
        overlap = min(e, ev[1] + ev[2]) - max(s, ev[1])
        if overlap >= need and (best is None or ev[2] < best[2]):
            best = ev
    return best[0] if best is not None else "(no host span)"


def read_xplane(trace_dir: str, marker: str = ""):
    """(device ops per device plane, host python events) from the newest
    ``.xplane.pb`` under ``trace_dir``; spans named ``marker`` are taken from
    every host line (a ``TraceAnnotation`` lands on its thread's line)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (ev.name, ev.start_ns, ev.duration_ns) for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [(ev.name, ev.start_ns, ev.duration_ns) for ev in line.events]
                calls = sum(name.startswith(PYTHON_CALL) for name, _, _ in events)
                if calls * 2 > len(events):  # a line of Python calls
                    host.extend(events)
                elif marker:
                    host.extend(ev for ev in events if marker in ev[0])
    return device, host


def reduce(device: Dict[str, List[Event]], host: Sequence[Event],
           marker: str) -> Optional[dict]:
    """The traced window is the host span named ``marker``; returns busy and
    window seconds (busy averaged over the device planes), the histogram
    kernels' seconds and calls, the ten device operations with most self
    time and the ten longest idle gaps by what the host was doing."""
    spans = [ev for ev in host if marker in ev[0]]
    if not spans or not device:
        return None
    _, lo, dur = max(spans, key=lambda ev: ev[2])
    hi = lo + dur
    busy = [union_seconds(evs, lo, hi) for evs in device.values()]
    first = next(iter(device.values()))
    inside = [ev for ev in first if ev[1] < hi and ev[1] + ev[2] > lo]
    kernel_s, kernel_n = matching_seconds(first, HIST_KERNEL, lo, hi)
    ops = sorted(self_seconds(inside).items(), key=lambda kv: -kv[1])[:10]
    by_host: Dict[str, float] = {}
    for g in gaps(inside, lo, hi)[:200]:
        label = attribute_gap(g, host)
        by_host[label] = by_host.get(label, 0.0) + (g[1] - g[0]) / 1e9
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": dur / 1e9,
        "window_start_ns": lo,
        "hist_kernel_s": kernel_s,
        "hist_kernel_calls": kernel_n,
        "device_ops": [[short_name(k), v] for k, v in ops],
        "idle_gaps": [[k[:120], v] for k, v in idle],
    }
