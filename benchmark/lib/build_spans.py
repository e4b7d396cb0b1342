"""What a fit pays before its first tree and what the chip waits on between
trees, by the program's own spans.

A fit's entry names its host steps (``data_info`` under ``tree_setup``,
``margin_download`` under ``budget_check``), and JAX's trace, lowering and
build of a program are leaf events of the ring (``jit_trace``, ``jit_lower``,
``jit_build``) with ``trace_s`` / ``lower_s`` on the spans that made them.
A program from before these spans opens no ``data_info`` span: then every
reader here returns ``None`` and the metric is left out of the line.

Ring stamps and the profiler's trace share one clock (``time.time_ns``);
the trace is nonetheless matched to the ring by ``span_id`` alone, so no
reading here depends on it.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

from . import trace as trace_mod

#: the span every fit of a program with these spans opens
MARKER = "data_info"
#: where ``harness.run`` leaves the traced window's profile
TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".bench_trace")

Interval = Tuple[int, int]


def named(tree: Optional[dict]) -> bool:
    """Whether the fit's spans are those of a program with these spans."""
    return tree is not None and any(e["kind"] == MARKER for e in tree["spans"])


def leaves(tree: dict) -> List[dict]:
    """The fit's spans that have no child, ``train`` left out."""
    return [e for e in tree["spans"]
            if e is not tree["train"] and not tree["children"].get(e["span_id"])]


def uncovered(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi] under none of the intervals (same unit)."""
    covered, end = 0.0, lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        s = max(s, end)
        if e <= s:
            continue
        covered += e - s
        end = e
    return max(hi - lo, 0) - covered


def entry_unspanned_s(tree: Optional[dict]) -> Optional[float]:
    """Seconds between the fit's ``train`` call and its first ``tree_block``
    under none of its leaf spans."""
    if not named(tree):
        return None
    blocks = [int(e["start_ns"]) for e in tree["spans"] if e["kind"] == "tree_block"]
    if not blocks:
        return None
    lo = int(tree["train"]["start_ns"])
    return uncovered(((int(e["start_ns"]), int(e["ns"])) for e in leaves(tree)),
                     lo, min(blocks)) / 1e9


def trace_lower_s(tree: Optional[dict]) -> Optional[float]:
    """The fit's ``train`` span's ``trace_s`` + ``lower_s``."""
    if not named(tree):
        return None
    train = tree["train"]
    return float(train.get("trace_s", 0.0)) + float(train.get("lower_s", 0.0))


# ---------------------------------------------------------------------------
# the device's idle time under the window's leaf spans


def read_trace(trace_dir: str, marker: str, kinds: Iterable[str]):
    """From the newest profile under ``trace_dir``: the first device's
    operations, the interval of the longest host event named ``marker``,
    and the interval of every host annotation whose name is one of
    ``kinds``, by its ``span_id`` argument; all on the trace's own time
    axis.  None where the profile has no device plane or no marker."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        return None
    kinds = set(kinds)
    data = ProfileData.from_file(files[-1])  # its planes live as long as it
    planes = list(data.planes)
    devices = [p for p in planes if p.name.startswith(trace_mod.DEVICE_PLANE)]
    if not devices:
        return None
    ops = [(ev.name, ev.start_ns, ev.duration_ns)
           for line in min(devices, key=lambda p: p.name).lines
           if line.name == trace_mod.OPS_LINE for ev in line.events]
    window: Optional[Interval] = None
    annotated: Dict[str, Interval] = {}
    for plane in planes:
        if plane.name == trace_mod.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name == marker:
                        if window is None or ev.duration_ns > window[1] - window[0]:
                            window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif name in kinds:
                        sid = dict(ev.stats).get("span_id")
                        if sid is not None:
                            annotated[str(sid)] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        return None
    return ops, window, annotated


def idle_unspanned_share(ops, window: Interval, leaf_intervals: Iterable[Interval]
                         ) -> Optional[float]:
    """Share (%) of the device's idle time in ``window`` during which none
    of ``leaf_intervals`` was open; None where the device never idled."""
    lo, hi = window
    idle = sorted(trace_mod.gaps(
        [ev for ev in ops if ev[1] < hi and ev[1] + ev[2] > lo], lo, hi))
    total = sum(e - s for s, e in idle)
    if total <= 0:
        return None
    merged: List[List[float]] = []  # the leaves' union, in order
    for s, e in sorted(leaf_intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    covered, j = 0.0, 0
    for s, e in idle:  # both lists in order: one sweep
        while j < len(merged) and merged[j][1] <= s:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < e:
            covered += min(e, merged[k][1]) - max(s, merged[k][0])
            k += 1
    return 100.0 * (total - covered) / total
