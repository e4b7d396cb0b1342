"""TimeLine — in-memory event ring for tracing (water/TimeLine.java).

Reference: a lock-free ring of every UDP/TCP send/recv with ns timestamps,
snapshotted over ``/3/Timeline`` (``water/TimeLine.java:22,75-110``,
``init/TimelineSnapshot.java``).

TPU-native: the interesting events are not packets (XLA owns transport)
but the compute lifecycle — jit compiles, training blocks, REST requests,
parse jobs, collectives-bearing steps. Each event is (ns timestamp, kind,
fields); the ring keeps the most recent ``CAPACITY`` events and the
``/3/Timeline`` route serves a snapshot.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Deque, Dict, List

CAPACITY = 8192

_lock = threading.Lock()
_ring: Deque[Dict[str, Any]] = collections.deque(maxlen=CAPACITY)
_counter = 0

#: Optional trace-context provider (installed by util/telemetry.py): returns
#: {"trace_id": ..., "span_id": ...} for the calling thread's open span, or
#: None. Kept as a hook so this module stays import-light and dependency-free.
_trace_provider = None


def set_trace_provider(fn) -> None:
    """Install a callable returning trace-context fields to merge into every
    recorded event (telemetry Spans use this to make /3/Timeline
    correlatable); pass None to uninstall."""
    global _trace_provider
    _trace_provider = fn


def record(kind: str, **fields: Any) -> None:
    """Append one event; cheap enough for per-block/per-request use."""
    global _counter
    if _trace_provider is not None and "trace_id" not in fields:
        try:
            ctx = _trace_provider()
        except Exception:  # tracing must never break recording
            ctx = None
        if ctx:
            fields = {**ctx, **fields}
    evt = {"ns": time.time_ns(), "kind": kind, **fields}
    with _lock:
        _counter += 1
        evt["seq"] = _counter
        _ring.append(evt)


def record_event(evt: Dict[str, Any]) -> None:
    """Append a pre-built event dict — the hot-path variant of
    :func:`record` for callers that already carry their trace fields
    (telemetry Spans): no kwargs splat, no provider merge, one dict.
    The caller hands over ownership of ``evt``; an event that carries its
    own end (``ns``, a step heard after it ended) keeps it."""
    global _counter
    if "ns" not in evt:
        evt["ns"] = time.time_ns()
    with _lock:
        _counter += 1
        evt["seq"] = _counter
        _ring.append(evt)


class timed:
    """Context manager: records kind with duration_ms on exit."""

    def __init__(self, kind: str, **fields: Any) -> None:
        self.kind = kind
        self.fields = fields

    def __enter__(self) -> "timed":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        record(
            self.kind,
            duration_ms=round((time.perf_counter() - self.t0) * 1e3, 3),
            ok=exc[0] is None,
            **self.fields,
        )


def snapshot(n: int = 1000) -> List[Dict[str, Any]]:
    if n <= 0:
        return []  # [-0:] would be the WHOLE ring, not zero events
    with _lock:
        return list(_ring)[-n:]


def snapshot_payload(n: int = 1000) -> Dict[str, Any]:
    """Snapshot + ring totals + this node's wall clock at snapshot time —
    the ``timeline_snapshot`` RPC body.  ``now_ns`` lets the merging node
    sanity-check its heartbeat-derived clock-skew estimate against the
    moment the events were actually collected."""
    return {
        "events": snapshot(n),
        "total_events": total_events(),
        "now_ns": time.time_ns(),
    }


def total_events() -> int:
    return _counter


def clear() -> None:
    global _counter
    with _lock:
        _ring.clear()
        _counter = 0
