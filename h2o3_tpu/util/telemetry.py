"""Process-wide telemetry: metrics registry + span-correlated tracing.

Reference: H2O-3 ships first-class self-observability — ``/3/Timeline``,
``/3/Profiler``, ``/3/Logs`` and the WaterMeter CPU/IO gauges (``water/api/
WaterMeterCpuTicksHandler.java``) — but no *quantitative* layer: nothing in
the seed counted REST requests, jit compile-cache misses, map_reduce
dispatches, bytes ingested or store churn.  This module is that layer:

* a lock-protected process-wide :class:`Registry` of :class:`Counter` /
  :class:`Gauge` / :class:`Histogram` families with labels, snapshot-able as
  JSON (``GET /3/Metrics``) and as Prometheus text exposition format v0.0.4
  (``GET /3/Metrics/prometheus``);
* a :class:`Span` context that threads a ``trace_id``/``parent_id`` through
  nested work (REST request -> model fit -> map_reduce dispatch) and records
  enriched events into the existing :mod:`h2o3_tpu.util.timeline` ring, so
  ``/3/Timeline`` becomes correlatable — every plain ``timeline.record``
  under an open span inherits the span's trace ids via the trace provider
  hook installed below;
* a ``jax.monitoring`` listener that counts XLA backend compiles process-wide
  (``jit_compiles_total`` / ``jit_compile_seconds_total``; a program the
  persistent cache served counts under ``jit_cache_loads_total`` instead), the
  substrate for per-dispatch jit cache hit/miss accounting in
  ``compute/mapreduce.py`` and for the ``compiles`` / ``cache_loads`` fields
  of a span inside which the calling thread built or loaded a program.  It
  also hears JAX's trace of a program into a jaxpr and its lowering to
  StableHLO: a span says ``trace_s`` / ``lower_s``, and under an open span
  each stage of ``JIT_LEAF_S`` or more is a leaf event of the ring
  (``jit_trace``, ``jit_lower``, ``jit_build``; shorter steps in a row are
  merged into one).

One clock: a ring event's ``start_ns`` and ``ns`` are ``time.time_ns()``
(the host's wall clock, CLOCK_REALTIME), and so is the profiler's: under a
``jax.profiler`` session every :class:`Span` is also a ``TraceAnnotation``
on its thread's line of the trace, whose start, offset by the trace's
``profile_start_time``, is the span's ``start_ns`` to well under a
millisecond (``tests/test_fit_spans.py``), so program spans and device
operations share one clock and a reader needs no conversion.

The TPU-native story (SURVEY.md §5): ``jax.profiler`` owns the device-side
trace; this registry owns the host-side control-plane numbers that DrJAX-style
per-primitive accounting needs before any hot path can be called "measurably
faster".
"""

from __future__ import annotations

import math
import random
import re
import sys
import threading
import time
from typing import (
    Any,
    Dict,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
)

from h2o3_tpu.util import timeline

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "REGISTRY",
    "Span",
    "counter",
    "gauge",
    "histogram",
    "current_span",
    "current_trace_id",
    "current_trace_context",
    "install_jax_compile_listener",
    "jit_compile_count",
    "merge_snapshots",
    "node_name",
    "node_scope",
    "set_node_name",
    "snapshot_prometheus",
]

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

#: default histogram buckets (seconds-flavored; jit compiles and model fits
#: span sub-ms REST pings to multi-minute training blocks)
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
)


def _escape_label(v: Any) -> str:
    """Prometheus label-value escaping: backslash, double-quote, newline."""
    return (
        str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _escape_help(h: str) -> str:
    return h.replace("\\", "\\\\").replace("\n", "\\n")


def _fmt_value(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    f = float(v)
    if math.isnan(f):
        return "NaN"
    if f.is_integer() and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


class _Bound:
    """A pre-validated handle on ONE series of a metric family: label
    checking and key construction happen once at :meth:`Metric.bind` time,
    so the per-event cost on a hot path (the RPC in-flight gauge ticks
    twice per call) drops to a lock plus a dict op.  The update logic
    stays on the metric class (``_inc_key``/``_set_key``/``_observe_key``),
    so a handle keeps its metric's type discipline — ``observe`` on a
    gauge-bound handle is an AttributeError, not silent corruption."""

    __slots__ = ("_metric", "_key")

    def __init__(self, metric: "Metric", key: Tuple[str, ...]) -> None:
        self._metric = metric
        self._key = key

    def inc(self, amount: float = 1.0) -> None:
        self._metric._inc_key(self._key, amount)

    def dec(self, amount: float = 1.0) -> None:
        self._metric._inc_key(self._key, -amount)

    def set(self, value: float) -> None:
        self._metric._set_key(self._key, value)

    def observe(self, value: float) -> None:
        self._metric._observe_key(self._key, value)


class Metric:
    """One metric family: a name + help + fixed label names, holding one
    series per distinct label-value tuple. All mutation is lock-protected
    (REST handler threads, training threads and the compile listener all
    write concurrently)."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "", labels: Sequence[str] = ()) -> None:
        if not _NAME_RE.match(name):
            raise ValueError(f"bad metric name {name!r}")
        for ln in labels:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"bad label name {ln!r} for metric {name!r}")
        self.name = name
        self.help = help
        self.labelnames: Tuple[str, ...] = tuple(labels)
        self._lock = threading.Lock()
        self._series: Dict[Tuple[str, ...], Any] = {}

    def _key(self, labels: Mapping[str, Any]) -> Tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"metric {self.name!r} takes labels {self.labelnames}, "
                f"got {tuple(labels)}"
            )
        return tuple(str(labels[k]) for k in self.labelnames)

    def bind(self, **labels: Any) -> _Bound:
        """Pre-resolve a label set into a cheap single-series handle
        (validates the labels now, never again)."""
        return _Bound(self, self._key(labels))

    def _label_str(self, key: Tuple[str, ...]) -> str:
        if not self.labelnames:
            return ""
        pairs = ",".join(
            f'{n}="{_escape_label(v)}"' for n, v in zip(self.labelnames, key)
        )
        return "{" + pairs + "}"

    # -- shared exposition scaffolding --------------------------------------
    def _header(self) -> List[str]:
        out = []
        if self.help:
            out.append(f"# HELP {self.name} {_escape_help(self.help)}")
        out.append(f"# TYPE {self.name} {self.kind}")
        return out

    def expose(self) -> List[str]:
        raise NotImplementedError

    def snapshot(self) -> Dict[str, Any]:
        raise NotImplementedError


class Counter(Metric):
    """Monotonically increasing count (rest_requests_total, ...)."""

    kind = "counter"

    def _inc_key(self, key: Tuple[str, ...], amount: float) -> None:
        if amount < 0:
            raise ValueError("counters only go up")
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        self._inc_key(self._key(labels), amount)

    def value(self, **labels: Any) -> float:
        key = self._key(labels)
        with self._lock:
            return float(self._series.get(key, 0.0))

    def total(self) -> float:
        """Sum over every label combination (the /3/Cloud summary number)."""
        with self._lock:
            return float(sum(self._series.values()))

    def expose(self) -> List[str]:
        out = self._header()
        with self._lock:
            items = sorted(self._series.items())
        for key, v in items:
            out.append(f"{self.name}{self._label_str(key)} {_fmt_value(v)}")
        return out

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            items = sorted(self._series.items())
        return {
            "type": self.kind,
            "help": self.help,
            "series": [
                {"labels": dict(zip(self.labelnames, key)), "value": v}
                for key, v in items
            ],
        }


class _GaugeTrack:
    """with-block in-flight accounting: inc on entry, dec on exit.  Key
    resolution happens once at :meth:`Gauge.track` time, so entering the
    block on a hot path (one per REST request) is a lock plus a dict op."""

    __slots__ = ("_metric", "_key")

    def __init__(self, metric: "Gauge", key: Tuple[str, ...]) -> None:
        self._metric = metric
        self._key = key

    def __enter__(self) -> "_GaugeTrack":
        self._metric._inc_key(self._key, 1.0)
        return self

    def __exit__(self, *exc: Any) -> None:
        self._metric._inc_key(self._key, -1.0)


class Gauge(Metric):
    """A value that goes both ways (dkv_keys, mesh_devices, ...)."""

    kind = "gauge"

    def _set_key(self, key: Tuple[str, ...], value: float) -> None:
        with self._lock:
            self._series[key] = float(value)

    def _inc_key(self, key: Tuple[str, ...], amount: float) -> None:
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def set(self, value: float, **labels: Any) -> None:
        self._set_key(self._key(labels), value)

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        self._inc_key(self._key(labels), amount)

    def dec(self, amount: float = 1.0, **labels: Any) -> None:
        self._inc_key(self._key(labels), -amount)

    def value(self, **labels: Any) -> float:
        key = self._key(labels)
        with self._lock:
            return float(self._series.get(key, 0.0))

    def track(self, **labels: Any) -> _GaugeTrack:
        """Context manager: inc on entry, dec on exit — the in-flight
        idiom (http_inflight while a request is admitted, connections
        while open) without the try/finally boilerplate."""
        return _GaugeTrack(self, self._key(labels))

    expose = Counter.expose
    snapshot = Counter.snapshot


class Histogram(Metric):
    """Cumulative-bucket histogram (model_fit_seconds, rest_request_seconds).

    Exposition follows the Prometheus contract: ``_bucket{le=...}`` lines are
    cumulative, the ``+Inf`` bucket equals ``_count``, plus ``_sum``."""

    kind = "histogram"

    def __init__(
        self,
        name: str,
        help: str = "",
        labels: Sequence[str] = (),
        buckets: Optional[Sequence[float]] = None,
    ) -> None:
        super().__init__(name, help, labels)
        # the +Inf bucket is implicit (it IS _count); an explicit inf here
        # would double the le="+Inf" exposition line and put a non-JSON
        # Infinity token into the /3/Metrics payload
        bs = tuple(sorted(
            b for b in (buckets if buckets is not None else DEFAULT_BUCKETS)
            if not math.isinf(b)
        ))
        if not bs:
            raise ValueError("histogram needs at least one finite bucket")
        self.buckets: Tuple[float, ...] = bs

    def _observe_key(self, key: Tuple[str, ...], value: float) -> None:
        v = float(value)
        with self._lock:
            st = self._series.get(key)
            if st is None:
                st = self._series[key] = {
                    "buckets": [0] * len(self.buckets), "sum": 0.0, "count": 0,
                }
            for i, ub in enumerate(self.buckets):
                if v <= ub:
                    st["buckets"][i] += 1
                    break
            st["sum"] += v
            st["count"] += 1

    def observe(self, value: float, **labels: Any) -> None:
        self._observe_key(self._key(labels), value)

    def count(self, **labels: Any) -> int:
        key = self._key(labels)
        with self._lock:
            st = self._series.get(key)
            return int(st["count"]) if st else 0

    def total_count(self) -> int:
        with self._lock:
            return int(sum(st["count"] for st in self._series.values()))

    def expose(self) -> List[str]:
        out = self._header()
        with self._lock:
            items = sorted(
                (k, list(st["buckets"]), st["sum"], st["count"])
                for k, st in self._series.items()
            )
        for key, counts, total, n in items:
            cum = 0
            for ub, c in zip(self.buckets, counts):
                cum += c
                le = dict(zip(self.labelnames, key))
                pairs = [f'{k}="{_escape_label(v)}"' for k, v in le.items()]
                pairs.append(f'le="{_fmt_value(ub)}"')
                out.append(
                    f"{self.name}_bucket{{{','.join(pairs)}}} {cum}"
                )
            pairs = [
                f'{k}="{_escape_label(v)}"'
                for k, v in zip(self.labelnames, key)
            ]
            pairs_inf = pairs + ['le="+Inf"']
            out.append(f"{self.name}_bucket{{{','.join(pairs_inf)}}} {n}")
            suffix = "{" + ",".join(pairs) + "}" if pairs else ""
            out.append(f"{self.name}_sum{suffix} {_fmt_value(total)}")
            out.append(f"{self.name}_count{suffix} {n}")
        return out

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            items = sorted(
                (k, list(st["buckets"]), st["sum"], st["count"])
                for k, st in self._series.items()
            )
        return {
            "type": self.kind,
            "help": self.help,
            "buckets": list(self.buckets),
            "series": [
                {
                    "labels": dict(zip(self.labelnames, key)),
                    "bucket_counts": counts,
                    "sum": total,
                    "count": n,
                }
                for key, counts, total, n in items
            ],
        }


class Registry:
    """Process-wide metric catalog. ``counter/gauge/histogram`` are
    get-or-create: re-registration with matching type+labels returns the
    existing family (instrumented modules declare their metrics at import
    time, in any order), a mismatch raises."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[str, Metric] = {}

    def _get_or_create(self, cls, name: str, help: str,
                       labels: Sequence[str], **kw) -> Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if type(m) is not cls or m.labelnames != tuple(labels):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{type(m).__name__}{m.labelnames}"
                    )
                want = kw.get("buckets")
                if want is not None and tuple(sorted(
                    b for b in want if not math.isinf(b)
                )) != m.buckets:
                    # silently handing back different buckets would skew
                    # the second caller's quantiles with no error
                    raise ValueError(
                        f"histogram {name!r} already registered with "
                        f"buckets {m.buckets}"
                    )
                return m
            m = cls(name, help, labels, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "", labels: Sequence[str] = (),
                  buckets: Optional[Sequence[float]] = None) -> Histogram:
        return self._get_or_create(
            Histogram, name, help, labels, buckets=buckets
        )

    def get(self, name: str) -> Optional[Metric]:
        with self._lock:
            return self._metrics.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._metrics)

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able view of every family (the /3/Metrics payload)."""
        with self._lock:
            metrics = sorted(self._metrics.items())
        return {name: m.snapshot() for name, m in metrics}

    def prometheus(self) -> str:
        """Text exposition format v0.0.4 (one family block per metric)."""
        with self._lock:
            metrics = sorted(self._metrics.items())
        lines: List[str] = []
        for _, m in metrics:
            lines.extend(m.expose())
        return "\n".join(lines) + "\n" if lines else ""

    def summary(self) -> Dict[str, float]:
        """Compact totals for /3/Cloud and the bench artifact: every counter
        and histogram collapsed over labels, gauges as-is when unlabeled."""
        with self._lock:
            metrics = sorted(self._metrics.items())
        out: Dict[str, float] = {}
        for name, m in metrics:
            if isinstance(m, Histogram):
                out[name + "_count"] = m.total_count()
            elif isinstance(m, Counter):
                out[name] = m.total()
            elif isinstance(m, Gauge) and not m.labelnames:
                out[name] = m.value()
        return out

def merge_snapshots(
    per_node: Mapping[str, Mapping[str, Any]]
) -> Dict[str, Any]:
    """Merge per-node :meth:`Registry.snapshot` payloads into one cluster
    view (the ``GET /3/Metrics?cluster=true`` body).

    Every series gains a ``node=`` label so per-member numbers stay
    visible.  Counters and histograms additionally get a ``node="_cluster"``
    aggregate per distinct label set — counters sum across nodes; histogram
    bucket counts, sums and counts add (one codebase per cloud, so bucket
    bounds match; a family whose bucket layout disagrees across nodes keeps
    only the per-node series).  Gauges stay strictly per-node: summing one
    member's free memory into another's means nothing.
    """
    merged: Dict[str, Dict[str, Any]] = {}
    for node in sorted(per_node):
        snap = per_node[node] or {}
        for name, fam in snap.items():
            slot = merged.setdefault(name, {
                "type": fam.get("type", "untyped"),
                "help": fam.get("help", ""),
                "series": [],
            })
            if "buckets" in fam and "buckets" not in slot:
                slot["buckets"] = list(fam["buckets"])
            for s in fam.get("series", []):
                entry = dict(s)
                entry["labels"] = {**s.get("labels", {}), "node": node}
                slot["series"].append(entry)
    for name, fam in merged.items():
        base_keys = [
            tuple(sorted(
                (k, v) for k, v in s["labels"].items() if k != "node"))
            for s in fam["series"]
        ]
        if fam["type"] == "counter":
            agg: Dict[Tuple, float] = {}
            for key, s in zip(base_keys, fam["series"]):
                agg[key] = agg.get(key, 0.0) + float(s.get("value", 0.0))
            for key in sorted(agg):
                fam["series"].append({
                    "labels": {**dict(key), "node": "_cluster"},
                    "value": agg[key],
                })
        elif fam["type"] == "histogram":
            nb = len(fam.get("buckets", ()))
            if any(len(s.get("bucket_counts", ())) != nb
                   for s in fam["series"]):
                continue  # bucket-layout skew: per-node series only
            hagg: Dict[Tuple, Dict[str, Any]] = {}
            for key, s in zip(base_keys, fam["series"]):
                st = hagg.setdefault(key, {
                    "bucket_counts": [0] * nb, "sum": 0.0, "count": 0})
                st["bucket_counts"] = [
                    a + b for a, b in
                    zip(st["bucket_counts"], s["bucket_counts"])]
                st["sum"] += float(s.get("sum", 0.0))
                st["count"] += int(s.get("count", 0))
            for key in sorted(hagg):
                fam["series"].append({
                    "labels": {**dict(key), "node": "_cluster"},
                    **hagg[key],
                })
    return merged


def snapshot_prometheus(snapshot: Mapping[str, Any]) -> str:
    """Render a snapshot dict (one node's :meth:`Registry.snapshot` or a
    :func:`merge_snapshots` result) as Prometheus text exposition v0.0.4 —
    the federation path cannot use :meth:`Registry.prometheus` because the
    merged series exist only as JSON, never as live Metric objects."""
    lines: List[str] = []
    for name in sorted(snapshot):
        fam = snapshot[name]
        kind = fam.get("type", "untyped")
        if fam.get("help"):
            lines.append(f"# HELP {name} {_escape_help(fam['help'])}")
        lines.append(f"# TYPE {name} {kind}")
        for s in fam.get("series", []):
            pairs = [
                '%s="%s"' % (k, _escape_label(v))
                for k, v in s.get("labels", {}).items()
            ]

            def _suffixed(extra_pair: Optional[str] = None) -> str:
                ps = pairs + ([extra_pair] if extra_pair else [])
                return "{" + ",".join(ps) + "}" if ps else ""

            if kind == "histogram":
                cum = 0
                for ub, c in zip(fam.get("buckets", ()),
                                 s.get("bucket_counts", ())):
                    cum += c
                    le = 'le="%s"' % _fmt_value(ub)
                    lines.append(f"{name}_bucket{_suffixed(le)} {cum}")
                n = int(s.get("count", 0))
                inf = 'le="+Inf"'
                lines.append(f"{name}_bucket{_suffixed(inf)} {n}")
                lines.append(
                    f"{name}_sum{_suffixed()} "
                    f"{_fmt_value(float(s.get('sum', 0.0)))}")
                lines.append(f"{name}_count{_suffixed()} {n}")
            else:
                lines.append(
                    f"{name}{_suffixed()} "
                    f"{_fmt_value(float(s.get('value', 0.0)))}")
    return "\n".join(lines) + "\n" if lines else ""


#: The process-wide registry — the analogue of the one WaterMeter per node.
#: Deliberately no reset(): instrumented modules hold direct references to
#: their families, so clearing the catalog would split-brain the process
#: (stale objects still incremented, fresh ones exposed). Tests wanting
#: isolation construct their own Registry.
REGISTRY = Registry()


def counter(name: str, help: str = "", labels: Sequence[str] = ()) -> Counter:
    return REGISTRY.counter(name, help, labels)


def gauge(name: str, help: str = "", labels: Sequence[str] = ()) -> Gauge:
    return REGISTRY.gauge(name, help, labels)


def histogram(name: str, help: str = "", labels: Sequence[str] = (),
              buckets: Optional[Sequence[float]] = None) -> Histogram:
    return REGISTRY.histogram(name, help, labels, buckets)


# ---------------------------------------------------------------------------
# Span-correlated tracing


_tls = threading.local()

#: span/trace id minting: a process-seeded PRNG formatted as 16 hex chars.
#: uuid4 costs ~2.5us per id; at three spans per traced RPC that is real
#: money against a ~100us loopback round trip — getrandbits is ~5x cheaper
#: and 64 random bits is ample for correlating events inside one ring
_ids = random.Random()


def _new_id() -> str:
    return "%016x" % _ids.getrandbits(64)


#: process-global node identity (set by the cluster bootstrap); every
#: timeline event and span records it so a merged cluster timeline can
#: attribute events to the member that emitted them
_node_name: Optional[str] = None


def set_node_name(name: Optional[str]) -> None:
    """Declare this process's cluster node name (``boot_node`` calls it);
    every subsequently recorded timeline event carries ``node=<name>``."""
    global _node_name
    _node_name = name


def node_name() -> Optional[str]:
    """The effective node identity: a thread-local :class:`node_scope`
    override (the RPC serving path) wins over the process-global name."""
    override = getattr(_tls, "node", None)
    return override if override is not None else _node_name


class node_scope:
    """Thread-local node-identity override: the RPC server dispatches a
    remote call under the *serving* cloud's name so events recorded during
    the call attribute correctly even with several in-process Clouds (the
    single-process test harness)."""

    def __init__(self, name: str) -> None:
        self.name = name
        self._prev: Optional[str] = None

    def __enter__(self) -> "node_scope":
        self._prev = getattr(_tls, "node", None)
        _tls.node = self.name
        return self

    def __exit__(self, *exc) -> None:
        _tls.node = self._prev


def _span_stack() -> List["Span"]:
    stack = getattr(_tls, "spans", None)
    if stack is None:
        stack = _tls.spans = []
    return stack


def current_span() -> Optional["Span"]:
    stack = _span_stack()
    return stack[-1] if stack else None


def current_trace_id() -> Optional[str]:
    sp = current_span()
    return sp.trace_id if sp else None


def current_trace_context() -> Optional[Dict[str, str]]:
    """``{"trace_id", "span_id"}`` of the calling thread's open span, or
    None — the envelope the RPC client injects so a remote child span can
    join the caller's trace."""
    sp = current_span()
    if sp is None or sp.trace_id is None:
        return None
    return {"trace_id": sp.trace_id, "span_id": sp.span_id}


def _trace_fields() -> Optional[Dict[str, Any]]:
    """Trace context injected into plain ``timeline.record`` calls made under
    an open span (the provider hook; the recording code stays span-unaware).
    Also stamps the recording node's identity when one is declared, so every
    event in a merged cluster timeline names its origin."""
    out: Dict[str, Any] = {}
    node = node_name()
    if node:
        out["node"] = node
    sp = current_span()
    if sp is not None:
        out["trace_id"] = sp.trace_id
        out["span_id"] = sp.span_id
    return out or None


timeline.set_trace_provider(_trace_fields)

# the log ring gets the same correlation: lines emitted under an open span
# carry its trace/span ids, so /3/Logs lines line up with /3/Timeline traces
from h2o3_tpu.util import log as _log  # noqa: E402  (import-light, no cycle)

_log.set_trace_provider(current_trace_context)


def _trace_annotation(kind: str, **ids: Any):
    """An open ``jax.profiler.TraceAnnotation`` named ``kind`` with ``ids``
    as its arguments, or None in a process that has not imported jax
    (telemetry never imports the backend itself).  With no profiler session
    running the annotation costs one atomic read."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation(kind, **ids)
    ann.__enter__()
    return ann


class Span:
    """Context manager: a unit of traced work.

    The outermost span mints a fresh ``trace_id``; nested spans inherit it and
    point at their parent via ``parent_id``. On exit one enriched event lands
    in the timeline ring (kind + start_ns + duration_ms + ok + ids + node +
    fields; ``ns`` is the end) — the same shape ``timeline.timed`` wrote, now
    correlatable across layers. Spans
    are thread-local: a REST handler thread's trace does not leak into a
    concurrently training thread.

    Under a ``jax.profiler`` session the span is also an event of the same
    name on its thread's line of the trace, with ``span_id``, ``trace_id``
    and ``parent_id`` as its arguments.  A span inside which the calling
    thread built or loaded XLA programs reports ``compiles``,
    ``cache_loads`` and ``compile_s``, and one inside which it traced or
    lowered them ``trace_s`` and ``lower_s`` (never written when zero; a
    program traced inside another's trace counts once, in the outer).

    ``trace_id``/``parent_id`` may be passed explicitly to continue a trace
    that started somewhere else — another thread (a fan-out worker joining
    its caller's trace) or another *node* (the RPC server parenting its
    dispatch span under the caller's envelope context). An explicit context
    wins over the thread-local parent."""

    __slots__ = ("kind", "fields", "span_id", "trace_id", "parent_id",
                 "_explicit", "t0", "start_ns", "_ann", "_built0")

    def __init__(self, kind: str, *, trace_id: Optional[str] = None,
                 parent_id: Optional[str] = None, **fields: Any) -> None:
        self.kind = kind
        self.fields = fields
        self.span_id = _new_id()
        self.trace_id: Optional[str] = trace_id
        self.parent_id: Optional[str] = parent_id
        self._explicit = trace_id is not None
        self.t0 = 0.0
        self.start_ns = 0
        self._ann = None
        self._built0 = (0, 0, 0.0)

    def set(self, **fields: Any) -> "Span":
        """Attach fields discovered mid-span (iterations, rows, ...)."""
        self.fields.update(fields)
        return self

    def __enter__(self) -> "Span":
        if not self._explicit:
            parent = current_span()
            if parent is not None:
                self.trace_id = parent.trace_id
                self.parent_id = parent.span_id
            else:
                self.trace_id = _new_id()
                # a parent_id passed WITHOUT a trace_id would dangle into
                # no trace (e.g. a proxy dropped the trace header but kept
                # the span header) — a fresh trace starts at a root
                self.parent_id = None
        _span_stack().append(self)
        self._built0 = _thread_counts()
        self._ann = _trace_annotation(
            self.kind, span_id=self.span_id, trace_id=self.trace_id,
            parent_id=self.parent_id or "")
        self.start_ns = time.time_ns()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        duration_ms = round((time.perf_counter() - self.t0) * 1e3, 3)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        # both stamps sit beside the annotation's own: one clock, to
        # microseconds, with the profiler's trace
        end_ns = time.time_ns()
        stack = _span_stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # tolerate exotic unwinding, never corrupt peers
            stack.remove(self)
        run = getattr(_tls_compiles, "run", None)
        if run is not None and run["parent"] is self:
            _flush_jit_run(_tls_compiles)
        evt = {
            "kind": self.kind,
            "start_ns": self.start_ns,
            "ns": end_ns,
            "duration_ms": duration_ms,
            "ok": exc_type is None,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
        }
        # the event carries an explicit trace_id, so the provider hook is
        # bypassed — stamp the node identity here too
        node = node_name()
        if node and "node" not in self.fields:
            evt["node"] = node
        if self.fields:
            evt.update(self.fields)
        builds0, loads0, secs0, trace0, lower0 = self._built0
        builds, loads, secs, trace, lower = _thread_counts()
        if builds != builds0:
            evt["compiles"] = builds - builds0
        if loads != loads0:
            evt["cache_loads"] = loads - loads0
        if builds != builds0 or loads != loads0:
            evt["compile_s"] = round(secs - secs0, 6)
        if trace != trace0:
            evt["trace_s"] = round(trace - trace0, 6)
        if lower != lower0:
            evt["lower_s"] = round(lower - lower0, 6)
        timeline.record_event(evt)


# ---------------------------------------------------------------------------
# XLA compile accounting (jax.monitoring)

_JIT_COMPILES = counter(
    "jit_compiles_total",
    "XLA programs built by the backend compiler, process-wide "
    "(jax.monitoring; persistent-cache loads are jit_cache_loads_total)",
)
_JIT_CACHE_LOADS = counter(
    "jit_cache_loads_total",
    "XLA programs served by the persistent compilation cache, process-wide",
)
_JIT_COMPILE_SECS = counter(
    "jit_compile_seconds_total",
    "total wall seconds spent building or loading XLA programs",
)

_jit_listener_lock = threading.Lock()
_jit_listener_installed = False
#: per-thread counts: XLA compiles run synchronously on the thread
#: that triggered them, so a thread-local delta attributes cache misses to
#: the right dispatch even when builds run concurrently (a global delta
#: would blame thread A for thread B's compile)
_tls_compiles = threading.local()


def install_jax_compile_listener() -> bool:
    """Register the process-wide compile listener once; idempotent.

    Returns False when jax (or jax.monitoring) is unavailable — telemetry
    must never be the reason a host-only code path imports the backend."""
    global _jit_listener_installed
    with _jit_listener_lock:
        if _jit_listener_installed:
            return True
        try:
            from jax import monitoring
        except Exception:  # pragma: no cover - jax is baked into the image
            return False

        def _on_event(name: str, **kw: Any) -> None:
            # recorded inside the backend_compile_duration it belongs to,
            # on the same thread: the duration that follows is a load
            if name == "/jax/compilation_cache/cache_hits":
                _tls_compiles.cache_hit = True

        def _on_scalar(name: str, value: Any, **kw: Any) -> None:
            # a stage opens (its start time is the value): a stage that
            # closes while another is open on the thread lies inside it
            if name in _JIT_STAGES:
                t = _tls_compiles
                t.depth = getattr(t, "depth", 0) + 1

        def _on_duration(name: str, secs: float, **kw: Any) -> None:
            kind = _JIT_STAGES.get(name)
            if kind is None:
                return
            t = _tls_compiles
            depth = getattr(t, "depth", 0)
            t.depth = max(depth - 1, 0)
            load = False
            if kind == "jit_build":
                load = getattr(t, "cache_hit", False)
                if load:
                    t.cache_hit = False
                    _JIT_CACHE_LOADS.inc()
                    t.loads = getattr(t, "loads", 0) + 1
                else:
                    _JIT_COMPILES.inc()
                    t.builds = getattr(t, "builds", 0) + 1
                _JIT_COMPILE_SECS.inc(secs)
                t.seconds = getattr(t, "seconds", 0.0) + secs
            if depth > 1:
                return  # inside an outer stage, whose seconds hold it
            if kind == "jit_trace":
                t.trace_s = getattr(t, "trace_s", 0.0) + secs
            elif kind == "jit_lower":
                t.lower_s = getattr(t, "lower_s", 0.0) + secs
            sp = current_span()
            if sp is not None:
                _jit_step(t, sp, kind, secs, str(kw.get("fun_name", "")), load)

        monitoring.register_event_listener(_on_event)
        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _jit_listener_installed = True
        return True


#: jax.monitoring's three stages of making a program (``jax._src.dispatch``)
#: and the kind of the ring event each becomes
_JIT_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower",
    "/jax/core/compile/backend_compile_duration": "jit_build",
}
#: a stage this long or longer is a leaf event of its own; shorter ones (the
#: op-by-op helpers a first fit builds: keys, padding, casts) are merged
#: with the short steps before them, under the same span and no more than
#: this far apart, into one event that says how many ``steps`` it holds,
#: and a merged run shorter than this is left to its span's fields.  So a
#: process's first fit adds tens of events to the ring, not thousands
JIT_LEAF_S = 0.010
_JIT_LEAF_NS = int(JIT_LEAF_S * 1e9)


def _jit_step(t: threading.local, sp: "Span", kind: str, secs: float,
              fun: str, load: bool) -> None:
    """One outermost stage of making a program under the open span ``sp``:
    its end is now, its start that less its duration (never before the
    span's own start)."""
    end = time.time_ns()
    start = max(end - int(secs * 1e9), sp.start_ns)
    run = getattr(t, "run", None)
    if secs >= JIT_LEAF_S:
        if run is not None:
            _flush_jit_run(t)
        evt = _jit_event(kind, start, end, sp, fun=fun)
        if load:
            evt["cache_load"] = True
        timeline.record_event(evt)
        return
    if (run is not None and run["parent"] is sp
            and start - run["ns"] <= _JIT_LEAF_NS):
        run["ns"] = end
        run["steps"] += 1
        run["s"][kind] = run["s"].get(kind, 0.0) + secs
        return
    if run is not None:
        _flush_jit_run(t)
    t.run = {"parent": sp, "start_ns": start, "ns": end, "steps": 1,
             "s": {kind: secs}, "fun": fun}


def _flush_jit_run(t: threading.local) -> None:
    """Record the thread's run of short steps as one leaf, named for the
    stage that took most of it, where the run lasted ``JIT_LEAF_S``."""
    run, t.run = t.run, None
    if run["ns"] - run["start_ns"] < _JIT_LEAF_NS:
        return
    kind = max(run["s"], key=run["s"].get)
    timeline.record_event(_jit_event(kind, run["start_ns"], run["ns"], run["parent"],
                                     fun=run["fun"], steps=run["steps"]))


def _jit_event(kind: str, start: int, end: int, sp: "Span", **fields: Any) -> dict:
    evt = {"kind": kind, "start_ns": start, "ns": end,
           "duration_ms": round((end - start) / 1e6, 3), "ok": True,
           "trace_id": sp.trace_id, "span_id": _new_id(), "parent_id": sp.span_id}
    node = node_name()
    if node:
        evt["node"] = node
    evt.update(fields)
    return evt


def jit_compile_count() -> float:
    """Programs built process-wide (the bench/summary number)."""
    return _JIT_COMPILES.total()


def _thread_builds() -> Tuple[int, int, float]:
    """(programs built, programs loaded from the persistent cache, seconds
    in either) on the CALLING thread since it started."""
    t = _tls_compiles
    return (getattr(t, "builds", 0), getattr(t, "loads", 0),
            getattr(t, "seconds", 0.0))


def _thread_counts() -> Tuple[int, int, float, float, float]:
    """:func:`_thread_builds` and the seconds of outermost traces and
    lowerings on the CALLING thread: what a span subtracts at its exit."""
    t = _tls_compiles
    return _thread_builds() + (getattr(t, "trace_s", 0.0), getattr(t, "lower_s", 0.0))


def thread_compile_count() -> int:
    """Programs that reached the backend on the CALLING thread, built or
    loaded — either way the in-process jit cache missed, so per-dispatch
    deltas give correct plan hit/miss attribution under concurrent builds."""
    builds, loads, _ = _thread_builds()
    return builds + loads


def thread_compile_seconds() -> float:
    """Compile wall seconds observed on the CALLING thread; the cost
    ledger charges per-dispatch deltas of this to the open trace."""
    return _thread_builds()[2]
