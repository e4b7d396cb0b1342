"""The program's own spans of one fit, read from its timeline ring.

From ISSUE 28 on every phase of a fit is a ``telemetry.Span``: an event of
the ring with ``kind``, ``start_ns`` and ``ns`` (the end) on the wall clock,
and ``trace_id`` / ``span_id`` / ``parent_id``; one fit is the tree under its
``train`` span.  A program from before that PR records ``train`` without a
start and no phase beneath it: then :func:`fit_tree` finds no fit and every
reader returns ``None``, and the metric is left out of the line.

All seconds here are wall-clock differences of the ring's own stamps.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional

Event = Dict[str, object]


class RingWrapped(RuntimeError):
    """The ring no longer reaches back to the fit that is asked for."""


def ring_events() -> List[Event]:
    """Every event the ring holds, oldest first."""
    from h2o3_tpu.util import timeline

    return timeline.snapshot(timeline.CAPACITY)


def check_reach(events: List[Event], total_events: int, capacity: int,
                t0_ns: int) -> None:
    """Fail loudly where the ring has dropped events newer than ``t0_ns``:
    sums over a fit that has lost spans would read low and look sound."""
    if total_events <= capacity:
        return
    if not events or int(events[0]["ns"]) > t0_ns:
        raise RingWrapped(
            f"the timeline ring holds {capacity} events and has recorded "
            f"{total_events}: it no longer reaches back to the fit that "
            f"started at {t0_ns}; its spans cannot be summed")


def fit_tree(events: Iterable[Event], t0_ns: int, t1_ns: int) -> Optional[dict]:
    """The span tree of the fit whose ``train`` span lies inside
    ``[t0_ns, t1_ns]`` (the last one, if several): ``{"train": event,
    "spans": every event under it, train included, "children": span_id ->
    events in order of their start, "by_id": span_id -> event}``.  None where no such span has a start
    on record."""
    events = list(events)
    trains = [e for e in events
              if e.get("kind") == "train" and "start_ns" in e
              and t0_ns <= int(e["start_ns"]) and int(e["ns"]) <= t1_ns]
    if not trains:
        return None
    train = trains[-1]
    same = [e for e in events
            if e.get("trace_id") == train["trace_id"] and "start_ns" in e
            and "span_id" in e]
    by_parent: Dict[object, List[Event]] = {}
    for e in same:
        by_parent.setdefault(e.get("parent_id"), []).append(e)
    spans, children = [train], {}
    stack = [train]
    while stack:
        parent = stack.pop()
        kids = sorted(by_parent.get(parent["span_id"], ()),
                      key=lambda e: int(e["start_ns"]))
        children[parent["span_id"]] = kids
        spans.extend(kids)
        stack.extend(kids)
    return {"train": train, "spans": spans, "children": children,
            "by_id": {e["span_id"]: e for e in spans}}


def seconds(event: Event) -> float:
    return (int(event["ns"]) - int(event["start_ns"])) / 1e9


def _ancestors(tree: dict, event: Event) -> List[str]:
    by_id = tree["by_id"]
    out, up = [], by_id.get(event.get("parent_id"))
    while up is not None:
        out.append(str(up["kind"]))
        up = by_id.get(up.get("parent_id"))
    return out


def kind_seconds(tree: Optional[dict], kind: str,
                 under: Optional[str] = None) -> Optional[float]:
    """Summed seconds of the fit's spans of ``kind`` — only those with an
    ancestor of kind ``under`` where that is given.  None without a tree or
    without such a span."""
    if tree is None:
        return None
    hits = [e for e in tree["spans"] if e["kind"] == kind
            and (under is None or under in _ancestors(tree, e))]
    return sum(seconds(e) for e in hits) if hits else None


def sums_by_kind(tree: dict) -> Dict[str, float]:
    """Seconds by kind; a kind under ``model_performance`` is keyed
    ``score/<kind>`` (the entry's ``tree_matrix`` is not the scoring's)."""
    out: Dict[str, float] = {}
    for e in tree["spans"]:
        key = str(e["kind"])
        if "model_performance" in _ancestors(tree, e):
            key = "score/" + key
        out[key] = out.get(key, 0.0) + seconds(e)
    return out


def uncovered_seconds(tree: dict) -> float:
    """Wall of the fit's ``train`` span that lies under none of its leaf
    spans (spans with no child): what is left when every phase that has a
    name of its own is taken away — the glue of ``train``, ``_fit``,
    ``train_boosted`` and ``model_performance`` between their phases, and
    the part of ``tree_setup`` and ``bins_resident`` outside their
    children."""
    train = tree["train"]
    lo, hi = int(train["start_ns"]), int(train["ns"])
    leaves = sorted((max(int(e["start_ns"]), lo), min(int(e["ns"]), hi))
                    for e in tree["spans"]
                    if not tree["children"].get(e["span_id"]) and e is not train)
    covered, end = 0, lo
    for s, e in leaves:
        if e <= end:
            continue
        covered += e - max(s, end)
        end = e
    return (hi - lo - covered) / 1e9


def in_order(tree: dict) -> List[dict]:
    """The fit's spans depth first, each with its depth, start (s after the
    ``train`` span's) and seconds: the host half of ``tools/phase_table``."""
    t0 = int(tree["train"]["start_ns"])
    out: List[dict] = []

    def walk(event: Event, depth: int) -> None:
        extra = {k: v for k, v in event.items()
                 if k in ("trees", "rows", "hit", "bytes", "stop", "compiles",
                          "cache_loads", "compile_s")}
        out.append({"kind": event["kind"], "depth": depth,
                    "start_s": (int(event["start_ns"]) - t0) / 1e9,
                    "seconds": seconds(event), **extra})
        for kid in tree["children"].get(event["span_id"], ()):
            walk(kid, depth + 1)

    walk(tree["train"], 0)
    return out


# ---------------------------------------------------------------------------
# what the metric readers call


def _tree_of(fit: dict) -> Optional[dict]:
    from h2o3_tpu.util import timeline

    events = ring_events()
    check_reach(events, timeline.total_events(), timeline.CAPACITY,
                int(fit["t0_ns"]))
    return fit_tree(events, int(fit["t0_ns"]), int(fit["t1_ns"]))


def warmup_tree(run: dict) -> Optional[dict]:
    """The warm-up fit's span tree."""
    return _tree_of(run["warmup"])


def window_trees(run: dict) -> List[dict]:
    """The span trees of the window's fits (those that have one)."""
    trees = [_tree_of(s) for s in run["served"]]
    return [t for t in trees if t is not None]


def window_kind_seconds(run: dict, kind: str,
                        under: Optional[str] = None) -> Optional[float]:
    """Summed over the window's fits; None where none has such a span."""
    vals = [kind_seconds(t, kind, under) for t in window_trees(run)]
    vals = [v for v in vals if v is not None]
    return sum(vals) if vals else None
