"""The frontier levels of a window's trees: which levels they are (the
``tree_block`` spans' ``hist_slots`` mark a level past the node ladder
``frontier``) and their device seconds by phase (``lib/scopes.py``).  A
program without frontier levels, or without the spans, yields None."""

from __future__ import annotations

from typing import Dict, List, Optional

from . import scopes, spans


def frontier_levels(run: dict) -> Optional[List[int]]:
    """The levels the window's first ``tree_block`` span marks frontier."""
    for tree in spans.window_trees(run):
        for e in tree["spans"]:
            if e["kind"] == "tree_block" and e.get("hist_slots"):
                levels = [d for d, lv in enumerate(e["hist_slots"]) if lv[2] == "frontier"]
                return levels or None
    return None


def level_phases(run: dict) -> Optional[Dict[str, object]]:
    """{"levels": the frontier levels, "phases": their device seconds by
    phase, summed, "trees": the window's trees}; None without a trace."""
    levels = frontier_levels(run)
    if not levels:
        return None
    scoped = scopes.window_scopes(run)
    if scoped is None or not scoped["trees"]:
        return None
    phases: Dict[str, float] = {}
    for d in levels:
        for phase, s in scoped["levels"].get("L%02d" % d, {}).items():
            phases[phase] = phases.get(phase, 0.0) + s
    return {"levels": levels, "phases": phases, "trees": scoped["trees"]}
