"""Device seconds of the training blocks by the program's named scopes.

From ISSUE 28 on every level and phase of the block program carries a
``jax.named_scope`` (``L03/split``, ``sorted_prep``, ``grad`` ...).  The
scope is metadata of the compiled program, not of the trace: read by hand
(ISSUE 28, TPU v5 lite, jax 0.9.0) an event of the device plane's ``XLA
Ops`` line carries its HLO text as its name (``%fusion.928 = s32[...]
fusion(...)``) and three stats (``device_offset_ps``, ``device_duration_ps``,
``Time Scale Multiplier``), no ``op_name``.  So an event is mapped to its
scope through the compiled block's text: the block is lowered again from
its shapes for the attached devices (``programs.compile_block``, as
``programs.block_footprint`` lowers it; the persistent cache serves the
compile) and every instruction's ``metadata={op_name="..."}`` is read.  An
event that does carry the name as a stat (``tf_op`` / ``op_name``, as other
backends' planes do) is taken at its word.

* A fusion carries the scope of its root: XLA gives a fusion the metadata
  of the instruction it was grown from.  A fusion with no metadata of its
  own takes the most frequent scope of the instructions it calls.
* An instruction the compiler added (a layout ``copy``, a ``slice`` of a
  packed buffer, ``copy-done``) has no metadata: it takes the scope of the
  operand it reads, a few steps back; with none it is unscoped.
* A kernel is the ``pallas_call`` under ``hist_nodematmul`` /
  ``hist_factorized`` / ``hist_sorted``; what else runs under ``L<dd>/hist``
  (the stack of g, h and counts, their cast, the result's transpose) is
  ``hist_prep``.
* Time is self time: a ``%while`` keeps what its body does not account for
  (the bubbles between the operations of a tree), and that is unscoped.

Only operations inside executions of the ``jit_block_fn`` module (the
``XLA Modules`` line) inside the traced window are counted.  A program
without the scopes (the parent of ISSUE 28) yields ``None`` everywhere.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
BLOCK_MODULE = "jit_block_fn"
#: stats under which a backend may give an event's op_name itself
OP_NAME_STATS = ("tf_op", "op_name")

KERNELS = ("nodematmul", "factorized", "sorted")
#: the phases of a level, in the order of the program
LEVEL_PHASES = ("hist_nodes", "sorted_prep", "hist_prep", "kernel",
                "hist_psum", "subtract", "split", "route")
TREE_PHASES = ("grad", "sample", "margin", "leaf")
UNSCOPED = "unscoped"

_LEVEL = re.compile(r"(?:^|/)L(\d\d)/(hist_nodes|hist|subtract|split|route)(?:/|$)")
_KERNEL = re.compile(r"(?:^|/)hist_(%s)/" % "|".join(KERNELS))
#: a scope is one component of a path (an operation inside a call XLA did
#: not inline keeps a relative path, ``margin/add``); a bare ``margin`` is
#: the block's parameter of that name
_TREE = re.compile(r"(?:^|/)(%s)/|/(%s)$" % (("|".join(TREE_PHASES),) * 2))

Op = Tuple[str, float, float, Optional[str]]  # instruction, start_ns, dur_ns, op_name stat


def phase_of(op_name: Optional[str]) -> Tuple[Optional[int], str]:
    """(level or None, phase) of an operation from its ``op_name``."""
    if not op_name:
        return None, UNSCOPED
    level = _LEVEL.search(op_name)
    lvl = int(level.group(1)) if level else None
    if "/sorted_prep/" in op_name or op_name.endswith("/sorted_prep"):
        return lvl, "sorted_prep"
    if "hist_psum" in op_name:
        return lvl, "hist_psum"
    if _KERNEL.search(op_name) and op_name.endswith("pallas_call"):
        return lvl, "kernel"
    if level:
        phase = level.group(2)
        return lvl, "hist_prep" if phase == "hist" else phase
    tree = _TREE.search(op_name)
    if tree:
        return None, tree.group(1) or tree.group(2)
    return None, UNSCOPED


# ---------------------------------------------------------------------------
# the compiled text: instruction -> op_name

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def instruction_of(event_name: str) -> str:
    """``%fusion.928 = s32[..] fusion(..)`` -> ``fusion.928``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def scopes_from_text(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` for every instruction of a compiled
    module's text, with the two fallbacks of the module docstring for
    instructions that carry no metadata."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    inside: Dict[str, List[str]] = {}  # computation -> its instructions
    current = None
    for line in text.splitlines():
        if not line.startswith(" "):
            head = _COMPUTATION.match(line)
            current = head.group(1) if head else None
            if current is not None:
                inside[current] = []
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        if current is not None:
            inside[current].append(name)
        rest = line[m.end():]
        meta = _OP_NAME.search(rest)
        if meta:
            own[name] = meta.group(1)
        called = _CALLS.search(rest)
        if called:
            calls[name] = called.group(1)
        body = rest.split(", metadata=", 1)[0].split(", backend_config=", 1)[0]
        operands[name] = _OPERAND.findall(body.split("(", 1)[1] if "(" in body else "")

    def from_callee(name: str) -> Optional[str]:
        counts: Dict[str, int] = {}
        for inner in inside.get(calls.get(name, ""), ()):
            if inner in own and phase_of(own[inner])[1] != UNSCOPED:
                counts[own[inner]] = counts.get(own[inner], 0) + 1
        return max(counts, key=counts.get) if counts else None

    out: Dict[str, str] = {}

    def resolve(name: str, depth: int) -> Optional[str]:
        if name in out:
            return out[name]
        got = own.get(name)
        if got is None or phase_of(got)[1] == UNSCOPED:
            got = from_callee(name) or got
        if (got is None or phase_of(got)[1] == UNSCOPED) and depth > 0:
            for operand in operands.get(name, ())[:2]:
                if operand == name or operand == calls.get(name):
                    continue
                up = resolve(operand, depth - 1)
                if up is not None and phase_of(up)[1] != UNSCOPED:
                    got = up
                    break
        if got is not None:
            out[name] = got
        return got

    for name in operands:
        resolve(name, 4)
    return out


# ---------------------------------------------------------------------------
# the trace: self time of the blocks' operations


def self_times(ops: Sequence[Op]) -> List[Tuple[Op, float]]:
    """Each operation with the part of its duration (ns) that no operation
    nested in it accounts for."""
    out: List[Tuple[Op, float]] = []
    stack: List[List] = []  # [op, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            op, _, self_ns = stack.pop()
            out.append((op, max(self_ns, 0.0)))

    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(op[1])
        if stack:
            stack[-1][2] -= op[2]
        stack.append([op, op[1] + op[2], op[2]])
    close(float("inf"))
    return out


def by_scope(ops: Sequence[Op], blocks: Sequence[Tuple[float, float]],
             op_names: Dict[str, str]) -> Optional[dict]:
    """Self seconds of the operations inside ``blocks`` (start, end in ns)
    by phase, by (level, phase) and, for what no scope reaches, by
    instruction.  None where no operation lies under a level's scope: a
    program from before the scopes."""
    inside = [op for op in ops
              if any(lo <= op[1] and op[1] + op[2] <= hi for lo, hi in blocks)]
    phases: Dict[str, float] = {}
    levels: Dict[str, Dict[str, float]] = {}
    loose: Dict[str, float] = {}
    for op, self_ns in self_times(inside):
        lvl, phase = phase_of(op[3] or op_names.get(op[0]))
        s = self_ns / 1e9
        phases[phase] = phases.get(phase, 0.0) + s
        if lvl is not None:
            row = levels.setdefault("L%02d" % lvl, {})
            row[phase] = row.get(phase, 0.0) + s
        if phase == UNSCOPED:
            loose[op[0]] = loose.get(op[0], 0.0) + s
    if not levels:
        return None
    return {"busy_s": sum(phases.values()), "phases": phases, "levels": levels,
            "unscoped_ops": sorted(loose.items(), key=lambda kv: -kv[1])[:20],
            "blocks": len(blocks)}


def read_trace(trace_dir: str, marker: str) -> Optional[dict]:
    """From the newest ``.xplane.pb`` under ``trace_dir``: the first device
    plane's operations, and the executions of the block module inside the
    host span named ``marker``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    data = ProfileData.from_file(files[-1])
    ops: List[Op] = []
    modules: List[Tuple[str, float, float]] = []
    spans: List[dict] = []
    window = None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE) and not ops:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    named = None  # does this plane give op names as a stat?
                    for ev in line.events:
                        stat = None
                        if named is not False:
                            stats = dict(ev.stats)
                            stat = next((str(stats[k]) for k in OP_NAME_STATS
                                         if k in stats), None)
                            if named is None:
                                named = stat is not None
                        ops.append((instruction_of(ev.name), ev.start_ns,
                                    ev.duration_ns, stat))
                elif line.name == MODULES_LINE:
                    modules = [(ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("$"):
                        continue  # a Python call
                    if ev.name == marker and (window is None or ev.duration_ns > window[1]):
                        window = (ev.start_ns, ev.duration_ns)
                    stats = dict(ev.stats)
                    if "span_id" in stats:  # a telemetry.Span's annotation
                        spans.append({"kind": ev.name, "start_ns": ev.start_ns,
                                      "ns": ev.start_ns + ev.duration_ns,
                                      "span_id": str(stats["span_id"]),
                                      "trace_id": str(stats.get("trace_id", "")),
                                      "parent_id": str(stats.get("parent_id", "")) or None})
    if window is None or not ops:
        return None
    lo, hi = window[0], window[0] + window[1]
    blocks = [(s, s + d) for name, s, d in modules
              if name.startswith(BLOCK_MODULE) and lo <= s and s + d <= hi]
    return {"ops": ops, "blocks": blocks, "file": files[-1],
            "window": (lo, hi), "spans": spans}


# ---------------------------------------------------------------------------
# the compiled block's text, lowered again from the configuration's shapes


def _config_of(root: str, cell: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = next(w["config"] for w in bench["workloads"] if w["name"] == cell)
    path = next(c["file"] for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(root, path)) as f:
        return json.load(f)


def block_text(spec: dict, rows: int, features: int, block: int,
               devices: Sequence) -> str:
    """The compiled text of the training block for ``devices``: the lowering
    ``programs.block_footprint`` reads the sizes of, so the same program as
    the window ran and the same instruction names.  ``spec`` is
    ``programs.block_spec`` of a fitted model (a run's ``block_program``)."""
    from . import programs

    return programs.compile_block(spec, rows, features, block, devices)[0].as_text()


# ---------------------------------------------------------------------------
# what the metric readers call: one parse a run, memoised

_MEMO: Dict[str, Optional[dict]] = {}


def window_scopes(run: dict, root: Optional[str] = None,
                  marker: str = "timed_window") -> Optional[dict]:
    """The by-scope sums of the traced window's blocks, or None: no trace,
    no block in it, a program without scopes, or a block that cannot be
    lowered again (a note goes to standard error)."""
    import sys
    import time

    if not run.get("trace"):
        return None
    root = root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    trace_dir = os.path.join(root, ".bench_trace")
    key = trace_dir + "|" + run["cell"]
    if key in _MEMO:
        return _MEMO[key]
    t0 = time.time()
    result = None
    traced = read_trace(trace_dir, marker)
    t_parse = time.time() - t0
    if traced and traced["blocks"]:
        op_names: Dict[str, str] = {}
        if not any(op[3] for op in traced["ops"]):
            try:
                import jax
                from h2o3_tpu.models.tree.booster import tree_block_size

                op_names = scopes_from_text(block_text(
                    run["block_program"], run["rows"], run["features"],
                    tree_block_size(), jax.devices()))
            except Exception as e:  # the program's internals moved
                print(f"note: the training block could not be lowered again "
                      f"({e!r}); the by-scope device metrics are left out",
                      file=sys.stderr)
                traced = None
        if traced:
            result = by_scope(traced["ops"], traced["blocks"], op_names)
    if result is not None:
        trees = sum(b["trees"] for s in run["served"] for b in s["blocks"])
        result["trees"] = trees
        result["parse_s"] = t_parse
        result["total_s"] = time.time() - t0
        print(f"scopes: second parse of the trace {t_parse:.2f} s, with the "
              f"block's text {result['total_s']:.2f} s", file=sys.stderr)
    _MEMO[key] = result
    return result


def ms_per_tree(run: dict, phases: Iterable[str]) -> Optional[float]:
    """Device ms a tree under ``phases`` in the window's blocks; 0.0 where
    the program has scopes and none of these ran."""
    scoped = window_scopes(run)
    if scoped is None or not scoped["trees"]:
        return None
    return 1e3 * sum(scoped["phases"].get(p, 0.0) for p in phases) / scoped["trees"]
