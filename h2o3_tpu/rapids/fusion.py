"""Rapids query fusion — compile munging pipelines into one jitted dispatch.

The evaluator in runtime.py executes op-at-a-time on host numpy: every prim
materializes a full intermediate Frame and never touches XLA, so a 10-op
pipeline pays 10 allocations plus 10 interpreter round-trips. This pass makes
the move XLA itself makes for elementwise chains (and DrJAX makes for placed
building blocks): before interpreting a prim application, greedily cover the
maximal subtree of *fusible* ops rooted there (h2o3_tpu/rapids/prims.FUSIBLE:
arithmetic/comparison/logical operators, bit-exact per-row math, per-row
mungers, trailing reducers), lower it to a single column-program, and dispatch
it as ONE jitted ``map_batches`` call.

Pipeline per candidate region:

1. **Scan** (static, no evaluation): walk the AST from the fusible root;
   non-fusible children become region *leaves* in depth-first argument order —
   exactly the order the interpreter would evaluate them.
2. **Leaf evaluation**: each leaf AST evaluates once through the normal
   evaluator (nested fusible regions inside a leaf fuse recursively).
3. **Plan lookup**: the compiled plan is memoized in the dispatch plan cache
   (:func:`h2o3_tpu.compute.mapreduce.plan_memo`) keyed on the subtree's
   canonical S-expression + the leaf schema, so a repeated pipeline compiles
   nothing.
4. **Lowering** (on miss): replicate ``binop_frame``'s broadcasting and
   naming rules symbolically, producing one expression per output column over
   column references and scalar slots. Literal-only scalar subexpressions fold
   on the host THROUGH the registered prims (exact by construction).
5. **Dispatch**: referenced columns resolve through the PR 3 devcache as
   float64 ``FrameTable``s keyed on per-Column version stamps (an unmutated
   frame re-uploads nothing), merge into one table, and run under
   ``jax.enable_x64`` so device arithmetic is true float64.
   Trailing reducers run as a host epilogue through their registered prim.

Anything the lowering cannot prove bit-identical — string/categorical
semantics, 1-row broadcasts, computed selectors, runtime type surprises —
raises :class:`_Unfusible` and the region *replays* through the same prim
functions on the already-evaluated leaf values: no double evaluation, and
results (including raised errors) match the interpreter exactly.

Env knobs: ``H2O3_TPU_RAPIDS_FUSION=0`` kills the pass entirely (the
evaluator is then byte-for-byte today's interpreter);
``H2O3_TPU_RAPIDS_FUSION_MIN_OPS`` (default 2) is the minimum fused-op count
worth a device round-trip.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp
from jax import enable_x64

from h2o3_tpu.compute.mapreduce import (
    FrameTable,
    gather_rows,
    map_batches,
    plan_memo,
)
from h2o3_tpu.frame.devcache import region_token
from h2o3_tpu.frame.frame import Column, ColType, Frame
from h2o3_tpu.parallel.mesh import default_mesh
from h2o3_tpu.rapids.parser import (
    AstExec,
    AstId,
    AstNum,
    AstNumList,
    AstStr,
    AstStrList,
    canonical_sexpr,
)
from h2o3_tpu.rapids.prims import FUSIBLE, PRIMS
from h2o3_tpu.rapids.runtime import Val, eval_ast
from h2o3_tpu.util import telemetry

_FUSION = telemetry.counter(
    "rapids_fusion_total",
    "fusion pass outcome per candidate region (fused = one compiled "
    "dispatch, fallback = replayed through the interpreter prims)",
    labels=("result",),
)
_FUSED_OPS = telemetry.histogram(
    "rapids_fused_ops",
    "prims folded into one fused column-program",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32),
)
_EVAL = telemetry.histogram(
    "rapids_eval_seconds",
    "end-to-end rapids expression evaluation wall time",
    labels=("path",),
)


def enabled() -> bool:
    """Fusion kill switch: H2O3_TPU_RAPIDS_FUSION=0 reproduces the
    pre-fusion interpreter exactly (the pass is a pre-dispatch hook)."""
    return os.environ.get("H2O3_TPU_RAPIDS_FUSION", "1").lower() not in (
        "0", "false", "off")


def min_ops() -> int:
    """Minimum fusible ops a region must cover to be worth one dispatch."""
    try:
        return max(1, int(os.environ.get("H2O3_TPU_RAPIDS_FUSION_MIN_OPS", 2)))
    except ValueError:
        return 2


# ---------------------------------------------------------------------------
# per-eval path accounting (exec_rapids brackets each expression)

_tls = threading.local()


def begin_eval() -> None:
    _tls.fused = False


def observe_eval(seconds: float) -> None:
    path = "fused" if getattr(_tls, "fused", False) else "interpreted"
    _EVAL.observe(seconds, path=path)


class _Unfusible(Exception):
    """Region cannot be compiled bit-identically — replay it instead."""


#: negative plan-cache sentinel: this (sexpr, schema) can never fuse
_UNFUSIBLE_PLAN = "unfusible"

#: AST children the scanner descends into, per fuse kind (remaining args —
#: round digits, cols selectors — are static and handled by the lowering)
_SCAN_ARITY = {"binop": 2, "uniop": 1, "ifelse": 3, "select": 1, "reduce": 1}
_DEFAULT_ARITY = {"binop": 2, "uniop": 1, "ifelse": 3}


# ---------------------------------------------------------------------------
# phase 1: static region scan


def _node_spec(node, root: bool):
    """FuseSpec if ``node`` is a fusible application, else None (leaf)."""
    if not (isinstance(node, AstExec) and isinstance(node.op, AstId)):
        return None
    spec = FUSIBLE.get(node.op.name)
    if spec is None:
        return None
    if spec.kind == "reduce" and not root:
        # interior reducers produce scalars; they stay interpreter leaves
        # (their own argument chain still fuses when the leaf evaluates)
        return None
    if spec.fuse_args is not None:
        if not spec.fuse_args(node.args):
            return None
    elif len(node.args) != _DEFAULT_ARITY.get(spec.kind, -1):
        return None
    return spec


def _scan(node, leaves: List, seen: set) -> int:
    """Fused-op count under ``node``; leaves collect in DFS arg order."""
    if isinstance(node, AstNum):
        return 0
    spec = _node_spec(node, root=False)
    if spec is None:
        if id(node) not in seen:
            seen.add(id(node))
            leaves.append(node)
        return 0
    n = 1
    for child in node.args[: _SCAN_ARITY[spec.kind]]:
        n += _scan(child, leaves, seen)
    return n


# ---------------------------------------------------------------------------
# phase 2: lowering — symbolic column sets replicating binop_frame exactly
#
# Column expressions (plain tuples, safe to close over and hash-print):
#   ("lit", v)            — float literal, baked into the plan key
#   ("sval", k)           — k-th runtime scalar leaf, passed as a traced arg
#   ("colref", li, name)  — column ``name`` of frame leaf ``li``
#   ("emit", prim, *xs)   — FUSIBLE[prim].emit(jnp, *xs)


class _C:
    """One symbolic column: name + expression + leaf-type flags."""

    __slots__ = ("name", "expr", "is_cat", "is_str")

    def __init__(self, name, expr, is_cat=False, is_str=False):
        self.name = name
        self.expr = expr
        self.is_cat = is_cat
        self.is_str = is_str

    def numeric(self):
        # the analogue of util.numeric_data: string columns cannot enter
        # numeric compute (the interpreter raises; we fall back and let it)
        if self.is_str:
            raise _Unfusible
        return self.expr


class _Cols:
    __slots__ = ("cols",)

    def __init__(self, cols):
        self.cols = cols


class _Scalar:
    __slots__ = ("expr",)

    def __init__(self, expr):
        self.expr = expr


def _fold(name: str, scalars: List[float]) -> float:
    """Host-fold a literal-only application through the registered prim —
    identical to the interpreter's scalar path by construction."""
    out = PRIMS[name](None, [Val.num(s) for s in scalars])
    return float(out.as_num())


def _leaf_schema(v: Val) -> Tuple:
    if v.kind == Val.FRAME:
        fr = v.value
        lay = getattr(fr, "chunk_layout", None)
        if lay is not None and getattr(fr, "_materialized", None) is None:
            # chunk-homed and unmaterialized: the layout already knows the
            # schema — inspecting it must not trigger a gather
            cols = tuple(
                (n,
                 1 if t in (ColType.STR, ColType.UUID) else
                 2 if t is ColType.CAT else 0)
                for n, t in zip(lay["column_names"], lay["column_types"]))
            return ("frame",) + cols
        cols = tuple(
            (c.name,
             1 if c.type in (ColType.STR, ColType.UUID) else
             2 if c.type is ColType.CAT else 0)
            for c in fr.columns)
        return ("frame",) + cols
    if v.kind == Val.NUM:
        return ("num",)
    if v.kind == Val.NUMS:
        return ("num",) if len(v.value) == 1 else ("nums", len(v.value))
    return ("other", v.kind)


class _Plan:
    __slots__ = ("static", "out_names", "outputs", "dev_exprs", "refs",
                 "sval_leaves", "lit_vals", "reduce_name", "fn",
                 "validated_token")

    def __init__(self):
        self.static = None          # folded scalar result, or None
        self.out_names = ()         # output column names
        self.outputs = ()           # ("host", li, name) | ("dev", k)
        self.dev_exprs = ()         # computed column expressions
        self.refs = ()              # ordered unique (li, name) device inputs
        self.sval_leaves = ()       # leaf indices feeding scalar slots
        self.lit_vals = ()          # literal constants fed as runtime scalars
        self.reduce_name = None     # host-epilogue reducer prim, if any
        self.fn = None              # the traceable program (stable identity)
        self.validated_token = None  # region_token of last validated inputs


def _build_plan(node, leaf_idx_by_id: Dict[int, int],
                schemas: Tuple) -> "_Plan":
    sval_slots: Dict[int, int] = {}
    for i, sch in enumerate(schemas):
        if sch == ("num",):
            sval_slots[i] = len(sval_slots)

    def leaf_cols(idx: int) -> "_Cols":
        sch = schemas[idx]
        names = [name for name, _tc in sch[1:]]
        if len(set(names)) != len(names):
            raise _Unfusible  # by-name column refs need unique names
        return _Cols([
            _C(name, ("colref", idx, name), is_cat=tc == 2, is_str=tc == 1)
            for name, tc in sch[1:]
        ])

    def branch01(v):
        """ifelse branch: scalar expr, or col(0) of a frame (the prim
        always takes column 0 regardless of width)."""
        if isinstance(v, _Scalar):
            return v.expr, False
        c = v.cols[0]
        return c.numeric(), c.is_cat

    def low(n, root=False):
        if isinstance(n, AstNum):
            return _Scalar(("lit", float(n.value)))
        idx = leaf_idx_by_id.get(id(n))
        if idx is not None:
            sch = schemas[idx]
            if sch[0] == "frame":
                return leaf_cols(idx)
            if sch == ("num",):
                return _Scalar(("sval", sval_slots[idx]))
            raise _Unfusible
        spec = _node_spec(n, root=root)
        if spec is None:  # scanner invariant: every non-leaf is fusible
            raise _Unfusible
        name = n.op.name
        if spec.kind == "reduce":
            child = low(n.args[0])
            return ("reduce", name, child)
        if spec.kind == "select":
            a = low(n.args[0])
            if isinstance(a, _Scalar):
                raise _Unfusible  # as_frame coercion of scalars: fall back
            return _Cols([a.cols[j] for j in _sel_indices(a, n.args[1])])
        if spec.kind == "uniop":
            a = low(n.args[0])
            if isinstance(a, _Scalar):
                if a.expr[0] == "lit":
                    return _Scalar(("lit", _fold(name, [a.expr[1]])))
                return _Scalar(("emit", name, a.expr))
            return _Cols([
                _C(c.name, ("emit", name, c.numeric())) for c in a.cols
            ])
        if spec.kind == "ifelse":
            t = low(n.args[0])
            y = low(n.args[1])
            z = low(n.args[2])
            for b in (y, z):
                if not isinstance(b, (_Scalar, _Cols)):
                    raise _Unfusible
            if isinstance(t, _Scalar):
                if t.expr[0] != "lit":
                    raise _Unfusible
                # (ifelse scalar y n): branch VALUE selection; NaN tests
                # true (nan != 0) exactly like the interpreter's as_num path
                return y if t.expr[1] != 0 else z
            ye, ycat = branch01(y)
            ze, zcat = branch01(z)
            if ycat and zcat:
                # both branches categorical: the interpreter may preserve a
                # shared domain — a non-NUM output shape we never fuse
                raise _Unfusible
            return _Cols([
                _C(tc.name, ("emit", name, tc.numeric(), ye, ze))
                for tc in t.cols
            ])
        # binop — replicate binop_frame's pairing and naming byte-for-byte
        a = low(n.args[0])
        b = low(n.args[1])
        if isinstance(a, _Scalar) and isinstance(b, _Scalar):
            if a.expr[0] == "lit" and b.expr[0] == "lit":
                return _Scalar(
                    ("lit", _fold(name, [a.expr[1], b.expr[1]])))
            return _Scalar(("emit", name, a.expr, b.expr))
        if isinstance(a, _Cols) and isinstance(b, _Scalar):
            return _Cols([
                _C(c.name, ("emit", name, c.numeric(), b.expr))
                for c in a.cols
            ])
        if isinstance(a, _Scalar) and isinstance(b, _Cols):
            return _Cols([
                _C(c.name, ("emit", name, a.expr, c.numeric()))
                for c in b.cols
            ])
        na, nb = len(a.cols), len(b.cols)
        if na == nb:
            pairs = zip(a.cols, b.cols)
        elif nb == 1:
            pairs = ((x, b.cols[0]) for x in a.cols)
        elif na == 1:
            pairs = ((a.cols[0], y) for y in b.cols)
        else:
            raise _Unfusible  # interpreter raises; the fallback will too
        return _Cols([
            _C(x.name, ("emit", name, x.numeric(), y.numeric()))
            for x, y in pairs
        ])

    plan = _Plan()
    res = low(node, root=True)
    if isinstance(res, tuple) and res[0] == "reduce":
        plan.reduce_name = res[1]
        res = res[2]
        if isinstance(res, _Scalar):
            # (reduce scalar) is the scalar itself (interpreter: as_num)
            if res.expr[0] == "lit":
                plan.static = res.expr[1]
                return plan
            raise _Unfusible
    if isinstance(res, _Scalar):
        if res.expr[0] == "lit":
            plan.static = res.expr[1]
            return plan
        raise _Unfusible  # pure-scalar chains: host interpreter is exact
    outputs: List[Tuple] = []
    dev_exprs: List[Tuple] = []
    for c in res.cols:
        if c.expr[0] == "colref":
            # bare pass-through: reuse the host Column object (type, domain
            # and aliasing identical to the interpreter's cols path)
            outputs.append(("host", c.expr[1], c.expr[2]))
        else:
            outputs.append(("dev", len(dev_exprs)))
            dev_exprs.append(c.expr)
    plan.out_names = tuple(c.name for c in res.cols)
    plan.outputs = tuple(outputs)
    # literals become runtime scalar slots, NEVER traced constants: XLA's
    # algebraic simplifier folds constant patterns like x + 0.0 -> x, which
    # flips the sign of zero (-0.0 + 0.0 is +0.0 in IEEE) — with the value
    # unknown at trace time no such folding can fire. The plan key already
    # pins the literal values via the canonical S-expression.
    dev_exprs, lit_vals = _externalize_lits(dev_exprs, len(sval_slots))
    plan.lit_vals = tuple(lit_vals)
    plan.dev_exprs = tuple(dev_exprs)
    refs: Dict[Tuple[int, str], None] = {}

    def walk(e):
        if e[0] == "colref":
            refs.setdefault((e[1], e[2]))
        elif e[0] == "emit":
            for x in e[2:]:
                walk(x)

    for e in dev_exprs:
        walk(e)
    plan.refs = tuple(refs)
    plan.sval_leaves = tuple(sorted(sval_slots, key=sval_slots.get))
    if dev_exprs:
        plan.fn = _make_fn(plan.dev_exprs)
    return plan


def _externalize_lits(exprs: List[Tuple], base_slot: int):
    """Rewrite every ("lit", v) into a fresh ("sval", slot) past the leaf
    slots, returning the rewritten exprs and the literal values in slot
    order."""
    lits: List[float] = []

    def sub(e):
        if e[0] == "lit":
            slot = base_slot + len(lits)
            lits.append(e[1])
            return ("sval", slot)
        if e[0] == "emit":
            return ("emit", e[1]) + tuple(sub(x) for x in e[2:])
        return e

    return [sub(e) for e in exprs], lits


def _sel_indices(a: "_Cols", sel) -> List[int]:
    """Static column selection, replicating util.col_indices; any
    out-of-range/unknown selector falls back so the interpreter raises."""
    names = [c.name for c in a.cols]
    if isinstance(sel, AstStr):
        picks = [sel.value]
    elif isinstance(sel, AstStrList):
        picks = list(sel.values)
    else:
        vals = [sel.value] if isinstance(sel, AstNum) else list(sel.values)
        out = []
        for v in vals:
            j = int(np.int64(v))
            if j < 0:
                j += len(names)
            if not 0 <= j < len(names):
                raise _Unfusible
            out.append(j)
        return out
    try:
        return [names.index(s) for s in picks]
    except ValueError:
        raise _Unfusible


def _akey(li: int, name: str) -> str:
    return f"{li}:{name}"


def _make_fn(dev_exprs: Tuple, decode: Tuple = ()):
    """The jitted column-program. ONE closure per cached plan: map_batches
    keys its shard_map plan on this function's identity, so a warm repeat
    re-traces and re-compiles nothing.

    ``decode`` maps column-ref akeys to chunk-codec decode specs
    (frame/codecs.py group reps) so ENCODED columns feed the program as
    packed codes with the decode arithmetic emitted INTO the trace — XLA
    fuses decompress-into-compute and no dense host copy ever exists:

    - ``("affine", off_slot, scale_slot, sentinel)`` — the input array
      holds u16 codes; decode is ``off + codes.astype(f64) * scale``
      (offset/scale as TRACED scalar slots, never baked constants — the
      _externalize_lits signed-zero rule applies to decode params too)
      with sentinel codes mapping to NaN;
    - ``("dict", table_slot)`` — u16 codes gather into a replicated
      unique-value table riding as a trailing map_batches arg (bit-exact
      by construction);
    - ``("const", val_slot)`` — the column never ships: its value
      broadcasts from a scalar slot;
    - ``("f32",)`` — f32 storage widens in-trace (exact by selection);
    - absent / ``("dense",)`` — the array is plain f64."""
    dec = dict(decode)

    def fused_program(arrays, mask, *svals):
        def col(li, name):
            akey = _akey(li, name)
            spec = dec.get(akey)
            if spec is None or spec[0] == "dense":
                return arrays[akey]
            kind = spec[0]
            if kind == "f32":
                return arrays[akey].astype(jnp.float64)
            if kind == "const":
                return jnp.full(mask.shape, svals[spec[1]],
                                dtype=jnp.float64)
            if kind == "affine":
                c = arrays[akey]
                x = svals[spec[1]] + c.astype(jnp.float64) * svals[spec[2]]
                return jnp.where(c == spec[3], jnp.nan, x)
            if kind == "dict":
                return jnp.take(svals[spec[1]], arrays[akey])
            raise ValueError(f"unknown decode spec {kind!r}")

        def ev(e):
            tag = e[0]
            if tag == "lit":
                return e[1]
            if tag == "sval":
                return svals[e[1]]
            if tag == "colref":
                return col(e[1], e[2])
            spec = FUSIBLE[e[1]]
            return spec.emit(jnp, *[ev(x) for x in e[2:]])

        return tuple(ev(e) for e in dev_exprs)

    return fused_program


# ---------------------------------------------------------------------------
# phase 3: dispatch


def _execute(plan: "_Plan", leaf_vals: List[Val], env) -> Val:
    if plan.static is not None:
        return Val.num(plan.static)
    used: Dict[int, None] = {}
    for kind, *rest in plan.outputs:
        if kind == "host":
            used.setdefault(rest[0])
    for li, _name in plan.refs:
        used.setdefault(li)
    frames = {li: leaf_vals[li].value for li in used}
    nrows = {fr.nrows for fr in frames.values()}
    if len(nrows) != 1 or 0 in nrows:
        raise _Unfusible  # mixed row counts = 1-row broadcasts: interpreter
    n_valid = next(iter(nrows))
    ref_lis = list(dict.fromkeys(li for li, _ in plan.refs))
    by_leaf = {li: [n for j, n in plan.refs if j == li] for li in ref_lis}
    rtok = region_token([(frames[li], by_leaf[li]) for li in ref_lis])
    if rtok is None or rtok != plan.validated_token:
        for li, name in plan.refs:
            if frames[li].col(name).type in (ColType.STR, ColType.UUID):
                raise _Unfusible
        plan.validated_token = rtok
    dev_host: List[np.ndarray] = []
    if plan.dev_exprs:
        svals = [float(leaf_vals[li].as_num()) for li in plan.sval_leaves]
        svals += list(plan.lit_vals)
        mesh = default_mesh()
        # float64 end-to-end: the interpreter computes in host float64, so
        # the device program must too — scoped here, not process-global,
        # so float32 model paths keep their dtype
        with enable_x64():
            merged: Dict[str, Any] = {}
            mask = None
            for li in ref_lis:
                t = FrameTable.from_frame(
                    frames[li], columns=by_leaf[li], mesh=mesh,
                    dtype=jnp.float64, cache=True)
                for name in by_leaf[li]:
                    merged[_akey(li, name)] = t.arrays[name]
                mask = t.mask
            table = FrameTable(merged, mask, n_valid, mesh)
            outs = map_batches(plan.fn, table, *svals)
        dev_host = [gather_rows(o, n_valid).copy() for o in outs]
    cols = []
    for name, out in zip(plan.out_names, plan.outputs):
        if out[0] == "host":
            cols.append(frames[out[1]].col(out[2]))
        else:
            cols.append(Column(name, dev_host[out[1]], ColType.NUM))
    result = Frame(cols)
    if plan.reduce_name is not None:
        return PRIMS[plan.reduce_name](env, [Val.frame(result)])
    return Val.frame(result)


# ---------------------------------------------------------------------------
# fallback: replay the region through the interpreter prims


def _replay(node, env, leaf_val_by_id: Dict[int, Val]) -> Val:
    v = leaf_val_by_id.get(id(node))
    if v is not None:
        return v
    if isinstance(node, AstExec):
        args = [_replay(a, env, leaf_val_by_id) for a in node.args]
        return PRIMS[node.op.name](env, args)
    return eval_ast(node, env)  # literals / static selector args


# ---------------------------------------------------------------------------
# entry point


def try_fuse(node: AstExec, env) -> Optional[Val]:
    """Attempt to execute ``node`` as one fused dispatch.

    Returns the result Val, or None when the node is not a worthwhile
    region root (the caller then interprets it normally). Leaf subtrees are
    evaluated exactly once in interpreter order; any lowering or dispatch
    failure replays the region over those values through the same prim
    functions, so results — and raised errors — match the interpreter."""
    if not enabled():
        return None
    spec = _node_spec(node, root=True)
    if spec is None:
        return None
    leaves: List = []
    seen: set = set()
    n_ops = 1
    for child in node.args[: _SCAN_ARITY[spec.kind]]:
        n_ops += _scan(child, leaves, seen)
    small = n_ops < min_ops()
    from h2o3_tpu.rapids import dist_exec as _dist

    if small and not _dist.peek_dist(leaves, env):
        # below the device-dispatch threshold and nothing chunk-homed in
        # sight: not worth a round-trip, interpret normally
        return None
    leaf_vals = [eval_ast(leaf, env) for leaf in leaves]
    dist = _dist.try_dist(node, leaves, leaf_vals, env)
    if dist is not None:
        _FUSION.inc(result="fused")
        _FUSED_OPS.observe(n_ops)
        _tls.fused = True
        return dist
    if small:
        # the DistFrame declined to ship (or the region is unfusible):
        # replay over the once-evaluated leaves — the interpreter path,
        # minus a second leaf evaluation
        return _replay(node, env, {id(l): v for l, v in zip(leaves,
                                                            leaf_vals)})
    try:
        schemas = tuple(_leaf_schema(v) for v in leaf_vals)
        key = (canonical_sexpr(node), schemas)
        leaf_idx_by_id = {id(leaf): i for i, leaf in enumerate(leaves)}

        def build():
            try:
                return _build_plan(node, leaf_idx_by_id, schemas)
            except _Unfusible:
                return _UNFUSIBLE_PLAN

        plan = plan_memo("rapids_fusion", key, build)
        if plan == _UNFUSIBLE_PLAN:
            raise _Unfusible
        result = _execute(plan, leaf_vals, env)
    except Exception:
        # correctness over cleverness: ANY fused-path failure replays the
        # region through the interpreter prims on the already-evaluated
        # leaves (genuine user errors re-raise from there, identically)
        _FUSION.inc(result="fallback")
        return _replay(node, env, {id(l): v for l, v in zip(leaves, leaf_vals)})
    _FUSION.inc(result="fused")
    _FUSED_OPS.observe(n_ops)
    _tls.fused = True
    return result
