"""Distributed Rapids — ship fused column programs to chunk homes.

The fusion pass (rapids/fusion.py) compiles a munging pipeline into one
column program; this module moves that program to the data instead of the
data to the program.  When every frame leaf of a fused region is an
unmaterialized chunk-homed :class:`~h2o3_tpu.cluster.frames.DistFrame` on
ONE layout, the region's canonical S-expression + leaf schemas (tiny,
like PR 15's ``__dist__`` frame reference) ship to each chunk home as a
``rapids_exec`` ctx-DTask.  Each home rebuilds the plan out of its own
mapreduce plan cache (:func:`plan_memo` — a warm op compiles nothing
home-side), assembles its group's columns through the devcache-resident
chunk path, runs the same jitted ``map_batches`` program the local pass
would, and either

* returns a tiny **reducer partial** (trailing-reducer regions — the
  caller merges partials in canonical home order, the ``mr_chunks``
  shape), or
* writes the derived columns straight back to the ring as **new
  chunk-homed vectors on the same layout** (same ESPC bounds, same
  homes, replicated ×``H2O3_TPU_CHUNK_REPLICAS``) and returns only the
  new layout arithmetic — ``:=`` assignment, filters, and column
  pipelines never move row data.

Recovery rides the chunk-home ladder exactly like ``mr_chunks``:
home → ring-successor replica → any survivor → caller-local execution
from replica chunks (``cluster_fanout_recovered_total{path=...}``).
Results stay bit-identical to the local interpreter (uint64 views,
both-NaN exempt): home arithmetic is the identical float64 program over
the identical chunk bytes, and partial merging is restricted to the
reduction shapes whose regrouping is IEEE-exact for the values involved
(min/max always; sum/mean/prod partials are combined with the same numpy
reduction the interpreter applies).  Anything else — unfusible regions,
mixed layouts, string outputs, row-subset assigns — declines and falls
back to the exact gather path: correctness never depends on fusibility.

Env knobs: ``H2O3_TPU_RAPIDS_DIST=0`` kills the pass (every DistFrame
eval gathers, today's behavior); ``H2O3_TPU_RAPIDS_DIST_TIMEOUT``
(seconds, default 120) bounds each per-group RPC before the ladder
moves to the next rung.
"""

from __future__ import annotations

import os
import pickle
import threading
import uuid
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from jax import enable_x64

from h2o3_tpu.cluster import frames as _frames
from h2o3_tpu.cluster import rpc as _rpc
from h2o3_tpu.cluster.dkv import MAX_REPLICAS
from h2o3_tpu.compute.mapreduce import FrameTable, gather_rows, map_batches, \
    plan_memo
from h2o3_tpu.frame import codecs as _codecs
from h2o3_tpu.frame import devcache as _devcache
from h2o3_tpu.frame.frame import ColType, NA_CAT
from h2o3_tpu.parallel.mesh import default_mesh, row_mask, shard_rows
from h2o3_tpu.rapids import fusion as _fusion
from h2o3_tpu.rapids.parser import AstId, canonical_sexpr
from h2o3_tpu.rapids.runtime import Val
from h2o3_tpu.util import flight as _flight
from h2o3_tpu.util import ledger as _ledger
from h2o3_tpu.util import telemetry

_DIST = telemetry.counter(
    "rapids_dist_total",
    "distributed-Rapids dispatch outcome per eligible region: dist = "
    "executed on the chunk homes (only sexpr out, partials/layout back), "
    "fallback = a distributed attempt failed mid-flight and the region "
    "re-ran on the exact gather path, gather = a DistFrame was present "
    "but the region could not ship (unfusible, mixed layouts, string "
    "outputs, no ring)",
    labels=("result",),
)
_PARTIAL_BYTES = telemetry.counter(
    "rapids_dist_partial_bytes_total",
    "bytes of reducer partials and layout arithmetic returned by chunk "
    "homes to the Rapids caller — the entire data-plane response of a "
    "distributed eval (compare against the frame bytes a gather would "
    "have moved)",
)


def enabled() -> bool:
    """Kill switch: H2O3_TPU_RAPIDS_DIST=0 makes every DistFrame eval
    gather through the store exactly as before this pass existed."""
    return os.environ.get("H2O3_TPU_RAPIDS_DIST", "1").lower() not in (
        "0", "false", "off")


def dist_timeout() -> float:
    """Per-group RPC deadline (H2O3_TPU_RAPIDS_DIST_TIMEOUT seconds)
    before the recovery ladder tries the next rung."""
    try:
        return float(os.environ.get("H2O3_TPU_RAPIDS_DIST_TIMEOUT", "120"))
    except ValueError:
        return 120.0


class _NotDistributable(Exception):
    """Region cannot ship — fall straight back to the gather path."""


class _NonBinary(Exception):
    """A filter selector turned out not to be a 0/1 mask home-side."""


#: trailing reducers whose partial/merge regrouping this module implements
#: (the full fusible set); anything else declines to the gather path
_RFNS = {"max": np.max, "maxNA": np.max, "min": np.min, "minNA": np.min,
         "sum": np.sum, "sumNA": np.sum, "prod": np.prod, "prodNA": np.prod}
_DIST_REDUCERS = frozenset(_RFNS) | {"mean"}


def _na_rm(reduce_name: str) -> bool:
    # mirror of prims.reducers._reduce's default na_rm resolution for the
    # fusible reducers (mean strips NAs; the NA-suffixed variants do too)
    return reduce_name.lower().endswith("na") or reduce_name == "mean"


def _is_dist(fr) -> bool:
    """An unmaterialized chunk-homed frame — the only shape worth
    shipping to (a materialized one already paid the gather)."""
    return (fr is not None
            and getattr(fr, "chunk_layout", None) is not None
            and getattr(fr, "_materialized", None) is None)


def _aligned(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    """Same row partitioning AND same homes: derived columns land beside
    their sources and per-group execution sees aligned row ranges."""
    if a is b:
        return True
    if [int(e) for e in a["espc"]] != [int(e) for e in b["espc"]]:
        return False
    ga, gb = a["groups"], b["groups"]
    if len(ga) != len(gb):
        return False
    return all(x["lo"] == y["lo"] and x["hi"] == y["hi"]
               and x["home"] == y["home"] for x, y in zip(ga, gb))


def peek_dist(leaves, env) -> bool:
    """Cheap pre-evaluation probe: does any identifier leaf resolve to an
    unmaterialized DistFrame?  Lets try_fuse ship single-op regions that
    would otherwise fall under MIN_OPS and trigger a gather."""
    if not enabled():
        return False
    for leaf in leaves:
        if not isinstance(leaf, AstId):
            continue
        try:
            v = env.lookup(leaf.name)
            fr = v.value if (v is not None and v.kind == Val.FRAME) \
                else env.session.lookup(leaf.name)
        except Exception:
            continue
        if _is_dist(fr):
            return True
    return False


def _context(base_frame):
    """(cloud, store, router, workers) when a ≥2-worker ring is up."""
    try:
        from h2o3_tpu.cluster import active_cloud
        from h2o3_tpu.cluster import tasks as _tasks
        cloud = active_cloud()
    except Exception:
        return None
    if cloud is None:
        return None
    store = getattr(base_frame, "_store", None)
    if store is None:
        try:
            store = _frames._resolve_store(cloud)
        except Exception:
            return None
    router = getattr(store, "router", None)
    workers = _tasks._healthy_workers(cloud)
    if router is None or not router.active() or len(workers) < 2:
        return None
    return cloud, store, router, workers


# ---------------------------------------------------------------------------
# home-side executor (the rapids_exec ctx-DTask body)


def _rep_inputs(refs, layouts: Dict[int, Dict[str, Any]], g: int,
                base_svals: List[Any], store):
    """Codec-aware device inputs for one group's referenced columns.

    Each referenced column homogenizes to one chunk-codec group rep
    (cluster/frames.group_column_rep) and the rep — not a dense f64
    column — becomes the program input: packed u16 codes (affine/dict),
    f32 storage, or nothing at all (const columns ride a scalar slot).
    Returns ``(decode, run_svals, uploads)`` where ``decode`` maps akeys
    to the specs _make_fn emits arithmetic for, ``run_svals`` extends the
    plan's scalar slots with decode params (offset/scale/const values as
    TRACED runtime args, dict tables as replicated trailing arrays), and
    ``uploads`` lists ``(li, name, akey, host_data, pad_fill)``."""
    reps = {}
    for li, x in refs:
        reps[(int(li), x)] = _frames.group_column_rep(
            store, layouts[int(li)], g, x)
    if reps and all(r[0] == "const" for r in reps.values()):
        # the shard shapes need at least one row-sharded array: demote
        # one all-const rep to its (tiny) dense broadcast
        k0 = next(iter(reps))
        rep0 = reps[k0]
        reps[k0] = ("dense", np.repeat(
            np.asarray(rep0[1], dtype=np.float64), int(rep0[2])))
    decode: Dict[str, Tuple] = {}
    run_svals = list(base_svals)
    uploads: List[Tuple] = []
    for (li, x), rep in reps.items():
        akey = _fusion._akey(li, x)
        kind = rep[0]
        if kind == "const":
            decode[akey] = ("const", len(run_svals))
            run_svals.append(float(rep[1][0]))
        elif kind == "affine":
            decode[akey] = ("affine", len(run_svals), len(run_svals) + 1,
                            int(rep[4]))
            run_svals.extend([float(rep[2]), float(rep[3])])
            uploads.append((li, x, akey, rep[1], int(rep[4])))
        elif kind == "dict":
            decode[akey] = ("dict", len(run_svals))
            run_svals.append(np.ascontiguousarray(rep[2]))
            uploads.append((li, x, akey, rep[1], 0))
        elif kind == "f32":
            decode[akey] = ("f32",)
            uploads.append((li, x, akey, rep[1], np.nan))
        else:
            uploads.append((li, x, akey, rep[1], np.nan))
    return decode, run_svals, uploads


def _partial(reduce_name: str, d: np.ndarray) -> Dict[str, Any]:
    """One column's reducer partial over one group's rows."""
    d = np.asarray(d, dtype=np.float64)
    dd = d[~np.isnan(d)] if _na_rm(reduce_name) else d
    n_valid = int(dd.size)
    with np.errstate(all="ignore"):
        if reduce_name == "mean":
            return {"s": float(np.sum(dd)) if n_valid else 0.0, "n": n_valid}
        v = float(_RFNS[reduce_name](dd)) if n_valid else float("nan")
    return {"v": v, "n": n_valid}


def _merge_partials(reduce_name: str, parts: List[Dict[str, Any]]) -> float:
    """Caller-side merge in canonical group order — the same numpy
    reduction the interpreter applies, over the per-group partials."""
    with np.errstate(all="ignore"):
        if reduce_name == "mean":
            ntot = sum(int(p["n"]) for p in parts)
            if ntot == 0:
                return float("nan")
            s = np.sum(np.array([p["s"] for p in parts if p["n"]],
                                dtype=np.float64))
            return float(s / ntot)
        vals = [p["v"] for p in parts if p["n"]]
        if not vals:
            return float("nan")
        return float(_RFNS[reduce_name](np.array(vals, dtype=np.float64)))


def rapids_exec(payload: Dict[str, Any], cloud, store) -> Dict[str, Any]:
    """Execute one group's slice of a shipped column program ON a chunk
    holder: assemble the group's columns (devcache-warm after the first
    touch), run the memoized jitted program, then either return reducer
    partials or write derived chunks back to the ring and return only
    their layout arithmetic."""
    if store is None:
        raise _rpc.RpcFault("no DKV store installed on this node", code=503)
    g = int(payload["g"])
    layouts: Dict[int, Dict[str, Any]] = {}
    for li, ref in payload["leaves"].items():
        layouts[int(li)] = _frames._layout_for(store, ref[0], ref[1])
    base = layouts[int(payload["base"])]
    grp = base["groups"][g]
    espc = base["espc"]
    lo, hi = int(grp["lo"]), int(grp["hi"])
    n = int(espc[hi]) - int(espc[lo])

    # dense host columns only where a dense copy is genuinely needed:
    # pass-through outputs and filter masks.  Program INPUTS go through
    # the codec rep path below instead — no dense working set for them.
    host_names: Dict[int, List[str]] = {}

    def _need_host(li: int, nm: str) -> None:
        cols = host_names.setdefault(int(li), [])
        if nm not in cols:
            cols.append(nm)

    for out in payload["outputs"]:
        if out[0] == "host":
            _need_host(int(out[1]), out[2])
    _flt = payload.get("filter")
    if _flt is not None:
        _need_host(int(_flt["li"]), _flt["name"])
    host: Dict[int, Dict[str, np.ndarray]] = {}
    for li, nms in host_names.items():
        host[li] = _frames.columns_from_group(store, layouts[li], g, nms)

    dev_host: List[np.ndarray] = []
    dev_exprs = tuple(payload.get("dev_exprs") or ())
    if dev_exprs:
        from h2o3_tpu.cluster import tasks as _tasks

        refs = [tuple(r) for r in payload["refs"]]
        svals = [float(s) for s in payload["svals"]]
        if n > 0:
            mesh = default_mesh()
            decode, run_svals, uploads = _rep_inputs(
                refs, layouts, g, svals, store)
            # the program is memoized per decode signature too: the same
            # region over differently-encoded frames (or the dense
            # H2O3_TPU_CODECS=0 plane) must not share a compiled decode
            dsig = tuple(sorted(
                (ak,) + tuple(s for s in sp) for ak, sp in decode.items()))
            fn = plan_memo(
                "rapids_dist",
                ("fn",) + tuple(payload["key"]) + (dsig,),
                lambda: _fusion._make_fn(dev_exprs,
                                         tuple(decode.items())))
            # one multi-device program at a time in this process — XLA:CPU
            # wedges on concurrent launches from several server threads
            with _tasks._SHARD_EXEC_LOCK:
                with enable_x64():
                    merged: Dict[str, Any] = {}
                    mask = None
                    for li, x, akey, data, fill in uploads:
                        lay = layouts[li]
                        token = (lay["frame_key"], lay["stamp"], int(g),
                                 x, decode.get(akey, ("dense",))[0])

                        def build(d=data, f=fill):
                            return shard_rows(np.asarray(d), mesh,
                                              fill=f)[0]

                        arr = _devcache.cached(
                            "rapids_rep_arr", token, (), mesh, build,
                            frame_key=lay["frame_key"])
                        merged[akey] = arr
                        mask = row_mask(n, int(arr.shape[0]), mesh)
                    table = FrameTable(merged, mask, n, mesh)
                    # _SHARD_EXEC_LOCK exists to serialize shard
                    # execution: XLA:CPU multi-device collectives
                    # deadlock when dispatched from concurrent threads
                    # h2o3: noqa[LOCK001]
                    outs = map_batches(fn, table, *run_svals)
                dev_host = [np.asarray(gather_rows(o, n)).copy()
                            for o in outs]
        else:
            dev_host = [np.empty(0, dtype=np.float64) for _ in dev_exprs]

    fills = payload.get("fills") or ()
    arrs: List[np.ndarray] = []
    for out in payload["outputs"]:
        if out[0] == "host":
            arrs.append(np.asarray(host[int(out[1])][out[2]],
                                   dtype=np.float64))
        elif out[0] == "dev":
            arrs.append(dev_host[int(out[1])])
        else:  # ("fill", j) — scalar := over the group's whole row range
            arrs.append(np.full(n, float(fills[int(out[1])]),
                                dtype=np.float64))

    reduce_name = payload.get("reduce")
    if reduce_name:
        return {"mode": "reduce", "rows": n,
                "cols": [_partial(reduce_name, a) for a in arrs]}

    keep = None
    flt = payload.get("filter")
    if flt is not None:
        mv = host[int(flt["li"])][flt["name"]]
        valid = mv[~np.isnan(mv)]
        if valid.size and not np.all(np.isin(valid, (0.0, 1.0))):
            # not a mask: row-INDEX selection semantics — decline before
            # writing anything so the caller can take the gather path
            return {"mode": "nonbinary"}
        keep = mv == 1.0

    w = payload["write"]
    out_names = payload["out_names"]
    types = w["types"]
    domains = w.get("domains") or {}
    replicas = int(w["replicas"])
    nrows_out: List[int] = []
    nbytes = 0
    off = int(espc[lo])
    for i in range(lo, hi):
        sl = slice(int(espc[i]) - off, int(espc[i + 1]) - off)
        k = keep[sl] if keep is not None else None
        pls: List[Any] = []
        ni = 0
        for nm2, a, t in zip(out_names, arrs, types):
            seg = a[sl]
            if k is not None:
                seg = seg[k]
            ni = int(seg.size)
            if t is ColType.CAT:
                codes = np.full(seg.shape, NA_CAT, dtype=np.int32)
                m = ~np.isnan(seg)
                codes[m] = seg[m].astype(np.int32)
                pls.append((codes, list(domains.get(nm2) or [])))
            else:
                pls.append(np.ascontiguousarray(seg, dtype=np.float64))
        # derived chunks land ENCODED exactly like parsed ones: the wire
        # guard, replica fan-out and layout nbytes all see codec bytes
        value = _codecs.encode_chunk([ni, pls, False])
        ck = _frames.chunk_key(w["anchor"], i)
        nbytes += _frames.guard_chunk_payload(ck, value)
        store.put(ck, value, replicas=replicas)
        nrows_out.append(ni)
    return {"mode": "frame", "nrows": nrows_out, "nbytes": int(nbytes)}


# ---------------------------------------------------------------------------
# caller-side fan-out (the mr_chunk_homed recovery ladder, rapids flavor)


def _run_groups(base_lay: Dict[str, Any], payloads: List[Dict[str, Any]],
                cloud, store, router, workers,
                kind: str) -> List[Dict[str, Any]]:
    """Fan the per-group programs to their CURRENT ring homes and collect
    responses in canonical group order.  Ladder on failure: home →
    replica successors → any survivor → caller-local execution from
    replica chunks (never a gather)."""
    from h2o3_tpu.cluster import tasks as _tasks

    groups = base_lay["groups"]
    timeout = dist_timeout()
    my_name = cloud.info.name
    _tasks._FANOUT.set(len(groups))
    results: List[Optional[Dict[str, Any]]] = [None] * len(groups)
    errors: List[Optional[BaseException]] = [None] * len(groups)

    with telemetry.Span("rapids_dist", groups=len(groups),
                        rows=int(base_lay["espc"][-1]), op=kind):
        ctx = telemetry.current_trace_context()
        fo = _flight.FANOUTS.begin("rapids_exec", len(groups),
                                   rows=int(base_lay["espc"][-1]))
        _flight.record(_flight.FANOUT, "info", "schedule",
                       kind="rapids_exec", groups=len(groups), op=kind)

        def _run(gi: int) -> None:
            try:
                _run_group(gi)
            finally:
                fo.progress()

        def _run_group(gi: int) -> None:
            grp = groups[gi]
            payload = payloads[gi]
            cands = router.home_members(grp["anchor"], MAX_REPLICAS)
            with telemetry.Span(
                    "rapids_group", trace_id=ctx["trace_id"],
                    parent_id=ctx["span_id"], group=gi,
                    anchor=grp["anchor"]):
                # rung 0: the group's CURRENT ring home (chunk-local)
                try:
                    if cands and cands[0].info.name == my_name:
                        results[gi] = rapids_exec(payload, cloud, store)
                        return
                    if cands:
                        results[gi] = _tasks.submit(
                            cloud, cands[0], "rapids_exec", payload,
                            timeout=timeout)
                        return
                except (_rpc.RPCError, _rpc.RpcFault):
                    pass
                # rung 1: ring successors hold replica CHUNKS
                for m in cands[1:]:
                    try:
                        if m.info.name == my_name:
                            out = rapids_exec(payload, cloud, store)
                        else:
                            out = _tasks.submit(cloud, m, "rapids_exec",
                                                payload, timeout=timeout)
                        _tasks._RECOVERED.inc(path="replica")
                        _flight.record(_flight.RECOVERY, "warn",
                                       "rapids_group", path="replica",
                                       group=gi, member=m.info.name)
                        results[gi] = out
                        return
                    except (_rpc.RPCError, _rpc.RpcFault):
                        continue
                # rung 2: any other healthy member (ring-walks the chunks)
                cand_names = {m.info.name for m in cands}
                for m in workers:
                    if (m.info.name in cand_names
                            or m.info.name == my_name or not m.healthy):
                        continue
                    try:
                        out = _tasks.submit(cloud, m, "rapids_exec",
                                            payload, timeout=timeout)
                        _tasks._RECOVERED.inc(path="survivor")
                        _flight.record(_flight.RECOVERY, "warn",
                                       "rapids_group", path="survivor",
                                       group=gi, member=m.info.name)
                        results[gi] = out
                        return
                    except (_rpc.RPCError, _rpc.RpcFault):
                        continue
                # rung 3: the caller itself, from replica chunks via the
                # store's ring walk — still never a gather
                try:
                    results[gi] = rapids_exec(payload, cloud, store)
                    _tasks._RECOVERED.inc(path="local")
                    _flight.record(_flight.RECOVERY, "warn", "rapids_group",
                                   path="local", group=gi)
                except BaseException as e:  # noqa: BLE001 — surfaced below
                    errors[gi] = e

        threads = [threading.Thread(target=_run, args=(gi,), daemon=True)
                   for gi in range(len(groups))]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=timeout)
        finally:
            fo.end()

        for gi in range(len(groups)):
            if results[gi] is None and errors[gi] is None:
                results[gi] = rapids_exec(payloads[gi], cloud, store)
                _tasks._RECOVERED.inc(path="local")
                _flight.record(_flight.RECOVERY, "warn", "rapids_group",
                               path="local", group=gi, deadline=True)
        for e in errors:
            if e is not None:
                raise e

        # the fan-out choke point: everything the homes sent back —
        # partials or layout arithmetic, never row data
        nb = sum(len(pickle.dumps(r, protocol=pickle.HIGHEST_PROTOCOL))
                 for r in results)
        _PARTIAL_BYTES.inc(nb)
        _ledger.charge(_ledger.RAPIDS_PARTIAL_BYTES, nb)
        _flight.record(_flight.FANOUT, "info", "partials",
                       kind="rapids_exec", groups=len(groups), bytes=nb)
    return results  # type: ignore[return-value]


def _cleanup_chunks(store, anchors: List[str],
                    groups: List[Dict[str, Any]]) -> None:
    """Best-effort removal of derived chunks after an aborted write."""
    for j, grp in enumerate(groups):
        for i in range(int(grp["lo"]), int(grp["hi"])):
            try:
                store.remove(_frames.chunk_key(anchors[j], i))
            except Exception:
                pass


def _derived_frame(store, router, base_fr, out_names: List[str],
                   out_types: List[ColType], domains: Dict[str, list],
                   new_key: str, anchors: List[str],
                   results: List[Dict[str, Any]],
                   filtered: bool):
    """Assemble the new chunk-homed frame's layout from the per-group
    write receipts and publish layout+setup to the ring."""
    from h2o3_tpu.frame.parse import ParseSetup

    base_lay = base_fr.chunk_layout
    groups_in = base_lay["groups"]
    if filtered:
        espc = [0]
        for gi, grp in enumerate(groups_in):
            for nr in results[gi]["nrows"]:
                espc.append(espc[-1] + int(nr))
    else:
        espc = [int(e) for e in base_lay["espc"]]
    groups = [{"g": gi, "anchor": anchors[gi],
               "lo": int(grp["lo"]), "hi": int(grp["hi"]),
               "home": grp["home"], "home_name": grp["home_name"]}
              for gi, grp in enumerate(groups_in)]
    layout = {
        "frame_key": new_key,
        "espc": espc,
        "replicas": _frames.chunk_replicas(),
        "groups": groups,
        "column_names": list(out_names),
        "column_types": list(out_types),
        "domains": {n: list(domains[n]) for n in domains},
        "nbytes": int(sum(int(r["nbytes"]) for r in results)),
        "stamp": _frames._layout_stamp(espc, anchors),
    }
    setup = ParseSetup(
        separator=",", header=True, column_names=list(out_names),
        column_types=list(out_types), na_strings=(),
        skip_blank_lines=True, quote_char='"')
    store.put(_frames.setup_key(new_key), _frames.setup_payload(setup),
              replicas=MAX_REPLICAS)
    store.put(_frames.layout_key(new_key), layout, replicas=MAX_REPLICAS)
    return _frames.DistFrame(layout, setup, store)


def _new_anchors(router, new_key: str,
                 groups: List[Dict[str, Any]]) -> List[str]:
    """Probe derived-frame anchors CALLER-side so the new layout homes on
    the same members as its source regardless of which ladder rung ends
    up executing each group."""
    return [_frames._probe_anchor(router, new_key, gi, grp["home"])
            for gi, grp in enumerate(groups)]


def _new_frame_key() -> str:
    return f"rapids_{uuid.uuid4().hex[:10]}"


# ---------------------------------------------------------------------------
# entry point 1: fused regions (hooked from fusion.try_fuse)


def try_dist(node, leaves, leaf_vals, env) -> Optional[Val]:
    """Attempt to run a fused region on the chunk homes.  Returns the
    result Val, or None — the caller then proceeds with the local
    (gather-based) execute/replay, which is always correct."""
    if not enabled():
        return None
    if not any(v.kind == Val.FRAME and _is_dist(v.value) for v in leaf_vals):
        return None
    try:
        return _dispatch_region(node, leaves, leaf_vals, env)
    except _NotDistributable:
        _DIST.inc(result="gather")
        return None
    except Exception:
        # a distributed attempt died mid-flight (beneath the ladder):
        # divert to the exact gather path — correctness over locality
        _DIST.inc(result="fallback")
        return None


def _dispatch_region(node, leaves, leaf_vals, env) -> Val:
    base_fr = next(v.value for v in leaf_vals
                   if v.kind == Val.FRAME and _is_dist(v.value))
    ctx = _context(base_fr)
    if ctx is None:
        raise _NotDistributable
    cloud, store, router, workers = ctx
    base_lay = base_fr.chunk_layout
    frame_leaves: Dict[int, Any] = {}
    for i, v in enumerate(leaf_vals):
        if v.kind == Val.FRAME:
            if not _is_dist(v.value) or \
                    not _aligned(base_lay, v.value.chunk_layout):
                raise _NotDistributable
            frame_leaves[i] = v.value
        elif v.kind != Val.NUM:
            raise _NotDistributable

    schemas = tuple(_fusion._leaf_schema(v) for v in leaf_vals)
    key = (canonical_sexpr(node), schemas)
    leaf_idx_by_id = {id(leaf): i for i, leaf in enumerate(leaves)}

    def build():
        try:
            return _fusion._build_plan(node, leaf_idx_by_id, schemas)
        except _fusion._Unfusible:
            return _fusion._UNFUSIBLE_PLAN

    plan = plan_memo("rapids_fusion", key, build)
    if plan == _fusion._UNFUSIBLE_PLAN:
        raise _NotDistributable
    if plan.static is not None:
        _DIST.inc(result="dist")
        return Val.num(plan.static)
    if plan.reduce_name is not None and \
            plan.reduce_name not in _DIST_REDUCERS:
        raise _NotDistributable
    if len(set(plan.out_names)) != len(plan.out_names):
        raise _NotDistributable  # derived layouts need unique column names

    def leaf_col_type(li: int, name: str) -> ColType:
        lay = frame_leaves[li].chunk_layout
        return lay["column_types"][lay["column_names"].index(name)]

    names: Dict[int, List[str]] = {}

    def need(li: int, nm: str) -> None:
        cols = names.setdefault(li, [])
        if nm not in cols:
            cols.append(nm)

    out_types: List[ColType] = []
    domains: Dict[str, list] = {}
    for nm, out in zip(plan.out_names, plan.outputs):
        if out[0] == "host":
            li, src = int(out[1]), out[2]
            t = leaf_col_type(li, src)
            if t in (ColType.STR, ColType.UUID):
                raise _NotDistributable
            out_types.append(t)
            if t is ColType.CAT:
                lay = frame_leaves[li].chunk_layout
                domains[nm] = list(lay["domains"].get(src) or [])
            need(li, src)
        else:
            out_types.append(ColType.NUM)
    for li, nm in plan.refs:
        need(int(li), nm)

    svals = [float(leaf_vals[li].as_num()) for li in plan.sval_leaves]
    svals += list(plan.lit_vals)
    base_li = min(frame_leaves)
    common = {
        "base": base_li,
        "leaves": {li: (fr.chunk_layout["frame_key"],
                        fr.chunk_layout["stamp"])
                   for li, fr in frame_leaves.items()},
        "names": names,
        "key": key,
        "dev_exprs": plan.dev_exprs,
        "refs": plan.refs,
        "svals": svals,
        "outputs": plan.outputs,
        "out_names": plan.out_names,
        "fills": (),
        "reduce": plan.reduce_name,
    }

    if plan.reduce_name is not None:
        payloads = [dict(common, g=gi, write=None)
                    for gi in range(len(base_lay["groups"]))]
        results = _run_groups(base_lay, payloads, cloud, store, router,
                              workers, kind="reduce")
        per_col = list(zip(*[r["cols"] for r in results]))
        vals = [_merge_partials(plan.reduce_name, list(parts))
                for parts in per_col]
        _DIST.inc(result="dist")
        return Val.num(vals[0]) if len(vals) == 1 else Val.nums(vals)

    new_key = _new_frame_key()
    anchors = _new_anchors(router, new_key, base_lay["groups"])
    payloads = [dict(common, g=gi,
                     write={"anchor": anchors[gi],
                            "replicas": _frames.chunk_replicas(),
                            "types": list(out_types),
                            "domains": domains})
                for gi in range(len(base_lay["groups"]))]
    results = _run_groups(base_lay, payloads, cloud, store, router,
                          workers, kind="frame")
    out = _derived_frame(store, router, base_fr, list(plan.out_names),
                         out_types, domains, new_key, anchors, results,
                         filtered=False)
    _DIST.inc(result="dist")
    return Val.frame(out)


# ---------------------------------------------------------------------------
# entry point 2: whole-frame := assignment (hooked from prims/assign.py)


def try_assign_dist(env, args) -> Optional[Val]:
    """``(:= dst src cols _)`` over a DistFrame: write the assigned
    columns home-side (scalar fill or an aligned dist source column) and
    pass the rest through as chunk references — no row data moves.
    Returns None for any shape outside that contract (row-subset
    assigns, string sources, misaligned layouts): the interpreter's
    gather-based path then runs, bit-identical as ever."""
    if not enabled():
        return None
    dstv = args[0]
    if not (dstv.is_frame() and _is_dist(dstv.value)):
        return None
    try:
        out = _assign_dist(env, args)
    except _NotDistributable:
        _DIST.inc(result="gather")
        return None
    except Exception:
        _DIST.inc(result="fallback")
        return None
    if out is None:
        _DIST.inc(result="gather")
        return None
    _DIST.inc(result="dist")
    return Val.frame(out)


def _assign_dist(env, args):
    from h2o3_tpu.rapids.prims.util import col_indices

    dst = args[0].value
    srcv, cselv, rselv = args[1], args[2], args[3]
    if not (rselv.is_num() and np.isnan(rselv.as_num())):
        raise _NotDistributable  # row-subset assign: interpreter path
    ctx = _context(dst)
    if ctx is None:
        raise _NotDistributable
    cloud, store, router, workers = ctx
    lay = dst.chunk_layout
    dst_names = list(lay["column_names"])
    dst_types = list(lay["column_types"])
    cidx = col_indices(dst, cselv)
    if len(set(cidx)) != len(cidx):
        raise _NotDistributable

    scalar = None
    src = None
    src_names: List[str] = []
    if srcv.is_frame():
        src = srcv.value
        if not (_is_dist(src) and _aligned(lay, src.chunk_layout)):
            raise _NotDistributable
        slay = src.chunk_layout
        src_names = list(slay["column_names"])
        stypes = list(slay["column_types"])
        for k in range(len(cidx)):
            j = k if len(src_names) > 1 else 0
            if j >= len(src_names) or \
                    stypes[j] not in (ColType.NUM, ColType.TIME):
                raise _NotDistributable
    elif srcv.kind == Val.NUM:
        scalar = float(srcv.as_num())
    else:
        raise _NotDistributable

    cset = {int(j): k for k, j in enumerate(cidx)}
    outputs: List[Tuple] = []
    out_types: List[ColType] = []
    fills: List[float] = []
    domains: Dict[str, list] = {}
    names: Dict[int, List[str]] = {}

    def need(li: int, nm: str) -> None:
        cols = names.setdefault(li, [])
        if nm not in cols:
            cols.append(nm)

    for j, nm in enumerate(dst_names):
        if j in cset:
            if dst_types[j] not in (ColType.NUM, ColType.TIME):
                raise _NotDistributable  # CAT/STR dst: interpreter path
            if scalar is not None:
                outputs.append(("fill", len(fills)))
                fills.append(scalar)
            else:
                sn = src_names[cset[j] if len(src_names) > 1 else 0]
                outputs.append(("host", 1, sn))
                need(1, sn)
            out_types.append(ColType.NUM)
        else:
            t = dst_types[j]
            if t in (ColType.STR, ColType.UUID):
                raise _NotDistributable
            outputs.append(("host", 0, nm))
            out_types.append(t)
            if t is ColType.CAT:
                domains[nm] = list(lay["domains"].get(nm) or [])
            need(0, nm)

    leaves = {0: (lay["frame_key"], lay["stamp"])}
    if src is not None:
        leaves[1] = (src.chunk_layout["frame_key"],
                     src.chunk_layout["stamp"])
    new_key = _new_frame_key()
    anchors = _new_anchors(router, new_key, lay["groups"])
    payloads = [
        {"base": 0, "g": gi, "leaves": leaves, "names": names,
         "key": None, "dev_exprs": (), "refs": (), "svals": (),
         "outputs": tuple(outputs), "out_names": tuple(dst_names),
         "fills": tuple(fills), "reduce": None,
         "write": {"anchor": anchors[gi],
                   "replicas": _frames.chunk_replicas(),
                   "types": list(out_types), "domains": domains}}
        for gi in range(len(lay["groups"]))]
    results = _run_groups(lay, payloads, cloud, store, router, workers,
                          kind="assign")
    return _derived_frame(store, router, dst, dst_names, out_types,
                          domains, new_key, anchors, results,
                          filtered=False)


# ---------------------------------------------------------------------------
# entry point 3: mask filters (hooked from prims/mungers.py rows)


def try_rows_dist(env, args) -> Optional[Val]:
    """``(rows fr sel)`` with an aligned one-column dist selector: each
    home validates its slice of the mask and writes the surviving rows of
    its own chunks as the new frame's chunks — ESPC recomputed from the
    per-chunk survivor counts, zero row movement.  A selector that turns
    out not to be 0/1 (row-index semantics) aborts cleanly, removes any
    chunks already written, and returns None for the exact gather path."""
    if not enabled():
        return None
    frv, selv = args[0], args[1]
    if not (frv.is_frame() and _is_dist(frv.value)):
        return None
    try:
        out = _filter_dist(env, frv.value, selv)
    except _NotDistributable:
        _DIST.inc(result="gather")
        return None
    except Exception:
        _DIST.inc(result="fallback")
        return None
    if out is None:
        _DIST.inc(result="gather")
        return None
    _DIST.inc(result="dist")
    return Val.frame(out)


def _filter_dist(env, fr, selv):
    if not selv.is_frame():
        raise _NotDistributable  # numeric row indices: interpreter path
    sel = selv.value
    if not (_is_dist(sel) and _aligned(fr.chunk_layout, sel.chunk_layout)):
        raise _NotDistributable
    slay = sel.chunk_layout
    if len(slay["column_names"]) != 1:
        raise _NotDistributable
    ctx = _context(fr)
    if ctx is None:
        raise _NotDistributable
    cloud, store, router, workers = ctx
    lay = fr.chunk_layout
    out_names = list(lay["column_names"])
    out_types = list(lay["column_types"])
    if any(t in (ColType.STR, ColType.UUID) for t in out_types):
        raise _NotDistributable
    domains = {n: list(lay["domains"].get(n) or [])
               for n, t in zip(out_names, out_types) if t is ColType.CAT}
    sel_name = slay["column_names"][0]
    if slay["column_types"][0] in (ColType.STR, ColType.UUID):
        raise _NotDistributable
    names: Dict[int, List[str]] = {0: list(out_names), 1: [sel_name]}
    outputs = tuple(("host", 0, nm) for nm in out_names)
    leaves = {0: (lay["frame_key"], lay["stamp"]),
              1: (slay["frame_key"], slay["stamp"])}
    new_key = _new_frame_key()
    anchors = _new_anchors(router, new_key, lay["groups"])
    payloads = [
        {"base": 0, "g": gi, "leaves": leaves, "names": names,
         "key": None, "dev_exprs": (), "refs": (), "svals": (),
         "outputs": outputs, "out_names": tuple(out_names),
         "fills": (), "reduce": None,
         "filter": {"li": 1, "name": sel_name},
         "write": {"anchor": anchors[gi],
                   "replicas": _frames.chunk_replicas(),
                   "types": list(out_types), "domains": domains}}
        for gi in range(len(lay["groups"]))]
    results = _run_groups(lay, payloads, cloud, store, router, workers,
                          kind="filter")
    if any(r.get("mode") == "nonbinary" for r in results):
        # the selector is an index list, not a mask: undo partial writes
        # and let the interpreter's exact row_indices path decide
        _cleanup_chunks(store, anchors, lay["groups"])
        return None
    return _derived_frame(store, router, fr, out_names, out_types,
                          domains, new_key, anchors, results,
                          filtered=True)
