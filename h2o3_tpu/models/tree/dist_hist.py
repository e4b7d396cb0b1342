"""Map-side distributed histogram tree building over chunk homes.

The booster's private-then-merge core (``ScoreBuildHistogram2``) moved to
the cluster: when the training frame is a chunk-homed :class:`DistFrame`,
each home keeps a per-fit context (bin codes, margins, node positions)
and per tree level only ``(feature, bin, {Sum g, Sum h, Sum w})`` histogram
partials and the chosen splits cross the wire — never rows.

Protocol (six ctx-DTasks, one global monotonic ``seq`` per fit):

``hist_open``
    seq 0 — assemble the group's local columns from the ring, filter rows
    the single-node path would drop (NaN response/weight/offset, weight
    <= 0), sketch every feature for global binning, and ship the one-time
    auxiliary vectors (y, w, offset) the caller needs for grad/hess-free
    bookkeeping.  Creates the context (``last_seq = 0``).
``hist_bind``
    seq 1 — receive the merged global edges, bin locally
    (``apply_bins`` never ships bin codes), drop the raw feature matrix,
    and install the fit parameters (f0, objective, seed, sample rate).
    The binned-code matrix is served resident from the device frame cache
    (keyed on layout stamp + bin-edges digest), so a repeat fit on an
    unmutated frame decodes and uploads nothing.
``hist_level``
    one op per level: ``level`` (apply parent routes, build this level's
    histogram partial — small side only under subtraction), ``totals``
    (terminal node G/H/W totals), ``fin`` (apply terminal routes, add the
    finished tree's leaf values into the local margins), and the seq-free
    ``margins`` read-back.
``hist_levels``
    several ``hist_level`` rounds in one RPC: output-free ``fin`` ops are
    deferred caller-side (``H2O3_TPU_DIST_HIST_BATCH``) and ride with the
    next output-bearing op — each item fences its own seq in issue order,
    so state and recovery are exactly the sequential rounds'.
``hist_replay``
    recovery: rebuild a lost context from the caller's op log (open +
    bind + every routing-relevant op replayed without building output),
    then fence at the caller's seq.
``hist_fin``
    drop the context.

Every context mutation is fenced: an op whose ``seq`` is not exactly
``last_seq + 1`` raises 409 and the caller replays, so a home that missed
a level (or a survivor adopting a dead home's group) converges to the
exact same state — no double-counted rows.  The caller merges partials in
canonical group order with float64 accumulation, so the fit is
bit-identical across topologies for a fixed seed.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import numpy as np

import jax.numpy as jnp

from h2o3_tpu.cluster import rpc as _rpc
from h2o3_tpu.cluster.dkv import MAX_REPLICAS
from h2o3_tpu.compute.quantile import merge_edges, sketch_column
from h2o3_tpu.frame import devcache as _devcache
from h2o3_tpu.frame.frame import ColType
from h2o3_tpu.models.data_info import DataInfo
from h2o3_tpu.ops.histogram import apply_bins, guard_hist_payload
from h2o3_tpu.util import flight as _flight
from h2o3_tpu.util import ledger as _ledger
from h2o3_tpu.util import telemetry

_FITS = telemetry.counter(
    "dist_hist_fits_total",
    "distributed histogram tree fits started, by execution mode",
    labels=("mode",))
_LEVELS = telemetry.counter(
    "dist_hist_levels_total",
    "tree-level histogram fan-outs issued by distributed fits")
_PARTIAL_BYTES = telemetry.counter(
    "dist_hist_partial_bytes_total",
    "bytes of histogram partials produced by chunk homes")
_BIND_CACHE = telemetry.counter(
    "dist_hist_bind_cache_total",
    "hist_bind binned-code lookups against the device frame cache",
    labels=("result",))
_CTX_ENTRIES = telemetry.gauge(
    "cluster_hist_context_entries",
    "live per-fit histogram contexts held by this member")


def dist_mode() -> str:
    """``H2O3_TPU_DIST_HIST``: ``1`` (fan to chunk homes when a cloud is
    up), ``local`` (same engine, every op runs caller-side) or ``0``
    (legacy path via lazy materialization)."""
    v = os.environ.get("H2O3_TPU_DIST_HIST", "1").strip().lower()
    return v if v in ("0", "1", "local") else "1"


def _timeout() -> float:
    try:
        return float(os.environ.get("H2O3_TPU_DIST_HIST_TIMEOUT", "120"))
    except ValueError:
        return 120.0


def _ctx_cap() -> int:
    try:
        return max(1, int(os.environ.get("H2O3_TPU_DIST_HIST_CTX", "4")))
    except ValueError:
        return 4


def _batch_enabled() -> bool:
    """``H2O3_TPU_DIST_HIST_BATCH``: coalesce output-free ``fin`` ops with
    the next output-bearing op into one ``hist_levels`` round (default on;
    ``0`` sends every op as its own ``hist_level`` RPC)."""
    return os.environ.get("H2O3_TPU_DIST_HIST_BATCH", "1").strip() != "0"


# ---------------------------------------------------------------------------
# home-side context store

_CTX_LOCK = threading.Lock()
#: ctx_id -> {group index -> _GroupState}; LRU-bounded so leaked fits
#: (caller died before hist_fin) cannot pin host memory forever
_CTXS: "OrderedDict[str, Dict[int, _GroupState]]" = OrderedDict()
_CTX_COUNTER = [0]


class _GroupState:
    """One group's training-local state on its executor."""

    def __init__(self, g: int) -> None:
        self.g = g
        self.X: Optional[np.ndarray] = None   # [n, F] f32, dropped at bind
        self.y: Optional[np.ndarray] = None   # [n] f64 (kept rows)
        self.w: Optional[np.ndarray] = None
        self.off: Optional[np.ndarray] = None
        self.last_seq = 0
        self.bins: Optional[np.ndarray] = None  # [n, F] int bin codes
        self.F = 0
        self.n_bins1 = 0
        self.base = 0          # this group's offset in the global row order
        self.n_total = 0
        self.C = 1
        self.objective = ""
        self.seed = 0
        self.sample_rate = 1.0
        self.margin: Optional[np.ndarray] = None   # [n, C] f64
        self.targets: Optional[np.ndarray] = None  # fixed-objective targets
        self.pos: Optional[np.ndarray] = None      # [C, n] int32 heap index
        self.gh_round = -1
        self.g_all: Optional[np.ndarray] = None
        self.h_all: Optional[np.ndarray] = None
        self.sample: Optional[np.ndarray] = None


def _ctx_store(ctx_id: str, g: int, st: _GroupState) -> None:
    with _CTX_LOCK:
        groups = _CTXS.setdefault(ctx_id, {})
        groups[g] = st
        _CTXS.move_to_end(ctx_id)
        cap = _ctx_cap()
        while len(_CTXS) > cap:
            _CTXS.popitem(last=False)
        _CTX_ENTRIES.set(float(len(_CTXS)))


def _ctx_group(payload: Dict[str, Any]) -> _GroupState:
    with _CTX_LOCK:
        groups = _CTXS.get(payload["ctx_id"])
        st = groups.get(int(payload["g"])) if groups else None
    if st is None:
        raise _rpc.RpcFault(
            f"no histogram context {payload['ctx_id']!r} for group "
            f"{payload['g']} on this member", code=404)
    return st


def _ctx_drop(ctx_id: str) -> None:
    with _CTX_LOCK:
        _CTXS.pop(ctx_id, None)
        _CTX_ENTRIES.set(float(len(_CTXS)))


def _check_seq(st: _GroupState, seq: int) -> None:
    if seq != st.last_seq + 1:
        raise _rpc.RpcFault(
            f"stale context: got seq {seq}, expected {st.last_seq + 1}",
            code=409)
    st.last_seq = seq


# ---------------------------------------------------------------------------
# home-side op execution


def _round_start(st: _GroupState, r: int) -> None:
    """Grad/hess for round ``r`` from the pre-round margins (computed once
    per round — block 2 of a multinomial round reuses the cache, matching
    the single-node engine computing g_all before its class trees)."""
    if st.gh_round == r:
        return
    n = st.y.size
    if st.objective == "fixed":
        g = -st.targets
        h = np.ones_like(st.targets)
    else:
        from h2o3_tpu.models.tree import common as _common
        g, h = _common.grad_hess(st.objective, st.y, st.margin)
        g = np.asarray(g, np.float64)
        h = np.asarray(h, np.float64)
    if st.w is not None:
        g = g * st.w[:, None]
        h = h * st.w[:, None]
    if st.sample_rate < 1.0:
        u = np.random.default_rng((st.seed, 1, r)).random(st.n_total)
        st.sample = u[st.base:st.base + n] < st.sample_rate
    else:
        st.sample = None
    st.g_all, st.h_all, st.gh_round = g, h, r


def _apply_routes(st: _GroupState, routes: Dict[str, Any],
                  c0: int, c1: int, n_bins1: int) -> None:
    """Advance node positions one level using the caller's split
    decisions — the same routing arithmetic as the single-node heap."""
    n = st.y.size
    if n == 0:
        return
    bf = np.asarray(routes["bf"], np.int32)
    bb = np.asarray(routes["bb"], np.int32)
    dl = np.asarray(routes["dl"], bool)
    can = np.asarray(routes["can"], bool)
    kp = bf.shape[1]
    lo_p = kp - 1
    rows = np.arange(n)
    for ci in range(c1 - c0):
        pos = st.pos[c0 + ci]
        local = pos - lo_p
        in_lvl = (local >= 0) & (local < kp)
        k = np.clip(local, 0, kp - 1)
        f = bf[ci][k]
        b = st.bins[rows, f]
        go_left = np.where(b >= n_bins1 - 1, dl[ci][k], b <= bb[ci][k])
        child = 2 * (lo_p + k) + np.where(go_left, 1, 2)
        st.pos[c0 + ci] = np.where(
            in_lvl & can[ci][k], child, pos).astype(np.int32)


def _build_partial(st: _GroupState, op: Dict[str, Any]) -> np.ndarray:
    """This group's ``[classes, nodes, F, n_bins1, 3]`` float64 histogram
    partial for one level — small-side nodes only under subtraction."""
    d, c0, c1 = int(op["d"]), int(op["c0"]), int(op["c1"])
    subtract = bool(op.get("subtract")) and d > 0
    k_lvl = 1 << d
    lo = k_lvl - 1
    kb = k_lvl // 2 if subtract else k_lvl
    n = st.y.size
    cb = c1 - c0
    out = np.zeros((cb, kb, st.F, st.n_bins1, 3), np.float64)
    if n == 0 or st.F == 0:
        return out
    sp = np.asarray(op["routes"]["sp"], np.int32) if subtract else None
    for ci in range(cb):
        local = st.pos[c0 + ci] - lo
        in_lvl = (local >= 0) & (local < k_lvl)
        if subtract:
            par = np.clip(local // 2, 0, kb - 1)
            parity = local % 2
            m = in_lvl & (parity == sp[ci][par])
            nodes = par
        else:
            m = in_lvl
            nodes = np.clip(local, 0, k_lvl - 1)
        if st.sample is not None:
            m = m & st.sample
        nm = int(np.count_nonzero(m))
        if nm == 0:
            continue
        flat = ((nodes[m].astype(np.int64)[:, None] * st.F
                 + np.arange(st.F)[None, :]) * st.n_bins1
                + st.bins[m]).ravel()
        rw = st.w[m] if st.w is not None else np.ones(nm, np.float64)
        size = kb * st.F * st.n_bins1
        for ch, v in enumerate((st.g_all[m, c0 + ci] if st.g_all.shape[1] > 1
                                else st.g_all[m, 0],
                                st.h_all[m, c0 + ci] if st.h_all.shape[1] > 1
                                else st.h_all[m, 0],
                                rw)):
            out[ci, :, :, :, ch] = np.bincount(
                flat,
                weights=np.broadcast_to(
                    np.asarray(v, np.float64)[:, None], (nm, st.F)).ravel(),
                minlength=size).reshape(kb, st.F, st.n_bins1)
    return out


def _node_totals(st: _GroupState, op: Dict[str, Any]) -> np.ndarray:
    """Terminal-level ``[classes, nodes, 3]`` G/H/W totals."""
    d, c0, c1 = int(op["d"]), int(op["c0"]), int(op["c1"])
    k_lvl = 1 << d
    lo = k_lvl - 1
    n = st.y.size
    cb = c1 - c0
    out = np.zeros((cb, k_lvl, 3), np.float64)
    if n == 0:
        return out
    for ci in range(cb):
        local = st.pos[c0 + ci] - lo
        m = (local >= 0) & (local < k_lvl)
        if st.sample is not None:
            m = m & st.sample
        nm = int(np.count_nonzero(m))
        if nm == 0:
            continue
        nodes = np.clip(local, 0, k_lvl - 1)[m]
        rw = st.w[m] if st.w is not None else np.ones(nm, np.float64)
        for ch, v in enumerate((st.g_all[m, c0 + ci] if st.g_all.shape[1] > 1
                                else st.g_all[m, 0],
                                st.h_all[m, c0 + ci] if st.h_all.shape[1] > 1
                                else st.h_all[m, 0],
                                rw)):
            out[ci, :, ch] = np.bincount(
                nodes, weights=np.asarray(v, np.float64),
                minlength=k_lvl)[:k_lvl]
    return out


def _apply_op(st: _GroupState, op: Dict[str, Any],
              build: bool = True) -> Optional[np.ndarray]:
    """Execute one protocol op against a group's state.  ``build=False``
    (the replay path) applies routing/margin effects without producing
    any output arrays."""
    kind = op["kind"]
    if kind == "margins":
        return st.margin.copy()
    if kind in ("level", "totals"):
        c0, c1 = int(op["c0"]), int(op["c1"])
        _round_start(st, int(op["r"]))
        routes = op.get("routes")
        if routes is None:
            st.pos[c0:c1] = 0
        else:
            _apply_routes(st, routes, c0, c1, st.n_bins1)
        if not build:
            return None
        return (_build_partial(st, op) if kind == "level"
                else _node_totals(st, op))
    if kind == "fin":
        c0, c1 = int(op["c0"]), int(op["c1"])
        routes = op.get("routes")
        if routes is not None:
            _apply_routes(st, routes, c0, c1, st.n_bins1)
        leaf = np.asarray(op["leaf"], np.float64)
        for ci in range(c1 - c0):
            st.margin[:, c0 + ci] += leaf[ci][st.pos[c0 + ci]]
        if build and op.get("want_margin"):
            return st.margin.copy()
        return None
    raise _rpc.RpcFault(f"unknown hist op kind {kind!r}", code=400)


# ---------------------------------------------------------------------------
# the five handlers (tasks.py wraps these as ctx-DTasks)


def hist_open(payload: Dict[str, Any], cloud, store) -> Dict[str, Any]:
    from h2o3_tpu.cluster import frames as _frames
    if store is None:
        raise _rpc.RpcFault("no chunk store on this member", code=503)
    layout = _frames._layout_for(
        store, payload["frame_key"], payload["stamp"])
    g = int(payload["g"])
    y_name = payload["y_name"]
    w_name = payload.get("w_name")
    off_name = payload.get("off_name")
    preds = list(payload["pred_names"])
    names = [y_name] + preds
    if w_name:
        names.append(w_name)
    if off_name:
        names.append(off_name)
    cols = _frames.columns_from_group(store, layout, g, names)
    y = np.asarray(cols[y_name], np.float64)
    if preds:
        X = np.column_stack(
            [cols[c] for c in preds]).astype(np.float32)
    else:
        X = np.zeros((y.size, 0), np.float32)
    keep = ~np.isnan(y)
    w = off = None
    neg = False
    if w_name:
        w = np.asarray(cols[w_name], np.float64)
        neg = bool(np.any(w < 0))
        keep &= ~np.isnan(w) & (w > 0)
    if off_name:
        off = np.asarray(cols[off_name], np.float64)
        keep &= ~np.isnan(off)
    X, y = X[keep], y[keep]
    if w is not None:
        w = w[keep]
    if off is not None:
        off = off[keep]
    nbins = int(payload["nbins"])
    # per-fit quantile sketches are a pure function of the group's kept
    # rows — identified by (layout stamp, column roles), the same identity
    # the bind cache keys on — so a repeat fit serves them resident too
    sk_token = ("hist_sketch_home", payload["frame_key"], payload["stamp"],
                g, y_name, w_name or "", off_name or "", tuple(preds))

    def _sketch():
        return [sketch_column(X[:, f].astype(np.float64), nbins)
                for f in range(X.shape[1])]

    sketches = _devcache.cached_host(
        "hist_sketch_home", sk_token, nbins, _sketch,
        frame_key=str(payload["frame_key"]))
    st = _GroupState(g)
    st.X, st.y, st.w, st.off = X, y, w, off
    st.last_seq = 0
    _ctx_store(payload["ctx_id"], g, st)
    return {"n": int(y.size), "y": y, "w": w, "off": off,
            "sketches": sketches, "neg_weights": neg}


def _edges_digest(edges: np.ndarray) -> str:
    return hashlib.sha1(
        np.ascontiguousarray(edges, np.float64).tobytes()).hexdigest()


def _bind_codes(st: _GroupState, payload: Dict[str, Any],
                edges: np.ndarray) -> np.ndarray:
    """This group's binned-code matrix, served device-cache-resident.

    Keyed on (frame_key, layout stamp, column roles, group, bin-edges
    digest): the stamp identifies the distributed data state and the edges
    are a pure function of (data, nbins), so a repeat fit on an unmutated
    DistFrame hits — zero ``apply_bins`` decodes, zero upload bytes (the
    miss path's ledger charge never happens). The entry is linked to the
    frame key so a DKV remove/rekey evicts it. Entries are read-only by
    protocol: routing/partials only ever index ``st.bins``."""
    bk = payload.get("bins_key")
    if bk is None:  # replayed pre-cache caller: decode uncached
        _BIND_CACHE.inc(result="miss")
        return np.asarray(apply_bins(st.X, edges))
    token = tuple(tuple(x) if isinstance(x, list) else x for x in bk)
    decoded = []

    def _decode() -> np.ndarray:
        decoded.append(True)
        return np.asarray(apply_bins(st.X, edges))

    bins = _devcache.cached_host(
        "hist_bins_home", token, (st.g, _edges_digest(edges)), _decode,
        frame_key=str(bk[0]))
    _BIND_CACHE.inc(result="miss" if decoded else "hit")
    return bins


def hist_bind(payload: Dict[str, Any], cloud, store) -> Dict[str, Any]:
    st = _ctx_group(payload)
    _check_seq(st, int(payload["seq"]))
    edges = np.asarray(payload["edges"], np.float64)
    st.bins = _bind_codes(st, payload, edges)
    st.X = None
    st.F = int(edges.shape[0])
    st.n_bins1 = int(edges.shape[1]) + 2
    st.base = int(payload["bases"][st.g])
    st.n_total = int(payload["n_total"])
    st.C = int(payload["C"])
    st.objective = str(payload["objective"])
    st.seed = int(payload["seed"])
    st.sample_rate = float(payload["sample_rate"])
    n = st.y.size
    f0 = np.asarray(payload["f0"], np.float64).reshape(-1)
    st.margin = np.tile(f0[None, :], (n, 1))
    if payload.get("use_offset") and st.off is not None:
        st.margin[:, 0] += st.off
    if st.objective == "fixed":
        if st.C > 1:
            t = np.zeros((n, st.C), np.float64)
            if n:
                t[np.arange(n), st.y.astype(np.int64)] = 1.0
        else:
            t = st.y[:, None].astype(np.float64)
        st.targets = t
    st.pos = np.zeros((st.C, n), np.int32)
    st.gh_round = -1
    return {"n": int(n)}


def _meter_level_out(st: _GroupState, op: Dict[str, Any], out) -> None:
    if op["kind"] == "level" and out is not None:
        guard_hist_payload("histogram partial", out.shape[0], out.shape[1],
                           st.F, st.n_bins1)
        _PARTIAL_BYTES.inc(float(out.nbytes))


def hist_level(payload: Dict[str, Any], cloud, store) -> Any:
    st = _ctx_group(payload)
    op = payload["op"]
    seq_fenced = op["kind"] != "margins"
    if seq_fenced:
        _check_seq(st, int(payload["seq"]))
    t0 = time.perf_counter()
    out = _apply_op(st, op, build=True)
    if seq_fenced:
        _ledger.charge(_ledger.HIST_LEVEL_WALL, time.perf_counter() - t0)
    _meter_level_out(st, op, out)
    return out


def hist_levels(payload: Dict[str, Any], cloud, store) -> List[Any]:
    """Batched protocol rounds: apply ``payload["ops"]`` — a list of
    ``{"seq", "op"}`` items in issue order — against one group and return
    the per-op outputs. Each fenced op checks/advances the seq exactly as
    its own ``hist_level`` round would, so the batch converges to the same
    state and the 404/409 -> replay ladder is unchanged (the payload's
    top-level ``seq`` is the first fenced op's, the replay fence point)."""
    st = _ctx_group(payload)
    t0 = time.perf_counter()
    outs: List[Any] = []
    fenced = False
    for item in payload["ops"]:
        op = item["op"]
        if op["kind"] != "margins":
            _check_seq(st, int(item["seq"]))
            fenced = True
        out = _apply_op(st, op, build=True)
        _meter_level_out(st, op, out)
        outs.append(out)
    if fenced:
        _ledger.charge(_ledger.HIST_LEVEL_WALL, time.perf_counter() - t0)
    return outs


def hist_replay(payload: Dict[str, Any], cloud, store) -> Dict[str, Any]:
    if store is None:
        raise _rpc.RpcFault("no chunk store on this member", code=503)
    hist_open(payload["open"], cloud, store)
    st = _ctx_group({"ctx_id": payload["ctx_id"], "g": payload["g"]})
    bind = payload.get("bind")
    if bind is not None:
        st.last_seq = int(bind["seq"]) - 1
        hist_bind(bind, cloud, store)
        for op in payload.get("ops") or []:
            _apply_op(st, op, build=False)
    st.last_seq = int(payload["last_seq"])
    return {"ok": True}


def hist_fin(payload: Dict[str, Any], cloud, store) -> Dict[str, Any]:
    _ctx_drop(payload["ctx_id"])
    return {"ok": True}


_HANDLERS = {
    "hist_open": hist_open,
    "hist_bind": hist_bind,
    "hist_level": hist_level,
    "hist_levels": hist_levels,
    "hist_replay": hist_replay,
    "hist_fin": hist_fin,
}


# ---------------------------------------------------------------------------
# caller-side driver


def use_dist(frame, p, encoding: str) -> bool:
    """Whether a fit over ``frame`` should run the distributed engine:
    chunk-homed frame, knob not ``0``, and no feature the map-side path
    does not implement (those fall back to lazy materialization)."""
    if getattr(frame, "chunk_layout", None) is None:
        return False
    if dist_mode() == "0":
        return False
    if getattr(p, "checkpoint", None):
        return False
    if getattr(p, "monotone_constraints", None):
        return False
    if str(getattr(p, "distribution", "auto")).startswith("custom"):
        return False
    if encoding == "one_hot_explicit":
        return False
    if (getattr(p, "min_child_weight", None) is not None
            or getattr(p, "scale_pos_weight", 1.0) != 1.0):
        return False  # xgboost's hessian floor and class weight
    from h2o3_tpu.models.tree.booster import frontier_start

    if frontier_start(int(getattr(p, "max_depth", 0)), subtract=False) is not None:
        return False  # every level here is a dense histogram of 2^d nodes
    return True


def _data_info_from_layout(layout: Dict[str, Any], y: str,
                           ignored=()) -> DataInfo:
    """A :class:`DataInfo` straight from a chunk layout — the same
    predictor filter as ``build_data_info`` without materializing rows."""
    skip = set(ignored) | {y}
    names = layout["column_names"]
    types = layout["column_types"]
    preds = [n for n, t in zip(names, types)
             if n not in skip and t in (ColType.NUM, ColType.TIME,
                                        ColType.CAT)]
    info = DataInfo(
        predictor_names=preds,
        response_name=y,
        use_all_factor_levels=True,
        standardize=False,
        missing_values_handling="mean_imputation")
    for n in preds:
        t = types[names.index(n)]
        if t is ColType.CAT:
            dom = list(layout["domains"].get(n) or [])
            info.cat_domains[n] = dom
            info.cat_mode[n] = 0
            info.coef_names.extend(f"{n}.{lv}" for lv in dom)
        else:
            info.num_means[n] = 0.0
            info.num_sds[n] = 1.0
            info.coef_names.append(n)
    yt = types[names.index(y)]
    if yt is ColType.CAT:
        info.response_domain = list(layout["domains"].get(y) or [])
    return info


class DistTreeMatrix:
    """The distributed fit's stand-in for the dense feature matrix: owns
    the per-home contexts, fans protocol ops, merges results in canonical
    group order, and walks the replica -> survivor -> caller-local ladder
    when a home dies mid-level."""

    is_dist_hist = True

    def __init__(self, frame, pred_names: List[str], y_name: str,
                 w_name: Optional[str] = None,
                 off_name: Optional[str] = None, nbins: int = 20) -> None:
        from h2o3_tpu.cluster import active_cloud
        from h2o3_tpu.cluster import frames as _frames
        from h2o3_tpu.cluster import tasks as _tasks
        self.frame = frame
        self.layout = frame.chunk_layout
        self.groups = self.layout["groups"]
        self.pred_names = list(pred_names)
        self.y_name = y_name
        self.w_name = w_name
        self.off_name = off_name
        self.nbins = int(nbins)
        store = getattr(frame, "_store", None)
        router = getattr(store, "router", None) if store is not None else None
        # the frame's OWN store/router names the cloud this fit belongs to
        # — with several in-process Clouds the process-global would lie
        cloud = getattr(router, "cloud", None)
        if cloud is None:
            try:
                cloud = active_cloud()
            except Exception:
                cloud = None
        self.cloud = cloud
        if store is None:
            store = _frames._resolve_store(cloud)
        self.store = store
        router = getattr(store, "router", None)
        workers = (_tasks._healthy_workers(cloud)
                   if cloud is not None else [])
        if (dist_mode() == "local" or cloud is None or router is None
                or not router.active() or len(workers) < 2):
            self.mode = "local"
        else:
            self.mode = "dist"
        self.router = router
        with _CTX_LOCK:
            _CTX_COUNTER[0] += 1
            n_fit = _CTX_COUNTER[0]
        self.ctx_id = (f"{self.layout['frame_key']}#{self.layout['stamp']}"
                       f"#{self.mode}#{n_fit}")
        self._seq = 0
        self._oplog: List[Dict[str, Any]] = []
        #: output-free ops (seq already assigned, oplog already appended)
        #: waiting to ride the next output-bearing hist_levels round
        self._pending: List[Dict[str, Any]] = []
        self._batch = _batch_enabled()
        self._bind_common: Optional[Dict[str, Any]] = None
        self._exec_map: Dict[int, str] = {}
        self._timeout = _timeout()
        self._finished = False
        self._ex = (ThreadPoolExecutor(
            max_workers=max(2, len(self.groups)),
            thread_name_prefix="dist-hist")
            if self.mode == "dist" else None)
        self._open()

    # -- protocol -----------------------------------------------------

    def _open(self) -> None:
        self._open_tmpl = [
            {"ctx_id": self.ctx_id,
             "frame_key": self.layout["frame_key"],
             "stamp": self.layout["stamp"],
             "g": gi,
             "y_name": self.y_name,
             "w_name": self.w_name,
             "off_name": self.off_name,
             "pred_names": self.pred_names,
             "nbins": self.nbins,
             "seq": 0}
            for gi in range(len(self.groups))]
        outs = self._fan("hist_open", self._open_tmpl)
        if any(o.get("neg_weights") for o in outs):
            self._finish()
            raise ValueError("weights_column must be non-negative")
        group_n = [int(o["n"]) for o in outs]
        self.bases = np.concatenate(
            [[0], np.cumsum(group_n)]).astype(int)
        self.n_total = int(self.bases[-1])
        self.y_all = np.concatenate(
            [np.asarray(o["y"], np.float64) for o in outs]) \
            if outs else np.empty(0)
        self.w_all = (np.concatenate(
            [np.asarray(o["w"], np.float64) for o in outs])
            if self.w_name else None)
        self.off_all = (np.concatenate(
            [np.asarray(o["off"], np.float64) for o in outs])
            if self.off_name else None)
        F = len(self.pred_names)
        edges = np.empty((F, max(self.nbins - 1, 0)), np.float64)
        for f in range(F):
            edges[f] = merge_edges(
                [o["sketches"][f] for o in outs], self.nbins)
        self.edges = edges
        self.shape = (self.n_total, F)

    def _bind(self, f0: np.ndarray, C: int, objective: str, seed: int,
              sample_rate: float, use_offset: bool) -> None:
        self._seq = 1
        self._bind_common = {
            "ctx_id": self.ctx_id,
            "seq": 1,
            # data identity of the binned codes: homes key their decoded
            # matrix on this + the edges digest so a repeat fit on an
            # unmutated frame re-decodes nothing (see _bind_codes)
            "bins_key": [self.layout["frame_key"], self.layout["stamp"],
                         self.y_name, self.w_name or "", self.off_name or "",
                         list(self.pred_names), int(self.nbins)],
            "edges": self.edges,
            "bases": [int(b) for b in self.bases[:-1]],
            "n_total": self.n_total,
            "f0": np.asarray(f0, np.float64),
            "C": int(C),
            "objective": objective,
            "seed": int(seed),
            "sample_rate": float(sample_rate),
            "use_offset": bool(use_offset)}
        self._fan("hist_bind",
                  [dict(self._bind_common, g=gi)
                   for gi in range(len(self.groups))])

    def _op(self, op: Dict[str, Any]) -> List[Any]:
        seq = self._seq + 1
        self._seq = seq
        self._oplog.append(op)
        if (self._batch and op["kind"] == "fin"
                and not op.get("want_margin")):
            # output-free fin: defer it — the next output-bearing op (the
            # following block's level 0, or the final margins read) ships
            # it in the same hist_levels round, one dispatch + wire trip
            # instead of two. Seq/oplog state is already advanced, so the
            # replay ladder sees exactly the sequential history.
            self._pending.append({"seq": seq, "op": op})
            return []
        return self._flush({"seq": seq, "op": op})

    def _flush(self, item: Dict[str, Any]) -> List[Any]:
        """One protocol round carrying ``item`` (plus any deferred ops):
        a plain ``hist_level`` when nothing is pending, else a batched
        ``hist_levels`` whose outputs list ends with ``item``'s."""
        if not self._pending:
            payloads = [{"ctx_id": self.ctx_id, "g": gi,
                         "seq": item.get("seq", self._seq + 1),
                         "op": item["op"]}
                        for gi in range(len(self.groups))]
            return self._fan("hist_level", payloads)
        items = self._pending + [item]
        self._pending = []
        first_seq = int(items[0]["seq"])
        outs = self._fan("hist_levels",
                         [{"ctx_id": self.ctx_id, "g": gi,
                           "seq": first_seq, "ops": items}
                          for gi in range(len(self.groups))])
        return [o[-1] for o in outs]

    def _margins(self) -> np.ndarray:
        outs = self._flush({"op": {"kind": "margins"}})
        return np.concatenate([np.asarray(o, np.float64) for o in outs],
                              axis=0)

    # -- fan-out / recovery -------------------------------------------

    def _replay_payload(self, gi: int, upto_seq: int) -> Dict[str, Any]:
        bind = (dict(self._bind_common, g=gi)
                if upto_seq >= 2 and self._bind_common is not None
                else None)
        ops = self._oplog[:max(0, upto_seq - 2)]
        return {"ctx_id": self.ctx_id, "g": gi,
                "open": self._open_tmpl[gi],
                "bind": bind, "ops": ops,
                "last_seq": upto_seq - 1}

    def _fan(self, task: str, payloads: List[Dict[str, Any]]) -> List[Any]:
        if self.mode == "local":
            return [self._attempt(gi, "<caller>", task, p)
                    for gi, p in enumerate(payloads)]
        ctx = telemetry.current_trace_context()
        fo = _flight.FANOUTS.begin(task, len(payloads))
        _flight.record(_flight.FANOUT, "info", "schedule", kind=task,
                       groups=len(payloads))

        def _run(gi: int, p: Dict[str, Any]):
            kw: Dict[str, Any] = {"group": gi, "task": task}
            if ctx is not None:
                kw["trace_id"] = ctx["trace_id"]
                kw["parent_id"] = ctx["span_id"]
            with telemetry.Span("hist_group", **kw):
                try:
                    return self._run_group(gi, task, p)
                finally:
                    fo.progress()

        try:
            futs = [self._ex.submit(_run, gi, p)
                    for gi, p in enumerate(payloads)]
            return [f.result() for f in futs]
        finally:
            fo.end()

    def _run_group(self, gi: int, task: str, payload: Dict[str, Any]):
        from h2o3_tpu.cluster import tasks as _tasks
        tried = set()
        sticky = self._exec_map.get(gi)
        if sticky == "<caller>":
            return self._attempt(gi, "<caller>", task, payload)
        if sticky is not None:
            m = next((m for m in _tasks._healthy_workers(self.cloud)
                      if m.info.name == sticky), None)
            if m is not None:
                try:
                    return self._attempt(gi, m, task, payload)
                except (_rpc.RPCError, _rpc.RpcFault):
                    tried.add(sticky)
        anchor = self.groups[gi]["anchor"]
        cands = (self.router.home_members(anchor, MAX_REPLICAS)
                 if self.router is not None else [])
        rungs = []
        if cands:
            rungs.append(("home", cands[0]))
            rungs.extend(("replica", m) for m in cands[1:])
        cand_names = {m.info.name for m in cands}
        my_name = self.cloud.info.name
        rungs.extend(
            ("survivor", m)
            for m in _tasks._healthy_workers(self.cloud)
            if m.info.name not in cand_names and m.info.name != my_name)
        for path, m in rungs:
            name = m.info.name
            if name in tried:
                continue
            tried.add(name)
            try:
                out = self._attempt(gi, m, task, payload)
            except (_rpc.RPCError, _rpc.RpcFault):
                continue
            if path != "home":
                _tasks._RECOVERED.inc(path=path)
                _flight.record(_flight.RECOVERY, "warn", "hist_group",
                               path=path, group=gi, task=task,
                               member=name)
            self._exec_map[gi] = name
            return out
        out = self._attempt(gi, "<caller>", task, payload)
        _tasks._RECOVERED.inc(path="local")
        _flight.record(_flight.RECOVERY, "warn", "hist_group",
                       path="local", group=gi, task=task)
        self._exec_map[gi] = "<caller>"
        return out

    def _attempt(self, gi: int, member, task: str, payload: Dict[str, Any]):
        from h2o3_tpu.cluster import tasks as _tasks

        def _send(t: str, p: Dict[str, Any]):
            if member == "<caller>" or (
                    self.cloud is not None
                    and member.info.name == self.cloud.info.name):
                return _HANDLERS[t](p, self.cloud, self.store)
            return _tasks.submit(self.cloud, member, t, p,
                                 timeout=self._timeout)

        try:
            return _send(task, payload)
        except (_rpc.RpcFault, _rpc.RemoteError) as e:
            code = getattr(e, "code", None)
            if task == "hist_open" or code not in (404, 409):
                raise
            _send("hist_replay",
                  self._replay_payload(gi, int(payload["seq"])))
            return _send(task, payload)

    def _finish(self) -> None:
        if self._finished:
            return
        self._finished = True
        from h2o3_tpu.cluster import tasks as _tasks
        payload = {"ctx_id": self.ctx_id}
        if self.mode == "dist" and self.cloud is not None:
            seen = set()
            workers = {m.info.name: m
                       for m in _tasks._healthy_workers(self.cloud)}
            for gi in range(len(self.groups)):
                name = self._exec_map.get(gi)
                if name is None or name in seen:
                    continue
                seen.add(name)
                try:
                    if name == "<caller>":
                        hist_fin(payload, self.cloud, self.store)
                    elif name in workers:
                        self._attempt(gi, workers[name],
                                      "hist_fin", payload)
                except Exception:
                    pass
        _ctx_drop(self.ctx_id)
        if self._ex is not None:
            self._ex.shutdown(wait=False)


# ---------------------------------------------------------------------------
# fit-setup fronts (the distributed analogues of tree_fit_setup)


def dist_fit_setup(frame, p, model_cls, use_offset: bool):
    """The distributed analogue of ``tree_fit_setup``: model + DataInfo
    straight from the chunk layout, aux vectors from the one-time open
    gather — rows never materialize on the caller."""
    from h2o3_tpu.models.tree import common as _common
    ignored = list(getattr(p, "ignored_columns", ()) or ())
    if p.weights_column:
        ignored.append(p.weights_column)
    if use_offset and getattr(p, "offset_column", None):
        ignored.append(p.offset_column)
    info = _data_info_from_layout(
        frame.chunk_layout, p.response_column, ignored)
    nclasses = (len(info.response_domain)
                if info.response_domain else 1)
    dist = p.distribution
    if dist == "auto":
        dist = _common.auto_distribution(nclasses)
    model = model_cls(p, info, dist)
    Xd = DistTreeMatrix(
        frame, info.predictor_names, p.response_column,
        w_name=p.weights_column or None,
        off_name=(getattr(p, "offset_column", None) or None)
        if use_offset else None,
        nbins=p.nbins)
    try:
        objective = _common.resolve_objective(dist, p, Xd.y_all)
        f0 = _common.init_margin(objective, Xd.y_all, nclasses,
                                 weights=Xd.w_all)
    except Exception:
        Xd._finish()
        raise
    n_class_trees = nclasses if dist == "multinomial" else 1
    return (model, Xd, Xd.y_all, Xd.w_all, Xd.off_all,
            objective, f0, n_class_trees, None)


def dist_drf_front(frame, p, model_cls):
    """DRF's front half over a chunk-homed frame: model + DataInfo +
    aux vectors, targets built caller-side from ``y_all``."""
    ignored = list(getattr(p, "ignored_columns", ()) or ())
    if p.weights_column:
        ignored.append(p.weights_column)
    info = _data_info_from_layout(
        frame.chunk_layout, p.response_column, ignored)
    nclasses = (len(info.response_domain)
                if info.response_domain else 1)
    model = model_cls(p, info, "gaussian")
    Xd = DistTreeMatrix(
        frame, info.predictor_names, p.response_column,
        w_name=p.weights_column or None, nbins=p.nbins)
    return model, Xd, Xd.y_all, Xd.w_all, nclasses


# ---------------------------------------------------------------------------
# the distributed training driver


def train_boosted_dist(Xd: DistTreeMatrix, objective: str, y, n_class_trees,
                       init_margin, params, average: bool = False,
                       monitor=None, score_interval: int = 1,
                       weights=None, offset=None, fit_eval=None):
    """``train_boosted`` over a :class:`DistTreeMatrix`: the level loop
    fans ``hist_level`` ops, merges float64 partials in canonical group
    order, and runs the existing ``_split_search`` caller-side — the
    result is a plain :class:`BoostedTrees`, with the fit's final margins
    beside the rows ``fit_eval`` names (as ``train_boosted`` leaves them),
    so the training metrics materialize no rows."""
    from h2o3_tpu.models.tree import booster as _booster
    p = params
    n_bins1 = p.nbins + 1
    C = int(n_class_trees)
    F = Xd.shape[1]
    try:
        if Xd.off_all is not None and C != 1:
            raise ValueError(
                "offset_column requires a single-margin objective")
        subtract = _booster._tree_subtract_enabled() and p.max_depth > 0
        D = p.max_depth
        cb = min(C, max(1, _booster.tree_block_size()))
        if D > 0:
            worst = (max(1, 1 << max(D - 2, 0)) if subtract
                     else 1 << (D - 1))
            guard_hist_payload("histogram partial", cb, worst, F, n_bins1)
        f0 = np.broadcast_to(
            np.asarray(init_margin, np.float64).reshape(-1), (C,)).copy()
        _FITS.inc(mode=Xd.mode)
        with telemetry.Span("dist_tree_fit", mode=Xd.mode,
                            groups=len(Xd.groups), trees=int(p.ntrees),
                            classes=C, rows=int(Xd.n_total)):
            Xd._bind(f0, C, objective, p.seed, p.sample_rate,
                     use_offset=Xd.off_all is not None)
            trees_per_class = [
                _booster.Trees(D, n_bins1, Xd.edges) for _ in range(C)]
            built = 0

            def _level_op(op):
                outs = Xd._op(op)
                _LEVELS.inc()
                return outs

            def one_block(r, c0, c1, feat_mask, want_margin):
                cb_n = c1 - c0
                heaps = [([], [], [], [], []) for _ in range(cb_n)]
                routes = None
                prev = [None] * cb_n
                for d in range(D):
                    k_lvl = 1 << d
                    op = {"kind": "level", "r": r, "d": d,
                          "c0": c0, "c1": c1,
                          "subtract": bool(subtract), "routes": routes}
                    parts = _level_op(op)
                    merged = np.zeros_like(np.asarray(parts[0], np.float64))
                    for part in parts:
                        merged = merged + np.asarray(part, np.float64)
                    bf_l, bb_l, dl_l, can_l, ls_l = [], [], [], [], []
                    prev_new = [None] * cb_n
                    for ci in range(cb_n):
                        if subtract and d > 0:
                            small = merged[ci]
                            pv = prev[ci]
                            can_m = pv["can"][:, None, None, None]
                            big = np.where(can_m, pv["hist"] - small, 0.0)
                            ls_m = pv["ls"][:, None, None, None]
                            left = np.where(ls_m, small, big)
                            right = np.where(ls_m, big, small)
                            hist_ci = np.stack(
                                [left, right], axis=1).reshape(
                                    k_lvl, F, n_bins1, 3)
                        else:
                            hist_ci = merged[ci]
                        if p.mtries > 0:
                            u = np.random.default_rng(
                                (p.seed, 3, r, c0 + ci, d)).random(
                                    (k_lvl, F))
                            th = np.sort(u, axis=1)[:, p.mtries - 1][:, None]
                            fm = (u <= th) & feat_mask[None, :]
                        else:
                            fm = feat_mask
                        out = _booster._split_search(
                            jnp.asarray(hist_ci.astype(np.float32)),
                            jnp.float32(p.reg_lambda),
                            jnp.float32(p.reg_alpha),
                            jnp.float32(p.gamma),
                            jnp.float32(p.learn_rate),
                            jnp.asarray(fm),
                            min_rows=float(p.min_rows),
                            n_bins1=n_bins1,
                            child_stats=True)
                        bf, bb, dl, gain, leaf, bwl, bwr, ls = (
                            np.asarray(v) for v in out)
                        can = ((gain > max(p.min_split_improvement, 0.0))
                               & np.isfinite(gain))
                        hf, hb, hdl, hsp, hlf = heaps[ci]
                        hf.append(bf.astype(np.int32))
                        hb.append(bb.astype(np.int32))
                        hdl.append(dl.astype(bool))
                        hsp.append(can.astype(bool))
                        hlf.append(leaf.astype(np.float32))
                        bf_l.append(bf.astype(np.int32))
                        bb_l.append(bb.astype(np.int32))
                        dl_l.append(dl.astype(bool))
                        can_l.append(can.astype(bool))
                        ls_l.append(ls.astype(bool))
                        prev_new[ci] = {
                            "hist": hist_ci, "can": can, "ls": ls,
                            "wl": np.asarray(bwl, np.float64),
                            "wr": np.asarray(bwr, np.float64)}
                    prev = prev_new
                    routes = {"bf": np.stack(bf_l), "bb": np.stack(bb_l),
                              "dl": np.stack(dl_l), "can": np.stack(can_l)}
                    if subtract:
                        routes["sp"] = np.where(
                            np.stack(ls_l), 0, 1).astype(np.int32)
                # terminal level
                k_term = 1 << D
                leaves = []
                if subtract and D > 0:
                    term_routes = routes
                    for ci in range(cb_n):
                        raw = np.stack(
                            [prev[ci]["wl"], prev[ci]["wr"]],
                            axis=1).reshape(k_term)
                        leaves.append(
                            np.float32(p.learn_rate)
                            * raw.astype(np.float32))
                else:
                    op = {"kind": "totals", "r": r, "d": D,
                          "c0": c0, "c1": c1, "routes": routes}
                    parts = _level_op(op)
                    tot = np.zeros_like(np.asarray(parts[0], np.float64))
                    for part in parts:
                        tot = tot + np.asarray(part, np.float64)
                    for ci in range(cb_n):
                        G = tot[ci, :, 0]
                        H = tot[ci, :, 1]
                        t = np.sign(G) * np.maximum(
                            np.abs(G) - p.reg_alpha, 0.0)
                        raw = -t / np.maximum(H + p.reg_lambda, 1e-12)
                        leaves.append(
                            np.float32(p.learn_rate)
                            * raw.astype(np.float32))
                    term_routes = None
                leaf_heap = []
                for ci in range(cb_n):
                    hf, hb, hdl, hsp, hlf = heaps[ci]
                    hf.append(np.zeros(k_term, np.int32))
                    hb.append(np.zeros(k_term, np.int32))
                    hdl.append(np.zeros(k_term, bool))
                    hsp.append(np.zeros(k_term, bool))
                    hlf.append(leaves[ci])
                    leaf_heap.append(np.concatenate(hlf))
                fin = {"kind": "fin", "r": r, "c0": c0, "c1": c1,
                       "routes": term_routes,
                       "leaf": np.stack(leaf_heap).astype(np.float64),
                       "want_margin": bool(want_margin)}
                outs = Xd._op(fin)
                for ci in range(cb_n):
                    hf, hb, hdl, hsp, hlf = heaps[ci]
                    trees_per_class[c0 + ci].append(
                        np.concatenate(hf), np.concatenate(hb),
                        np.concatenate(hdl), np.concatenate(hsp),
                        np.concatenate(hlf))
                if want_margin:
                    return np.concatenate(
                        [np.asarray(o, np.float64) for o in outs], axis=0)
                return None

            stop = False
            margin_host = None
            for r in range(p.ntrees):
                if p.col_sample_rate_per_tree < 1.0:
                    u = np.random.default_rng((p.seed, 2, r)).random(F)
                    ncols = max(
                        1, int(round(p.col_sample_rate_per_tree * F)))
                    th = np.sort(u)[ncols - 1]
                    feat_mask = u <= th
                else:
                    feat_mask = np.ones(F, bool)
                want = monitor is not None and (
                    (built + 1) % score_interval == 0
                    or built + 1 == p.ntrees)
                blocks = [(c0, min(c0 + cb, C))
                          for c0 in range(0, C, cb)]
                margin_host = None
                for bi, (c0, c1) in enumerate(blocks):
                    out = one_block(r, c0, c1, feat_mask,
                                    want and bi == len(blocks) - 1)
                    if out is not None:
                        margin_host = out
                built += 1
                if monitor is not None and margin_host is not None:
                    if monitor(built - 1, margin_host):
                        stop = True
                if stop:
                    break

            margin_score = Xd._margins()
            if average and built > 0:
                margin_score = (f0[None, :]
                                + (margin_score - f0[None, :]) / built)
        bt = _booster.BoostedTrees(
            trees_per_class, np.asarray(init_margin, np.float64), p,
            average=average)
        if fit_eval is not None:
            bt.fit_eval = dict(fit_eval, margin=margin_score)
        return bt
    finally:
        Xd._finish()
