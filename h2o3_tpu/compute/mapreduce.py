"""The compute primitive: shard_map + psum ≡ MRTask map + tree-reduce.

Reference: ``new MRTask(){ map(Chunk[]); reduce(T); }.doAll(frame)``
(``water/MRTask.java:15-64,391``) — fan out over the node tree, map each home
chunk, reduce partials pairwise back up the tree (``MRTask.java:96-127``).

TPU-native: the node tree and hand-rolled reduction disappear. A user map
function runs per device shard under ``shard_map`` and partials are combined
with ``lax.psum`` — XLA emits the log-depth reduction over ICI natively.
Everything above this layer (rollups, metrics, GLM Gram, tree histograms,
KMeans assignments, …) is expressed in terms of these two calls, exactly the
way everything in the reference sits on MRTask (SURVEY.md §1).

Two entry points:
  * ``map_reduce(fn, table)``   — fn: (cols, mask) -> pytree of partials; psum'd.
  * ``map_batches(fn, table)``  — fn: (cols, mask) -> per-row outputs; stays sharded
    (the analogue of an MRTask producing NewChunks / outputFrame).

Caching (the DrJAX accounting gap, PAPERS.md): repeat dispatches must not
pay trace+compile again, and repeat placements must not pay host->mesh
transfer again. Two levels close it:
  * a *dispatch plan cache* memoizes the jitted ``shard_map`` program keyed
    on (fn identity, reduce op, mesh, argument shapes/dtypes/treedef) —
    re-dispatching the same fn over same-shaped data reuses the compiled
    executable instead of rebuilding ``jax.jit(mapped)`` per call;
  * ``FrameTable.from_frame`` memoizes the whole device placement in the
    process-wide :data:`h2o3_tpu.frame.devcache.DEVCACHE`, keyed on column
    version stamps, and ``matrix()`` caches its stacked design matrix.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from h2o3_tpu.frame.devcache import (
    DEVCACHE,
    REQUESTS as _DEVCACHE_REQUESTS,
    frame_token,
    mesh_fingerprint,
)
from h2o3_tpu.frame.frame import ColType, Frame
from h2o3_tpu.parallel.mesh import DATA_AXIS, default_mesh, row_mask, shard_rows
from h2o3_tpu.util import ledger as _ledger
from h2o3_tpu.util import telemetry

#: per-primitive accounting (DrJAX's point for MapReduce-in-JAX: you cannot
#: place sharded work without counting it) — op is map_reduce | map_batches
_DISPATCHES = telemetry.counter(
    "mapreduce_dispatch_total", "MRTask-analogue dispatches", labels=("op",)
)
_SHARDS = telemetry.gauge(
    "mapreduce_shards", "shard count of the most recent dispatch",
    labels=("op",),
)
_WALL = telemetry.histogram(
    "mapreduce_wall_seconds",
    "dispatch wall time (trace + compile + execute + device sync)",
    labels=("op",),
)
_JIT_CACHE = telemetry.counter(
    "mapreduce_jit_cache_total",
    "XLA compile-cache outcome per dispatch (compile-count delta)",
    labels=("op", "result"),
)
_PLAN_CACHE = telemetry.counter(
    "mapreduce_plan_cache_total",
    "compiled shard_map plan reuse per dispatch",
    labels=("op", "result"),
)
_PLAN_EVICTIONS = telemetry.counter(
    "mapreduce_plan_evictions_total",
    "dispatch plans dropped from the LRU plan cache",
)


# ---------------------------------------------------------------------------
# dispatch plan cache: (fn, reduce, mesh, arg signature) -> jitted program


def _plan_cache_size() -> int:
    try:
        return max(1, int(os.environ.get("H2O3_TPU_PLAN_CACHE_SIZE", 128)))
    except ValueError:
        return 128


_plans: "OrderedDict[Tuple, Callable]" = OrderedDict()
_plans_lock = threading.Lock()


def _leaf_sig(x) -> Tuple:
    """Hashable trace signature of one argument leaf: arrays by
    shape+dtype (jit programs depend on avals, not values), python
    scalars by type (weak-typed scalars trace identically per type)."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("arr", tuple(x.shape), str(x.dtype))
    return ("py", type(x).__name__)


def _plan_key(op: str, fn: Callable, reduce: str, table: "FrameTable",
              extra_args: tuple) -> Optional[Tuple]:
    """Cache key for the jitted shard_map program, or None when the
    dispatch is uncacheable (unhashable fn). The entry holds ``fn``
    strongly, so a key can never alias a dead function's identity.

    Deliberately NOT weakref-keyed: the cached plan closes over ``fn``
    (shard_fn wraps it for retracing), so a weak key could never fire —
    the entry itself is what keeps fn alive. The cost is that up to
    H2O3_TPU_PLAN_CACHE_SIZE callables (+ captured closures) stay pinned
    until LRU-evicted; callers dispatching per-call closures over large
    captured arrays should prefer passing those arrays as extra_args."""
    leaves, treedef = jax.tree.flatten(tuple(extra_args))
    key = (
        op, fn, reduce, table.mesh,
        tuple((k, tuple(v.shape), str(v.dtype))
              for k, v in sorted(table.arrays.items())),
        _leaf_sig(table.mask),
        treedef,
        tuple(_leaf_sig(leaf) for leaf in leaves),
    )
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _get_plan(op: str, fn: Callable, reduce: str, table: "FrameTable",
              extra_args: tuple, build: Callable[[], Callable]) -> Callable:
    key = _plan_key(op, fn, reduce, table, extra_args)
    if key is None:
        _PLAN_CACHE.inc(op=op, result="uncacheable")
        return build()
    with _plans_lock:
        plan = _plans.get(key)
        if plan is not None:
            _plans.move_to_end(key)
            _PLAN_CACHE.inc(op=op, result="hit")
            return plan
    _PLAN_CACHE.inc(op=op, result="miss")
    _ledger.charge(_ledger.PLAN_CACHE_MISSES, 1)
    plan = build()
    with _plans_lock:
        existing = _plans.get(key)
        if existing is not None:
            return existing  # lost a build race: converge on one program
        _plans[key] = plan
        limit = _plan_cache_size()
        while len(_plans) > limit:
            _plans.popitem(last=False)
            _PLAN_EVICTIONS.inc()
    return plan


def plan_memo(namespace: str, key: Tuple, build: Callable[[], object]):
    """Generic entry point into the dispatch plan cache for callers that
    assemble their own compiled programs (the rapids fusion pass memoizes
    lowered column-programs here keyed on canonical S-expression + input
    schema). Shares the LRU — and its budget and eviction accounting — with
    the shard_map dispatch plans; evicting a fused plan also retires the
    jitted program it holds, since map_batches keys on the program's
    function identity."""
    full = ("memo", namespace, key)
    with _plans_lock:
        hit = _plans.get(full)
        if hit is not None:
            _plans.move_to_end(full)
            _PLAN_CACHE.inc(op=namespace, result="hit")
            return hit
    _PLAN_CACHE.inc(op=namespace, result="miss")
    _ledger.charge(_ledger.PLAN_CACHE_MISSES, 1)
    value = build()
    with _plans_lock:
        existing = _plans.get(full)
        if existing is not None:
            return existing  # lost a build race: converge on one plan
        _plans[full] = value
        limit = _plan_cache_size()
        while len(_plans) > limit:
            _plans.popitem(last=False)
            _PLAN_EVICTIONS.inc()
    return value


def _dispatch(op: str, table: "FrameTable", call):
    """Shared accounting envelope: count + span + jit hit/miss attribution."""
    telemetry.install_jax_compile_listener()
    n_shards = int(table.mesh.devices.size)
    _DISPATCHES.inc(op=op)
    _SHARDS.set(n_shards, op=op)
    # thread-local delta: compiles run on the dispatching thread, so this
    # stays correct when several builds dispatch concurrently
    compiles_before = telemetry.thread_compile_count()
    compile_secs_before = telemetry.thread_compile_seconds()
    t0 = time.perf_counter()
    with telemetry.Span("mapreduce", op=op, shards=n_shards,
                        rows=table.n_valid):
        out = call()
        # charge inside the span so the delta lands on the mapreduce
        # span_id; compiles run on the dispatching thread, so the
        # thread-local delta is this dispatch's own compile bill
        compile_secs = (telemetry.thread_compile_seconds()
                        - compile_secs_before)
        if compile_secs > 0.0:
            _ledger.charge(_ledger.COMPILE_SECONDS, compile_secs)
    _WALL.observe(time.perf_counter() - t0, op=op)
    missed = telemetry.thread_compile_count() > compiles_before
    _JIT_CACHE.inc(op=op, result="miss" if missed else "hit")
    return out


class FrameTable:
    """Device-resident, row-sharded view of (a subset of) a Frame.

    Columns are float32 by default (the TPU-native compute dtype; float64 on
    request for e.g. exact Gram accumulation), padded to a multiple of the
    mesh size, with a boolean validity ``mask`` for the pad rows.
    """

    def __init__(
        self,
        arrays: Dict[str, jax.Array],
        mask: jax.Array,
        n_valid: int,
        mesh: Mesh,
    ) -> None:
        self.arrays = arrays
        self.mask = mask
        self.n_valid = n_valid
        self.mesh = mesh
        # cached tables are process-shared: concurrent first matrix() calls
        # must not double-build (and double byte-account) the stack
        self._matrix_lock = threading.Lock()
        self._matrix_cache: Dict[Tuple[str, ...], jax.Array] = {}
        #: devcache key when this table is cache-resident — stacked
        #: matrices built on it are byte-attributed to that entry
        self._devcache_key: Optional[Tuple] = None

    @staticmethod
    def from_frame(
        frame: Frame,
        columns: Optional[Sequence[str]] = None,
        mesh: Optional[Mesh] = None,
        dtype=jnp.float32,
        cache: bool = True,
    ) -> "FrameTable":
        """Device-resident view of ``frame``, memoized process-wide.

        Placement is cached in :data:`~h2o3_tpu.frame.devcache.DEVCACHE`
        keyed on (column versions, dtype, mesh), so repeat calls on an
        unmutated frame return the SAME resident table — no re-upload, no
        new ``shard_bytes_total``. ``cache=False`` forces a fresh upload."""
        mesh = mesh or default_mesh()
        np_dtype = np.dtype(dtype)  # normalize jnp/np scalar types once
        names = list(columns) if columns is not None else [
            c.name for c in frame.columns if c.type not in (ColType.STR, ColType.UUID)
        ]
        if not names:
            raise ValueError("no device-shardable (numeric/categorical/time) columns")

        def build() -> "FrameTable":
            arrays: Dict[str, jax.Array] = {}
            n = frame.nrows
            for name in names:
                host = frame.col(name).numeric_view().astype(np_dtype)
                arr, n = shard_rows(host, mesh, fill=np.nan)
                arrays[name] = arr
            some = next(iter(arrays.values()))
            mask = row_mask(n, some.shape[0], mesh)
            return FrameTable(arrays, mask, n, mesh)

        token = frame_token(frame, names) if cache else None
        if token is None:
            return build()
        key = ("frame_table", token, str(np_dtype), mesh_fingerprint(mesh))
        table = DEVCACHE.get_or_put(
            key, build, frame_key=getattr(frame, "key", None),
            kind="frame_table",
        )
        table._devcache_key = key
        return table

    @property
    def n_padded(self) -> int:
        return next(iter(self.arrays.values())).shape[0]

    def matrix(self, columns: Optional[Sequence[str]] = None) -> jax.Array:
        """[N_pad, F] feature matrix (column-stacked, row-sharded).

        The stacked matrix is cached per column tuple: with the table
        itself cached, repeat fits stack (and re-place) nothing."""
        names = tuple(columns) if columns is not None else tuple(self.arrays)
        with self._matrix_lock:
            cached = self._matrix_cache.get(names)
        if cached is not None:
            _DEVCACHE_REQUESTS.inc(kind="table_matrix", result="hit")
            return cached
        _DEVCACHE_REQUESTS.inc(kind="table_matrix", result="miss")
        # stack OUTSIDE the lock: a device dispatch while holding a lock
        # other threads contend is the deadlock class _SHARD_EXEC_LOCK
        # exists to prevent; the insert below re-checks like _get_plan
        m = jnp.stack([self.arrays[n] for n in names], axis=1)
        with self._matrix_lock:
            cur = self._matrix_cache.get(names)
            if cur is not None:
                return cur  # lost the stack race; the winner is cached
            self._matrix_cache[names] = m
            if self._devcache_key is not None:
                # a stacked matrix on a cache-resident table is resident
                # device memory: fold it into the entry so the budget sees it
                DEVCACHE.grow_entry(self._devcache_key, int(m.nbytes))
        return m


#: valid ``map_reduce(reduce=...)`` choices -> the collective combiner
_REDUCERS = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin}


def map_reduce(
    fn: Callable,
    table: FrameTable,
    *extra_args,
    reduce: str = "sum",
):
    """Run ``fn(cols_dict, mask, *extra)`` per shard; psum/pmax/pmin partials.

    ``fn`` must be jax-traceable and return a pytree of arrays whose shapes do
    not depend on the shard content (static shapes — the SPMD contract).
    The returned pytree is fully reduced and replicated on every device.
    Repeat dispatches of the same ``fn`` over same-shaped arguments reuse
    the compiled program via the plan cache (zero re-trace/re-compile).
    """
    if reduce not in _REDUCERS:
        raise ValueError(
            f"unknown reduce {reduce!r}; valid choices: {sorted(_REDUCERS)}"
        )

    def build() -> Callable:
        red = _REDUCERS[reduce]

        def shard_fn(arrays, mask, *extras):
            part = fn(arrays, mask, *extras)
            return jax.tree.map(lambda x: red(x, DATA_AXIS), part)

        mapped = _shard_map(
            shard_fn,
            mesh=table.mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS)) + tuple(P() for _ in extra_args),
            out_specs=P(),
        )
        return jax.jit(mapped)

    jitted = _get_plan("map_reduce", fn, reduce, table, extra_args, build)
    return _dispatch(
        "map_reduce",
        table,
        lambda: jitted(table.arrays, table.mask, *extra_args),
    )


def map_batches(fn: Callable, table: FrameTable, *extra_args):
    """Run ``fn(cols_dict, mask, *extra)`` per shard, keep outputs row-sharded.

    The analogue of an MRTask writing NewChunks into an output Frame
    (``water/MRTask.java:558-559`` outputFrame)."""

    def build() -> Callable:
        mapped = _shard_map(
            fn,
            mesh=table.mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS)) + tuple(P() for _ in extra_args),
            out_specs=P(DATA_AXIS),
        )
        return jax.jit(mapped)

    jitted = _get_plan("map_batches", fn, "shard", table, extra_args, build)
    return _dispatch(
        "map_batches",
        table,
        lambda: jitted(table.arrays, table.mask, *extra_args),
    )


def gather_rows(x: jax.Array, n_valid: int) -> np.ndarray:
    """Pull a row-sharded device result back to host, dropping pad rows."""
    return np.asarray(jax.device_get(x))[:n_valid]


def map_reduce_frame(
    fn: Callable,
    frame: Frame,
    columns: Optional[Sequence[str]] = None,
    reduce: str = "sum",
):
    """Cluster-aware MRTask entry: ``map_reduce`` over a Frame that fans
    contiguous row ranges out to the members of a live multi-node
    application-plane cloud (h2o3_tpu/cluster/tasks.py), each member
    running the local shard_map+psum path over its range.  With no cloud
    — or a cloud of one — this is exactly the local path.  Returns the
    reduced pytree as HOST (numpy) arrays in both cases, so callers see
    one contract regardless of where the shards ran."""
    layout = getattr(frame, "chunk_layout", None)
    if columns is not None:
        names = list(columns)
    elif layout is not None:
        # metadata off the layout: listing a chunk-homed frame's numeric
        # columns must not gather its remote chunks
        names = [n for n, t in zip(layout["column_names"],
                                   layout["column_types"])
                 if t not in (ColType.STR, ColType.UUID)]
    else:
        names = [c.name for c in frame.columns
                 if c.type not in (ColType.STR, ColType.UUID)]
    try:
        from h2o3_tpu.cluster import active_cloud

        cloud = active_cloud()
    except Exception:
        cloud = None
    # span both paths under one kind: a trace reads identically whether the
    # shards ran on this node's mesh or fanned out over the cloud, and the
    # distributed path's member/RPC child spans hang underneath
    with telemetry.Span("map_reduce_frame", rows=int(frame.nrows),
                        columns=len(names), distributed=cloud is not None):
        if cloud is not None and layout is not None:
            # chunk-homed frame: map-side execution on each group's ring
            # home, only partials cross the wire (cluster/frames.py)
            from h2o3_tpu.cluster.frames import map_reduce_chunk_homed

            return map_reduce_chunk_homed(
                fn, frame, reduce=reduce, cloud=cloud, names=names)
        if cloud is None:
            table = FrameTable.from_frame(frame, columns=names)
            out = map_reduce(fn, table, reduce=reduce)
            return jax.tree.map(np.asarray, out)
        from h2o3_tpu.cluster.tasks import distributed_map_reduce

        host = {n: frame.col(n).numeric_view() for n in names}
        return distributed_map_reduce(fn, host, reduce=reduce, cloud=cloud)
