"""Device-side distributed sort / searchsorted / groupby aggregation.

Reference: ``water/rapids/RadixOrder.java:20,74-85`` (MSB radix partition
of keys across the cluster, per-partition local order) and
``BinaryMerge.java`` (batched merges of sorted key ranges between nodes);
``AstGroup``'s distributed reduction. The reference moves ragged key
ranges between JVMs over its RPC; that shape is hostile to XLA, so the
TPU-native design is a **sample sort** with static shapes:

  1. each shard sorts its rows locally (``lax.sort``),
  2. evenly-spaced key samples are ``all_gather``-ed and D-1 splitters
     chosen (the MSB-partition analogue — data-driven, so skew that
     would starve fixed MSB buckets balances automatically),
  3. every shard scatters its rows into D capacity-S send buffers
     (S = rows/shard, so a destination can NEVER overflow: each of the
     D sources contributes at most S rows) and one ``all_to_all``
     exchanges them over ICI,
  4. each shard merges what it received with one more local sort.

Keys are order-preserving uint64 encodings split into (hi, lo) uint32
lanes (x64 stays off); ties break on the original row id, which both
makes the sort stable and lets multi-column sorts compose LSD-style
exactly like the host ``lexsort``.

Group-by aggregation needs no sort at all: it is a segment reduction,
so each shard computes ``segment_sum`` partials over the group codes
and one ``psum`` combines them (MRTask shape, ``compute/mapreduce.py``).

The host paths in ``merge.py``/``groupby.py`` remain the small-N fast
path and the parity oracle (tests assert device == host).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from h2o3_tpu.parallel.mesh import DATA_AXIS, default_mesh, pad_rows

#: below this many rows the host numpy paths win on latency; overridable
#: for tests and for TPU slices where the crossover sits lower
DIST_SORT_MIN = int(os.environ.get("H2O3_TPU_DIST_SORT_MIN", 262_144))

_SENT_HI = np.uint32(0xFFFFFFFF)
_SENT_LO = np.uint32(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# key encoding: float64 / int codes -> order-preserving uint64 -> (hi, lo)


def encode_f64(x: np.ndarray, ascending: bool = True,
               na_first: bool = True) -> np.ndarray:
    """Order-preserving uint64 image of float64 (the radix key transform,
    RadixOrder's byte-order trick): flip sign bit for positives, all bits
    for negatives; NaN pinned to the low (or high) end."""
    x = np.ascontiguousarray(x, dtype=np.float64)
    x = x + 0.0  # canonicalize -0.0 == +0.0, matching the host oracle
    u = x.view(np.uint64).copy()
    neg = (u >> np.uint64(63)) != 0
    u[neg] = ~u[neg]
    u[~neg] |= np.uint64(1) << np.uint64(63)
    if not ascending:
        u = ~u
    nan = np.isnan(x)
    # reserve the extreme values for NA so it sorts first (Merge.sort
    # semantics: NA = -Inf) regardless of direction
    u[nan] = np.uint64(0) if na_first else np.uint64(0xFFFFFFFFFFFFFFFE)
    return u


def split_u64(u: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    return ((u >> np.uint64(32)).astype(np.uint32),
            (u & np.uint64(0xFFFFFFFF)).astype(np.uint32))


# ---------------------------------------------------------------------------
# distributed argsort (sample sort over the mesh)


@partial(jax.jit, static_argnames=("mesh_size", "n_samples"))
def _sample_sort_program(hi, lo, idx, *, mesh_size: int, n_samples: int):
    """The SPMD program: hi/lo/idx are [Npad] row-sharded; returns
    [Npad * mesh_size]-per-shard (stacked: [D, D*S]) sorted (idx, hi, lo)."""
    mesh = default_mesh(mesh_size)
    D = mesh_size

    def shard_fn(hi_s, lo_s, idx_s):
        S = hi_s.shape[0]
        # 1. local sort (idx as final key => deterministic + stable)
        hi_l, lo_l, idx_l = jax.lax.sort(
            (hi_s, lo_s, idx_s), num_keys=3)
        # 2. splitters from gathered evenly-spaced samples
        pos = (jnp.arange(n_samples) * S) // n_samples
        samp_hi = jax.lax.all_gather(hi_l[pos], DATA_AXIS).reshape(-1)
        samp_lo = jax.lax.all_gather(lo_l[pos], DATA_AXIS).reshape(-1)
        samp_hi, samp_lo = jax.lax.sort((samp_hi, samp_lo), num_keys=2)
        cut = (jnp.arange(1, D) * (D * n_samples)) // D
        sp_hi, sp_lo = samp_hi[cut], samp_lo[cut]  # [D-1]
        # 3. destination shard per row: count of splitters < key
        gt = (hi_l[:, None] > sp_hi[None, :]) | (
            (hi_l[:, None] == sp_hi[None, :]) & (lo_l[:, None] > sp_lo[None, :]))
        dest = jnp.sum(gt, axis=1).astype(jnp.int32)  # [S] in [0, D)
        # position within destination group (dest is sorted ascending
        # because the rows are key-sorted): pos = i - first_i_with_my_dest
        counts = jnp.bincount(dest, length=D)
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  jnp.cumsum(counts)[:-1].astype(jnp.int32)])
        within = jnp.arange(S, dtype=jnp.int32) - starts[dest]
        # scatter into [D, S] send buffers, sentinel-padded
        buf_hi = jnp.full((D, S), _SENT_HI, jnp.uint32).at[dest, within].set(hi_l)
        buf_lo = jnp.full((D, S), _SENT_LO, jnp.uint32).at[dest, within].set(lo_l)
        buf_ix = jnp.full((D, S), -1, jnp.int32).at[dest, within].set(idx_l)
        # 4. one all_to_all moves bucket d of every shard onto shard d
        r_hi = jax.lax.all_to_all(buf_hi, DATA_AXIS, 0, 0, tiled=False)
        r_lo = jax.lax.all_to_all(buf_lo, DATA_AXIS, 0, 0, tiled=False)
        r_ix = jax.lax.all_to_all(buf_ix, DATA_AXIS, 0, 0, tiled=False)
        # 5. merge the D received runs; sentinels sort last
        m_hi, m_lo, m_ix = jax.lax.sort(
            (r_hi.reshape(-1), r_lo.reshape(-1), r_ix.reshape(-1)),
            num_keys=3)
        return (m_ix[None, :], m_hi[None, :], m_lo[None, :])

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS, None),) * 3,
        check_vma=False,
    )(hi, lo, idx)


def device_argsort_u64(keys: np.ndarray,
                       mesh_size: Optional[int] = None) -> np.ndarray:
    """Global stable argsort of uint64 keys on the device mesh."""
    mesh = default_mesh(mesh_size)
    D = mesh.devices.size
    n = len(keys)
    padded, _ = pad_rows(keys, D, fill=np.uint64(0xFFFFFFFFFFFFFFFF))
    hi, lo = split_u64(padded)
    idx = np.arange(len(padded), dtype=np.int32)
    idx[n:] = -1
    sh = NamedSharding(mesh, P(DATA_AXIS))
    m_ix, m_hi, m_lo = _sample_sort_program(
        jax.device_put(hi, sh), jax.device_put(lo, sh),
        jax.device_put(idx, sh),
        mesh_size=D, n_samples=max(1, min(64, len(padded) // D)))
    out = np.asarray(m_ix).reshape(-1)
    return out[out >= 0].astype(np.int64)


def device_lexsort(keys: Sequence[np.ndarray],
                   mesh_size: Optional[int] = None) -> np.ndarray:
    """np.lexsort-compatible (last key primary) via LSD passes of the
    stable device sort: each pass sorts one column with the previous
    pass's rank as the tiebreak id."""
    order = device_argsort_u64(np.asarray(keys[0], dtype=np.uint64),
                               mesh_size)
    for k in keys[1:]:
        k = np.asarray(k, dtype=np.uint64)
        # stable: tiebreak on current rank, then map ranks back to rows
        sub = device_argsort_u64(k[order], mesh_size)
        order = order[sub]
    return order


# ---------------------------------------------------------------------------
# distributed searchsorted (the probe side of the sort-merge join)


def _pair_less(th, tl, qh, ql, or_equal: bool):
    lt = (th < qh) | ((th == qh) & (tl < ql))
    if or_equal:
        lt = lt | ((th == qh) & (tl == ql))
    return lt


def _pair_bisect(thi, tlo, qh, ql, or_equal: bool):
    """Binary search for one (qh, ql) pair in the sorted pair table —
    the single probe body both searchsorted programs share."""
    N = thi.shape[0]

    def cond(state):
        lft, rgt = state
        return lft < rgt

    def body(state):
        lft, rgt = state
        mid = (lft + rgt) // 2
        go_right = _pair_less(thi[mid], tlo[mid], qh, ql, or_equal)
        return jnp.where(go_right, mid + 1, lft), \
            jnp.where(go_right, rgt, mid)

    lft, _ = jax.lax.while_loop(cond, body, (jnp.int32(0), jnp.int32(N)))
    return lft


@partial(jax.jit, static_argnames=("mesh_size", "side"))
def _searchsorted_program(thi, tlo, qhi, qlo, *, mesh_size: int,
                          side: str):
    """uint64 keys live as (hi, lo) uint32 pairs (x64 off), so the probe
    is an explicit vmapped binary search on pairs; the table is
    replicated, the queries row-sharded (every node probes its rows —
    BinaryMerge's binary-search leg)."""
    mesh = default_mesh(mesh_size)
    or_equal = side == "right"

    def shard_fn(qh, ql):
        return jax.vmap(
            lambda a, b: _pair_bisect(thi, tlo, a, b, or_equal))(qh, ql)

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )(qhi, qlo)


@partial(jax.jit, static_argnames=("mesh_size",))
def _searchsorted_both_program(thi, tlo, qhi, qlo, *, mesh_size: int):
    """Both probe sides in ONE program: a large join would otherwise
    ship the table + queries to the mesh twice."""
    mesh = default_mesh(mesh_size)

    def shard_fn(qh, ql):
        lo = jax.vmap(
            lambda a, b: _pair_bisect(thi, tlo, a, b, False))(qh, ql)
        hi = jax.vmap(
            lambda a, b: _pair_bisect(thi, tlo, a, b, True))(qh, ql)
        return lo, hi

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        check_vma=False,
    )(qhi, qlo)


def _prep_probe(sorted_keys, queries, mesh):
    D = mesh.devices.size
    qpad, _ = pad_rows(np.asarray(queries, np.uint64), D)
    thi, tlo = split_u64(np.asarray(sorted_keys, np.uint64))
    qhi, qlo = split_u64(qpad)
    sh = NamedSharding(mesh, P(DATA_AXIS))
    return (jnp.asarray(thi), jnp.asarray(tlo),
            jax.device_put(qhi, sh), jax.device_put(qlo, sh))


def device_searchsorted(sorted_keys: np.ndarray, queries: np.ndarray,
                        side: str = "left",
                        mesh_size: Optional[int] = None) -> np.ndarray:
    """Probe a replicated sorted uint64 key vector with mesh-sharded
    uint64 queries; matches np.searchsorted(sorted_keys, queries, side)."""
    mesh = default_mesh(mesh_size)
    n = len(queries)
    thi, tlo, qhi, qlo = _prep_probe(sorted_keys, queries, mesh)
    out = _searchsorted_program(
        thi, tlo, qhi, qlo, mesh_size=mesh.devices.size, side=side)
    return np.asarray(out)[:n].astype(np.int64)


def device_searchsorted_both(
    sorted_keys: np.ndarray, queries: np.ndarray,
    mesh_size: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """(left, right) insertion points in one device round trip."""
    mesh = default_mesh(mesh_size)
    n = len(queries)
    thi, tlo, qhi, qlo = _prep_probe(sorted_keys, queries, mesh)
    lo, hi = _searchsorted_both_program(
        thi, tlo, qhi, qlo, mesh_size=mesh.devices.size)
    return (np.asarray(lo)[:n].astype(np.int64),
            np.asarray(hi)[:n].astype(np.int64))


# ---------------------------------------------------------------------------
# distributed group-by aggregation (segment reduction + psum)


@partial(jax.jit, static_argnames=("mesh_size", "num_groups"))
def _segment_agg_program(codes, vals, valid, *, mesh_size: int,
                         num_groups: int):
    """codes/vals/valid row-sharded; vals pre-cleaned (no NaN); valid
    already excludes padding AND NA rows."""
    mesh = default_mesh(mesh_size)

    def shard_fn(c, v, m):
        w = m.astype(jnp.float32)
        vw = v * w
        ones = jax.ops.segment_sum(w, c, num_segments=num_groups)
        s = jax.ops.segment_sum(vw, c, num_segments=num_groups)
        s2 = jax.ops.segment_sum(v * vw, c, num_segments=num_groups)
        big = jnp.where(m, v, jnp.inf)
        small = jnp.where(m, v, -jnp.inf)
        mn = jax.ops.segment_min(big, c, num_segments=num_groups)
        mx = jax.ops.segment_max(small, c, num_segments=num_groups)
        ones = jax.lax.psum(ones, DATA_AXIS)
        s = jax.lax.psum(s, DATA_AXIS)
        s2 = jax.lax.psum(s2, DATA_AXIS)
        mn = jax.lax.pmin(mn, DATA_AXIS)
        mx = jax.lax.pmax(mx, DATA_AXIS)
        return ones, s, s2, mn, mx

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS),) * 3,
        out_specs=(P(),) * 5,
        check_vma=False,
    )(codes, vals, valid)


def device_group_aggregate(
    codes: np.ndarray, values: np.ndarray, num_groups: int,
    mesh_size: Optional[int] = None,
) -> Dict[str, np.ndarray]:
    """Per-group {count, sum, sumsq, min, max, nacnt} of one value column
    over mesh-sharded rows. NaN values count into nacnt and are excluded
    from the moments (AstGroup ignore-NA aggregation). float32 on device
    (TPU-native accumulate; callers needing exact f64 moments use the
    host path — the parity tests bound the difference)."""
    mesh = default_mesh(mesh_size)
    D = mesh.devices.size
    n = len(codes)
    codes = np.asarray(codes, np.int32)
    values = np.asarray(values, np.float64)
    cpad, _ = pad_rows(codes, D)
    vpad, _ = pad_rows(values, D)
    nan_in = np.isnan(vpad)
    valid = np.zeros(len(cpad), dtype=bool)
    valid[:n] = True
    sh = NamedSharding(mesh, P(DATA_AXIS))
    ones, s, s2, mn, mx = _segment_agg_program(
        jax.device_put(cpad, sh),
        jax.device_put(np.nan_to_num(vpad).astype(np.float32), sh),
        jax.device_put(valid & ~nan_in, sh),
        mesh_size=D, num_groups=num_groups)
    na_counts = np.bincount(
        codes[np.isnan(values)], minlength=num_groups
    ).astype(np.float64)
    return {
        "count": np.asarray(ones, dtype=np.float64),
        "sum": np.asarray(s, dtype=np.float64),
        "sumsq": np.asarray(s2, dtype=np.float64),
        "min": np.asarray(mn, dtype=np.float64),
        "max": np.asarray(mx, dtype=np.float64),
        "nacnt": na_counts,
    }
