"""Device frame cache + dispatch plan cache — the PR 3 caching tentpole.

Acceptance (ISSUE 3): with the cache warm, a second identical map_reduce
dispatch records result="hit" with ZERO new XLA compiles, and a second
GLM/GBM fit on the same unmutated frame adds 0 to shard_bytes_total.
Mutation through rapids assign / as_factor / column append re-uploads;
KeyedStore remove/clear evict; the byte budget evicts LRU-first.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from h2o3_tpu import Frame
from h2o3_tpu.compute.mapreduce import FrameTable, map_reduce
from h2o3_tpu.frame import devcache
from h2o3_tpu.frame.devcache import DEVCACHE, DeviceFrameCache, frame_token
from h2o3_tpu.keyed import DKV
from h2o3_tpu.util import telemetry

# models register themselves in the DKV; the module-level sweeper
# removes everything at module end
pytestmark = pytest.mark.leaks_keys


def _counter(name, **labels):
    m = telemetry.REGISTRY.get(name)
    return m.value(**labels) if m is not None else 0.0


def _frame(rng, n=4000):
    return Frame.from_dict({
        "a": rng.normal(size=n),
        "b": rng.normal(size=n),
        "y": rng.normal(size=n),
    })


def _sum_a(cols, mask):
    # module-level fn: repeat dispatches share one plan-cache identity
    return jnp.sum(jnp.where(mask & ~jnp.isnan(cols["a"]), cols["a"], 0.0))


# ---------------------------------------------------------------------------
# version stamps


class TestVersionStamps:
    def test_invalidate_rollups_bumps_version(self):
        fr = Frame.from_dict({"x": [1.0, 2.0]})
        v0 = fr.col("x").version
        fr.col("x").invalidate_rollups()
        assert fr.col("x").version > v0
        assert fr.version == (fr.col("x").version,)

    def test_rapids_assign_changes_token(self):
        from h2o3_tpu.rapids import Session, exec_rapids

        s = Session()
        fr = Frame.from_dict({"a": [1.0, 2.0, 3.0], "b": [4.0, 5.0, 6.0]})
        s.assign("devc_fr", fr)
        t0 = frame_token(fr)
        out = exec_rapids("(:= devc_fr 99 [0] [0:2])", s).as_frame()
        assert frame_token(out) != t0
        assert frame_token(fr) == t0  # source frame untouched
        s.end()

    def test_as_factor_and_append_change_token(self):
        fr = Frame.from_dict({"a": [1.0, 2.0, 1.0], "b": [0.0, 1.0, 0.0]})
        t0 = frame_token(fr)
        fr2 = fr.add_column(fr.col("b").as_factor())
        assert frame_token(fr2) != t0
        from h2o3_tpu.frame.frame import Column, ColType

        fr3 = fr.add_column(Column("c", np.zeros(3), ColType.NUM))
        assert frame_token(fr3) != t0


# ---------------------------------------------------------------------------
# FrameTable placement cache + warm dispatch


class TestFrameTableCache:
    def test_from_frame_hit_is_same_table_no_upload(self, mesh, rng):
        fr = _frame(rng)
        before = _counter("shard_bytes_total")
        t1 = FrameTable.from_frame(fr, mesh=mesh)
        uploaded = _counter("shard_bytes_total") - before
        assert uploaded > 0
        t2 = FrameTable.from_frame(fr, mesh=mesh)
        assert t2 is t1
        assert _counter("shard_bytes_total") - before == uploaded  # no re-up
        # matrix() caches its stacked matrix on the (cached) table
        assert t1.matrix() is t1.matrix()

    def test_mutation_forces_reupload(self, mesh, rng):
        fr = _frame(rng)
        t1 = FrameTable.from_frame(fr, mesh=mesh)
        old_device_a = t1.arrays["a"]
        fr.col("a").data[0] = 123.0
        fr.col("a").invalidate_rollups()  # the mutating-path contract
        before = _counter("shard_bytes_total")
        t2 = FrameTable.from_frame(fr, mesh=mesh)
        assert t2 is not t1
        assert t2.arrays["a"] is not old_device_a
        assert _counter("shard_bytes_total") > before
        assert float(np.asarray(t2.arrays["a"])[0]) == 123.0

    def test_warm_dispatch_zero_recompiles(self, mesh, rng):
        """ISSUE acceptance: second identical dispatch -> plan + jit cache
        hits and a compile-listener delta of exactly zero."""
        telemetry.install_jax_compile_listener()
        fr = _frame(rng)
        t = FrameTable.from_frame(fr, mesh=mesh)
        cold = float(map_reduce(_sum_a, t))
        hits0 = _counter("mapreduce_jit_cache_total",
                         op="map_reduce", result="hit")
        plan0 = _counter("mapreduce_plan_cache_total",
                         op="map_reduce", result="hit")
        compiles0 = telemetry.thread_compile_count()
        warm = float(map_reduce(_sum_a, t))
        assert warm == cold
        assert telemetry.thread_compile_count() - compiles0 == 0
        assert _counter("mapreduce_jit_cache_total",
                        op="map_reduce", result="hit") == hits0 + 1
        assert _counter("mapreduce_plan_cache_total",
                        op="map_reduce", result="hit") == plan0 + 1

    def test_unknown_reduce_raises_value_error(self, mesh, rng):
        t = FrameTable.from_frame(_frame(rng), mesh=mesh)
        with pytest.raises(ValueError, match="valid choices.*max.*min.*sum"):
            map_reduce(_sum_a, t, reduce="bogus")


# ---------------------------------------------------------------------------
# model fits: second fit uploads nothing


class TestWarmFits:
    def test_second_glm_fit_adds_zero_shard_bytes(self, mesh, rng):
        from h2o3_tpu.models.glm import GLM

        fr = _frame(rng, n=1500)
        m1 = GLM(response_column="y", lambda_=0.0).train(fr)
        before = _counter("shard_bytes_total")
        m2 = GLM(response_column="y", lambda_=0.0).train(fr)
        assert _counter("shard_bytes_total") == before
        assert m1.coefficients == pytest.approx(m2.coefficients)
        # mutated frame re-uploads
        fr.col("a").invalidate_rollups()
        GLM(response_column="y", lambda_=0.0).train(fr)
        assert _counter("shard_bytes_total") > before

    def test_second_gbm_fit_hits_tree_bins_cache(self, mesh, rng):
        from h2o3_tpu.models.tree.gbm import GBM

        fr = _frame(rng, n=800)
        GBM(response_column="y", ntrees=2, max_depth=3, seed=5).train(fr)
        hit0 = _counter("devcache_requests_total",
                        kind="tree_bins", result="hit")
        shard0 = _counter("shard_bytes_total")
        GBM(response_column="y", ntrees=2, max_depth=3, seed=5).train(fr)
        assert _counter("devcache_requests_total",
                        kind="tree_bins", result="hit") == hit0 + 1
        assert _counter("shard_bytes_total") == shard0


# ---------------------------------------------------------------------------
# lifecycle eviction + budget


class TestEviction:
    def test_dkv_remove_evicts_placements(self, mesh, rng):
        fr = _frame(rng)
        fr.key = "devc_evict.hex"
        DKV.put(fr.key, fr)
        FrameTable.from_frame(fr, mesh=mesh)
        token = frame_token(fr)
        assert any(k[1] == token for k in DEVCACHE._entries
                   if k[0] == "frame_table")
        ev0 = _counter("devcache_evictions_total", reason="invalidate")
        DKV.remove(fr.key)
        assert not any(k[1] == token for k in DEVCACHE._entries)
        assert _counter("devcache_evictions_total",
                        reason="invalidate") == ev0 + 1

    def test_rekey_evicts_old_registration(self, mesh, rng):
        fr = _frame(rng)
        fr.key = "devc_rekey.hex"
        DKV.put(fr.key, fr)
        FrameTable.from_frame(fr, mesh=mesh)
        token = frame_token(fr)
        DKV.rekey(fr, "devc_rekey2.hex")
        assert not any(k[1] == token for k in DEVCACHE._entries)
        DKV.remove("devc_rekey2.hex")

    def test_store_clear_empties_devcache(self, mesh, rng):
        # a scratch store, NOT the global DKV (clearing that mid-suite
        # would wipe persisted Jobs); KeyedStore.clear drops the whole
        # device tier regardless of which store instance nukes the world
        from h2o3_tpu.keyed import KeyedStore

        store = KeyedStore()
        fr = _frame(rng)
        store.put("devc_clear.hex", fr)
        FrameTable.from_frame(fr, mesh=mesh)
        assert len(DEVCACHE) > 0
        store.clear()
        assert len(DEVCACHE) == 0

    def test_budget_lru_eviction(self):
        cache = DeviceFrameCache(max_bytes=100)
        a = np.zeros(10, dtype=np.float64)  # 80 bytes
        b = np.ones(10, dtype=np.float64)
        c = np.full(10, 2.0)
        cache.get_or_put(("k1",), lambda: a, kind="test")
        cache.get_or_put(("k2",), lambda: b, kind="test")  # evicts k1 (LRU)
        assert ("k1",) not in cache._entries
        assert ("k2",) in cache._entries
        # touching k2 then inserting keeps k2 the newest... LRU is insertion
        # + access ordered: hit k2, insert k3 -> k2 evicted? no: k2 touched
        assert cache.get_or_put(("k2",), lambda: b, kind="test") is b
        cache.get_or_put(("k3",), lambda: c, kind="test")
        # over budget again: the oldest (k2) goes, newest (k3) stays
        assert ("k3",) in cache._entries
        assert cache.stats()["bytes"] <= 100 or len(cache._entries) == 1

    def test_single_oversized_entry_stays_usable(self):
        cache = DeviceFrameCache(max_bytes=8)
        big = np.zeros(100)
        assert cache.get_or_put(("big",), lambda: big, kind="test") is big
        assert cache.get_or_put(("big",), lambda: big, kind="test") is big

    def test_matrix_bytes_attributed_to_entry(self, mesh, rng):
        fr = _frame(rng)
        t = FrameTable.from_frame(fr, mesh=mesh)
        before = DEVCACHE.stats()["bytes"]
        m = t.matrix()
        # the stacked matrix on a cache-resident table must be visible to
        # the byte budget (review finding: silent undercount)
        assert DEVCACHE.stats()["bytes"] >= before + int(m.nbytes)
        t.matrix()  # cached: no double counting
        assert DEVCACHE.stats()["bytes"] < before + 2 * int(m.nbytes)

    def test_set_max_bytes_shrinks(self):
        cache = DeviceFrameCache(max_bytes=10_000)
        for i in range(4):
            cache.get_or_put((f"k{i}",), lambda: np.zeros(100), kind="test")
        cache.set_max_bytes(900)  # one 800-byte entry fits
        assert len(cache) == 1


# ---------------------------------------------------------------------------
# apply_bins vectorization (satellite)


class TestApplyBins:
    @staticmethod
    def _reference(X, edges):
        n, F = X.shape
        nbins = edges.shape[1] + 1
        out = np.empty((n, F), dtype=np.int32)
        for f in range(F):
            out[:, f] = np.searchsorted(edges[f], X[:, f], side="right")
            out[np.isnan(X[:, f]), f] = nbins
        return out

    def test_matches_reference_with_na_inf_ties(self, rng):
        from h2o3_tpu.ops.histogram import apply_bins, make_bins

        X = rng.normal(size=(3000, 6))
        X[:, 1] = rng.integers(0, 3, size=3000)  # low cardinality
        X[:, 2] = 1.5                            # constant
        X[::7, 3] = np.nan
        X[::11, 4] = np.inf
        X[::13, 4] = -np.inf
        X[::17, 5] = -0.0
        edges = make_bins(X, nbins=16)
        assert np.array_equal(apply_bins(X, edges), self._reference(X, edges))
        # values exactly on edges (tie semantics: side='right')
        Xe = np.repeat(edges[:6, 3:4].T, 5, axis=0)
        assert np.array_equal(apply_bins(Xe, edges[:6]),
                              self._reference(Xe, edges[:6]))

    def test_a_feature_a_thread_matches_reference(self, rng, monkeypatch):
        """Past ``_BIN_THREADS_MIN_CELLS`` a feature is binned on a thread of
        its own: the same codes, NA and a categorical column among them."""
        from h2o3_tpu.ops import histogram

        monkeypatch.setattr(histogram, "_BIN_THREADS_MIN_CELLS", 1000)
        X = rng.normal(size=(5000, 7))
        X[::7, 3] = np.nan
        X[:, 6] = rng.integers(0, 5, size=5000)
        X[::9, 6] = np.nan
        X[::10, 6] = 7.0  # a level the fit did not know
        edges = histogram.make_bins(X, nbins=16)
        assert np.array_equal(histogram.apply_bins(X, edges), self._reference(X, edges))
        cats = (0, 0, 0, 0, 0, 0, 5)
        want = self._reference(X, edges)
        known = (X[:, 6] >= 0) & (X[:, 6] < 5)
        want[:, 6] = np.where(known, X[:, 6], histogram.na_code(16, cats))
        want[np.isnan(X[:, 3]), 3] = histogram.na_code(16, cats)
        assert np.array_equal(histogram.apply_bins(X, edges, cats), want)

    def test_batched_wide_path_matches_reference(self, rng):
        from h2o3_tpu.ops.histogram import _apply_bins_batched, apply_bins

        X = rng.normal(size=(4, 200))  # wide-short: batched dispatch
        X[0, 5] = np.nan
        edges = np.sort(rng.normal(size=(200, 9)), axis=1)
        assert np.array_equal(apply_bins(X, edges),
                              self._reference(X, edges))
        raw = _apply_bins_batched(X, edges)
        want = self._reference(X, edges)
        want_no_na = want.copy()
        want_no_na[0, 5] = np.searchsorted(edges[5], np.nan, side="right")
        assert np.array_equal(raw, want_no_na)

    def test_empty_shapes(self):
        from h2o3_tpu.ops.histogram import apply_bins

        edges = np.array([[0.0, 1.0]])
        assert apply_bins(np.empty((0, 1)), edges).shape == (0, 1)
