"""Distributed (map-side histogram) tree training over chunk homes.

The contract under test (h2o3_tpu/models/tree/dist_hist.py): when the
training frame is a chunk-homed DistFrame, GBM/DRF/XGBoost build each
tree level map-side — grad/hess and histograms computed on the rows'
homes, only ``(feature, bin, {Σg, Σh, Σw})`` partials crossing the wire
— and the result is BIT-IDENTICAL to running the same engine entirely
on the caller (``H2O3_TPU_DIST_HIST=local``), at a fixed seed, with or
without histogram subtraction, and through a home's refusal/death
mid-level (replica ladder + seq-fenced context replay).

The multi-run seeded-verdict version of the death drill lives in
scripts/chaos.py (``kill_hist_home``); here each invariant asserts once.
"""

import pickle
import threading
import time

import numpy as np
import pytest

from h2o3_tpu.cluster import dkv as cdkv
from h2o3_tpu.cluster import rpc as crpc
from h2o3_tpu.cluster import tasks as ctasks
from h2o3_tpu.cluster.frames import DistFrame
from h2o3_tpu.cluster.membership import Cloud, set_local_cloud
from h2o3_tpu.frame.parse import _iter_body_chunks, parse_setup
from h2o3_tpu.keyed import KeyedStore
from h2o3_tpu.models.grid import metric_value
from h2o3_tpu.models.tree import dist_hist
from h2o3_tpu.models.tree.drf import DRF, DRFParameters
from h2o3_tpu.models.tree.gbm import GBM, GBMParameters
from h2o3_tpu.models.tree.xgboost import XGBoost, XGBoostParameters

pytestmark = pytest.mark.leaks_keys

RESPONSES = ("reg", "bin", "multi")


def _csv(n=6000):
    """Deterministic integer-valued features (exact under any partition
    order) + a CAT feature + one response column per family."""
    f = [np.arange(n) % p for p in (97, 31, 13, 7, 53, 23)]
    cats = ("lo", "mid", "hi")
    bins = ("no", "yes")
    multis = ("a", "b", "c")
    lines = ["x0,x1,x2,x3,x4,x5,c,reg,bin,multi"]
    for i in range(n):
        s = (f[0][i] * 3 + f[1][i]) % 11
        lines.append(
            f"{f[0][i]},{f[1][i]},{f[2][i]},{f[3][i]},{f[4][i]},{f[5][i]},"
            f"{cats[i % 3]},{s}.0,{bins[int(s < 4)]},{multis[s % 3]}")
    return "\n".join(lines) + "\n"


def _wait_for(cond, timeout=15.0, every=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(every)
    pytest.fail(f"timed out after {timeout}s waiting for {msg}")


def _form_cloud(n, prefix):
    clouds = []
    for i in range(n):
        c = Cloud("disttree", f"{prefix}{i}", hb_interval=0.05)
        s = KeyedStore()
        cdkv.install(c, s)
        ctasks.install(c)
        clouds.append(c)
    seeds = [c.info.addr for c in clouds]
    for c in clouds:
        c.start([a for a in seeds if a != c.info.addr])
    _wait_for(lambda: all(c.size() == n for c in clouds),
              msg=f"{n}-node cloud formation")
    return clouds


def _stop_all(clouds):
    for c in clouds:
        try:
            c.stop()
        except Exception:
            pass


def _parse_to_homes(cloud, key):
    text = _csv()
    setup = parse_setup(text)
    chunks = list(_iter_body_chunks(
        [text.encode()], 16384, setup.header, setup.skip_blank_lines))
    fr = ctasks.distributed_parse_chunks(chunks, setup, cloud=cloud, key=key)
    assert isinstance(fr, DistFrame)
    assert len({g["home_name"] for g in fr.chunk_layout["groups"]}) >= 2
    return fr


@pytest.fixture(scope="module")
def homed():
    """A formed 3-node cloud + a CSV parsed ONTO the ring."""
    clouds = _form_cloud(3, "dt")
    set_local_cloud(clouds[0])
    try:
        fr = _parse_to_homes(clouds[0], "dist_tree_df")
        yield clouds, fr
    finally:
        set_local_cloud(None)
        _stop_all(clouds)


def _params(algo, resp):
    ignored = [r for r in RESPONSES if r != resp]
    common = dict(response_column=resp, ignored_columns=ignored,
                  ntrees=3, max_depth=3, min_rows=1.0, seed=11)
    if algo == "gbm":
        return GBM(GBMParameters(nbins=12, **common))
    if algo == "drf":
        return DRF(DRFParameters(nbins=12, sample_rate=0.7, **common))
    return XGBoost(XGBoostParameters(nbins=12, **common))


def _fit(algo, resp, fr):
    return _params(algo, resp).train(fr)


def _sig(model):
    """Leaderboard-relevant bytes: every tree array + training metric."""
    bt = model.booster
    arrays = [
        np.stack(getattr(t, f))
        for t in bt.trees_per_class
        for f in ("feat", "split_bin", "default_left", "is_split", "leaf")
    ]
    return pickle.dumps([arrays, np.asarray(bt.init_margin),
                         metric_value(model, "auto")[0]])


def _counter(name, **labels):
    from h2o3_tpu.util import telemetry

    c = telemetry.REGISTRY.get(name)
    if c is None:
        return 0.0
    return c.value(**labels) if labels else c.total()


def _wire_bytes():
    """Data-plane wire bytes: everything but the heartbeats and replica
    checks, which tick in the background by the clock whatever the
    workload (as ``test_rapids_dist._data_wire_bytes`` leaves them out)."""
    from h2o3_tpu.util import telemetry

    c = telemetry.REGISTRY.get("rpc_payload_bytes_total")
    if c is None:
        return 0.0
    return sum(s["value"] for s in c.snapshot()["series"]
               if s["labels"].get("method") not in ("heartbeat", "dkv_replica_check"))


# ---------------------------------------------------------------------------
# the bit-identity matrix


class TestBitIdentity:
    @pytest.mark.parametrize("algo", ["gbm", "drf", "xgb"])
    @pytest.mark.parametrize("resp", ["reg", "bin", "multi"])
    def test_dist_matches_local(self, homed, monkeypatch, algo, resp):
        clouds, fr = homed
        monkeypatch.setenv("H2O3_TPU_DIST_HIST", "local")
        ref = _fit(algo, resp, fr)
        monkeypatch.setenv("H2O3_TPU_DIST_HIST", "1")
        t0 = _counter("dist_hist_fits_total", mode="dist")
        dist = _fit(algo, resp, fr)
        assert _counter("dist_hist_fits_total", mode="dist") == t0 + 1, (
            "fit did not take the distributed fan-out path")
        assert _sig(dist) == _sig(ref)

    @pytest.mark.parametrize("subtract", ["0", "1"])
    def test_subtract_modes(self, homed, monkeypatch, subtract):
        clouds, fr = homed
        monkeypatch.setenv("H2O3_TPU_TREE_SUBTRACT", subtract)
        monkeypatch.setenv("H2O3_TPU_DIST_HIST", "local")
        ref = _fit("gbm", "multi", fr)
        monkeypatch.setenv("H2O3_TPU_DIST_HIST", "1")
        assert _sig(_fit("gbm", "multi", fr)) == _sig(ref)


# ---------------------------------------------------------------------------
# wire discipline: partials cross, rows never do


def test_partials_only(homed, monkeypatch):
    clouds, fr = homed
    lay = fr.chunk_layout
    frame_bytes = 8 * int(lay["espc"][-1]) * len(lay["column_names"])
    monkeypatch.setenv("H2O3_TPU_DIST_HIST", "1")
    # the first fit on a cloud pays its one-time traffic (1.2-1.4 MB here);
    # measured is a fit after it, whichever test a worker runs first
    _fit("gbm", "bin", fr)
    levels0 = _counter("dist_hist_levels_total")
    partial0 = _counter("dist_hist_partial_bytes_total")
    wire0 = _wire_bytes()
    _fit("gbm", "bin", fr)
    wire = _wire_bytes() - wire0
    levels = _counter("dist_hist_levels_total") - levels0
    partial = _counter("dist_hist_partial_bytes_total") - partial0
    assert levels > 0
    # per level, each home ships at most n_nodes x F x n_bins1 x 3 x 8
    # (one class block at depth<=3: <=4 frontier nodes)
    n_homes = len(lay["groups"])
    n_feat = 7  # x0..x5 + c
    n_bins1 = 12 + 1  # interior edges + NA bin
    per_level_cap = 4 * n_feat * n_bins1 * 3 * 8 * n_homes
    assert partial <= levels * per_level_cap
    # total wire (requests + responses, incl. the y gather and the model's
    # puts) stays well under shipping the frame to the members
    assert wire < frame_bytes / 2


# ---------------------------------------------------------------------------
# warm fits ride the device cache: zero re-decode, zero re-upload


def test_warm_fit_reuses_resident_bins(homed, monkeypatch):
    """A second fit on an unmutated DistFrame must serve every home's
    binned codes and sketches from the device cache: zero apply_bins
    decodes, zero upload-charging misses — one bind-cache hit per group."""
    clouds, fr = homed
    monkeypatch.setenv("H2O3_TPU_DIST_HIST", "1")
    _fit("gbm", "reg", fr)  # cold at most once; later fits must be warm
    n_groups = len(fr.chunk_layout["groups"])
    hit0 = _counter("dist_hist_bind_cache_total", result="hit")
    miss0 = _counter("dist_hist_bind_cache_total", result="miss")
    up0 = _counter("devcache_requests_total",
                   kind="hist_bins_home", result="miss")
    sk0 = _counter("devcache_requests_total",
                   kind="hist_sketch_home", result="miss")
    _fit("gbm", "reg", fr)
    assert _counter("dist_hist_bind_cache_total", result="miss") == miss0, (
        "warm fit re-decoded binned codes")
    assert _counter("dist_hist_bind_cache_total",
                    result="hit") == hit0 + n_groups
    assert _counter("devcache_requests_total", kind="hist_bins_home",
                    result="miss") == up0, "warm fit re-uploaded binned codes"
    assert _counter("devcache_requests_total", kind="hist_sketch_home",
                    result="miss") == sk0, "warm fit re-sketched columns"


# ---------------------------------------------------------------------------
# batched level rounds


def test_batched_rounds_bit_identical(homed, monkeypatch):
    """Coalescing output-free fin ops into hist_levels multi-op rounds
    must not move a single bit — and must actually batch (>=2 ops per
    round) when enabled."""
    clouds, fr = homed
    monkeypatch.setenv("H2O3_TPU_DIST_HIST", "1")
    monkeypatch.setenv("H2O3_TPU_DIST_HIST_BATCH", "0")
    ref = _sig(_fit("gbm", "bin", fr))

    calls = {"n": 0}
    real = dist_hist.hist_levels

    def counting(payload, cloud, store):
        assert len(payload["ops"]) >= 2, "single-op round routed to batch op"
        calls["n"] += 1
        return real(payload, cloud, store)

    monkeypatch.setenv("H2O3_TPU_DIST_HIST_BATCH", "1")
    monkeypatch.setattr(dist_hist, "hist_levels", counting)
    monkeypatch.setitem(dist_hist._HANDLERS, "hist_levels", counting)
    assert _sig(_fit("gbm", "bin", fr)) == ref
    assert calls["n"] > 0, "batching on but no multi-op round went out"


# ---------------------------------------------------------------------------
# context fencing + replay


def test_seq_fence_409():
    st = dist_hist._GroupState(0)
    st.last_seq = 5
    with pytest.raises(crpc.RpcFault) as ei:
        dist_hist._check_seq(st, 8)
    assert ei.value.code == 409
    dist_hist._check_seq(st, 6)  # in-order op advances the fence
    assert st.last_seq == 6


def test_missing_ctx_404():
    with pytest.raises(crpc.RpcFault) as ei:
        dist_hist._ctx_group({"ctx_id": "nope#0", "g": 0})
    assert ei.value.code == 404


def test_replay_after_ctx_eviction(homed, monkeypatch):
    """An evicted home context (LRU pressure, member restart) must 404
    the next op and rebuild bit-identically from open+bind+oplog."""
    clouds, fr = homed
    monkeypatch.setenv("H2O3_TPU_DIST_HIST", "local")
    ref = _sig(_fit("gbm", "reg", fr))
    monkeypatch.setenv("H2O3_TPU_DIST_HIST", "1")

    real = dist_hist.hist_level
    lock = threading.Lock()
    fired = {"n": 0}

    def evicting(payload, cloud, store):
        with lock:
            if fired["n"] == 0 and payload["op"]["kind"] == "level":
                fired["n"] = 1
                dist_hist._ctx_drop(payload["ctx_id"])
        return real(payload, cloud, store)

    monkeypatch.setattr(dist_hist, "hist_level", evicting)
    monkeypatch.setitem(dist_hist._HANDLERS, "hist_level", evicting)
    assert _sig(_fit("gbm", "reg", fr)) == ref
    assert fired["n"] == 1


# ---------------------------------------------------------------------------
# a home refuses + dies mid-fit: the replica ladder finishes the fit


def test_dead_home_recovers(monkeypatch):
    from h2o3_tpu.cluster import faults

    clouds = _form_cloud(3, "dk")
    set_local_cloud(clouds[0])
    try:
        fr = _parse_to_homes(clouds[0], "dist_tree_kill_df")
        monkeypatch.setenv("H2O3_TPU_DIST_HIST", "local")
        ref = _sig(_fit("gbm", "bin", fr))
        monkeypatch.setenv("H2O3_TPU_DIST_HIST", "1")

        victim_name = next(
            g["home_name"] for g in fr.chunk_layout["groups"]
            if g["home_name"] != clouds[0].info.name)
        victim = next(c for c in clouds if c.info.name == victim_name)
        plan = faults.plan_from_dict({"seed": 7, "rules": [
            {"action": "drop", "side": "server", "src": victim_name,
             "method": "dtask:hist_level"},
        ]})
        faults.set_plan(plan)
        rep0 = _counter("cluster_fanout_recovered_total", path="replica")
        box = {}

        def _train():
            try:
                box["sig"] = _sig(_fit("gbm", "bin", fr))
            except Exception as e:  # pragma: no cover - invariant failure
                box["err"] = e

        th = threading.Thread(target=_train, daemon=True)
        th.start()
        time.sleep(0.3)
        victim.stop()
        th.join(timeout=120.0)
        assert plan.hits()[0] > 0, "fault rule never fired"
        assert "err" not in box, f"fit failed: {box.get('err')}"
        assert box["sig"] == ref
        assert _counter("cluster_fanout_recovered_total",
                        path="replica") > rep0
    finally:
        faults.clear_plan()
        set_local_cloud(None)
        _stop_all(clouds)


# ---------------------------------------------------------------------------
# grid search trains against the homed frame by reference


def test_search_ships_dist_reference(homed):
    from h2o3_tpu.cluster import search as csearch

    clouds, fr = homed
    payload = csearch.frame_payload(fr)
    assert set(payload) == {"__dist__"}
    assert payload["__dist__"]["frame_key"] == fr.key
    # a member rebuilds the handle from ITS OWN store, ring-resolved
    store2 = clouds[1].dkv_store
    fr2 = csearch.frame_restore(payload, store2)
    assert isinstance(fr2, DistFrame)
    assert fr2.chunk_layout["stamp"] == fr.chunk_layout["stamp"]
    assert fr2.nrows == fr.nrows and fr2.names == fr.names
    # no store (a member without the DKV plane) is a typed refusal
    with pytest.raises(crpc.RpcFault) as ei:
        csearch.frame_restore(payload, None)
    assert ei.value.code == 503
