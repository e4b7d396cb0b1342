"""What a frontier level (a tree level past the dense node ladder) moves of
each row: the row's slot fetches, in one gather of the level's slot table,
its node's split fields and the feature list of the node it goes to next;
and the packed row travels through the sort by slot as its payload, so the
tile layout is one gather of the sorted rows. The trees are the ones the
levels grew when each row was gathered four times a level, bit for bit."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.models.tree import booster
from h2o3_tpu.models.tree.booster import TreeParams, train_boosted
from h2o3_tpu.parallel.mesh import default_mesh

pytestmark = pytest.mark.leaks_keys

SEED = 2147490777


def table(n, seed=SEED):
    """Higgs-shaped rows (28 numeric features, NA in one) and a 0/1 response."""
    rng = np.random.default_rng(seed % (2**32))
    X = rng.normal(size=(n, 28)).astype(np.float32)
    w = rng.normal(size=28) / np.sqrt(28)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w + 0.5 * X[:, 0] * X[:, 1])))).astype(np.int32)
    X[rng.random(n) < 0.05, 3] = np.nan
    return X.astype(np.float64), y


#: deep fits whose frontier levels the gathers feed: DRF at depth 14 as the
#: ``drf-higgs-d20`` configuration draws its nodes' features (5 of 28), with
#: row weights (also over an odd count of rows: under weights a frontier
#: level has a slot a row, and the scatter path pads no rows on one
#: device, so the slots are odd), and with a monotone feature; a GBM at
#: depth 12, every feature a node's
CASES = {
    "drf": dict(depth=14, mtries=5),
    "drf_weighted": dict(depth=14, mtries=5, weighted=True),
    "drf_weighted_odd": dict(depth=14, mtries=5, weighted=True, rows=1499),
    "drf_monotone": dict(depth=14, mtries=5, monotone=True),
    "gbm": dict(depth=12, mtries=-1),
}


def fit_digest(case: str, impl: str, monkeypatch, n: int = 1500) -> str:
    """sha256 of a fit's every tree (each field of its list of nodes) and
    its final margin, with the histograms of ``impl`` (``scatter``, or the
    Pallas kernels interpreted), on one device (a third of the eight-device
    mesh's time; the forests' digests are that mesh's too)."""
    c = CASES[case]
    n = c.get("rows", n)
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", impl)
    booster._make_block_fn.cache_clear()
    X, y = table(n)
    drf = c["mtries"] > 0
    p = TreeParams(ntrees=2, max_depth=c["depth"], nbins=20, min_rows=1.0, seed=7,
                   learn_rate=1.0 if drf else 0.3, reg_lambda=0.0,
                   sample_rate=0.632 if drf else 0.8, mtries=c["mtries"])
    rng = np.random.default_rng(5)
    weights = rng.integers(1, 4, size=n).astype(np.float64) if c.get("weighted") else None
    monotone = np.zeros(28, np.int32)
    monotone[0] = 1
    mesh = default_mesh(n_devices=1)
    if drf:
        bt = train_boosted(X, "fixed", y[:, None].astype(np.float64), 1, np.zeros(1), p,
                           average=True, mesh=mesh, weights=weights,
                           monotone=monotone if c.get("monotone") else None,
                           fit_eval={"frame": None, "y": y, "w": weights})
    else:
        bt = train_boosted(X, "bernoulli", y, 1, np.zeros(1), p, mesh=mesh,
                           fit_eval={"frame": None, "y": y, "w": None})
    trees = bt.trees_per_class[0]
    assert trees.deep and max(int(t.max()) for t in trees.node) >= 2**10 - 1
    digest = hashlib.sha256()
    for i in range(trees.ntrees):
        for name in trees._fields():
            digest.update(np.ascontiguousarray(getattr(trees, name)[i]).tobytes())
    digest.update(np.ascontiguousarray(bt.fit_eval["margin"]).tobytes())
    booster._make_block_fn.cache_clear()
    return digest.hexdigest()


#: what ``fit_digest`` read at commit 71cfc99, the last whose frontier
#: levels gathered each row four times a level (recorded there, on the CPU)
PARENT_DIGESTS = {
    ("drf", "scatter"): "b5c598c9c2b70b234bf6e05b2859ed17a1f3cbade2c887360d40d9f36ed10d8d",
    ("drf", "pallas"): "15bd77eb64cac59c8149088ce5eac014c7835cd9b90a0bc4cc24fe81c656c0da",
    ("drf_weighted", "scatter"): "a9fcea56d23bba50bf90ccec0e0ced10e0d98a95ca173adf2cc281eb17adc958",
    ("drf_weighted", "pallas"): "0fd2ad50ab385289db1d14f1f5fe62881857dc358f6393895b6f9d2313b987f7",
    ("drf_weighted_odd", "scatter"): "a23de7e92e6f977b98838c34b5e12dbb42b8d81d10c64c24ceb69d18cc9a1200",
    ("drf_weighted_odd", "pallas"): "3b023c9806625b04f24d288abf65ca80505d9ccad8936e5836254d873dd62680",
    ("drf_monotone", "scatter"): "c9f92840553927a4a3ddf750730137a197e34c8cce4bf91a9a34a3a90cbe033c",
    ("drf_monotone", "pallas"): "72463cf73ced7c66826b8bf9519bcc81312aeef4324ff8678f7cb8932633dfb1",
    ("gbm", "scatter"): "f276b54a35cd74b14f1135eede7e12916f9e3fb5510ba9098b017335eb3cc1a0",
    ("gbm", "pallas"): "e92e2f5d9b7e853c31b474f7d000caddf9145795f628e77c214ee381a416e152",
}


@pytest.mark.parametrize("impl", ["scatter", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_frontier_levels_grow_the_parents_trees(case, impl, monkeypatch):
    assert fit_digest(case, impl, monkeypatch) == PARENT_DIGESTS[case, impl]


# ---------------------------------------------------------------------------
# the gathers of a level, as the block is traced


def frontier_gathers(impl: str, monkeypatch, rows: int = 4096, mtries: int = 5,
                     min_rows: float = 5.0):
    """The gathers inside the frontier levels' scan of a depth-20 DRF block
    of ``rows`` rows on one device whose result has a row a row (``rows``)
    or a row a tile place of the frontier kernel's layout (``t_max x R``);
    ``min_rows`` keeps the level's slots (rows / min_rows) apart from both."""
    from h2o3_tpu.ops import pallas_histogram as PH

    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", impl)
    booster._make_block_fn.cache_clear()
    p = TreeParams(ntrees=0, seed=0, max_depth=20, nbins=20, learn_rate=1.0,
                   reg_lambda=0.0, sample_rate=0.632, mtries=mtries, min_rows=min_rows)
    subtract = impl == "pallas"
    fn = booster._make_block_fn("fixed", 1, 1, p, default_mesh(n_devices=1),
                                subtract=subtract)
    S = jax.ShapeDtypeStruct
    fm = S((32, rows), jnp.int32) if impl == "pallas" else None
    jaxpr = jax.make_jaxpr(fn)(
        S((rows, 28), jnp.int32), S((rows, 1), jnp.float32), S((rows,), jnp.bool_),
        S((rows, 1), jnp.float32), S((1, 2), jnp.uint32), fm, None, None)
    booster._make_block_fn.cache_clear()
    d_f = booster.frontier_start(20, subtract)
    slots = booster.frontier_slots(p, rows)
    nb = -(-slots // PH._FRONTIER_SLOTS)
    tiled = (-(-rows // PH._ROW_TILE) + nb) * PH._ROW_TILE
    assert len({rows, tiled, slots, slots + 1}) == 4

    def subjaxprs(eqn):
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                    yield j.jaxpr
                elif hasattr(j, "eqns"):
                    yield j

    def walk(jx):
        for eqn in jx.eqns:
            yield eqn
            for sub in subjaxprs(eqn):
                yield from walk(sub)

    scans = [e for e in walk(jaxpr.jaxpr)
             if e.primitive.name == "scan" and e.params["length"] == 20 - d_f]
    assert len(scans) == 1
    return sum(1 for e in walk(scans[0].params["jaxpr"].jaxpr)
               if e.primitive.name == "gather"
               and e.outvars[0].aval.shape[:1] in ((rows,), (tiled,)))


@pytest.mark.parametrize("impl", ["scatter", "pallas"])
@pytest.mark.parametrize("mtries", [5, -1])
def test_a_frontier_level_gathers_each_row_once_in_each_index_space(
        impl, mtries, monkeypatch):
    """Once by its slot (the slot table: split fields and the feature words
    of the node it goes to) and, on the Pallas path, once into the kernel's
    tile layout: 2, where each row used to be gathered four times a level
    (the split table and the node's features by slot, the sorted position
    and then the row at it); the scatter oracle sorts nothing. With every
    feature a node's nothing is gathered for the codes at all. The count a
    deep fit's ``tree_block`` states is this one."""
    got = frontier_gathers(impl, monkeypatch, mtries=mtries)
    assert got == booster.frontier_row_gathers(impl) == {"scatter": 1, "pallas": 2}[impl]


def test_a_deep_fit_says_what_its_frontier_levels_gather(monkeypatch):
    from h2o3_tpu.frame.frame import ColType, Column, Frame
    from h2o3_tpu.models.tree import DRF
    from h2o3_tpu.util import timeline

    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "scatter")
    X, y = table(600)
    fr = Frame([Column(f"f{i}", X[:, i]) for i in range(28)]
               + [Column("y", y, ColType.CAT, ["0", "1"])])
    before = booster.TREE_FRONTIER_ROW_GATHERS.value()
    model = DRF(response_column="y", seed=SEED, ntrees=2, max_depth=12, nbins=20,
                min_rows=1).train(fr)
    blocks = [e for e in timeline.snapshot(4096) if e["kind"] == "tree_block"]
    assert blocks[-1]["frontier_row_gathers"] == 1
    trees = sum(e["trees"] for e in blocks if e["trace_id"] == blocks[-1]["trace_id"])
    # two frontier levels a tree (10 and 11: the CPU builds without subtraction)
    assert trees == 2
    assert booster.TREE_FRONTIER_ROW_GATHERS.value() - before == trees * 2 * 1
    prof = model.fit_profile["tree_block"]
    assert prof["frontier_row_gathers"] == prof["n"] * 1
    # a tree with no frontier level states none
    DRF(response_column="y", seed=SEED, ntrees=1, max_depth=5).train(fr)
    last = [e for e in timeline.snapshot(4096) if e["kind"] == "tree_block"][-1]
    assert "frontier_row_gathers" not in last


# ---------------------------------------------------------------------------
# the frontier kernel's layout against a plain one


def layout_reference(codes, slots, g, h, w, n_slots, n_bins1, r, width, t_max, dtype):
    """The layout ``_prep_frontier`` is to give, by plain numpy: rows in
    stable slot order, the slots cut into groups of ``width``, each group's
    rows padded to whole tiles of ``r`` (at least one tile a group), the
    tiles one group after the other, and unused tiles to ``t_max``; a pad
    row has slot -1 and zero values."""
    n = len(slots)
    nb = -(-n_slots // width)
    key = np.where((slots >= 0) & (slots < n_slots), slots, nb * width)
    order = np.argsort(key, kind="stable")
    codes = np.where((codes >= 0) & (codes < n_bins1), codes, n_bins1)
    rnd = (lambda x: np.asarray(jnp.asarray(x, jnp.float32).astype(dtype).astype(jnp.float32)))
    vals = np.stack([rnd(g), rnd(h), rnd(w), np.zeros(n, np.float32)], axis=1)
    lslot = np.full(t_max * r, -1)
    codes_p = np.zeros((t_max * r, codes.shape[1]), np.int64)
    vals_p = np.zeros((t_max * r, 4), np.float32)
    item, valid = [], np.zeros(t_max * r, bool)
    for b in range(nb):
        rows = [i for i in order if b * width <= key[i] < (b + 1) * width]
        base = len(item) * r
        for j, i in enumerate(rows):
            lslot[base + j] = key[i] - b * width
            codes_p[base + j] = codes[i]
            vals_p[base + j] = vals[i]
            valid[base + j] = True
        item += [b] * max(1, -(-len(rows) // r))
    assert len(item) <= t_max
    item += [nb] * (t_max - len(item))
    first = [1] + [int(a != b) for a, b in zip(item[1:], item[:-1])]
    return lslot, codes_p, vals_p, np.asarray(item), np.asarray(first), valid


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("n,n_slots,m,n_bins1,shape", [
    (1500, 700, 5, 21, "spread"),     # every group, a few rows a slot
    (1200, 900, 3, 9, "crowded"),     # one group holds more than a tile
    (700, 2000, 4, 21, "sparse"),     # most groups empty
])
def test_the_frontier_prep_lays_rows_out_as_the_plain_layout(
        n, n_slots, m, n_bins1, shape, dtype):
    from h2o3_tpu.ops import pallas_histogram as PH

    rng = np.random.default_rng(n + n_slots)
    r, width = 128, 256
    if shape == "crowded":  # 60% of the rows in the second group's slots
        slots = np.where(rng.random(n) < 0.6, rng.integers(256, 300, size=n),
                         rng.integers(0, n_slots + 1, size=n))
    elif shape == "sparse":  # the first and the last group only
        slots = np.where(rng.random(n) < 0.5, rng.integers(0, 40, size=n),
                         rng.integers(1800, n_slots + 1, size=n))
    else:
        slots = rng.integers(0, n_slots + 1, size=n)  # n_slots: no slot
    slots[:7] = n_slots
    codes = rng.integers(0, n_bins1 + 2, size=(n, m))  # past the NA code too
    g, h = rng.normal(size=n), rng.random(n)
    w = rng.integers(1, 4, size=n).astype(np.float64)
    nb = -(-n_slots // width)
    t_max = -(-n // r) + nb
    dt = PH._DTYPES[dtype]
    got = PH._prep_frontier(
        jnp.asarray(codes, jnp.int32), jnp.asarray(slots, jnp.int32),
        jnp.asarray(g, jnp.float32), jnp.asarray(h, jnp.float32), n_slots, n_bins1,
        r, width, t_max, rw=jnp.asarray(w, jnp.float32), dtype=dt)
    lslot, codes_p, vals_p, item, first, valid = layout_reference(
        codes, slots, g, h, w, n_slots, n_bins1, r, width, t_max, dt)
    if shape == "crowded":
        assert np.sum(item == 1) > 1  # the group spans tiles
    if shape == "sparse":
        assert len(set(item.tolist())) == nb + 1  # empty groups hold a tile
    np.testing.assert_array_equal(np.asarray(got[0])[0], lslot)
    np.testing.assert_array_equal(np.asarray(got[1]).T[valid], codes_p[valid])
    np.testing.assert_array_equal(np.asarray(got[2], np.float32).T, vals_p)
    np.testing.assert_array_equal(np.asarray(got[3]), item)
    np.testing.assert_array_equal(np.asarray(got[4]), first)


# ---------------------------------------------------------------------------
# the packer both layouts share


@pytest.mark.parametrize("bits", [
    (9,) * 28,            # the packed row's codes: three a word at 257 bins
    (5,) * 5,             # a node's five feature ids of 28: one word
    (5, 9, 1, 20),        # the slot table's split fields: the rank spills
])
def test_fields_pack_into_words_and_back(bits):
    from h2o3_tpu.ops.bitpack import pack_words, unpack_words, word_layout

    rng = np.random.default_rng(len(bits))
    fields = [rng.integers(0, 2**b, size=64) for b in bits]
    fields[0][:2] = (0, 2**bits[0] - 1)
    words = pack_words((jnp.asarray(f, jnp.uint32) for f in fields), bits)
    at, n = word_layout(bits)
    assert len(words) == n == max(w for w, _ in at) + 1
    assert all(s + b <= 32 for b, (_, s) in zip(bits, at))
    back = unpack_words(lambda i: words[i], bits)
    for f, g in zip(fields, back):
        np.testing.assert_array_equal(np.asarray(g), f)
