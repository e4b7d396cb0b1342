"""Ahead-of-time compiles of the tree hot path for a described TPU v5e.

The TPU's compiler is installed with JAX and compiles for a chip that is
described, not attached (/opt/skills/guides/on-chip-measurement §2.3), so
these run on the CPU tier and guard what interpret mode cannot see: Mosaic
refusing a kernel (tiling, VMEM), a sharded kernel that loses its collective,
and a training block whose temporaries outgrow the chip. Nothing executes —
a compile that passes is not a chip run; ``chip_smoke.py`` is the run.

``ops/histogram.py`` and ``ops/pallas_histogram.py`` ask
``jax.default_backend()`` to pick Pallas over scatter, compiled over
interpreted and bf16 over f32; here that answer is steered to "tpu" by the
test, not by an option of the program.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from h2o3_tpu.parallel.mesh import DATA_AXIS

V5E_HBM_BYTES = 16 * 10**9  # Google Cloud documentation, "TPU v5e"
F, FP, B1 = 28, 32, 257  # Higgs width; features padded to _FEAT_BLOCK; 256 bins + NA
N_KERNEL = 1 << 20
N_BLOCK = 2_000_896  # 2M rows padded to the 512-row kernel tile


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e:2x2, with Pallas steered on and
    the persistent compile cache off (an AOT entry cannot be read back
    without a chip; the next compile would warn and recompile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / unknown topology on this install
        pytest.skip(f"cannot describe a v5e:2x2 here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    yield list(topo.devices)
    mp.undo()
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _mesh(devices, n):
    return Mesh(np.array(devices[:n]), (DATA_AXIS,))


def _level_args(mesh, n):
    """Abstract (bins, nodes, g, h, bins_fm) of one histogram level, row
    sharded the way ``train_boosted`` places them."""
    row = NamedSharding(mesh, P(DATA_AXIS))
    S = jax.ShapeDtypeStruct
    return (
        S((n, F), jnp.int32, sharding=NamedSharding(mesh, P(DATA_AXIS, None))),
        S((n,), jnp.int32, sharding=row),
        S((n,), jnp.float32, sharding=row),
        S((n,), jnp.float32, sharding=row),
        S((FP, n), jnp.int32, sharding=NamedSharding(mesh, P(None, DATA_AXIS))),
    )


@pytest.mark.parametrize("n_nodes,kernel", [
    (1, "nodematmul"), (2, "nodematmul"), (4, "nodematmul"),
    (8, "nodematmul"), (16, "nodematmul"), (32, "nodematmul"),
    (64, "nodematmul"), (512, "sorted")])
def test_histogram_kernel_compiles(v5e, n_nodes, kernel):
    """Every rung of the node ladder (``histogram._NODE_BUCKETS``): the
    node-matmul shapes of levels 0-7 and the sorted kernel of deep levels,
    bf16, at 1M rows on one chip."""
    from h2o3_tpu.ops.histogram import pad_nodes

    assert pad_nodes(n_nodes) == n_nodes  # a rung: what a level launches
    from h2o3_tpu.ops.pallas_histogram import (
        _C, _NODE_MATMUL_MAX_KC, _build_histogram_pallas_jit)

    assert (n_nodes * _C <= _NODE_MATMUL_MAX_KC) == (kernel == "nodematmul")
    bins, nodes, g, h, bins_fm = _level_args(_mesh(v5e, 1), N_KERNEL)
    compiled = _build_histogram_pallas_jit.lower(
        bins, nodes, g, h, n_nodes, B1, None, False, (), "auto",
        bins_fm, None, "bf16").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_histogram_keeps_its_all_reduce(v5e):
    """The Pallas level under a four-device mesh: kernel per shard, then
    the psum that merges the shard-private histograms."""
    from h2o3_tpu.ops.histogram import _build_histogram_jit

    mesh = _mesh(v5e, 4)
    bins, nodes, g, h, bins_fm = _level_args(mesh, N_KERNEL)
    text = _build_histogram_jit.lower(
        bins, nodes, g, h, bins_fm, None, 64, B1, mesh, "pallas",
        ).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text


def test_chip_smoke_builds_its_table_from_the_benchmarks_generator():
    """``chip_smoke.training_table`` reads ``benchmark/tables/higgs-synth.py``;
    the digests are of what ``bench.synth_higgs(1000, 28, 7)`` returned at
    commit b201c72, so the chip proof still fits the table it always fit."""
    import hashlib

    import chip_smoke

    X, y = chip_smoke.training_table(1000, 7)
    assert (X.dtype, X.shape, y.dtype, y.shape) == (
        np.float32, (1000, 28), np.float64, (1000,))
    assert hashlib.sha256(X.tobytes()).hexdigest() == (
        "19eafbb0362d68c6499464bcbfe321b6b296467990a26269114bb8a66d282266")
    assert hashlib.sha256(y.tobytes()).hexdigest() == (
        "82f8a351dfa77f5cb7c6cdcad77b72bc5b16c4b6559c2d9132802060a425dd61")


def test_training_block_fits_one_chip(v5e, capsys):
    """chip_smoke.py's fit: bernoulli, 10 trees, depth 6, 256 bins,
    2,000,896 x 28 rows — one block program on one chip."""
    from h2o3_tpu.models.tree import booster

    mesh = _mesh(v5e, 1)
    assert booster._tree_subtract_enabled()  # the TPU default level flow
    bins, _, y, _, bins_fm = _level_args(mesh, N_BLOCK)
    row = NamedSharding(mesh, P(DATA_AXIS))
    S = jax.ShapeDtypeStruct
    ntrees = 10
    fn = booster._make_block_fn(
        "bernoulli", 1, ntrees,
        booster.TreeParams(ntrees=0, max_depth=6, learn_rate=0.1, nbins=256,
                           min_rows=1.0, reg_lambda=0.0, seed=0),
        mesh, subtract=True)
    compiled = fn.lower(
        bins, y, S((N_BLOCK,), jnp.bool_, sharding=row),
        S((N_BLOCK, 1), jnp.float32,
          sharding=NamedSharding(mesh, P(DATA_AXIS, None))),
        S((ntrees, 2), jnp.uint32, sharding=NamedSharding(mesh, P())),
        bins_fm, None, None).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    with capsys.disabled():
        print(f"\n[tpu-compile] training block {N_BLOCK}x{F}, {ntrees} trees "
              f"depth 6 on one v5e: temp {mem.temp_size_in_bytes:,} + "
              f"arguments {mem.argument_size_in_bytes:,} = {need:,} bytes "
              f"of {V5E_HBM_BYTES:,}")
    assert need < V5E_HBM_BYTES
    # the [N] vectors round the kernels stay lane-dense (the barrier on the
    # node-matmul kernel's operands, ISSUE 35): computed as [N, 1] rows of
    # 128 lanes they made 14,112,391,680 bytes of temporaries at this very
    # row count (ROADMAP S4b), 1,580,557,312 since
    assert mem.temp_size_in_bytes < 2 * 10**9
