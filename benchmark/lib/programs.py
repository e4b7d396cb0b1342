"""What the benchmark knows of the program's compiled programs.

Three things cannot be had through ``train()`` today, and each is listed in
PERF.md's open questions for the tracing issue to offer properly:

* the device memory of the training block — the allocator's
  ``peak_bytes_in_use`` does not count a program's temporaries on this
  backend (PR 24), so the block is lowered again from its shapes and its
  ``memory_analysis()`` read: on the attached chip after a window, or for a
  described v5e with no chip at all (``tools/size_block.py``);
* the post-fit scoring program has the tree count in its shapes, and the
  second block adds an offset to its tree indices: both are built in
  set-up so that the window compiles nothing.

This is the one place that reaches past the estimator into
``models/tree/booster``; the mapping from estimator parameters to
``TreeParams`` is a copy of ``GBM._fit``'s.  A later PR may change what is
reached for here and may not edit this file, so both entries the harness
calls (``attached_footprint``, ``build_scoring_programs``) fail soft: a
note on standard error, and the run goes on without what they would give.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence


def _tree_params(config: dict):
    from h2o3_tpu.models.tree.booster import TreeParams

    if not config["builder"].endswith(":GBM"):
        raise NotImplementedError(
            f"no parameter mapping for builder {config['builder']}")
    p = config["params"]
    return TreeParams(
        ntrees=0, max_depth=int(p["max_depth"]), learn_rate=float(p["learn_rate"]),
        nbins=int(p["nbins"]), min_rows=float(p["min_rows"]),
        min_split_improvement=float(p.get("min_split_improvement", 1e-5)),
        reg_lambda=0.0, reg_alpha=0.0,
        sample_rate=float(p.get("sample_rate", 1.0)),
        col_sample_rate_per_tree=float(p.get("col_sample_rate_per_tree", 1.0)),
        seed=0)


def block_footprint(config: dict, rows: int, features: int, classes: int,
                    block: int, devices: Sequence) -> Dict[str, int]:
    """Bytes per device of the training block compiled for ``devices``
    (attached or described): temporaries, arguments, outputs, and the
    padded row count they were compiled at."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from h2o3_tpu.models.tree import booster
    from h2o3_tpu.ops.pallas_histogram import _FEAT_BLOCK, _ROW_TILE
    from h2o3_tpu.parallel.mesh import DATA_AXIS

    mesh = Mesh(np.array(list(devices)), (DATA_AXIS,))
    n = rows + (-rows) % (len(devices) * _ROW_TILE)
    fb = min(_FEAT_BLOCK, features)
    fp = features + (-features) % fb
    dist = config["params"]["distribution"]
    c = classes if dist == "multinomial" else 1
    row = NamedSharding(mesh, P(DATA_AXIS))
    row2 = NamedSharding(mesh, P(DATA_AXIS, None))
    S = jax.ShapeDtypeStruct
    fn = booster._make_block_fn(
        dist, c, block, _tree_params(config), mesh,
        subtract=booster._tree_subtract_enabled())
    compiled = fn.lower(
        S((n, features), jnp.int32, sharding=row2),
        S((n,), jnp.float32, sharding=row),
        S((n,), jnp.bool_, sharding=row),
        S((n, c), jnp.float32, sharding=row2),
        S((block, 2), jnp.uint32, sharding=NamedSharding(mesh, P())),
        S((fp, n), jnp.int32, sharding=NamedSharding(mesh, P(None, DATA_AXIS))),
        None, None).compile()
    mem = compiled.memory_analysis()
    out = {"padded_rows": n,
           "temp": int(mem.temp_size_in_bytes),
           "argument": int(mem.argument_size_in_bytes),
           "output": int(mem.output_size_in_bytes),
           "alias": int(mem.alias_size_in_bytes),
           "kernels": compiled.as_text().count("tpu_custom_call")}
    # the margin is donated: its output aliases an argument and is not new memory
    out["total"] = out["temp"] + out["argument"] + out["output"] - out["alias"]
    return out


def attached_footprint(config: dict, rows: int, features: int, classes: int,
                       block: int) -> Optional[Dict[str, int]]:
    """The block's footprint on the attached devices, plus what else is
    live there (the response, the predict path's codes...)."""
    import jax

    try:
        out = block_footprint(config, rows, features, classes, block, jax.devices())
    except Exception as e:  # the program's internals moved, or another builder
        print(f"note: the training block could not be lowered again ({e!r}); "
              "memory_peak_bytes is the allocator's peak alone, which leaves "
              "out a program's temporaries", file=sys.stderr)
        return None
    n_dev = len(jax.devices())
    live = sum(a.nbytes for a in jax.live_arrays()) // n_dev
    # arguments of the block are live arrays already (codes, response, mask)
    out["other_live"] = max(0, int(live) - out["argument"])
    out["total"] += out["other_live"]
    return out


def build_scoring_programs(model, rows: int, features: int,
                           tree_counts: List[int]) -> None:
    """Compile, into the persistent cache, the scoring program for each tree
    count a budgeted fit may end on, and run the one small program only a
    second block needs (its tree indices start past zero)."""
    import jax
    import jax.numpy as jnp

    try:
        from h2o3_tpu.models.tree import booster

        block = booster.tree_block_size()
        jnp.arange(block, 2 * block).block_until_ready()
        trees = model.booster.trees_per_class[0]
        m = 2 ** (trees.max_depth + 1) - 1
        S = jax.ShapeDtypeStruct
        for t in tree_counts:
            booster._predict_stacked.lower(
                S((rows, features), jnp.int32),
                S((t, m), jnp.int32), S((t, m), jnp.int32), S((t, m), jnp.bool_),
                S((t, m), jnp.bool_), S((t, m), jnp.float32),
                max_depth=trees.max_depth, n_bins1_arr=S((), jnp.int32),
            ).compile()
    except Exception as e:  # the program's internals moved
        print(f"note: the scoring programs could not be built ahead ({e!r}); "
              "if the window's fit ends on another tree count than the "
              "warm-up's, its scoring compiles inside the window and the run "
              "fails", file=sys.stderr)
