"""What the benchmark knows of the program's compiled programs.

Three things cannot be had through ``train()`` today, and each is listed in
PERF.md's open questions for a later issue to offer properly:

* the device memory of the training block — the allocator's
  ``peak_bytes_in_use`` does not count a program's temporaries on this
  backend (PR 24), so the block is lowered again from its shapes and its
  ``memory_analysis()`` read: on the attached chip after a window, or for a
  described v5e with no chip at all (``tools/size_block.py``);
* the compiled block's text, whose ``op_name`` metadata ``lib/scopes.py``
  maps the trace's events through: the same lowering, ``compile_block``;
* the post-fit scoring program has the tree count in its shapes, and the
  second block adds an offset to its tree indices: both are built in
  set-up so that the window compiles nothing.

This is the one place that reaches past the estimator into
``models/tree/booster``: ``_make_block_fn`` (lowered by today's signature),
``_tree_subtract_enabled``, ``_predict_stacked``, the row tile and feature
block of ``ops/pallas_histogram``, and the fitted model's own
``booster`` (``BoostedTrees.params``, ``.nclasses_trees``, ``.average``).
The block's ``TreeParams`` are no longer worked out here from the
configuration (a copy of one builder's ``_fit``): they are read off the
model the warm-up fitted, so GBM, XGBoost and DRF lower alike; the booster
objective too is the model's word (a booster that averages fits fixed
targets, any other the configuration's ``distribution``).  A later PR may
change what is reached for here and may not edit this file, so both entries
the harness calls (``attached_footprint``, ``build_scoring_programs``) fail soft: a
note on standard error, and the run goes on without what they would give.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Dict, List, Optional, Sequence


def block_spec(model, config: dict) -> dict:
    """What the training block of a fitted model was compiled from: the
    booster objective, the class-tree count and the ``TreeParams`` the
    builder made (less ``ntrees`` and ``seed``, which the program too keeps
    out of its compile key)."""
    b = model.booster
    # a booster that averages (DRF) was handed raw targets, objective "fixed"
    objective = "fixed" if b.average else config["params"].get("distribution")
    return {"objective": objective, "class_trees": int(b.nclasses_trees),
            "params": dataclasses.replace(b.params, ntrees=0, seed=0)}


def tiny_fit_spec(config: dict, root: str, rows: int = 2000) -> dict:
    """``block_spec`` of a one-tree fit on ``rows`` rows of the
    configuration's own table: for a tool or a test that has no fitted model
    at hand.  The parameters do not depend on the rows."""
    from . import harness

    table = harness.make_table(root, config, rows, seed=1)
    served = harness.fit(harness.load_builder(config["builder"]), config,
                         harness.make_frame(table["X"], table["y"], config,
                                            table["columns"]), 1, {"ntrees": 1})
    return block_spec(served["model"], config)


def compile_block(spec: dict, rows: int, features: int, block: int,
                  devices: Sequence):
    """The training block compiled for ``devices`` (attached or described)
    at the padded row count, from shapes alone: ``(compiled, padded_rows)``.
    Rows are padded, and the feature-major copy of the codes handed over,
    as ``_train_boosted`` does where the Pallas kernels run (on the chip)."""
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
    c = spec["class_trees"]
    row = NamedSharding(mesh, P(DATA_AXIS))
    row2 = NamedSharding(mesh, P(DATA_AXIS, None))
    S = jax.ShapeDtypeStruct
    fixed = spec["objective"] == "fixed"  # the response is then [n, C] targets
    fn = booster._make_block_fn(
        spec["objective"], c, block, spec["params"], mesh,
        subtract=booster._tree_subtract_enabled())
    compiled = fn.lower(
        S((n, features), jnp.int32, sharding=row2),
        S((n, c), jnp.float32, sharding=row2) if fixed
        else S((n,), jnp.float32, sharding=row),
        S((n,), jnp.bool_, sharding=row),
        S((n, c), jnp.float32, sharding=row2),
        S((block, 2), jnp.uint32, sharding=NamedSharding(mesh, P())),
        S((fp, n), jnp.int32, sharding=NamedSharding(mesh, P(None, DATA_AXIS))),
        None, None).compile()
    return compiled, n


def block_footprint(spec: dict, rows: int, features: int, block: int,
                    devices: Sequence) -> Dict[str, int]:
    """Bytes per device of the training block compiled for ``devices``:
    temporaries, arguments, outputs, and the padded row count they were
    compiled at."""
    compiled, n = compile_block(spec, rows, features, block, devices)
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


def attached_footprint(spec: Optional[dict], rows: int, features: int,
                       block: int) -> Optional[Dict[str, int]]:
    """The block's footprint on the attached devices, plus what else is
    live there (the response, the predict path's codes...); None where the
    block could not be lowered again."""
    import jax

    try:
        if spec is None:
            raise ValueError("the warm-up's model gave no block to lower")
        out = block_footprint(spec, rows, features, block, jax.devices())
    except Exception as e:  # the program's internals moved
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
    count a fit of the window may end on, and run the one small program only
    a second block needs (its tree indices start past zero)."""
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
              "if a fit of the window ends on another tree count than the "
              "warm-up's, its scoring compiles inside the window and the run "
              "fails", file=sys.stderr)
