"""Size a configuration's training block for a described TPU v5e — no chip.

    JAX_PLATFORMS=cpu python benchmark/tools/size_block.py <config> [--rows N] [--chips 1|4]

Compiles the block program (``booster._make_block_fn``) with the TPU
compiler for a chip that is described, not attached, and prints the bytes
of temporaries and arguments per device and their share of the chip: the
probe the cells of this benchmark were sized with.  What the block is
compiled from is read off a one-tree fit of 2,000 rows by the
configuration's own builder, on the CPU (``programs.tiny_fit_spec``); of
the block itself nothing runs, and a compile that passes is not a chip run.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--rows", type=int)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--block", type=int, default=16)
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from lib import programs

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "lib", "peaks.json")) as f:
        hbm = json.load(f)["TPU v5 lite"]["hbm_bytes"]
    rows = args.rows or int(config["table"]["rows"])
    # before the backend is described as a TPU: this fit runs on the CPU
    spec = programs.tiny_fit_spec(config, os.path.dirname(HERE))
    # an entry written for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # ops/histogram asks jax.default_backend() to choose Pallas and bf16
    jax.default_backend = lambda: "tpu"
    mem = programs.block_footprint(
        spec, rows, int(config["table"]["features"]), args.block,
        list(topo.devices)[:args.chips])
    mem["share_of_chip"] = mem["total"] / hbm
    mem["bytes_per_row"] = mem["total"] / (mem["padded_rows"] / args.chips)
    print(json.dumps({"config": args.config, "rows": rows, "chips": args.chips,
                      "block": args.block, **mem}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
