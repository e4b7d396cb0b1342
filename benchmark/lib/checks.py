"""What decides ``correct`` is data: a configuration's limits file names the
numbers compared, each beside a limit of its own
(``benchmark/limits/<config>.json``; PERF.md gives the readings each limit
was set from), and its ``"reference"`` key names the file that computes
them, ``benchmark/references/<name>.py``:

* ``NUMBERS`` — every number the reference can compute;
* ``extract(model, numbers) -> answer`` — what a timed ``train()`` returned,
  as plain arrays (the harness keeps the answer and frees the model);
* ``compare(config, seed, table, answers, block, numbers) -> {number: worst
  reading}`` over the window's answers, ``table`` holding ``X``, ``y``,
  ``classes`` and ``columns`` (the generator's typed columns, or None).

A configuration that names no reference, a name with no file, and a limits
key outside the reference's ``NUMBERS`` are each an error, not a pass.
"""

from __future__ import annotations

import json
import os
from typing import Dict

REFERENCE_OFFERS = ("NUMBERS", "extract", "compare")


def load_limits(root: str, config_name: str) -> Dict[str, float]:
    """The numbers a configuration is held to, each with its limit."""
    with open(os.path.join(root, "benchmark", "limits", config_name + ".json")) as f:
        limits = json.load(f)["limits"]
    if not limits:
        raise SystemExit(f"limits of {config_name} name no number to compare")
    return {k: float(v) for k, v in limits.items()}


def load_reference(root: str, config: dict, limits: Dict[str, float]):
    """The reference a configuration names, able to compute every number of
    its limits."""
    from .harness import load_named

    name = config.get("reference")
    if not name:
        raise SystemExit(
            f"configuration {config.get('name')!r} names no \"reference\": "
            "nothing could decide `correct`")
    ref = load_named(root, "references", name)
    lacking = [k for k in REFERENCE_OFFERS if not hasattr(ref, k)]
    if lacking:
        raise SystemExit(f"benchmark/references/{name}.py offers no {lacking}")
    unknown = [k for k in limits if k not in ref.NUMBERS]
    if unknown:
        raise SystemExit(
            f"reference {name!r} cannot compute the limits' numbers {unknown}: "
            f"it has {sorted(ref.NUMBERS)}")
    return ref


def decide(ref, limits: Dict[str, float], config: dict, seed: int, table: dict,
           answers: list, block: int) -> Dict[str, tuple]:
    """Each of the limits' numbers as (reading, limit), from the reference's
    ``compare``; a number it returns no reading for is an error."""
    compared = ref.compare(config, seed, table, answers, block, list(limits))
    missing = [k for k in limits if k not in compared]
    if missing:
        raise SystemExit(f"the reference's compare returned no {missing}")
    return {k: (float(compared[k]), limits[k]) for k in limits}
