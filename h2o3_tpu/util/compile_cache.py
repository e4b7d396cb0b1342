"""Where the persistent XLA compilation cache lives — decided in one place.

The training-block programs take tens of seconds to compile for the chip and
a node compiles them again on every start unless the cache is on. The
launcher (``python -m h2o3_tpu``), ``chip_smoke.py`` and the benchmark all
call :func:`configure` before their first jit.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set in
  code, so whoever runs the process places the cache.
* otherwise, and only in a process that may own an accelerator:
  ``<checkout>/.jax_cache`` (git-ignored). The path is part of the cache key,
  so it is fixed, not a temp dir.
* a process pinned to the CPU (``JAX_PLATFORMS=cpu`` in its environment — the
  test tier and every child a test or bench boots) gets no cache: XLA:CPU AOT
  entries carry machine feature sets that can mismatch at load time
  (tests/conftest.py).
"""

from __future__ import annotations

import os
from typing import Optional

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure() -> Optional[str]:
    """Turn the persistent cache on per the rules above; returns the
    directory in use, or None when this process caches nothing."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
