"""Test harness: single-process multi-device CPU mesh.

Reference analogue: tests run against an "N JVMs on localhost" cloud via
``water.runner.H2ORunner`` + ``@CloudSize(n)`` (SURVEY.md §4). Here the cloud
is 8 virtual XLA CPU devices in one process — the sharding/collective code
paths are identical to a real TPU slice.
"""

import os

# Force CPU before any backend initializes: the test tier always runs on the
# virtual 8-device CPU mesh, even when a real TPU is attached. (The config
# calls below are authoritative; the env vars cover subprocesses.)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# NOTE: the persistent compilation cache is deliberately NOT enabled for
# the CPU test tier: XLA:CPU AOT executables serialized here carry machine
# feature sets (prefer-no-scatter et al.) that mismatch the host at load
# time and intermittently SIGSEGV in compilation_cache.get/put_executable.
# Processes that may own a chip get their cache from
# h2o3_tpu/util/compile_cache.py, which leaves CPU-pinned processes alone.

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh():
    from h2o3_tpu.parallel.mesh import default_mesh

    m = default_mesh()
    assert m.devices.size == 8, f"expected 8 virtual devices, got {m.devices.size}"
    return m


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "leaks_keys: legacy test/module exempt from the strict DKV "
        "key-leak check (keys are still swept after the test)",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'): multi-node "
        "formation tests and other long-wall-clock coverage",
    )


def _sweep_keys(keys):
    from h2o3_tpu.keyed import DKV

    DKV.unlock_all()
    for k in keys:
        try:
            DKV.remove(k)
        except Exception:
            pass


@pytest.fixture(autouse=True)
def _check_dkv_keys(request):
    """CheckKeysTask analogue (h2o-test-support/.../runner/
    CheckKeysTask.java): every test must leave the DKV exactly as it
    found it. Keys created and not removed FAIL the test (and are swept
    so one failure cannot cascade). Tests/modules marked ``leaks_keys``
    are exempt — their state persists (module-scoped fixtures share
    keys) and the module-level sweeper below cleans up at module end."""
    from h2o3_tpu.keyed import DKV
    from h2o3_tpu.models.framework import Job

    before = set(DKV.keys())
    yield
    # Jobs persist by design: the /3/Jobs listing is the history of past
    # work (reference: Job keys are CheckKeysTask-exempt the same way)
    leaked = sorted(
        k for k in set(DKV.keys()) - before
        if not isinstance(DKV.peek(k), Job)
    )
    if leaked and request.node.get_closest_marker("leaks_keys") is None:
        _sweep_keys(leaked)
        pytest.fail(
            f"DKV key leak: {len(leaked)} key(s) left behind "
            f"(CheckKeysTask): {leaked[:10]}{'...' if len(leaked) > 10 else ''}"
        )


@pytest.fixture(scope="module", autouse=True)
def _sweep_dkv_between_modules():
    """Whatever a module's tests/fixtures accumulated (including marked
    leaks_keys debt) is removed at module end, so no module ever sees
    another module's keys."""
    from h2o3_tpu.keyed import DKV

    before = set(DKV.keys())
    yield
    _sweep_keys(sorted(set(DKV.keys()) - before))


@pytest.fixture(scope="module", autouse=True)
def _clear_jax_caches_between_modules():
    """Release compiled executables after each test module.

    Without this, the suite accumulates hundreds of live XLA:CPU
    executables in one process and intermittently SIGSEGVs inside a later
    backend_compile_and_load (JIT code-memory exhaustion — reproducible at
    ~90+ heavy compiles regardless of which tests ran). The reference
    suite runs as many separate JVMs; one long-lived Python process needs
    the explicit release."""
    yield
    jax.clear_caches()


def _max_map_count() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530


_MAX_MAPS = _max_map_count()


@pytest.fixture(autouse=True)
def _release_compiled_programs_near_the_map_limit():
    """Release compiled executables after a test that leaves the process
    near the kernel's limit of memory mappings (``vm.max_map_count``).

    Each live XLA:CPU executable holds a few mappings of its JIT code; a
    module that fits many models (``test_automl.py``: hundreds of programs
    across its AutoML runs) climbs to the limit within the module, and the
    next compile that cannot map its code dies with SIGSEGV. Half the limit
    leaves room for the largest single test; below it nothing is cleared,
    so a module's tests still share their programs."""
    yield
    try:
        with open("/proc/self/maps") as f:
            mapped = f.read().count("\n")
    except OSError:
        return
    if mapped > _MAX_MAPS // 2:
        jax.clear_caches()


@pytest.fixture()
def parents_level_plan(monkeypatch):
    """Trace a tree level as the commits before ISSUE 35 did: the node
    ladder 8 / 64 / 512 (``histogram._NODE_BUCKETS``) and no
    ``optimization_barrier`` on the node-matmul kernel's operands
    (``pallas_histogram._build_histogram_nodematmul``). A program can then
    be held to the digest of such a commit: the slots a level launches and
    that barrier are shown to be all that a level's program gained. The
    block and the level jits cache their traces by shape, so they are
    emptied on both sides: no patched trace is inherited, and none is left."""
    from h2o3_tpu.models.tree import booster
    from h2o3_tpu.ops import histogram, pallas_histogram

    def clear():
        booster._make_block_fn.cache_clear()
        histogram._build_histogram_jit.clear_cache()
        pallas_histogram._build_histogram_pallas_jit.clear_cache()

    clear()
    with monkeypatch.context() as patch:
        patch.setattr(histogram, "_NODE_BUCKETS", (8, 64, 512))
        patch.setattr(jax.lax, "optimization_barrier", lambda operands: operands)
        yield
    clear()
