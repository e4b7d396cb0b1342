"""One place decides where the persistent XLA compile cache lives
(h2o3_tpu/util/compile_cache.py)."""

import os

import pytest

import jax

from h2o3_tpu.util import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("env,returned,set_in_code", [
    # set from outside: JAX reads the variable, the code sets nothing
    ({"JAX_COMPILATION_CACHE_DIR": "/some/dir", "JAX_PLATFORMS": "tpu"},
     "/some/dir", None),
    ({"JAX_COMPILATION_CACHE_DIR": "/some/dir", "JAX_PLATFORMS": "cpu"},
     "/some/dir", None),
    # a CPU-pinned process (this tier, every child a test boots): no cache
    ({"JAX_PLATFORMS": "cpu"}, None, None),
    # a process that may own a chip: the git-ignored directory of the checkout
    ({"JAX_PLATFORMS": "tpu,cpu"}, os.path.join(REPO, ".jax_cache"),
     os.path.join(REPO, ".jax_cache")),
    ({}, os.path.join(REPO, ".jax_cache"), os.path.join(REPO, ".jax_cache")),
])
def test_configure(monkeypatch, env, returned, set_in_code):
    for k in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        assert compile_cache.configure() == returned
        assert jax.config.jax_compilation_cache_dir == set_in_code
    finally:  # the CPU tier itself must stay uncached (tests/conftest.py)
        jax.config.update("jax_compilation_cache_dir", before)


def test_cache_directory_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
