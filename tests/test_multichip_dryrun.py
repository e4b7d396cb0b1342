"""The driver-facing multi-chip gate, run in-tier.

Covers both driver environments: (a) this process, where conftest already
bootstrapped the 8-device CPU mesh (config route); (b) a process whose
backend initialized with too few devices, forcing the subprocess re-exec
path (a driver that probed a one-chip backend first).
"""

import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_in_process():
    from __graft_entry__ import dryrun_multichip

    assert len(jax.devices()) == 8
    dryrun_multichip(8)


def test_dryrun_multichip_from_initialized_backend():
    # Simulate the driver: backend comes up with 1 CPU device *before*
    # dryrun_multichip is called, so the config route is closed and the
    # subprocess re-exec must kick in.
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "jax.config.update('jax_num_cpu_devices', 1)\n"
        "assert len(jax.devices()) == 1\n"
        "from __graft_entry__ import dryrun_multichip\n"
        "dryrun_multichip(8)\n"
    )
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "one tpu_hist boosting round OK" in proc.stdout


def test_entry_compiles():
    from __graft_entry__ import entry

    fn, args = entry()
    res = jax.jit(fn)(*args)
    assert res.shape == (256,)
