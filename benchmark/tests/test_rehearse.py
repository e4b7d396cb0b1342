"""``--rehearse`` drives the control flow on the CPU and prints no metric; a
run that finds no chip, or a chip with no peaks on record, is an error."""

import json
import os
import subprocess
import sys
import types

import pytest

from lib import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "gbm-higgs-d6-b256.budget-fit"


def run_cli(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_rehearsal_prints_no_metric():
    done = run_cli("--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearse"] is True and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu" and "busy_s" not in line["device"]
    assert line["correct"] is True and list(line)[-1] == "checks"
    assert "check leaf_gap" in done.stderr


def test_cpu_without_rehearse_is_an_error():
    done = run_cli()
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no accelerator" in done.stderr


def fake(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_unknown_device_kind_is_an_error():
    peaks = {"TPU v5 lite": {"flops_per_s": 1.0}}
    with pytest.raises(SystemExit, match="no peaks on record"):
        harness.check_devices([fake("tpu", "TPU v9")], peaks, 1, False)
    assert harness.check_devices([fake("tpu", "TPU v5 lite")], peaks, 1, False) == peaks["TPU v5 lite"]
    with pytest.raises(SystemExit, match="needs 4 chips"):
        harness.check_devices([fake("tpu", "TPU v5 lite")], peaks, 4, False)
    assert harness.check_devices([fake("cpu", "cpu")], peaks, 1, True) is None
