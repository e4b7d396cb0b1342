"""CLI launcher (water/H2O.java OptArgs + H2OApp), Lockable, and UDF
custom metrics (water/udf)."""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from h2o3_tpu.frame.frame import ColType, Column, Frame


# legacy module predating the CheckKeysTask fixture: tests here
# share/train keys without per-test cleanup; the module-level
# sweeper still removes everything at module end
pytestmark = pytest.mark.leaks_keys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _frame(rng, n=300):
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] - X[:, 1] + 0.3 * rng.normal(size=n) > 0).astype(np.int32)
    cols = [Column(f"x{i}", X[:, i]) for i in range(3)]
    cols.append(Column("y", y, ColType.CAT, ["n", "p"]))
    return Frame(cols)


class TestLauncher:
    def test_python_dash_m_starts_a_node(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "h2o3_tpu", "--port", "0",
             "--name", "launcher-test", "--log-dir", str(tmp_path)],
            cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        try:
            line = ""
            deadline = time.time() + 120
            while time.time() < deadline:
                line = proc.stdout.readline()
                if "up at http" in line:
                    break
            assert "up at http" in line, line
            url = line.strip().rsplit(" ", 1)[-1]
            with urllib.request.urlopen(url + "/3/Cloud") as resp:
                cloud = json.loads(resp.read())
            assert cloud["cloud_name"] == "launcher-test"
            with urllib.request.urlopen(url + "/3/Ping") as resp:
                assert json.loads(resp.read())["ok"]
            # graceful shutdown on SIGTERM
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=30) == 0
        finally:
            if proc.poll() is None:
                proc.kill()

    def test_parse_mem(self):
        from h2o3_tpu.__main__ import _parse_mem

        assert _parse_mem("4g") == 4 << 30
        assert _parse_mem("512m") == 512 << 20
        assert _parse_mem("1024") == 1024


class TestLockable:
    def test_training_frame_cannot_be_deleted_mid_build(self, rng):
        """water/Lockable.java: a frame read-locked by a training job
        refuses deletion until the job finishes."""
        from h2o3_tpu.keyed import DKV
        from h2o3_tpu.models.glm import GLM

        fr = _frame(rng)
        fr.key = "lockable_fr"
        DKV.put(fr.key, fr)

        observed = {}
        from h2o3_tpu.models import glm as glm_mod

        orig_fit = GLM._fit

        def snooping_fit(self, frame, valid=None):
            # mid-build: deletion must raise
            try:
                DKV.remove("lockable_fr")
                observed["deleted"] = True
            except ValueError as e:
                observed["error"] = str(e)
            return orig_fit(self, frame, valid)

        GLM._fit = snooping_fit
        try:
            GLM(response_column="y", family="binomial").train(fr)
        finally:
            GLM._fit = orig_fit

        assert "deleted" not in observed
        assert "locked" in observed["error"]
        # after training the lock is released
        DKV.remove("lockable_fr")
        assert DKV.get("lockable_fr") is None


class TestCustomMetricUDF:
    def test_in_process_callable(self, rng):
        from h2o3_tpu.models.glm import GLM
        from h2o3_tpu.udf import custom_metric

        fr = _frame(rng)
        m = GLM(response_column="y", family="binomial").train(fr)

        def brier(actual, predicted):
            return float(np.mean((actual - predicted) ** 2))

        v = custom_metric(m, fr, brier)
        assert 0.0 <= v <= 0.3

    def test_upload_gated_and_enabled(self, rng, monkeypatch):
        from h2o3_tpu import udf

        src = "def metric(actual, predicted):\n    return float(abs(actual - predicted).mean())\n"
        monkeypatch.delenv("H2O3_TPU_ENABLE_UDF", raising=False)
        with pytest.raises(PermissionError):
            udf.compile_metric("mae_udf", src)
        monkeypatch.setenv("H2O3_TPU_ENABLE_UDF", "1")
        udf.compile_metric("mae_udf", src)

        from h2o3_tpu.models.glm import GLM
        from h2o3_tpu.udf import custom_metric

        fr = _frame(rng)
        m = GLM(response_column="y", family="binomial").train(fr)
        v = custom_metric(m, fr, "mae_udf")
        assert 0.0 <= v <= 1.0

    def test_udf_over_rest(self, rng, monkeypatch):
        from h2o3_tpu.api import start_server
        from h2o3_tpu.keyed import DKV

        monkeypatch.setenv("H2O3_TPU_ENABLE_UDF", "1")
        fr = _frame(rng)
        fr.key = "udf_fr"
        DKV.put(fr.key, fr)
        from h2o3_tpu.models.glm import GLM

        m = GLM(response_column="y", family="binomial").train(fr)

        s = start_server(port=0)
        try:
            def post(path, payload):
                req = urllib.request.Request(
                    s.url + path, data=json.dumps(payload).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                with urllib.request.urlopen(req) as resp:
                    return json.loads(resp.read())

            post("/3/CustomMetric", {
                "name": "acc",
                "source": "def metric(actual, predicted):\n"
                          "    return float(((predicted > 0.5) == actual).mean())\n",
            })
            out = post("/3/CustomMetric/eval", {
                "model_id": m.key, "frame_id": "udf_fr", "name": "acc",
            })
            assert 0.5 <= out["value"] <= 1.0
        finally:
            s.stop()
            DKV.remove("udf_fr")


class TestPodLaunch:
    """--coordinator multi-host flags (the h2odriver / h2o-k8s analogue)."""

    def test_coordinator_requires_pod_shape(self, capsys):
        from h2o3_tpu.__main__ import main

        rc = main(["--coordinator", "localhost:9999", "--port", "0"])
        assert rc == 2

    def test_single_process_pod_forms_and_serves(self, tmp_path):
        """A 1-process pod rendezvous at its own coordinator and serves —
        the same code path every pod member runs (k8s ordinal 0)."""
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            coord = f"127.0.0.1:{s.getsockname()[1]}"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "h2o3_tpu", "--port", "0",
             "--name", "pod-test", "--coordinator", coord,
             "--num-processes", "1", "--process-id", "0"],
            cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        try:
            line, seen = "", []
            deadline = time.time() + 120
            while time.time() < deadline:
                line = proc.stdout.readline()
                if line:
                    seen.append(line)
                if "up at http" in line:
                    break
                if line == "":
                    # EOF: either the child died, or it closed stdout while
                    # still running — both mean the banner can never arrive;
                    # spinning on instant-'' reads would burn the deadline
                    break
            assert "up at http" in line, "".join(seen)
            url = line.strip().rsplit(" ", 1)[-1]
            with urllib.request.urlopen(url + "/3/Cloud") as resp:
                cloud = json.loads(resp.read())
            assert cloud["cloud_name"] == "pod-test"
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=30) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
