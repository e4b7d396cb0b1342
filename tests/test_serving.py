"""Serving plane: event-loop front-end, admission control, coalesced
batched scoring (ISSUE 9).

Like test_rest_api.py these run real sockets on localhost (SURVEY.md §4
'no mocked network backends').  Each class that needs non-default knobs
starts its own server with ``http={...}`` overrides; the coalescer tests
assert the tentpole contract directly: N concurrent scoring requests
execute in far fewer dispatches than N, bit-identical to serial.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from h2o3_tpu import Frame
from h2o3_tpu.api import start_server
from h2o3_tpu.api.coalesce import _BATCH_SIZE
from h2o3_tpu.api.server import _HTTP_SHED, H2OServer
from h2o3_tpu.keyed import DKV

# servers and trained models share keys across tests; the module-level
# sweeper removes everything at module end
pytestmark = pytest.mark.leaks_keys


def _req(server, method, path, data=None):
    url = server.url + path
    body = None
    headers = {}
    if data is not None:
        body = json.dumps(data).encode()
        headers["Content-Type"] = "application/json"
    req = urllib.request.Request(
        url, data=body, headers=headers, method=method)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _train_binomial(n=600, seed=3):
    from h2o3_tpu.models.glm import GLM

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4))
    logit = X @ np.array([1.2, -0.8, 0.5, 0.0]) - 0.2
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(float)
    fr = Frame.from_dict(
        {f"x{i}": X[:, i] for i in range(4)}
        | {"y": np.where(y > 0, "yes", "no").astype(object)}
    )
    fr.key = f"serve_bin_{n}_{seed}.hex"
    DKV.put(fr.key, fr)
    m = GLM(family="binomial", response_column="y", lambda_=0.0).train(fr)
    return m, fr


def _frame_cols(key):
    fr = DKV.get(key)
    assert isinstance(fr, Frame)
    return {c.name: np.asarray(c.data, dtype=np.float64) for c in fr.columns}


class TestCoalescedScoring:
    """The tentpole contract: concurrency collapses into few dispatches,
    results stay bit-identical to serial execution."""

    def test_concurrent_predicts_coalesce_and_match_serial(self):
        m, fr = _train_binomial()
        srv = H2OServer(port=0, http=dict(
            workers=4, batch_window_ms=50.0)).start()
        try:
            serial = m.predict(fr)
            want = {c.name: np.asarray(c.data, dtype=np.float64)
                    for c in serial.columns}
            n = 16
            path = f"/3/Predictions/models/{m.key}/frames/{fr.key}"
            barrier = threading.Barrier(n)
            statuses = [None] * n

            def shoot(i):
                barrier.wait()
                statuses[i] = _req(srv, "POST", path, {
                    "predictions_frame": f"serve_pred_{i}"})[0]

            before = _BATCH_SIZE.total_count()
            threads = [threading.Thread(target=shoot, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            dispatches = _BATCH_SIZE.total_count() - before
            assert statuses == [200] * n
            # the point of the coalescer: nowhere near one dispatch per
            # request (same model + same frame usually lands in 1-2)
            assert 1 <= dispatches <= n // 2
            for i in range(n):
                got = _frame_cols(f"serve_pred_{i}")
                assert set(got) == set(want)
                for name, col in want.items():
                    np.testing.assert_array_equal(got[name], col), name
        finally:
            srv.stop()

    def test_window_zero_disables_coalescing(self):
        m, fr = _train_binomial(n=80, seed=9)
        srv = H2OServer(port=0, http=dict(
            workers=2, batch_window_ms=0)).start()
        try:
            assert srv._coalescer is None
            before = _BATCH_SIZE.total_count()
            st, out = _req(
                srv, "POST", f"/3/Predictions/models/{m.key}/frames/{fr.key}",
                {"predictions_frame": "serve_pred_nc"})
            assert st == 200
            pf = out["model_metrics"][0]["predictions_frame"]
            assert pf["name"] == "serve_pred_nc"
            assert _BATCH_SIZE.total_count() == before
        finally:
            srv.stop()


class TestKeepAlive:
    def test_two_requests_one_connection(self):
        srv = start_server(port=0, http=dict(workers=2))
        try:
            with socket.create_connection(
                    ("127.0.0.1", srv.port), timeout=10) as s:
                f = s.makefile("rb")
                for _ in range(2):
                    s.sendall(b"GET /3/About HTTP/1.1\r\n"
                              b"Host: localhost\r\n\r\n")
                    status = f.readline().split()[1]
                    assert status == b"200"
                    length = 0
                    while True:
                        h = f.readline()
                        if h in (b"\r\n", b"\n"):
                            break
                        if h.lower().startswith(b"content-length:"):
                            length = int(h.split(b":")[1])
                    assert length > 0
                    json.loads(f.read(length))  # full body on same socket
        finally:
            srv.stop()


class TestAdmissionControl:
    def _slow_server(self, **http):
        srv = H2OServer(port=0, http=http)

        def slow(params):
            time.sleep(float(params.get("sleep_s", 0.4)))
            return {"ok": True}

        srv.registry.register("POST", "/3/TestSlow", slow, "test-only")
        return srv.start()

    def test_queue_overflow_sheds_429_never_hangs(self):
        srv = self._slow_server(workers=1, queue=2, batch_window_ms=0)
        try:
            n = 10
            results = [None] * n
            barrier = threading.Barrier(n)

            def shoot(i):
                barrier.wait()
                results[i] = _req(srv, "POST", "/3/TestSlow", {})

            shed0 = _HTTP_SHED.value(route="/3/TestSlow")
            t0 = time.monotonic()
            threads = [threading.Thread(target=shoot, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            took = time.monotonic() - t0
            statuses = [r[0] for r in results]
            assert set(statuses) <= {200, 429}       # never 5xx
            assert statuses.count(200) >= 1          # in-flight completed
            assert statuses.count(429) >= 1          # overflow was shed
            assert _HTTP_SHED.value(route="/3/TestSlow") > shed0
            # worker=1 x 0.4s each: admitted <= 3, so the whole burst
            # resolves in a couple of seconds — overload never hangs
            assert took < 20
            for st, out in results:
                if st == 429:
                    assert out["http_status"] == 429
        finally:
            srv.stop()

    def test_per_route_budget_sheds_429(self):
        srv = self._slow_server(
            workers=4, queue=64, batch_window_ms=0,
            route_budgets={"/3/TestSlow": 1})
        try:
            results = [None] * 4
            barrier = threading.Barrier(4)

            def shoot(i):
                barrier.wait()
                results[i] = _req(srv, "POST", "/3/TestSlow", {})

            threads = [threading.Thread(target=shoot, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            statuses = sorted(r[0] for r in results)
            assert statuses[0] == 200 and statuses[-1] == 429
            # other routes keep their own budget: not shed
            assert _req(srv, "GET", "/3/About")[0] == 200
        finally:
            srv.stop()


class TestRequestHygiene:
    def test_oversized_header_413(self):
        srv = start_server(port=0, http=dict(
            workers=2, max_header_bytes=1024))
        try:
            st, out = _req(srv, "GET", "/3/About?x=" + "a" * 4096)
            assert st == 413
            assert out["http_status"] == 413
        finally:
            srv.stop()

    def test_oversized_body_413(self):
        srv = start_server(port=0, http=dict(
            workers=2, max_body_bytes=2048))
        try:
            st, out = _req(srv, "POST", "/3/PostFile",
                           {"data": "x" * 8192})
            assert st == 413
            assert out["http_status"] == 413
        finally:
            srv.stop()

    def test_slow_client_408(self):
        srv = start_server(port=0, http=dict(
            workers=2, read_timeout_s=0.3))
        try:
            with socket.create_connection(
                    ("127.0.0.1", srv.port), timeout=10) as s:
                # request line arrives, headers never finish: slow-loris
                s.sendall(b"GET /3/About HTTP/1.1\r\nHost: lo")
                t0 = time.monotonic()
                data = s.recv(4096)
                assert time.monotonic() - t0 < 10
                assert b"408" in data.split(b"\r\n", 1)[0]
        finally:
            srv.stop()


class TestBoundedDrain:
    def test_stop_returns_within_drain_deadline(self):
        srv = H2OServer(port=0, http=dict(
            workers=2, batch_window_ms=0, drain_s=0.5))

        def very_slow(params):
            time.sleep(30)
            return {"ok": True}

        srv.registry.register("POST", "/3/TestVerySlow", very_slow, "")
        srv.start()
        outcome = {}

        def shoot():
            try:
                outcome["resp"] = _req(srv, "POST", "/3/TestVerySlow", {})
            except Exception as e:  # connection cut mid-drain is legal
                outcome["err"] = type(e).__name__

        t = threading.Thread(target=shoot)
        t.start()
        time.sleep(0.3)  # let the request reach a worker
        t0 = time.monotonic()
        srv.stop()
        took = time.monotonic() - t0
        assert took < 10  # drain_s + bounded teardown, not the 30s handler
        t.join(timeout=15)
        assert not t.is_alive()  # the client got 503 or a closed socket
        if "resp" in outcome:
            assert outcome["resp"][0] == 503
        srv.stop()  # idempotent

    def test_drain_flushes_open_batches(self):
        m, fr = _train_binomial(n=60, seed=11)
        # a window far longer than the test: only the drain flush can
        # close the batch
        srv = H2OServer(port=0, http=dict(
            workers=2, batch_window_ms=60000.0, drain_s=5.0)).start()
        out = {}

        def shoot():
            out["r"] = _req(
                srv, "POST",
                f"/3/Predictions/models/{m.key}/frames/{fr.key}",
                {"predictions_frame": "serve_pred_drain"})

        t = threading.Thread(target=shoot)
        t.start()
        time.sleep(0.5)  # request is parked in the open batch
        srv.stop()
        t.join(timeout=30)
        assert not t.is_alive()
        assert out["r"][0] == 200  # flushed and answered before teardown

