"""The REST server: route registry + event-loop HTTP front-end.

Reference: ``water/api/RequestServer.java:56-80,157-192,241`` (route table,
{placeholder} path params, fallback per-algo routes), ``RegisterV3Api.java``
(endpoint registration), ``water/api/Handler.java`` (schema in/out),
``water/api/H2OErrorV3`` (error payloads).

Design notes (TPU-native): the REST layer is pure control plane — every
handler manipulates host-side objects (frames, model keys, jobs) and the
device work happens inside the models' jitted programs.  The front-end is
an asyncio event loop in one thread (replacing both Jetty and the earlier
thread-per-connection stand-in): keep-alive connections, a global
connection cap, per-route concurrency budgets and a bounded request queue.  Overload sheds with
429 + ``Retry-After`` — never a hang, never an unbounded thread pile.
Handlers stay synchronous: admitted requests run on a bounded worker pool
off the loop, so all registered routes work unchanged.  Coalescable routes
(POST /3/Predictions) route through ``api/coalesce.py`` instead — same-model
requests collect for ``H2O3_TPU_BATCH_WINDOW_MS`` and execute as ONE
devcache-warm batched score, bit-identical to serial execution.
"""

from __future__ import annotations

import asyncio
import functools
import json
import os
import queue
import re
import struct
import threading
import time
import traceback
import urllib.parse
from concurrent.futures import Future as _CFuture
from http.client import responses as _HTTP_REASONS
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from h2o3_tpu import __version__
from h2o3_tpu.util import flight as _flight
from h2o3_tpu.util import ledger as _ledger
from h2o3_tpu.util import telemetry

Route = Tuple[str, "re.Pattern[str]", List[str], Callable, str]

#: REST traffic meters. The route label is the registered *pattern*
#: (/3/Models/{model_id}), never the raw path — raw paths would explode the
#: label cardinality with every model key ever scored.
_REST_REQUESTS = telemetry.counter(
    "rest_requests_total", "REST requests served",
    labels=("method", "route", "status"),
)
_REST_SECONDS = telemetry.histogram(
    "rest_request_seconds", "REST request wall seconds",
    labels=("method", "route"),
)
#: serving-plane meters: what admission control is doing right now
#: (in-flight = admitted, not yet responded; queue depth = waiting for a
#: worker) and what it refused (sheds answer 429 + Retry-After)
_HTTP_INFLIGHT = telemetry.gauge(
    "http_inflight", "REST requests admitted and not yet responded")
_HTTP_QUEUE_DEPTH = telemetry.gauge(
    "http_queue_depth", "REST requests waiting for a worker thread")
_HTTP_SHED = telemetry.counter(
    "http_shed_total", "REST requests shed by admission control (429)",
    labels=("route",),
)
_HTTP_CONNS = telemetry.gauge(
    "http_open_connections", "open REST client connections")


class RestError(Exception):
    """``headers`` ride the error response verbatim — the serving plane
    uses them to propagate a remote home's ``Retry-After`` through the
    front door unchanged (the front door's own admission meters never
    tick for a shed that happened elsewhere)."""

    def __init__(self, status: int, msg: str,
                 headers: Tuple[Tuple[str, str], ...] = ()) -> None:
        super().__init__(msg)
        self.status = status
        self.headers = tuple(headers)


class RequestServer:
    """Route registry (RequestServer.java:56-80)."""

    def __init__(self) -> None:
        self.routes: List[Route] = []
        #: compiled pattern text -> the original {name} path template; the
        #: request meters and the docs lint both label routes with this
        self._templates: Dict[str, str] = {}
        #: (method, raw path) -> match result; scoring traffic hits the
        #: same few concrete paths thousands of times, so a linear scan of
        #: ~150 regexes per request would dominate the loop thread
        self._match_cache: Dict[Tuple[str, str],
                                Tuple[Callable, Dict[str, str], str]] = {}

    def register(self, method: str, path: str, handler: Callable, summary: str = "") -> None:
        """path uses {name} placeholders, e.g. /3/Models/{model_id}."""
        names = re.findall(r"\{(\w+)\}", path)
        pattern = re.compile(
            "^" + re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", path) + "$"
        )
        self.routes.append((method.upper(), pattern, names, handler, summary))
        self._templates[pattern.pattern] = path
        self._match_cache.clear()

    def templates(self) -> List[Tuple[str, str]]:
        """(method, {name}-template) of every registered route."""
        return [
            (m, self._templates.get(p.pattern, p.pattern[1:-1]))
            for m, p, _names, _handler, _summary in self.routes
        ]

    def match(
        self, method: str, path: str
    ) -> Optional[Tuple[Callable, Dict[str, str], str]]:
        """(handler, path_kwargs, route_pattern) of the first matching route;
        the pattern string is the stable low-cardinality label the request
        meters use."""
        hit = self._match_cache.get((method, path))
        if hit is not None:
            handler, kw, route = hit
            return handler, dict(kw), route
        for m, pattern, _names, handler, _ in self.routes:
            if m != method:
                continue
            mt = pattern.match(path)
            if mt:
                kw = {
                    k: urllib.parse.unquote(v)
                    for k, v in mt.groupdict().items()
                }
                # label with the {name} template the route was registered
                # under, not the compiled (?P<name>...) regex
                route = self._templates.get(
                    pattern.pattern, pattern.pattern[1:-1])
                # hits only — caching misses would let a path scanner grow
                # the dict without bound
                if len(self._match_cache) < 4096:
                    self._match_cache[(method, path)] = (
                        handler, dict(kw), route)
                return handler, kw, route
        return None

    def dispatch(self, method: str, path: str, params: Dict[str, Any]) -> Any:
        found = self.match(method, path)
        if found is None:
            raise RestError(404, f"no route for {method} {path}")
        handler, kw, _route = found
        return handler(params, **kw)

    def endpoints(self) -> List[Dict[str, str]]:
        return [
            {"method": m, "url_pattern": p.pattern[1:-1], "summary": s}
            for m, p, _, _, s in self.routes
        ]


#: inbound trace-context headers must look like the ids we mint (hex, 8-32
#: chars): the value is echoed back as a response header and recorded into
#: every timeline event and log line of the request, so an unvalidated
#: value would be a response-header-injection (CRLF) primitive and a
#: timeline-pollution vector
_TRACE_ID_RE = re.compile(r"^[0-9a-f]{8,32}$")


def _trace_header(value: Optional[str]) -> Optional[str]:
    if value and _TRACE_ID_RE.match(value):
        return value
    return None


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        v = float(o)
        return None if np.isnan(v) else v
    if isinstance(o, np.ndarray):
        return o.tolist()
    if isinstance(o, float) and np.isnan(o):
        return None
    raise TypeError(f"not JSON serializable: {type(o)}")


#: URLs of servers CURRENTLY running in this process (start() adds,
#: stop() removes — a dead server's port may be reused by anything)
_LIVE_URLS: set = set()


def served_from_this_process(url: str) -> bool:
    """True if `url` is served by a live H2OServer in this process RIGHT
    NOW. Callers that need "was this endpoint ours?" later (e.g. after
    the server stops) must evaluate this at connection time and remember
    the answer — a stopped server's port can be reused by an unrelated
    external service."""
    return url.rstrip("/") in _LIVE_URLS


# -- serving-plane knobs ------------------------------------------------------

#: field -> (env var, default, cast).  Env sets the process default; the
#: H2OServer(http={...}) constructor arg overrides per server (tests run
#: tiny queues, the bench flips the batch window).
_KNOBS: Dict[str, Tuple[str, Any, Callable[[Any], Any]]] = {
    "workers": ("H2O3_TPU_HTTP_WORKERS", 16, int),
    "queue": ("H2O3_TPU_HTTP_QUEUE", 512, int),
    "max_conns": ("H2O3_TPU_HTTP_MAX_CONNS", 8192, int),
    "route_budget": ("H2O3_TPU_HTTP_ROUTE_BUDGET", 256, int),
    "max_header_bytes": ("H2O3_TPU_HTTP_MAX_HEADER_BYTES", 64 * 1024, int),
    "max_body_bytes": ("H2O3_TPU_HTTP_MAX_BODY_BYTES", 256 << 20, int),
    "read_timeout_s": ("H2O3_TPU_HTTP_READ_TIMEOUT_S", 30.0, float),
    "idle_timeout_s": ("H2O3_TPU_HTTP_IDLE_TIMEOUT_S", 120.0, float),
    "drain_s": ("H2O3_TPU_HTTP_DRAIN_S", 5.0, float),
    "batch_window_ms": ("H2O3_TPU_BATCH_WINDOW_MS", 2.0, float),
    "batch_max_rows": ("H2O3_TPU_BATCH_MAX_ROWS", 262144, int),
    "batch_max_requests": ("H2O3_TPU_BATCH_MAX_REQUESTS", 256, int),
}


class HttpOptions:
    """Resolved serving-plane configuration (see ``_KNOBS`` for the env
    names and defaults)."""

    __slots__ = tuple(_KNOBS) + ("route_budgets",)

    def __init__(self, **overrides: Any) -> None:
        budgets = overrides.pop("route_budgets", None) or {}
        for fld, (env, default, cast) in _KNOBS.items():
            if fld in overrides:
                v = overrides.pop(fld)
            else:
                raw = os.environ.get(env)
                v = raw if raw is not None else default
            setattr(self, fld, cast(v))
        if overrides:
            raise TypeError(f"unknown http option(s): {sorted(overrides)}")
        #: route pattern -> per-route in-flight budget override
        self.route_budgets: Dict[str, int] = {
            k: int(v) for k, v in budgets.items()}

    def budget_for(self, route: str) -> int:
        return self.route_budgets.get(route, self.route_budget)


# -- request/response plumbing ------------------------------------------------

def _body_bytes(status: int, msg: str) -> bytes:
    """A loop-built error payload (water/api/schemas3/H2OErrorV3 shape)."""
    return json.dumps({
        "http_status": status,
        "msg": msg,
        "dev_msg": msg,
        "exception_type": "RestError",
    }).encode()


def _error_body(e: BaseException) -> Tuple[int, bytes]:
    if isinstance(e, RestError):
        return e.status, json.dumps({
            "http_status": e.status,
            "msg": str(e),
            "dev_msg": str(e),
            "exception_type": "RestError",
        }).encode()
    return 500, json.dumps({
        "http_status": 500,
        "msg": f"{type(e).__name__}: {e}",
        "dev_msg": "".join(
            traceback.format_exception(type(e), e, e.__traceback__)),
        "exception_type": type(e).__name__,
    }).encode()


def _encode_out(out: Any) -> Tuple[bytes, str]:
    if (isinstance(out, tuple) and len(out) == 2
            and isinstance(out[0], (bytes, bytearray))):
        return bytes(out[0]), out[1]
    if isinstance(out, (bytes, bytearray)):
        return bytes(out), "application/octet-stream"
    return json.dumps(out, default=_json_default).encode(), "application/json"


def _build_params(query: str, body: bytes, ctype: str) -> Dict[str, Any]:
    params: Dict[str, Any] = {
        k: v[0] if len(v) == 1 else v
        for k, v in urllib.parse.parse_qs(query).items()
    }
    if body:
        if "json" in ctype:
            params.update(json.loads(body))
        elif "octet-stream" in ctype:
            # binary upload (model files, NPS blobs): handlers read the
            # bytes under _raw_body
            params["_raw_body"] = body
        else:  # h2o-py posts urlencoded forms
            try:
                params.update({
                    k: v[0] if len(v) == 1 else v
                    for k, v in urllib.parse.parse_qs(body.decode()).items()
                })
            except UnicodeDecodeError:
                params["_raw_body"] = body
    return params


def _render_head(status: int, length: int, ctype: str,
                 extra: Tuple[Tuple[str, str], ...] = (),
                 close: bool = False) -> bytes:
    head = [f"HTTP/1.1 {status} {_HTTP_REASONS.get(status, '')}"]
    for k, v in extra:
        head.append(f"{k}: {v}")
    head.append(f"Server: h2o3-tpu/{__version__}")
    head.append(f"Content-Type: {ctype}")
    head.append(f"Content-Length: {length}")
    if close:
        head.append("Connection: close")
    return ("\r\n".join(head) + "\r\n\r\n").encode("latin-1")


async def _write_response(writer: asyncio.StreamWriter, status: int,
                          payload: bytes, ctype: str = "application/json",
                          extra: Tuple[Tuple[str, str], ...] = (),
                          close: bool = False) -> bool:
    data = _render_head(status, len(payload), ctype, extra, close) + payload
    try:
        writer.write(data)
        await writer.drain()
    except (ConnectionError, RuntimeError):
        return False
    return True


def _keep_alive(version: str, headers: Dict[str, str]) -> bool:
    conn = headers.get("connection", "").lower()
    if "close" in conn:
        return False
    if version == "HTTP/1.0":
        return "keep-alive" in conn
    return True


#: what the event-loop side resolves a request future to
#: (status, payload, content-type, trace id to echo[, extra headers]) —
#: the optional fifth element carries handler-supplied response headers
#: (RestError.headers, e.g. a forwarded Retry-After)
_Resp = Tuple[int, bytes, str, Optional[str]]
_DRAIN_RESP: _Resp = (
    503, _body_bytes(503, "server draining"), "application/json", None)


class _Job:
    """One admitted request travelling loop -> worker -> loop."""

    __slots__ = ("method", "path", "query", "ctype", "body", "handler",
                 "path_kw", "route", "trace_id", "parent_id", "future")

    def __init__(self, method: str, path: str, query: str, ctype: str,
                 body: bytes, handler: Callable, path_kw: Dict[str, str],
                 route: str, trace_id: Optional[str],
                 parent_id: Optional[str]) -> None:
        self.method = method
        self.path = path
        self.query = query
        self.ctype = ctype
        self.body = body
        self.handler = handler
        self.path_kw = path_kw
        self.route = route
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.future: _CFuture = _CFuture()


def _resolve(fut: _CFuture, resp: _Resp) -> None:
    try:
        fut.set_result(resp)
    except Exception:
        pass  # cancelled/drained: the connection already got an answer


def _run_job(job: _Job) -> None:
    """Worker-side execution of one non-coalesced request: params build,
    Span, handler, encode — everything that may block or compute."""
    from h2o3_tpu.util.log import get_logger

    status, ctype = 200, "application/json"
    # a proxied/forwarded request may carry its caller's trace: honor the
    # headers (id-shaped values only) so one trace threads client -> this
    # REST span -> any node RPC it fans out
    span = telemetry.Span(
        "rest", method=job.method, route=job.route, path=job.path,
        trace_id=job.trace_id, parent_id=job.parent_id,
    )
    t0 = time.perf_counter()
    hdrs: Tuple[Tuple[str, str], ...] = ()
    try:
        with span:
            # logged INSIDE the span so the /3/Logs line carries this
            # request's trace/span ids
            get_logger("rest").info("%s %s", job.method, job.path)
            out = job.handler(
                _build_params(job.query, job.body, job.ctype), **job.path_kw)
        payload, ctype = _encode_out(out)
    except BaseException as e:  # noqa: BLE001
        status, payload = _error_body(e)
        ctype = "application/json"
        hdrs = tuple(getattr(e, "headers", ()) or ())
    # cost accounting BEFORE the future resolves: a client reading its
    # response can immediately GET /3/Traces/{id} and see route/wall meta
    wall_ms = (time.perf_counter() - t0) * 1e3
    _ledger.LEDGER.annotate(span.trace_id, route=job.route,
                            wall_ms=round(wall_ms, 3), status=status)
    _ledger.SLOWOPS.record(job.route, wall_ms, span.trace_id, status)
    _resolve(job.future, (status, payload, ctype, span.trace_id, hdrs))


def _run_batch(route: str, batch_fn: Callable, jobs: List[_Job]) -> List[_Resp]:
    """Worker-side execution of one coalesced batch: build params per
    entry, ONE batch-handler call, encode per entry.  A bad entry (params
    or handler error) gets its own error response; the rest proceed."""
    from h2o3_tpu.util.log import get_logger

    log = get_logger("rest")
    built: List[Optional[BaseException]] = []
    live: List[Tuple[Dict[str, Any], Dict[str, str]]] = []
    for job in jobs:
        try:
            live.append((
                _build_params(job.query, job.body, job.ctype), job.path_kw))
            built.append(None)
        except BaseException as e:  # noqa: BLE001
            built.append(e)
    # the batch span runs under the LEADER's trace (jobs[0]); each rider
    # keeps its own trace id for its response header and its ledger share
    # (the coalescer splits the dispatch wall across riders)
    span = telemetry.Span(
        "rest", method=jobs[0].method, route=route, batch=len(jobs),
        trace_id=jobs[0].trace_id, parent_id=jobs[0].parent_id,
    )
    outs: List[Any]
    t0 = time.perf_counter()
    with span:
        for job in jobs:
            log.info("%s %s (coalesced x%d)", job.method, job.path, len(jobs))
        try:
            outs = list(batch_fn(live))
            if len(outs) != len(live):
                raise RuntimeError(
                    f"batch handler returned {len(outs)} results "
                    f"for {len(live)} requests")
        except BaseException as e:  # noqa: BLE001
            outs = [e] * len(live)
    wall_ms = (time.perf_counter() - t0) * 1e3
    results: List[_Resp] = []
    it = iter(outs)
    for job, err in zip(jobs, built):
        res = err if err is not None else next(it)
        hdrs: Tuple[Tuple[str, str], ...] = ()
        if isinstance(res, BaseException):
            status, payload = _error_body(res)
            ctype = "application/json"
            hdrs = tuple(getattr(res, "headers", ()) or ())
        else:
            try:
                payload, ctype = _encode_out(res)
                status = 200
            except BaseException as e:  # noqa: BLE001
                status, payload = _error_body(e)
                ctype = "application/json"
        tid = job.trace_id or span.trace_id
        _ledger.LEDGER.annotate(tid, route=route,
                                wall_ms=round(wall_ms, 3), status=status,
                                batch=len(jobs))
        _ledger.SLOWOPS.record(route, wall_ms, tid, status)
        results.append((status, payload, ctype, tid, hdrs))
    return results


class _WorkerPool:
    """Bounded handler execution off the event loop.  The queue object is
    unbounded (SimpleQueue); boundedness is enforced up front by the
    loop-side admission counters — an explicit 429 at admission beats the
    implicit unbounded backlog a ThreadPoolExecutor would hide."""

    def __init__(self, n: int) -> None:
        self._q: "queue.SimpleQueue[Optional[Callable[[], None]]]" = (
            queue.SimpleQueue())
        self._threads: List[threading.Thread] = []
        for i in range(n):
            t = threading.Thread(
                target=self._run, daemon=True,
                name=f"http-worker-{i}",  # /3/Profiler's "^http" filter
            )
            t.start()
            self._threads.append(t)

    def submit(self, fn: Callable[[], None]) -> None:
        self._q.put(fn)

    def stop(self) -> None:
        for _ in self._threads:
            self._q.put(None)

    def _run(self) -> None:
        while True:
            fn = self._q.get()
            if fn is None:
                return
            try:
                fn()
            except BaseException:  # noqa: BLE001
                from h2o3_tpu.util.log import get_logger

                get_logger("rest").error(
                    "worker job crashed: %s", traceback.format_exc())


class H2OServer:
    """The server facade (h2o-webserver-iface HttpServerFacade analogue).

    Security (water/network + LoginType hash-file auth): ``ssl_cert``/
    ``ssl_key`` wrap the listener in TLS (the reference's jetty SSL config;
    asyncio handshakes per connection without blocking the accept path);
    ``auth_file`` — lines of ``user:sha256(password)`` — enables HTTP Basic
    auth on every route (LoginType.HASH_FILE).

    ``http`` overrides serving-plane knobs (see ``_KNOBS``), e.g.
    ``http=dict(workers=2, queue=8, batch_window_ms=0)``."""

    def __init__(
        self,
        port: int = 54321,
        name: str = "h2o3-tpu",
        ssl_cert: Optional[str] = None,
        ssl_key: Optional[str] = None,
        auth_file: Optional[str] = None,
        auth_backend=None,
        ip: str = "127.0.0.1",
        http: Optional[Dict[str, Any]] = None,
    ) -> None:
        self.name = name
        #: bind address (-ip / web_ip OptArg); 0.0.0.0 for pod/container
        #: serving where probes and clients arrive on the pod IP
        self.ip = ip
        self.start_time = time.time()
        self.registry = RequestServer()
        from h2o3_tpu.api import handlers

        handlers.register_all(self.registry, self)
        self.port = port
        self.ssl_cert = ssl_cert
        self.ssl_key = ssl_key
        self.http = HttpOptions(**(http or {}))
        #: the auth SPI (api/auth.py LoginBackend); auth_file builds the
        #: hash-file backend for back-compat, auth_backend wins when given
        self._auth = auth_backend
        if self._auth is None and auth_file:
            from h2o3_tpu.api.auth import HashFileBackend

            self._auth = HashFileBackend(auth_file)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._listener: Optional[asyncio.AbstractServer] = None
        self._pool: Optional[_WorkerPool] = None
        self._coalescer = None
        # loop-confined connection/request accounting (single-threaded
        # event loop => no locks); stop() only READS _inflight cross-thread
        self._conns: set = set()
        self._nconns = 0
        self._inflight = 0
        self._route_inflight: Dict[str, int] = {}
        # queue depth is written from both sides (loop enqueues, workers
        # dequeue), so it gets a lock
        self._qlock = threading.Lock()
        self._queued = 0
        self._draining = False
        self._stop_lock = threading.Lock()
        self._stopped = False

    def _check_auth(self, header: Optional[str]) -> bool:
        if self._auth is None:
            return True
        if not header or not header.startswith("Basic "):
            return False
        import base64

        try:
            user, _, password = (
                base64.b64decode(header[6:]).decode().partition(":")
            )
        except Exception:
            return False
        return self._auth.authenticate(user, password)

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "H2OServer":
        # the /3/Logs ring must be live from the first request, whether or
        # not any module logged before the server came up (satellite of the
        # telemetry PR: init() is idempotent, dir comes from H2O3_TPU_LOG_DIR)
        from h2o3_tpu.util import log as _log

        _log.init()
        # standalone REST nodes (no boot_node) still get a watchdog +
        # crash hooks; on a clustered node boot_node already started it
        from h2o3_tpu.cluster import health as _health

        _health.start()
        opts = self.http
        self._pool = _WorkerPool(opts.workers)
        if opts.batch_window_ms > 0:
            from h2o3_tpu.api.coalesce import Coalescer

            self._coalescer = Coalescer(
                dispatch=self._pool.submit,
                window_s=opts.batch_window_ms / 1000.0,
                max_rows=opts.batch_max_rows,
                max_requests=opts.batch_max_requests,
            )
        ctx = None
        if self.ssl_cert:
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(self.ssl_cert, self.ssl_key)
        self._loop = asyncio.new_event_loop()
        bound: _CFuture = _CFuture()

        async def _serve() -> None:
            try:
                srv = await asyncio.start_server(
                    self._handle_conn, self.ip, self.port, ssl=ctx,
                    # the stream limit backs the header-size cap: an
                    # overlong line surfaces as LimitOverrunError -> 413
                    limit=max(opts.max_header_bytes, 64 * 1024),
                    backlog=1024,
                )
            except BaseException as e:  # noqa: BLE001
                bound.set_exception(e)
                return
            self._listener = srv
            bound.set_result(srv.sockets[0].getsockname()[1])

        def _loop_main() -> None:
            loop = self._loop
            asyncio.set_event_loop(loop)
            try:
                loop.run_until_complete(_serve())
                if bound.exception() is None:
                    loop.run_forever()
            finally:
                try:
                    pending = asyncio.all_tasks(loop)
                    for t in pending:
                        t.cancel()
                    if pending:
                        loop.run_until_complete(asyncio.gather(
                            *pending, return_exceptions=True))
                finally:
                    loop.close()

        self._thread = threading.Thread(
            target=_loop_main, daemon=True,
            name="http-loop",  # matches /3/Profiler's "^http" filter
        )
        self._thread.start()
        try:
            self.port = int(bound.result(timeout=30))
        except BaseException:
            self.stop()
            raise
        # a live application-plane cloud learns where this node's REST
        # surface landed (OS-assigned ports resolve only here); gossip
        # then carries it to every member's /3/Cloud listing
        from h2o3_tpu import cluster

        _cloud = cluster.local_cloud()
        if _cloud is not None:
            _cloud.advertise_rest_port(self.port)
        # registry of live in-process servers: lets clients answer "is
        # this endpoint one of ours?" exactly at connect time, instead
        # of guessing from the address (a port-forwarded remote can
        # look like loopback)
        _LIVE_URLS.add(self.url)
        return self

    def stop(self) -> None:
        # idempotent + thread-safe: /3/Shutdown schedules a delayed stop
        # that may race the owner's own stop() call.  Shutdown is a
        # bounded drain: close the listener, let in-flight requests finish
        # for up to drain_s, then 503 what's still queued and cut the
        # connections — a lingering keep-alive client can never wedge a
        # test teardown or a chaos restart.
        with self._stop_lock:
            if self._stopped:
                return
            self._stopped = True
        _LIVE_URLS.discard(self.url)
        loop, thread = self._loop, self._thread
        self._draining = True
        if loop is not None and thread is not None and thread.is_alive():
            async def _begin() -> None:
                if self._listener is not None:
                    self._listener.close()
                if self._coalescer is not None:
                    self._coalescer.flush()

            try:
                asyncio.run_coroutine_threadsafe(
                    _begin(), loop).result(timeout=5)
            except Exception:
                pass
            deadline = time.monotonic() + self.http.drain_s
            while time.monotonic() < deadline and self._inflight > 0:
                time.sleep(0.01)

            async def _finish() -> None:
                for t in list(self._conns):
                    t.cancel()

            try:
                asyncio.run_coroutine_threadsafe(
                    _finish(), loop).result(timeout=5)
            except Exception:
                pass
            loop.call_soon_threadsafe(loop.stop)
            thread.join(timeout=10)
        if self._pool is not None:
            self._pool.stop()

    @property
    def url(self) -> str:
        scheme = "https" if self.ssl_cert else "http"
        # a wildcard bind is reachable via loopback for local clients
        host = "127.0.0.1" if self.ip in ("0.0.0.0", "::") else self.ip
        return f"{scheme}://{host}:{self.port}"

    # -- connection handling (event-loop side) -------------------------------
    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        task = asyncio.current_task()
        self._conns.add(task)
        self._nconns += 1
        try:
            with _HTTP_CONNS.track():
                if self._nconns > self.http.max_conns:
                    _HTTP_SHED.inc(route="(connection_limit)")
                    _flight.record(_flight.COALESCE, "warn", "shed",
                                   route="(connection_limit)",
                                   conns=self._nconns)
                    await _write_response(
                        writer, 429,
                        _body_bytes(429, "connection limit reached"),
                        extra=(("Retry-After", "1"),), close=True)
                    return
                await self._conn_loop(reader, writer)
        except (asyncio.CancelledError, ConnectionError):
            pass
        except Exception:  # noqa: BLE001
            from h2o3_tpu.util.log import get_logger

            get_logger("rest").error(
                "connection handler crashed: %s", traceback.format_exc())
        finally:
            self._conns.discard(task)
            self._nconns -= 1
            try:
                writer.close()
            except Exception:
                pass

    async def _conn_loop(self, reader: asyncio.StreamReader,
                         writer: asyncio.StreamWriter) -> None:
        opts = self.http
        loop = self._loop
        while not self._draining:
            # request line: wait out keep-alive idleness, then put the
            # rest of the head under a read deadline — a slow-loris client
            # gets 408, it never pins anything
            try:
                line = await asyncio.wait_for(
                    reader.readline(), opts.idle_timeout_s)
            except asyncio.TimeoutError:
                return  # idle keep-alive expired: close silently
            except (ValueError, asyncio.LimitOverrunError):
                await _write_response(
                    writer, 413,
                    _body_bytes(413, "request line too long"), close=True)
                return
            if not line:
                return
            if line in (b"\r\n", b"\n"):
                continue  # tolerate stray blank lines between requests
            try:
                method, target, version = (
                    line.decode("latin-1").rstrip("\r\n").split(" ", 2))
            except ValueError:
                await _write_response(
                    writer, 400,
                    _body_bytes(400, "malformed request line"), close=True)
                return
            deadline = loop.time() + opts.read_timeout_s
            headers: Dict[str, str] = {}
            hbytes = len(line)
            bad: Optional[Tuple[int, str]] = None
            while True:
                try:
                    h = await asyncio.wait_for(
                        reader.readline(),
                        max(0.001, deadline - loop.time()))
                except asyncio.TimeoutError:
                    bad = (408, "request header read deadline exceeded")
                    break
                except (ValueError, asyncio.LimitOverrunError):
                    bad = (413, "request header line too long")
                    break
                if not h:
                    return  # client went away mid-header
                if h in (b"\r\n", b"\n"):
                    break
                hbytes += len(h)
                if hbytes > opts.max_header_bytes:
                    bad = (413, f"request headers exceed "
                                f"{opts.max_header_bytes} bytes")
                    break
                k, sep, v = h.decode("latin-1").partition(":")
                if sep:
                    headers[k.strip().lower()] = v.strip()
            if bad is not None:
                await _write_response(
                    writer, bad[0], _body_bytes(*bad), close=True)
                return
            path = urllib.parse.urlsplit(target).path
            if (method == "GET" and path == "/3/Steam.web"
                    and "websocket" in headers.get("upgrade", "").lower()):
                await self._serve_websocket(reader, writer, headers)
                return
            # body: Content-Length only (the clients we serve — h2o-py,
            # the R client, curl uploads — all send it)
            if "chunked" in headers.get("transfer-encoding", "").lower():
                await _write_response(
                    writer, 411,
                    _body_bytes(411, "chunked transfer encoding not "
                                     "supported; send Content-Length"),
                    close=True)
                return
            try:
                length = int(headers.get("content-length") or 0)
            except ValueError:
                await _write_response(
                    writer, 400,
                    _body_bytes(400, "bad Content-Length"), close=True)
                return
            if length > opts.max_body_bytes:
                await _write_response(
                    writer, 413,
                    _body_bytes(413, f"request body exceeds "
                                     f"{opts.max_body_bytes} bytes"),
                    close=True)
                return
            body = b""
            if length:
                if "100-continue" in headers.get("expect", "").lower():
                    writer.write(b"HTTP/1.1 100 Continue\r\n\r\n")
                try:
                    body = await asyncio.wait_for(
                        reader.readexactly(length), opts.read_timeout_s)
                except asyncio.TimeoutError:
                    await _write_response(
                        writer, 408,
                        _body_bytes(408, "request body read deadline "
                                         "exceeded"),
                        close=True)
                    return
                except asyncio.IncompleteReadError:
                    return
            keep = _keep_alive(version, headers)
            ok = await self._serve_request(
                writer, method, target, headers, body, keep)
            if not ok or not keep:
                return

    async def _serve_request(self, writer: asyncio.StreamWriter, method: str,
                             target: str, headers: Dict[str, str],
                             body: bytes, keep: bool) -> bool:
        """Route + admission + response for one parsed request.  Returns
        False when the connection should close."""
        from h2o3_tpu.util.log import get_logger

        t0 = time.perf_counter()
        parsed = urllib.parse.urlsplit(target)
        path = parsed.path
        if method not in ("GET", "POST", "DELETE"):
            return await _write_response(
                writer, 501,
                _body_bytes(501, f"unsupported method {method}"),
                close=not keep) and keep
        # the request meters label by registered route pattern; an
        # unmatched path collapses into one "(unmatched)" series so
        # scanners can't mint unbounded label values
        found = self.registry.match(method, path)
        route = found[2] if found else "(unmatched)"
        if not self._check_auth(headers.get("authorization")):
            get_logger("rest").info("%s %s", method, path)
            resp: _Resp = (
                401,
                json.dumps({"http_status": 401,
                            "msg": "authentication required"}).encode(),
                "application/json", None)
            return await self._finish_request(
                writer, method, route, t0, resp, keep,
                extra=(("WWW-Authenticate", 'Basic realm="h2o3-tpu"'),))
        if found is None:
            with telemetry.Span("rest", method=method,
                                route=route, path=path) as span:
                get_logger("rest").info("%s %s", method, path)
            status, payload = _error_body(
                RestError(404, f"no route for {method} {path}"))
            return await self._finish_request(
                writer, method, route, t0,
                (status, payload, "application/json", span.trace_id), keep)
        handler, path_kw, route = found
        # -- admission control ------------------------------------------------
        budget = self.http.budget_for(route)
        if self._route_inflight.get(route, 0) >= budget:
            _HTTP_SHED.inc(route=route)
            _flight.record(_flight.COALESCE, "warn", "shed", route=route,
                           why="route_budget")
            resp = (429,
                    _body_bytes(429, f"route {route} concurrency budget "
                                     f"({budget}) exhausted"),
                    "application/json", None)
            return await self._finish_request(
                writer, method, route, t0, resp, keep,
                extra=(("Retry-After", "1"),))
        batch_fn = getattr(handler, "_h2o3_batch", None)
        coalesce = (self._coalescer is not None and batch_fn is not None
                    and not self._draining)
        if not coalesce and self._queued >= self.http.queue:
            _HTTP_SHED.inc(route=route)
            _flight.record(_flight.COALESCE, "warn", "shed", route=route,
                           why="queue_full")
            resp = (429,
                    _body_bytes(429, f"request queue full "
                                     f"({self.http.queue})"),
                    "application/json", None)
            return await self._finish_request(
                writer, method, route, t0, resp, keep,
                extra=(("Retry-After", "1"),))
        # -- admitted ---------------------------------------------------------
        self._route_inflight[route] = self._route_inflight.get(route, 0) + 1
        self._inflight += 1
        _HTTP_INFLIGHT.inc()
        try:
            job = _Job(method, path, parsed.query,
                       headers.get("content-type", ""), body, handler,
                       path_kw, route,
                       _trace_header(headers.get("x-h2o3-trace-id")),
                       _trace_header(headers.get("x-h2o3-span-id")))
            if coalesce:
                if job.trace_id is None:
                    # every coalesced rider gets its own trace identity up
                    # front (not just the leader's batch span), so the
                    # dispatch cost splits across rider traces and each
                    # response echoes an id /3/Traces/{id} can resolve
                    job.trace_id = telemetry._new_id()
                key = (route, handler._h2o3_batch_key(path_kw))
                group_fn = getattr(handler, "_h2o3_batch_group", None)
                rows_fn = getattr(handler, "_h2o3_batch_rows", None)
                cfut = self._coalescer.submit(
                    functools.partial(_run_batch, route, batch_fn),
                    key, job,
                    rows_hint=rows_fn(path_kw) if rows_fn else 0,
                    group=(key, group_fn(path_kw)) if group_fn else None,
                    trace_id=job.trace_id,
                )
            else:
                cfut = job.future
                with self._qlock:
                    self._queued += 1
                _HTTP_QUEUE_DEPTH.inc()
                self._pool.submit(functools.partial(self._exec_job, job))
            try:
                resp = await asyncio.wrap_future(cfut)
            except asyncio.CancelledError:
                # drain deadline expired with this request still queued:
                # best-effort 503 (buffered, no drain — the loop is
                # stopping) before the connection is cut
                _resolve(cfut, _DRAIN_RESP)
                try:
                    writer.write(_render_head(
                        503, len(_DRAIN_RESP[1]), "application/json",
                        close=True) + _DRAIN_RESP[1])
                except Exception:
                    pass
                raise
            except BaseException as e:  # noqa: BLE001
                status, payload = _error_body(e)
                resp = (status, payload, "application/json", None,
                        tuple(getattr(e, "headers", ()) or ()))
            return await self._finish_request(
                writer, method, route, t0, resp, keep)
        finally:
            self._route_inflight[route] = (
                self._route_inflight.get(route, 1) - 1)
            self._inflight -= 1
            _HTTP_INFLIGHT.dec()

    async def _finish_request(self, writer: asyncio.StreamWriter, method: str,
                              route: str, t0: float, resp: _Resp, keep: bool,
                              extra: Tuple[Tuple[str, str], ...] = ()) -> bool:
        status, payload, ctype, trace_id, *rest = resp
        if rest and rest[0]:
            # handler-supplied headers (RestError.headers): e.g. the
            # serving plane forwarding a remote home's Retry-After
            extra = extra + tuple(rest[0])
        # account BEFORE the response flushes: a client that has read its
        # response can immediately see the request in /3/Metrics
        # (read-your-writes for the meters)
        _REST_REQUESTS.inc(method=method, route=route, status=str(status))
        _REST_SECONDS.observe(
            time.perf_counter() - t0, method=method, route=route)
        if trace_id:
            # clients correlate their request with /3/Timeline
            extra = extra + (("X-H2O3-Trace-Id", trace_id),)
        return await _write_response(
            writer, status, payload, ctype=ctype, extra=extra,
            close=not keep) and keep

    def _exec_job(self, job: _Job) -> None:
        with self._qlock:
            self._queued -= 1
        _HTTP_QUEUE_DEPTH.dec()
        if job.future.done():
            return  # drained/cancelled while queued: nobody is listening
        _run_job(job)

    def _in_worker(self, fn: Callable, *args: Any) -> "asyncio.Future":
        """Run fn on the bounded worker pool, awaitable from the loop."""
        fut: _CFuture = _CFuture()

        def run() -> None:
            try:
                fut.set_result(fn(*args))
            except BaseException as e:  # noqa: BLE001
                try:
                    fut.set_exception(e)
                except Exception:
                    pass

        self._pool.submit(run)
        return asyncio.wrap_future(fut)

    # -- websocket (Steam) ---------------------------------------------------
    async def _serve_websocket(self, reader: asyncio.StreamReader,
                               writer: asyncio.StreamWriter,
                               headers: Dict[str, str]) -> None:
        """RFC 6455 server endpoint for /3/Steam.web (async reimplementation
        of steam.serve_websocket's frame loop; the handshake/encode/dispatch
        pieces are steam's pure helpers)."""
        from h2o3_tpu.api import steam

        if not self._check_auth(headers.get("authorization")):
            await _write_response(writer, 401, b"", close=True)
            return
        key = headers.get("sec-websocket-key", "")
        if not key:
            await _write_response(writer, 400, b"", close=True)
            return
        writer.write(
            b"HTTP/1.1 101 Switching Protocols\r\n"
            b"Upgrade: websocket\r\n"
            b"Connection: Upgrade\r\n"
            b"Sec-WebSocket-Accept: " + steam.accept_key(key).encode()
            + b"\r\n\r\n")
        await writer.drain()
        try:
            while True:
                head = await reader.readexactly(2)
                opcode = head[0] & 0x0F
                masked = head[1] & 0x80
                n = head[1] & 0x7F
                if n == 126:
                    n = struct.unpack(">H", await reader.readexactly(2))[0]
                elif n == 127:
                    n = struct.unpack(">Q", await reader.readexactly(8))[0]
                if n > (1 << 22):
                    return  # oversized control-plane frame: drop
                mask = await reader.readexactly(4) if masked else b""
                payload = await reader.readexactly(n) if n else b""
                if masked:
                    payload = bytes(
                        b ^ mask[i % 4] for i, b in enumerate(payload))
                if opcode == 0x8:  # close: echo and stop
                    writer.write(steam.encode_frame(payload, 0x8))
                    await writer.drain()
                    return
                if opcode == 0x9:  # ping -> pong
                    writer.write(steam.encode_frame(payload, 0xA))
                    await writer.drain()
                    continue
                if opcode != 0x1:
                    continue  # binary/continuation: the exchange is text-only
                try:
                    message = json.loads(payload.decode())
                except (ValueError, UnicodeDecodeError):
                    continue
                # messengers may import/compute (hello touches the device
                # mesh), so the dispatch runs off-loop
                for resp in await self._in_worker(steam.dispatch, message):
                    writer.write(
                        steam.encode_frame(json.dumps(resp).encode()))
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            return


def start_server(port: int = 0, name: str = "h2o3-tpu", **kw) -> H2OServer:
    """Start a server on localhost (port 0 = OS-assigned). Keyword args
    pass through to H2OServer (ssl_cert/ssl_key/auth_file/http)."""
    return H2OServer(port=port, name=name, **kw).start()
