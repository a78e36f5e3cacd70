"""Socket-level serving edge: an ASGI front-end for the Hub Gateway.

``HubEdgeApp`` is a dependency-light ASGI 3.0 callable (it runs under
uvicorn unchanged, no framework required) that maps HTTP bodies through
the strict-JSON wire codec (``repro.api.codec``) into ``AsyncHubGateway``
operations:

    POST /v1/predict       PredictRequest   -> PredictResult
    POST /v1/choose        ChooseRequest    -> ChooseResult
    POST /v1/contribute    ContributeRequest -> ContributeResult
    POST /v1/model_errors  ModelErrorsRequest -> ModelErrorsResult
    POST /v1/search        SearchRequest    -> SearchResult
    POST /v1/trust_state   TrustStateRequest -> TrustStateResult
    POST /v1/compact       CompactRequest   -> CompactResult
    POST /v1               any of the above (routes on "__type__")
    GET  /healthz          -> HealthResult
    GET  /stats            -> StatsResult

Every HTTP response body is a codec-encoded ``Response`` envelope —
malformed JSON, unknown ops, oversized bodies, auth refusals, and even
internal faults come back as TYPED error envelopes with a mapped HTTP
status, never a raw 500 page.  Requests wrapped in ``AuthedRequest``
carry bearer tokens exactly as in-process.  Single-row predict and
choose requests coalesce on the gateway's per-(job, machine) /
per-(job) micro-batch lanes, so socket concurrency turns into batched
engine dispatches.

``EdgeServer`` is the bundled minimal asyncio HTTP/1.1 host (keep-alive,
content-length framing) so the edge binds a REAL socket in environments
without uvicorn — the closed-loop load generator
(``repro.serve.loadgen``) and the ``edge`` benchmark lane drive it over
localhost.  Shutdown drains: in-flight requests (including in-flight
lane dispatches) finish, new requests answer a typed ``shutting_down``
envelope, and only then are the gateway lanes stopped.

Quickstart (demo hub with emulated Spark jobs):

    PYTHONPATH=src python -m repro.serve.edge --port 8787
    curl -s localhost:8787/healthz
    curl -s -X POST localhost:8787/v1/choose -d '{"__type__":
      "ChooseRequest","job":"grep","context":[15.0,0.02],"t_max":400.0}'
"""
from __future__ import annotations

import argparse
import asyncio
import math
import time
from typing import Dict, Optional, Tuple

from repro.api import codec
from repro.api.gateway import AsyncHubGateway
from repro.api.types import (API_VERSION, ERR_BAD_REQUEST, ERR_INTERNAL,
                             ERR_QUOTA_EXCEEDED, ERR_SHUTTING_DOWN,
                             ERR_TIMEOUT, ERR_UNAUTHORIZED, ERR_UNKNOWN_JOB,
                             AuthedRequest, ChooseRequest, CompactRequest,
                             ContributeRequest, HealthResult, LaneSnapshot,
                             ModelErrorsRequest, PredictRequest, Response,
                             SearchRequest, StatsResult, TrustStateRequest)
from repro.serve.config_service import ServeStats

#: request-envelope type expected by each POST /v1/<op> endpoint
OPS: Dict[str, type] = {
    "predict": PredictRequest,
    "choose": ChooseRequest,
    "contribute": ContributeRequest,
    "model_errors": ModelErrorsRequest,
    "search": SearchRequest,
    "trust_state": TrustStateRequest,
    "compact": CompactRequest,
}

#: HTTP status for each typed error code (ok envelopes are 200); the
#: body is ALWAYS a codec-encoded Response — the status is advisory for
#: generic HTTP tooling, the envelope is the contract
STATUS_FOR_ERROR: Dict[str, int] = {
    ERR_BAD_REQUEST: 400,
    ERR_UNAUTHORIZED: 403,
    ERR_UNKNOWN_JOB: 404,
    ERR_QUOTA_EXCEEDED: 429,
    ERR_INTERNAL: 500,
    ERR_SHUTTING_DOWN: 503,
    ERR_TIMEOUT: 504,
}

_REASONS = {200: "OK", 400: "Bad Request", 403: "Forbidden",
            404: "Not Found", 405: "Method Not Allowed",
            413: "Payload Too Large", 429: "Too Many Requests",
            431: "Request Header Fields Too Large",
            500: "Internal Server Error", 503: "Service Unavailable",
            504: "Gateway Timeout"}


def _ms(seconds: float) -> float:
    return seconds * 1e3 if math.isfinite(seconds) else seconds


class HubEdgeApp:
    """ASGI app serving an ``AsyncHubGateway`` over HTTP.

    ``max_body`` caps the request body (bytes); anything larger answers
    a typed ``bad_request`` envelope with HTTP 413 before the gateway is
    touched.  HTTP-level latency (receive to response) lands in a
    bounded ``ServeStats`` reservoir served back on ``GET /stats``
    alongside every micro-batch lane's snapshot."""

    def __init__(self, gateway: AsyncHubGateway, *,
                 max_body: int = 1 << 20):
        self.gateway = gateway
        self.max_body = int(max_body)
        self.stats = ServeStats()
        self.errors = 0                    # responses with error envelopes
        self.in_flight = 0
        self.draining = False

    # ------------------------- ASGI entry ---------------------------------
    async def __call__(self, scope, receive, send) -> None:
        if scope["type"] == "lifespan":
            await self._lifespan(receive, send)
            return
        if scope["type"] != "http":        # pragma: no cover - ws etc.
            raise RuntimeError(f"unsupported ASGI scope {scope['type']!r}")
        t0 = time.monotonic()
        self.in_flight += 1
        try:
            try:
                status, resp = await self._handle(scope, receive)
            except asyncio.CancelledError:
                raise
            except Exception as e:         # noqa: BLE001 — never a raw 500
                status, resp = 500, Response.failure(
                    ERR_INTERNAL, f"{type(e).__name__}: {e}")
            if not resp.ok:
                self.errors += 1
            body = codec.encode(resp).encode("ascii")
            await send({"type": "http.response.start", "status": status,
                        "headers": [(b"content-type", b"application/json"),
                                    (b"content-length",
                                     str(len(body)).encode("ascii"))]})
            await send({"type": "http.response.body", "body": body})
        finally:
            self.in_flight -= 1
            self.stats.record_batch(1)
            self.stats.record_latency(time.monotonic() - t0)

    async def _lifespan(self, receive, send) -> None:
        """Minimal lifespan protocol so uvicorn-style hosts can manage
        the drain: shutdown runs the same path as ``EdgeServer.stop``."""
        while True:
            msg = await receive()
            if msg["type"] == "lifespan.startup":
                await send({"type": "lifespan.startup.complete"})
            elif msg["type"] == "lifespan.shutdown":
                await self.shutdown()
                await send({"type": "lifespan.shutdown.complete"})
                return

    # ------------------------- lifecycle ----------------------------------
    async def shutdown(self, *, drain_timeout_s: float = 30.0) -> None:
        """Drain, then stop the gateway lanes.

        New requests answer ``shutting_down`` envelopes the moment this
        is called; requests already being served — including in-flight
        micro-batch lane dispatches — run to completion (bounded by
        ``drain_timeout_s``), and only then are the lane workers
        stopped, so no accepted request is dropped on the floor."""
        self.draining = True
        deadline = time.monotonic() + drain_timeout_s
        while self.in_flight > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.005)
        await self.gateway.stop()

    # ------------------------- request handling ---------------------------
    async def _handle(self, scope, receive) -> Tuple[int, Response]:
        method = scope["method"]
        path = scope["path"]
        if path == "/healthz":
            if method != "GET":
                return 405, Response.failure(
                    ERR_BAD_REQUEST, f"{method} not allowed on {path}: "
                    "use GET")
            return 200, Response.success(self._health())
        if path == "/stats":
            if method != "GET":
                return 405, Response.failure(
                    ERR_BAD_REQUEST, f"{method} not allowed on {path}: "
                    "use GET")
            return 200, Response.success(self.snapshot())
        if self.draining:
            # introspection stays up through the drain; API operations
            # are refused with the typed envelope so clients fail over
            return 503, Response.failure(
                ERR_SHUTTING_DOWN,
                "edge is draining for shutdown; retry against another "
                "replica")
        op = None
        if path != "/v1":
            if not path.startswith("/v1/"):
                return 404, Response.failure(
                    ERR_BAD_REQUEST,
                    f"no such endpoint: {path!r} (POST /v1/<op> with op in "
                    f"{sorted(OPS)}, GET /healthz, GET /stats)")
            op = path[len("/v1/"):]
            if op not in OPS:
                return 404, Response.failure(
                    ERR_BAD_REQUEST,
                    f"unknown operation {op!r} (known: {sorted(OPS)})")
        if method != "POST":
            return 405, Response.failure(
                ERR_BAD_REQUEST,
                f"{method} not allowed on {path}: API v1 operations are "
                "POST")
        body, overflow = await self._read_body(receive)
        if overflow:
            return 413, Response.failure(
                ERR_BAD_REQUEST,
                f"request body exceeds the {self.max_body}-byte cap")
        if body is None:
            return 400, Response.failure(
                ERR_BAD_REQUEST, "client disconnected mid-body")
        try:
            request = codec.decode(body.decode("utf-8"))
        except Exception as e:             # noqa: BLE001 — client's bytes
            return 400, Response.failure(
                ERR_BAD_REQUEST,
                f"malformed request body: {type(e).__name__}: {e}")
        inner = request.request if isinstance(request, AuthedRequest) \
            else request
        if op is not None and not isinstance(inner, OPS[op]):
            return 400, Response.failure(
                ERR_BAD_REQUEST,
                f"endpoint /v1/{op} expects a {OPS[op].__name__}, got "
                f"{type(inner).__name__}")
        if type(inner) not in OPS.values():
            return 400, Response.failure(
                ERR_BAD_REQUEST,
                f"not an API v1 request: {type(inner).__name__}")
        resp = await self.gateway.handle_async(request)
        return self._status(resp), resp

    async def _read_body(self, receive) -> Tuple[Optional[bytes], bool]:
        """Accumulate the request body up to ``max_body``; returns
        ``(body, overflow)`` — body is None if the client vanished."""
        chunks = bytearray()
        while True:
            msg = await receive()
            if msg["type"] == "http.disconnect":
                return None, False
            chunks += msg.get("body", b"")
            if len(chunks) > self.max_body:
                return None, True
            if not msg.get("more_body", False):
                return bytes(chunks), False

    # ------------------------- introspection ------------------------------
    def _status(self, resp: Response) -> int:
        return 200 if resp.ok else STATUS_FOR_ERROR.get(resp.error_code, 500)

    def _health(self) -> HealthResult:
        return HealthResult("draining" if self.draining else "ok",
                            API_VERSION,
                            tuple(self.gateway.gateway.hub.jobs()))

    def snapshot(self) -> StatsResult:
        """Server-side serving stats: HTTP-level counters/percentiles
        plus one snapshot per live micro-batch lane."""
        lanes = []
        for name, s in sorted(self.gateway.lane_stats.items()):
            lanes.append(LaneSnapshot(
                name, s.requests, s.batches, s.mean_batch,
                _ms(s.p50), _ms(s.p95), _ms(s.p99)))
        return StatsResult(self.stats.requests, self.errors, self.in_flight,
                           self.draining, _ms(self.stats.p50),
                           _ms(self.stats.p95), _ms(self.stats.p99),
                           tuple(lanes))


class EdgeServer:
    """Minimal asyncio HTTP/1.1 host for ``HubEdgeApp``.

    Speaks exactly what the edge needs over localhost and CI: request
    line + headers, content-length framing (chunked transfer encoding is
    refused with a typed envelope), keep-alive connections.  ``port=0``
    binds an ephemeral port (read it back from ``.port`` after
    ``start``).  ``stop()`` closes the listener FIRST (new connections
    are refused at the TCP layer), then drains the app — requests still
    arriving on live connections answer ``shutting_down`` envelopes —
    and finally force-closes whatever connections remain."""

    #: header-block cap (readuntil limit); requests with more header
    #: bytes than this answer 431 and close
    MAX_HEAD = 32 * 1024

    def __init__(self, app: HubEdgeApp, host: str = "127.0.0.1",
                 port: int = 0):
        self.app = app
        self.host = host
        self.port = port
        self._server: Optional[asyncio.AbstractServer] = None
        self._writers: set = set()

    async def __aenter__(self) -> "EdgeServer":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    async def start(self) -> "EdgeServer":
        self._server = await asyncio.start_server(
            self._serve_connection, self.host, self.port,
            limit=self.MAX_HEAD)
        self.port = self._server.sockets[0].getsockname()[1]
        return self

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()           # refuse NEW connections first
        await self.app.shutdown()          # drain in-flight, stop lanes
        if self._server is not None:
            await self._server.wait_closed()
            self._server = None
        for w in list(self._writers):      # idle keep-alive stragglers
            w.close()

    # ------------------------- connection loop ----------------------------
    async def _serve_connection(self, reader: asyncio.StreamReader,
                                writer: asyncio.StreamWriter) -> None:
        self._writers.add(writer)
        try:
            while True:
                head = await self._read_head(reader)
                if head is None:
                    break
                if head == "overflow":     # header block past MAX_HEAD
                    await self._write_simple(
                        writer, 431, Response.failure(
                            ERR_BAD_REQUEST,
                            f"request head exceeds {self.MAX_HEAD} bytes"))
                    break
                method, path, headers = head
                if headers.get("transfer-encoding"):
                    await self._write_simple(
                        writer, 400, Response.failure(
                            ERR_BAD_REQUEST,
                            "chunked transfer encoding is not supported: "
                            "send content-length framed bodies"))
                    break
                try:
                    length = int(headers.get("content-length", "0"))
                    if length < 0:
                        raise ValueError
                except ValueError:
                    await self._write_simple(
                        writer, 400, Response.failure(
                            ERR_BAD_REQUEST,
                            "unparseable content-length"))
                    break
                keep_alive = headers.get("connection", "").lower() != "close"
                done = await self._run_app(reader, writer, method, path,
                                           length, keep_alive)
                if not done or not keep_alive or self.app.draining:
                    break
        except (ConnectionResetError, BrokenPipeError,
                asyncio.IncompleteReadError):
            pass                           # client went away mid-exchange
        finally:
            self._writers.discard(writer)
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    async def _read_head(self, reader):
        """Parse one request head; None on clean EOF, ``"overflow"`` on
        an oversized header block."""
        try:
            raw = await reader.readuntil(b"\r\n\r\n")
        except asyncio.IncompleteReadError:
            return None                    # connection closed between reqs
        except asyncio.LimitOverrunError:
            return "overflow"
        lines = raw.decode("latin-1").split("\r\n")
        parts = lines[0].split(" ")
        if len(parts) != 3:
            return None
        method, target = parts[0], parts[1]
        headers = {}
        for line in lines[1:]:
            if ":" in line:
                k, v = line.split(":", 1)
                headers[k.strip().lower()] = v.strip()
        path = target.split("?", 1)[0]
        return method.upper(), path, headers

    async def _run_app(self, reader, writer, method, path, length,
                       keep_alive) -> bool:
        """Bridge one request through the ASGI app.  Returns False when
        the connection can no longer be reused (unconsumed body)."""
        remaining = length
        consumed_all = length == 0

        async def receive():
            nonlocal remaining, consumed_all
            if remaining <= 0:
                consumed_all = True
                return {"type": "http.request", "body": b"",
                        "more_body": False}
            chunk = await reader.read(min(65536, remaining))
            if not chunk:
                return {"type": "http.disconnect"}
            remaining -= len(chunk)
            consumed_all = remaining == 0
            return {"type": "http.request", "body": chunk,
                    "more_body": remaining > 0}

        async def send(msg):
            if msg["type"] == "http.response.start":
                status = msg["status"]
                conn = b"keep-alive" if keep_alive and not self.app.draining \
                    else b"close"
                head = [f"HTTP/1.1 {status} "
                        f"{_REASONS.get(status, 'OK')}".encode("ascii")]
                head += [k + b": " + v for k, v in msg.get("headers", [])]
                head.append(b"connection: " + conn)
                writer.write(b"\r\n".join(head) + b"\r\n\r\n")
            elif msg["type"] == "http.response.body":
                writer.write(msg.get("body", b""))
                if not msg.get("more_body", False):
                    await writer.drain()

        scope = {"type": "http", "asgi": {"version": "3.0"},
                 "http_version": "1.1", "method": method, "path": path,
                 "raw_path": path.encode("latin-1"), "query_string": b"",
                 "headers": [], "scheme": "http"}
        await self.app(scope, receive, send)
        # the app may answer before reading the body (unknown path, 405,
        # over-cap refusal); drain a small remainder so keep-alive
        # framing survives, but a large one closes the connection
        if not consumed_all and 0 < remaining <= 65536:
            try:
                await reader.readexactly(remaining)
                remaining = 0
                consumed_all = True
            except asyncio.IncompleteReadError:
                pass
        return consumed_all

    async def _write_simple(self, writer, status: int,
                            resp: Response) -> None:
        """Protocol-level refusal (bad head), outside the ASGI app."""
        body = codec.encode(resp).encode("ascii")
        writer.write((f"HTTP/1.1 {status} {_REASONS.get(status, 'OK')}\r\n"
                      "content-type: application/json\r\n"
                      f"content-length: {len(body)}\r\n"
                      "connection: close\r\n\r\n").encode("ascii"))
        writer.write(body)
        await writer.drain()


async def serve_edge(gateway, host: str = "127.0.0.1", port: int = 0, *,
                     max_batch: int = 256, tick_s: float = 0.0,
                     timeout_s: Optional[float] = None,
                     max_body: int = 1 << 20
                     ) -> Tuple[HubEdgeApp, EdgeServer]:
    """One-call edge bring-up: wrap a ``HubGateway`` in lanes, an app,
    and a bound listening server (ephemeral port with ``port=0``)."""
    agw = AsyncHubGateway(gateway, max_batch=max_batch, tick_s=tick_s,
                          timeout_s=timeout_s)
    app = HubEdgeApp(agw, max_body=max_body)
    server = await EdgeServer(app, host, port).start()
    return app, server


def _demo_gateway(jobs=("grep", "sort")):
    """A hub of emulated Spark jobs for the quickstart CLI."""
    from repro.core.datastore import RuntimeDataStore
    from repro.core.hub import Hub, JobRepo
    from repro.workloads import spark_emul as W
    hub = Hub()
    for job in jobs:
        d = W.generate_job_data(job)
        hub.publish(JobRepo(job, job, d.schema, RuntimeDataStore(d, seed=0),
                            predictor_kw=dict(pad_rows=True,
                                              max_cv_folds=15)))
    prices = {m.name: m.price for m in W.MACHINES.values()}
    return hub.gateway(prices, (2, 3, 4, 6, 8, 12, 16))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="serve a demo C3O hub (emulated Spark jobs) over HTTP")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8787)
    ap.add_argument("--jobs", default="grep,sort",
                    help="comma-separated emulated jobs to publish")
    ap.add_argument("--max-batch", type=int, default=256)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    async def run():
        gw = _demo_gateway(tuple(j for j in args.jobs.split(",") if j))
        app, server = await serve_edge(gw, args.host, args.port,
                                       max_batch=args.max_batch)
        print(f"edge listening on http://{args.host}:{server.port} "
              f"jobs={args.jobs}", flush=True)
        try:
            await asyncio.Event().wait()
        finally:
            await server.stop()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
