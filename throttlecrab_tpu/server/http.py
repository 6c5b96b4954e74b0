"""HTTP/JSON transport.

Same wire surface as the reference's axum router (`http.rs:103-163`):
`POST /throttle` with `{key, max_burst, count_per_period, period, quantity?}`
(quantity defaults to 1, `http.rs:135`), `GET /health` returning "OK",
`GET /metrics` returning Prometheus text, and `GET /stats` returning the
insight tier's JSON analytics document (L3.75; no reference equivalent).  Timestamps are always server-side
(`http.rs:127-128`); client-supplied timestamps are ignored by design.
Errors return 500 with `{"error": ...}` like the reference's error handler
(`http.rs:148-157`).

Implemented directly on asyncio streams — a deliberately minimal HTTP/1.1
(keep-alive, Content-Length bodies) server, the same spirit as the
reference's hand-rolled RESP transport.
"""

from __future__ import annotations

import asyncio
import json
import logging
from typing import Optional

from .engine import (
    BatchingEngine,
    DeadlineError,
    OverloadError,
    ThrottleError,
)
from ..runtime import health_suffix
from .metrics import Metrics
from .transport_base import ConnTrackingMixin
from .types import ThrottleRequest

log = logging.getLogger("throttlecrab.http")

MAX_HEADER_BYTES = 16 * 1024
MAX_BODY_BYTES = 1 << 20


class HttpTransport(ConnTrackingMixin):
    """`POST /throttle` + `GET /health` + `GET /metrics` + `GET /stats`."""

    name = "http"

    def __init__(
        self, host: str, port: int, engine: BatchingEngine, metrics: Metrics
    ) -> None:
        self.host = host
        self.port = port
        self.engine = engine
        self.metrics = metrics
        self._server: Optional[asyncio.AbstractServer] = None
        self._init_conn_tracking()

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle_connection, self.host, self.port
        )
        log.info("HTTP transport listening on %s:%d", self.host, self.port)

    async def serve_forever(self) -> None:
        if self._server is None:
            await self.start()
        async with self._server:
            await self._server.serve_forever()

    async def stop(self) -> None:
        if self._server is not None:
            await self._stop_dropping_conns(self._server)

    @property
    def bound_port(self) -> int:
        return self._server.sockets[0].getsockname()[1]

    # ------------------------------------------------------------------ #

    async def _handle_connection(self, reader, writer) -> None:
        task = self._track_conn()
        try:
            while True:
                request = await self._read_request(reader)
                if request is None:
                    break
                method, path, headers, body = request
                keep_alive = (
                    headers.get("connection", "keep-alive").lower()
                    != "close"
                )
                status, payload, content_type = await self._route(
                    method, path, body, headers
                )
                await self._write_response(
                    writer, status, payload, content_type, keep_alive
                )
                if not keep_alive:
                    break
        except (
            asyncio.IncompleteReadError,
            ConnectionResetError,
            BrokenPipeError,
        ):
            pass
        except asyncio.CancelledError:
            pass  # server shutdown dropped the connection
        except Exception:
            log.exception("HTTP connection error")
        finally:
            writer.close()
            try:
                # Untrack only after the last await: stop()'s cancel loop
                # must still reach a handler stuck in wait_closed.
                await writer.wait_closed()
            except Exception:
                pass
            finally:
                self._untrack_conn(task)

    async def _read_request(self, reader):
        """Parse one HTTP/1.1 request; None on clean EOF."""
        try:
            head = await reader.readuntil(b"\r\n\r\n")
        except asyncio.IncompleteReadError as e:
            if not e.partial:
                return None
            raise
        except asyncio.LimitOverrunError:
            raise ValueError("header section too large")
        if len(head) > MAX_HEADER_BYTES:
            raise ValueError("header section too large")
        lines = head.decode("latin-1").split("\r\n")
        method, path, _version = lines[0].split(" ", 2)
        headers = {}
        for line in lines[1:]:
            if ":" in line:
                k, v = line.split(":", 1)
                headers[k.strip().lower()] = v.strip()
        length = int(headers.get("content-length", "0"))
        if length > MAX_BODY_BYTES:
            raise ValueError("body too large")
        body = await reader.readexactly(length) if length else b""
        return method, path, headers, body

    async def _route(
        self, method: str, path: str, body: bytes, headers=None
    ):
        if method == "POST" and path == "/throttle":
            return await self._handle_throttle(body, headers or {})
        if method == "GET" and path == "/health":
            # "OK" in the ok state (reference-compatible, http.rs:141);
            # otherwise the failure-domain state machine's state name
            # (server/supervisor.py).  Always 200: a degraded node is
            # still serving — a load balancer must not drain exactly
            # the traffic degraded mode exists to keep answering.
            state = self.engine.health_state()
            body = b"OK" if state == "ok" else state.encode()
            # The device the limiter computes on rides every /health.
            body += b" " + health_suffix().encode()
            ck = getattr(self.engine, "checkpointer", None)
            if ck is not None:
                # Last-checkpoint age rides /health only when the
                # durability subsystem is armed — the bare "OK" body is
                # a wire contract (reference-compatible) otherwise.
                body += b" " + ck.health_suffix().encode()
            return 200, body, "text/plain"
        if method == "GET" and path == "/health/cluster":
            # The cluster view (ring deployments): membership epoch,
            # per-peer breaker/migration state, handoff and replica
            # status — what an operator needs mid-join or mid-failover.
            # Single-node deployments answer {"mode": "none"} so
            # pollers need no probe logic.
            view_fn = getattr(self.engine.limiter, "cluster_view", None)
            payload = json.dumps(
                view_fn() if view_fn is not None else {"mode": "none"}
            ).encode()
            return 200, payload, "application/json"
        if method == "GET" and path == "/trace/dump":
            # Admin: dump the flight recorder's retained windows to a
            # trace file (throttlecrab_tpu/replay/).  Disarmed servers
            # answer enabled:false so pollers need no probe logic; the
            # dump itself (encode + file write) runs on the executor —
            # never on the event loop.
            from ..replay.recorder import active_recorder

            recorder = active_recorder()
            if recorder is None:
                payload = json.dumps({"enabled": False}).encode()
                return 200, payload, "application/json"
            loop = asyncio.get_running_loop()
            dump_path, n_windows = await loop.run_in_executor(
                None, recorder.dump
            )
            payload = json.dumps({
                "enabled": True,
                "path": dump_path,
                "windows": n_windows,
                "stats": recorder.stats(),
            }).encode()
            return 200, payload, "application/json"
        if method == "GET" and path == "/control":
            # Control-plane JSON (L3.9): mode, tick count, objective
            # score, actuator values/bounds, and the bounded actuation
            # log.  With the plane disabled the shape still answers
            # (enabled: false) so pollers need no probe logic.
            control = getattr(self.engine, "control", None)
            if control is None:
                payload = json.dumps({"control": {"enabled": False}})
            else:
                payload = control.stats_json()
            return 200, payload.encode(), "application/json"
        if method == "GET" and path == "/metrics":
            return (
                200,
                self.metrics.export_prometheus().encode(),
                "text/plain; version=0.0.4",
            )
        if method == "GET" and path == "/stats":
            # Insight-tier JSON (L3.75): traffic totals, windowed
            # rates, top denied keys, hot-set concentration.  With the
            # tier disabled the shape still answers (enabled: false)
            # so pollers need no probe logic.
            from .metrics import merge_cluster_stats

            insight = getattr(self.engine, "insight", None)
            if insight is None:
                payload = json.dumps({"insight": {"enabled": False}})
            else:
                payload = insight.stats_json(
                    state=self.engine.health_state()
                )
            # Cluster deployments: membership/handoff/replica state and
            # the per-peer counters ride the same poll (no-op and no
            # re-serialize otherwise).
            payload = merge_cluster_stats(payload, self.engine.limiter)
            return 200, payload.encode(), "application/json"
        return 404, b"Not Found", "text/plain"

    async def _handle_throttle(self, body: bytes, headers=None):
        """http.rs:123-159 — server timestamp, quantity default 1.

        `X-Throttlecrab-Deadline-Ms: N` (optional) stamps a client
        deadline N ms out; a request still queued past it is shed with
        504 instead of spending a device launch on an answer the client
        stopped waiting for."""
        try:
            data = json.loads(body)
            request = ThrottleRequest(
                key=str(data["key"]),
                max_burst=int(data["max_burst"]),
                count_per_period=int(data["count_per_period"]),
                period=int(data["period"]),
                quantity=int(data.get("quantity", 1)),
            )
            deadline_ms = (
                headers.get("x-throttlecrab-deadline-ms")
                if headers
                else None
            )
            if deadline_ms is not None:
                ms = int(deadline_ms)
                if ms > 0:
                    request.deadline_ns = (
                        self.engine.now_fn() + ms * 1_000_000
                    )
        except (json.JSONDecodeError, KeyError, TypeError, ValueError) as e:
            self.metrics.record_error(self.name)
            return (
                400,
                json.dumps({"error": f"invalid request: {e}"}).encode(),
                "application/json",
            )
        try:
            response = await self.engine.throttle(request)
        except OverloadError as e:
            # Shed by admission control: 503, the HTTP overload status
            # (NOT 500 — clients must distinguish "back off" from
            # "server bug").
            self.metrics.record_error(self.name)
            return (
                503,
                json.dumps({"error": str(e)}).encode(),
                "application/json",
            )
        except DeadlineError as e:
            # The client's deadline lapsed in-queue: 504, the HTTP
            # timeout status (clients gave up; 500 would page for a
            # condition the client caused).
            self.metrics.record_error(self.name)
            return (
                504,
                json.dumps({"error": str(e)}).encode(),
                "application/json",
            )
        except ThrottleError as e:
            self.metrics.record_error(self.name)
            return (
                500,
                json.dumps({"error": str(e)}).encode(),
                "application/json",
            )
        self.metrics.record_request_with_key(
            self.name, response.allowed, request.key
        )
        payload = json.dumps(
            {
                "allowed": response.allowed,
                "limit": response.limit,
                "remaining": response.remaining,
                "reset_after": response.reset_after,
                "retry_after": response.retry_after,
            }
        ).encode()
        return 200, payload, "application/json"

    async def _write_response(
        self, writer, status, payload, content_type, keep_alive
    ) -> None:
        reason = {200: "OK", 400: "Bad Request", 404: "Not Found",
                  500: "Internal Server Error",
                  503: "Service Unavailable",
                  504: "Gateway Timeout"}.get(status, "OK")
        head = (
            f"HTTP/1.1 {status} {reason}\r\n"
            f"Content-Type: {content_type}\r\n"
            f"Content-Length: {len(payload)}\r\n"
            f"Connection: {'keep-alive' if keep_alive else 'close'}\r\n"
            "\r\n"
        ).encode("latin-1")
        writer.write(head + payload)
        await writer.drain()
