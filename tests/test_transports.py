"""In-process transport tests over real sockets.

The reference tests its transports against real listeners without external
processes (`grpc.rs:196-296`, `transport/redis_test.rs`); same here: each
test boots the transport on an ephemeral port, drives it with a raw client,
and asserts wire-level behavior — shared limiter state across transports
included (`tests/integration/multi_transport.rs:159-225`).
"""

import asyncio
import json

from throttlecrab_tpu.runtime import health_suffix
from throttlecrab_tpu.server.engine import BatchingEngine
from throttlecrab_tpu.server.http import HttpTransport
from throttlecrab_tpu.server.metrics import Metrics
from throttlecrab_tpu.server.redis import RedisTransport
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

T0 = 1_700_000_000 * 1_000_000_000


def make_stack(**engine_kwargs):
    metrics = Metrics(max_denied_keys=10)
    limiter = TpuRateLimiter(capacity=1024)
    engine = BatchingEngine(
        limiter,
        batch_size=engine_kwargs.pop("batch_size", 64),
        max_linger_us=engine_kwargs.pop("max_linger_us", 500),
        now_fn=lambda: T0,
        **engine_kwargs,
    )
    return engine, metrics


async def http_request(port, method, path, body=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode() if body is not None else b""
    head = (
        f"{method} {path} HTTP/1.1\r\n"
        "Host: localhost\r\n"
        f"Content-Length: {len(payload)}\r\n"
        "Connection: close\r\n\r\n"
    ).encode()
    writer.write(head + payload)
    await writer.drain()
    raw = await reader.read(-1)
    writer.close()
    await writer.wait_closed()
    head_raw, _, body_raw = raw.partition(b"\r\n\r\n")
    status = int(head_raw.split(b" ", 2)[1])
    return status, body_raw


async def resp_command(reader, writer, *parts):
    frame = b"*%d\r\n" % len(parts)
    for part in parts:
        data = part.encode() if isinstance(part, str) else part
        frame += b"$%d\r\n%s\r\n" % (len(data), data)
    writer.write(frame)
    await writer.drain()
    return await asyncio.wait_for(reader.read(4096), timeout=2.0)


# ------------------------------------------------------------------ HTTP #


def test_http_throttle_health_metrics():
    async def main():
        engine, metrics = make_stack()
        transport = HttpTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port

        body = {"key": "u:1", "max_burst": 3, "count_per_period": 10,
                "period": 60}
        allowed = []
        for _ in range(5):
            status, raw = await http_request(port, "POST", "/throttle", body)
            assert status == 200
            allowed.append(json.loads(raw)["allowed"])

        status, raw = await http_request(port, "GET", "/health")
        assert (status, raw) == (200, b"OK " + health_suffix().encode())

        status, raw = await http_request(port, "GET", "/metrics")
        assert status == 200
        text = raw.decode()
        assert "throttlecrab_requests_total 5" in text
        assert 'transport="http"} 5' in text
        assert "throttlecrab_requests_allowed 3" in text
        assert "throttlecrab_requests_denied 2" in text
        assert 'throttlecrab_top_denied_keys{key="u:1",rank="1"} 2' in text

        await transport.stop()
        return allowed

    allowed = asyncio.run(main())
    assert allowed == [True, True, True, False, False]


def test_http_error_shapes():
    async def main():
        engine, metrics = make_stack()
        transport = HttpTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port

        # Malformed JSON → 400 with error payload.
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        bad = b"not json"
        writer.write(
            b"POST /throttle HTTP/1.1\r\nHost: x\r\nContent-Length: "
            + str(len(bad)).encode() + b"\r\nConnection: close\r\n\r\n" + bad
        )
        await writer.drain()
        raw = await reader.read(-1)
        writer.close()
        assert b" 400 " in raw.split(b"\r\n", 1)[0]
        assert b"error" in raw

        # Invalid params → 500 (engine-level error, like the reference).
        status, raw = await http_request(
            port, "POST", "/throttle",
            {"key": "k", "max_burst": -1, "count_per_period": 10,
             "period": 60},
        )
        assert status == 500
        assert b"invalid rate limit parameters" in raw

        # Unknown route → 404.
        status, _ = await http_request(port, "GET", "/nope")
        assert status == 404

        await transport.stop()

    asyncio.run(main())


def test_http_quantity_defaults_to_one():
    async def main():
        engine, metrics = make_stack()
        transport = HttpTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port
        body = {"key": "q", "max_burst": 10, "count_per_period": 100,
                "period": 60}
        _, raw = await http_request(port, "POST", "/throttle", body)
        first = json.loads(raw)
        await transport.stop()
        return first

    first = asyncio.run(main())
    assert first["allowed"] is True
    assert first["remaining"] == 9  # one token consumed


def test_http_keep_alive_pipelining():
    async def main():
        engine, metrics = make_stack()
        transport = HttpTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps({"key": "ka", "max_burst": 10,
                           "count_per_period": 100, "period": 60}).encode()
        one = (
            b"POST /throttle HTTP/1.1\r\nHost: x\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\n\r\n" + body
        )
        writer.write(one + one)  # two requests, one connection
        await writer.drain()
        data = b""
        while data.count(b"HTTP/1.1 200") < 2:
            chunk = await asyncio.wait_for(reader.read(4096), timeout=2.0)
            if not chunk:
                break
            data += chunk
        writer.close()
        await transport.stop()
        return data

    data = asyncio.run(main())
    assert data.count(b"HTTP/1.1 200") == 2


# ----------------------------------------------------------------- Redis #


def test_redis_ping_throttle_quit():
    async def main():
        engine, metrics = make_stack()
        transport = RedisTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port
        reader, writer = await asyncio.open_connection("127.0.0.1", port)

        assert await resp_command(reader, writer, "PING") == b"+PONG\r\n"
        assert await resp_command(reader, writer, "PING", "hi") == (
            b"$2\r\nhi\r\n"
        )
        # Case-insensitive commands (redis/mod.rs:166).
        # burst 3 @ 10/60s: emission 6s, tolerance 12s → first hit leaves
        # remaining=2, reset_after=12s.
        out = await resp_command(reader, writer, "throttle", "rk", "3",
                                 "10", "60")
        assert out == b"*5\r\n:1\r\n:3\r\n:2\r\n:12\r\n:0\r\n"
        for _ in range(2):
            out = await resp_command(reader, writer, "THROTTLE", "rk", "3",
                                     "10", "60")
        assert out.startswith(b"*5\r\n:1\r\n")
        out = await resp_command(reader, writer, "THROTTLE", "rk", "3",
                                 "10", "60")
        assert out.startswith(b"*5\r\n:0\r\n")  # burst exhausted

        assert await resp_command(reader, writer, "QUIT") == b"+OK\r\n"
        assert await reader.read(16) == b""  # server closed

        await transport.stop()
        return metrics

    metrics = asyncio.run(main())
    assert metrics.requests_total == 4
    assert metrics.requests_denied == 1


def test_redis_error_cases():
    async def main():
        engine, metrics = make_stack()
        transport = RedisTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port
        reader, writer = await asyncio.open_connection("127.0.0.1", port)

        out = await resp_command(reader, writer, "NOSUCH")
        assert out == b"-ERR unknown command 'NOSUCH'\r\n"
        out = await resp_command(reader, writer, "THROTTLE", "k")
        assert b"wrong number of arguments" in out
        out = await resp_command(reader, writer, "THROTTLE", "k", "abc",
                                 "10", "60")
        assert out == b"-ERR invalid max_burst\r\n"
        # Quantity argument works: burst 10 @ 100/60s, qty 5 → remaining 5,
        # reset_after 7.8s truncated to 7.
        out = await resp_command(reader, writer, "THROTTLE", "qk", "10",
                                 "100", "60", "5")
        assert out == b"*5\r\n:1\r\n:10\r\n:5\r\n:7\r\n:0\r\n"
        writer.close()
        await transport.stop()

    asyncio.run(main())


def test_redis_partial_frames_accumulate():
    async def main():
        engine, metrics = make_stack()
        transport = RedisTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        frame = b"*1\r\n$4\r\nPING\r\n"
        writer.write(frame[:5])
        await writer.drain()
        await asyncio.sleep(0.05)
        writer.write(frame[5:])
        await writer.drain()
        out = await asyncio.wait_for(reader.read(64), timeout=2.0)
        writer.close()
        await transport.stop()
        return out

    assert asyncio.run(main()) == b"+PONG\r\n"


def test_redis_malformed_input_closes_with_error():
    async def main():
        engine, metrics = make_stack()
        transport = RedisTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"*999999999999\r\n")
        await writer.drain()
        out = await asyncio.wait_for(reader.read(256), timeout=2.0)
        writer.close()
        await transport.stop()
        return out

    assert asyncio.run(main()).startswith(b"-ERR")


# ------------------------------------------------------------------ gRPC #


def test_grpc_throttle_roundtrip():
    import grpc.aio

    from throttlecrab_tpu.server.grpc import GrpcTransport
    from throttlecrab_tpu.server.proto import throttlecrab_pb2 as pb

    async def main():
        engine, metrics = make_stack()
        transport = GrpcTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port

        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as channel:
            method = channel.unary_unary(
                "/throttlecrab.RateLimiter/Throttle",
                request_serializer=pb.ThrottleRequest.SerializeToString,
                response_deserializer=pb.ThrottleResponse.FromString,
            )
            results = []
            for _ in range(5):
                response = await method(
                    pb.ThrottleRequest(
                        key="g:1", max_burst=3, count_per_period=10,
                        period=60, quantity=1,
                    )
                )
                results.append(response.allowed)
            last = response
        await transport.stop()
        return results, last, metrics

    results, last, metrics = asyncio.run(main())
    assert results == [True, True, True, False, False]
    assert last.limit == 3
    assert last.retry_after >= 1
    assert metrics.requests_by_transport["grpc"] == 5


# ------------------------------------- shared state across transports #


def test_multi_transport_shared_limits():
    """One key, limits shared across HTTP and Redis
    (multi_transport.rs:159-225)."""

    async def main():
        engine, metrics = make_stack()
        http_t = HttpTransport("127.0.0.1", 0, engine, metrics)
        redis_t = RedisTransport("127.0.0.1", 0, engine, metrics)
        await http_t.start()
        await redis_t.start()

        body = {"key": "shared", "max_burst": 4, "count_per_period": 10,
                "period": 60}
        seq = []
        for _ in range(2):
            _, raw = await http_request(
                http_t.bound_port, "POST", "/throttle", body
            )
            seq.append(json.loads(raw)["allowed"])
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", redis_t.bound_port
        )
        for _ in range(3):
            out = await resp_command(reader, writer, "THROTTLE", "shared",
                                     "4", "10", "60")
            seq.append(out.startswith(b"*5\r\n:1\r\n"))
        writer.close()
        await http_t.stop()
        await redis_t.stop()
        return seq

    assert asyncio.run(main()) == [True, True, True, True, False]


def test_stop_with_open_connections_returns_promptly():
    """stop() must drop idle open connections (the reference aborts its
    transport tasks on shutdown) instead of waiting out the 5-minute idle
    read — Server.wait_closed() on 3.12+ waits for every handler."""

    async def main():
        engine, metrics = make_stack()
        http_t = HttpTransport("127.0.0.1", 0, engine, metrics)
        redis_t = RedisTransport("127.0.0.1", 0, engine, metrics)
        await http_t.start()
        await redis_t.start()

        # One live connection per transport, both left open and idle.
        r1, w1 = await asyncio.open_connection(
            "127.0.0.1", redis_t.bound_port
        )
        out = await resp_command(r1, w1, "THROTTLE", "sd", "3", "10", "60")
        assert out.startswith(b"*5\r\n:1\r\n")
        r2, w2 = await asyncio.open_connection(
            "127.0.0.1", http_t.bound_port
        )
        w2.write(b"GET /health HTTP/1.1\r\nHost: x\r\n\r\n")
        await w2.drain()
        await r2.read(64)  # keep-alive: handler stays in its read loop

        await asyncio.wait_for(redis_t.stop(), timeout=5.0)
        await asyncio.wait_for(http_t.stop(), timeout=5.0)
        for w in (w1, w2):
            w.close()

    asyncio.run(main())


# ------------------------------------------------- client deadlines #


class _Clock:
    def __init__(self, start=T0):
        self.now = start

    def __call__(self):
        return self.now


def make_deadline_stack():
    """batch_size=2 + huge linger: the deadline-carrying request parks
    in the queue until a second one fills the batch, so the test —
    not the scheduler — decides what the flush-time clock reads."""
    metrics = Metrics(max_denied_keys=10)
    limiter = TpuRateLimiter(capacity=1024)
    clock = _Clock()
    engine = BatchingEngine(
        limiter, batch_size=2, max_linger_us=10_000_000, now_fn=clock
    )
    return engine, metrics, clock


def test_http_deadline_header_sheds_504():
    """`X-Throttlecrab-Deadline-Ms` stamps a client deadline; a request
    still queued past it answers 504 while its batchmate — flushed in
    the same window — still gets a real decision."""

    async def main():
        engine, metrics, clock = make_deadline_stack()
        transport = HttpTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port

        async def with_deadline():
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port
            )
            payload = json.dumps(
                {"key": "dl", "max_burst": 3, "count_per_period": 10,
                 "period": 60}
            ).encode()
            writer.write((
                "POST /throttle HTTP/1.1\r\nHost: x\r\n"
                "X-Throttlecrab-Deadline-Ms: 5\r\n"
                f"Content-Length: {len(payload)}\r\n"
                "Connection: close\r\n\r\n"
            ).encode() + payload)
            await writer.drain()
            raw = await reader.read(-1)
            writer.close()
            head, _, body = raw.partition(b"\r\n\r\n")
            return int(head.split(b" ", 2)[1]), body

        t1 = asyncio.create_task(with_deadline())
        # Let it enqueue, then lapse its 5 ms budget on the virtual
        # clock before the batch-filling second request flushes.
        await asyncio.sleep(0.1)
        clock.now += 10 * 1_000_000
        status2, raw2 = await http_request(
            port, "POST", "/throttle",
            {"key": "dl2", "max_burst": 3, "count_per_period": 10,
             "period": 60},
        )
        status1, raw1 = await t1
        await transport.stop()
        return status1, raw1, status2, raw2, engine.deadline_shed

    status1, raw1, status2, raw2, shed = asyncio.run(main())
    assert status1 == 504
    assert b"deadline exceeded" in raw1
    assert status2 == 200 and json.loads(raw2)["allowed"]
    assert shed == 1


def test_redis_deadline_token_sheds_err():
    """THROTTLE's optional 7th token is a deadline in ms: an invalid
    one answers -ERR immediately; a lapsed one sheds the queued request
    with -ERR deadline exceeded (single RESP error channel)."""

    async def main():
        engine, metrics, clock = make_deadline_stack()
        transport = RedisTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port
        r1, w1 = await asyncio.open_connection("127.0.0.1", port)
        r2, w2 = await asyncio.open_connection("127.0.0.1", port)

        out = await resp_command(
            r1, w1, "THROTTLE", "dk", "3", "10", "60", "1", "abc"
        )
        assert out == b"-ERR invalid deadline_ms\r\n"

        t1 = asyncio.create_task(
            resp_command(
                r1, w1, "THROTTLE", "dk", "3", "10", "60", "1", "5"
            )
        )
        await asyncio.sleep(0.1)
        clock.now += 10 * 1_000_000
        out2 = await resp_command(r2, w2, "THROTTLE", "dk2", "3", "10",
                                  "60")
        out1 = await t1
        for w in (w1, w2):
            w.close()
        await transport.stop()
        return out1, out2, engine.deadline_shed

    out1, out2, shed = asyncio.run(main())
    assert out1 == b"-ERR deadline exceeded\r\n"
    assert out2.startswith(b"*5\r\n:1\r\n")
    assert shed == 1


def test_grpc_native_deadline_sheds_deadline_exceeded():
    """gRPC carries deadlines natively: the call's remaining budget
    maps onto the engine deadline, so a request whose budget lapses
    in-queue is shed host-side with DEADLINE_EXCEEDED instead of
    spending a device launch on an abandoned call."""
    import grpc
    import grpc.aio

    from throttlecrab_tpu.server.grpc import GrpcTransport
    from throttlecrab_tpu.server.proto import throttlecrab_pb2 as pb

    async def main():
        engine, metrics, clock = make_deadline_stack()
        transport = GrpcTransport("127.0.0.1", 0, engine, metrics)
        await transport.start()
        port = transport.bound_port
        async with grpc.aio.insecure_channel(
            f"127.0.0.1:{port}"
        ) as channel:
            method = channel.unary_unary(
                "/throttlecrab.RateLimiter/Throttle",
                request_serializer=pb.ThrottleRequest.SerializeToString,
                response_deserializer=pb.ThrottleResponse.FromString,
            )
            # 30 s real-time budget: far more than the test needs, so
            # the DEADLINE_EXCEEDED below can only come from the
            # engine's virtual-clock shed, not the client timer.
            t1 = asyncio.ensure_future(method(
                pb.ThrottleRequest(
                    key="gd", max_burst=3, count_per_period=10,
                    period=60, quantity=1,
                ),
                timeout=30.0,
            ))
            await asyncio.sleep(0.2)
            clock.now += 60 * 1_000_000_000
            ok = await method(
                pb.ThrottleRequest(
                    key="gd2", max_burst=3, count_per_period=10,
                    period=60, quantity=1,
                )
            )
            code = None
            try:
                await t1
            except grpc.aio.AioRpcError as e:
                code = e.code()
        await transport.stop()
        return code, ok.allowed, engine.deadline_shed

    code, ok, shed = asyncio.run(main())
    assert code == grpc.StatusCode.DEADLINE_EXCEEDED
    assert ok
    assert shed == 1
