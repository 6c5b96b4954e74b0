"""Native C++ HTTP transport tests: wire behavior must match the asyncio
HTTP transport (test_transports.py) for the same requests."""

import asyncio
import json

import pytest

from throttlecrab_tpu.native import (
    toolchain_available,
    wire_available,
    wire_build_error,
)
from throttlecrab_tpu.runtime import health_suffix
from throttlecrab_tpu.server.metrics import Metrics
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

if not wire_available() and toolchain_available():
    pytest.fail(
        "C++ wire server failed to build with g++ present:\n"
        f"{wire_build_error()}",
        pytrace=False,
    )
pytestmark = pytest.mark.skipif(
    not wire_available(),
    reason=f"no C++ toolchain for the wire server: {wire_build_error()}",
)

T0 = 1_700_000_000 * 1_000_000_000


def make_transport(**kwargs):
    from throttlecrab_tpu.server.native_http import NativeHttpTransport

    metrics = Metrics(max_denied_keys=10)
    limiter = TpuRateLimiter(capacity=1024)
    transport = NativeHttpTransport(
        "127.0.0.1", 0, limiter, metrics,
        batch_size=kwargs.pop("batch_size", 64),
        max_linger_us=kwargs.pop("max_linger_us", 500),
        now_fn=lambda: T0,
        **kwargs,
    )
    return transport, metrics


async def http_request(port, method, path, body=None, close=True,
                       reader=None, writer=None):
    if reader is None:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode() if body is not None else b""
    head = (
        f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
        f"Content-Length: {len(payload)}\r\n"
        + ("Connection: close\r\n" if close else "")
        + "\r\n"
    ).encode()
    writer.write(head + payload)
    await writer.drain()
    status_line = await asyncio.wait_for(
        reader.readuntil(b"\r\n"), timeout=5.0
    )
    status = int(status_line.split(b" ")[1])
    headers = await asyncio.wait_for(
        reader.readuntil(b"\r\n\r\n"), timeout=5.0
    )
    length = 0
    for line in headers.split(b"\r\n"):
        if line.lower().startswith(b"content-length:"):
            length = int(line.split(b":")[1])
    data = await asyncio.wait_for(reader.readexactly(length), timeout=5.0)
    if close:
        writer.close()
    return status, data


def test_native_http_throttle_flow():
    async def main():
        transport, metrics = make_transport()
        await transport.start()
        port = transport.bound_port
        body = {"key": "nh:1", "max_burst": 3, "count_per_period": 10,
                "period": 60}
        allowed = []
        for _ in range(5):
            status, raw = await http_request(port, "POST", "/throttle", body)
            assert status == 200
            r = json.loads(raw)
            allowed.append(r["allowed"])
        assert r["limit"] == 3 and r["retry_after"] >= 1
        await transport.stop()
        return allowed, metrics

    allowed, metrics = asyncio.run(main())
    assert allowed == [True, True, True, False, False]
    assert metrics.requests_total == 5
    assert metrics.requests_denied == 2


def test_native_http_health_and_metrics():
    async def main():
        transport, metrics = make_transport()
        await transport.start()
        port = transport.bound_port
        status, raw = await http_request(port, "GET", "/health")
        assert (status, raw) == (200, b"OK " + health_suffix().encode())
        # Generate some traffic, then wait for the 1s metrics refresh.
        body = {"key": "m", "max_burst": 1, "count_per_period": 1,
                "period": 60}
        for _ in range(3):
            await http_request(port, "POST", "/throttle", body)
        await asyncio.sleep(1.2)
        status, raw = await http_request(port, "GET", "/metrics")
        assert status == 200
        text = raw.decode()
        assert "throttlecrab_requests_total 3" in text
        assert 'transport="http"} 3' in text
        await transport.stop()

    asyncio.run(main())


def test_native_http_drain_health_keeps_device():
    """Draining flips the /health state at once and still reports the
    device, so a balancer reading the body never loses it."""
    async def main():
        transport, _ = make_transport()
        await transport.start()
        await transport.drain()
        status, raw = await http_request(
            transport.bound_port, "GET", "/health"
        )
        await transport.stop()
        return status, raw

    assert asyncio.run(main()) == (
        200, b"draining " + health_suffix().encode()
    )


def test_native_http_error_shapes():
    async def main():
        transport, _ = make_transport()
        await transport.start()
        port = transport.bound_port
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        bad = b"not json"
        writer.write(
            b"POST /throttle HTTP/1.1\r\nHost: x\r\nContent-Length: "
            + str(len(bad)).encode() + b"\r\nConnection: close\r\n\r\n"
            + bad
        )
        await writer.drain()
        raw = await reader.read(-1)
        assert b" 400 " in raw.split(b"\r\n", 1)[0]
        assert b"error" in raw
        writer.close()

        status, raw = await http_request(
            port, "POST", "/throttle",
            {"key": "k", "max_burst": -1, "count_per_period": 10,
             "period": 60},
        )
        assert status == 500
        assert b"invalid rate limit parameters" in raw

        status, _ = await http_request(port, "GET", "/nope")
        assert status == 404
        await transport.stop()

    asyncio.run(main())


def test_native_http_quantity_default_and_escapes():
    async def main():
        transport, _ = make_transport()
        await transport.start()
        port = transport.bound_port
        # No quantity → defaults to 1 (http.rs:135).
        status, raw = await http_request(
            port, "POST", "/throttle",
            {"key": "q", "max_burst": 10, "count_per_period": 100,
             "period": 60},
        )
        assert json.loads(raw)["remaining"] == 9
        # Escaped key: json.dumps produces \" and \n escapes; both engines
        # must see the same unescaped identity.
        weird = 'a"b\nc'
        body = {"key": weird, "max_burst": 2, "count_per_period": 10,
                "period": 3600}
        seq = []
        for _ in range(3):
            _, raw = await http_request(port, "POST", "/throttle", body)
            seq.append(json.loads(raw)["allowed"])
        assert seq == [True, True, False]  # one bucket, burst 2
        await transport.stop()

    asyncio.run(main())


def test_native_http_keep_alive_pipelining():
    async def main():
        transport, _ = make_transport()
        await transport.start()
        port = transport.bound_port
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        results = []
        for i in range(4):
            status, raw = await http_request(
                port, "POST", "/throttle",
                {"key": f"ka{i}", "max_burst": 5, "count_per_period": 10,
                 "period": 60},
                close=False, reader=reader, writer=writer,
            )
            results.append(status)
        writer.close()
        await transport.stop()
        return results

    assert asyncio.run(main()) == [200, 200, 200, 200]
