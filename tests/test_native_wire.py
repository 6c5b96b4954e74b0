"""Native C++ RESP transport tests: same wire behavior as the asyncio
transport (test_transports.py), driven over real sockets."""

import asyncio

import pytest

from throttlecrab_tpu.native import (
    toolchain_available,
    wire_available,
    wire_build_error,
)
from throttlecrab_tpu.server.metrics import Metrics
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

# A broken build with a compiler present is a bug, not an environment gap:
# fail the whole module loudly instead of skipping.
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
    from throttlecrab_tpu.server.native_redis import NativeRedisTransport

    metrics = Metrics(max_denied_keys=10)
    limiter = TpuRateLimiter(capacity=1024)
    transport = NativeRedisTransport(
        "127.0.0.1", 0, limiter, metrics,
        batch_size=kwargs.pop("batch_size", 64),
        max_linger_us=kwargs.pop("max_linger_us", 500),
        now_fn=lambda: T0,
        **kwargs,
    )
    return transport, metrics


async def resp_command(reader, writer, *parts):
    frame = b"*%d\r\n" % len(parts)
    for part in parts:
        data = part.encode() if isinstance(part, str) else part
        frame += b"$%d\r\n%s\r\n" % (len(data), data)
    writer.write(frame)
    await writer.drain()
    return await asyncio.wait_for(reader.read(4096), timeout=5.0)


def test_native_ping_throttle_quit():
    async def main():
        transport, metrics = make_transport()
        await transport.start()
        port = transport.bound_port
        reader, writer = await asyncio.open_connection("127.0.0.1", port)

        assert await resp_command(reader, writer, "PING") == b"+PONG\r\n"
        assert await resp_command(reader, writer, "PING", "hey") == (
            b"$3\r\nhey\r\n"
        )
        out = await resp_command(reader, writer, "throttle", "nk", "3",
                                 "10", "60")
        assert out == b"*5\r\n:1\r\n:3\r\n:2\r\n:12\r\n:0\r\n"
        for _ in range(2):
            out = await resp_command(reader, writer, "THROTTLE", "nk", "3",
                                     "10", "60")
        assert out.startswith(b"*5\r\n:1\r\n")
        out = await resp_command(reader, writer, "THROTTLE", "nk", "3",
                                 "10", "60")
        assert out.startswith(b"*5\r\n:0\r\n")  # exhausted

        assert await resp_command(reader, writer, "QUIT") == b"+OK\r\n"
        assert await reader.read(16) == b""

        await transport.stop()
        return metrics

    metrics = asyncio.run(main())
    assert metrics.requests_total == 4
    assert metrics.requests_denied == 1


def test_native_error_cases():
    async def main():
        transport, _ = make_transport()
        await transport.start()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", transport.bound_port
        )
        out = await resp_command(reader, writer, "BOGUS")
        assert out == b"-ERR unknown command 'BOGUS'\r\n"
        out = await resp_command(reader, writer, "THROTTLE", "k")
        assert b"wrong number of arguments" in out
        out = await resp_command(reader, writer, "THROTTLE", "k", "x",
                                 "10", "60")
        assert out == b"-ERR invalid max_burst\r\n"
        # Engine-level validation error surfaces as -ERR.
        out = await resp_command(reader, writer, "THROTTLE", "k", "-5",
                                 "10", "60")
        assert out == b"-ERR invalid rate limit parameters\r\n"
        # Quantity arg.
        out = await resp_command(reader, writer, "THROTTLE", "qk", "10",
                                 "100", "60", "5")
        assert out == b"*5\r\n:1\r\n:10\r\n:5\r\n:7\r\n:0\r\n"
        writer.close()
        await transport.stop()

    asyncio.run(main())


def test_native_pipelined_commands():
    async def main():
        transport, _ = make_transport()
        await transport.start()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", transport.bound_port
        )
        one = b"*4\r\n$8\r\nTHROTTLE\r\n$2\r\npk\r\n$2\r\n10\r\n$3\r\n100\r\n"
        # Malformed on purpose? No: THROTTLE needs 4-5 args after the name;
        # build a full valid frame instead.
        one = (b"*5\r\n$8\r\nTHROTTLE\r\n$2\r\npk\r\n$2\r\n10\r\n"
               b"$3\r\n100\r\n$2\r\n60\r\n")
        writer.write(one * 20)  # 20 pipelined commands in one write
        await writer.drain()
        data = b""
        while data.count(b"*5\r\n") < 20:
            chunk = await asyncio.wait_for(reader.read(8192), timeout=5.0)
            if not chunk:
                break
            data += chunk
        writer.close()
        await transport.stop()
        return data

    data = asyncio.run(main())
    assert data.count(b"*5\r\n:1\r\n") == 10  # burst 10
    assert data.count(b"*5\r\n:0\r\n") == 10  # the rest denied


def test_native_partial_frames():
    async def main():
        transport, _ = make_transport()
        await transport.start()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", transport.bound_port
        )
        frame = b"*1\r\n$4\r\nPING\r\n"
        writer.write(frame[:6])
        await writer.drain()
        await asyncio.sleep(0.05)
        writer.write(frame[6:])
        await writer.drain()
        out = await asyncio.wait_for(reader.read(64), timeout=5.0)
        writer.close()
        await transport.stop()
        return out

    assert asyncio.run(main()) == b"+PONG\r\n"


def test_native_protocol_attack_vectors():
    async def main():
        outs = []
        for payload in (
            b"*999999999999\r\n",
            b"!inline\r\n",
            b"*1\r\n$99999999999999\r\n",
        ):
            transport, _ = make_transport()
            await transport.start()
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", transport.bound_port
            )
            writer.write(payload)
            await writer.drain()
            outs.append(
                await asyncio.wait_for(reader.read(256), timeout=5.0)
            )
            writer.close()
            await transport.stop()
        return outs

    for out in asyncio.run(main()):
        assert out.startswith(b"-ERR")


def _frame(*parts):
    """RESP array frame; None parts encode as null bulk strings ($-1)."""
    frame = b"*%d\r\n" % len(parts)
    for part in parts:
        if part is None:
            frame += b"$-1\r\n"
        else:
            data = part.encode() if isinstance(part, str) else part
            frame += b"$%d\r\n%s\r\n" % (len(data), data)
    return frame


def test_native_pipelined_inline_after_throttle_stays_ordered():
    """A PING pipelined behind a THROTTLE must answer after it: inline
    replies wait for the driver-answered slots ahead of them."""

    async def main():
        transport, _ = make_transport()
        await transport.start()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", transport.bound_port
        )
        writer.write(
            _frame("THROTTLE", "ok1", "10", "100", "60") + _frame("PING")
        )
        await writer.drain()
        data = b""
        while b"+PONG\r\n" not in data:
            chunk = await asyncio.wait_for(reader.read(4096), timeout=5.0)
            assert chunk, f"connection closed early: {data!r}"
            data += chunk
        writer.close()
        await transport.stop()
        return data

    data = asyncio.run(main())
    assert data.index(b"*5\r\n:1\r\n") < data.index(b"+PONG\r\n")


def test_native_quit_waits_for_pipelined_throttle():
    """QUIT pipelined behind THROTTLEs must deliver their responses, then
    +OK, then close — not close early and drop them."""

    async def main():
        transport, _ = make_transport()
        await transport.start()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", transport.bound_port
        )
        writer.write(
            _frame("THROTTLE", "qk1", "10", "100", "60")
            + _frame("THROTTLE", "qk2", "10", "100", "60")
            + _frame("QUIT")
        )
        await writer.drain()
        data = b""
        while True:
            chunk = await asyncio.wait_for(reader.read(4096), timeout=5.0)
            if not chunk:
                break
            data += chunk
        await transport.stop()
        return data

    data = asyncio.run(main())
    assert data.count(b"*5\r\n:1\r\n") == 2
    assert data.endswith(b"+OK\r\n")


def test_native_half_close_still_delivers_pipelined_responses():
    """Client pipelines THROTTLE+THROTTLE+QUIT then shutdown(SHUT_WR)
    (printf | nc style): all responses and the +OK must still arrive —
    EOF with pending slots must not drop the connection early."""
    import socket as socket_mod

    async def main():
        transport, _ = make_transport()
        await transport.start()
        loop = __import__("asyncio").get_running_loop()

        def client():
            s = socket_mod.create_connection(
                ("127.0.0.1", transport.bound_port), 5
            )
            s.sendall(
                _frame("THROTTLE", "hc1", "10", "100", "60")
                + _frame("THROTTLE", "hc2", "10", "100", "60")
                + _frame("QUIT")
            )
            s.shutdown(socket_mod.SHUT_WR)  # half-close before reading
            s.settimeout(5)
            data = b""
            while True:
                try:
                    chunk = s.recv(4096)
                except socket_mod.timeout:
                    break
                if not chunk:
                    break
                data += chunk
            s.close()
            return data

        data = await loop.run_in_executor(None, client)
        await transport.stop()
        return data

    data = asyncio.run(main())
    assert data.count(b"*5\r\n:1\r\n") == 2
    assert data.endswith(b"+OK\r\n")


def test_native_null_bulk_arguments_rejected():
    async def main():
        transport, _ = make_transport()
        await transport.start()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", transport.bound_port
        )

        async def roundtrip(frame):
            writer.write(frame)
            await writer.drain()
            return await asyncio.wait_for(reader.read(4096), timeout=5.0)

        outs = {
            "null_key": await roundtrip(
                _frame("THROTTLE", None, "10", "100", "60")
            ),
            "null_cmd": await roundtrip(_frame(None, "x")),
            "null_burst": await roundtrip(
                _frame("THROTTLE", "k", None, "100", "60")
            ),
            "null_ping": await roundtrip(_frame("PING", None)),
        }
        writer.close()
        await transport.stop()
        return outs

    outs = asyncio.run(main())
    assert outs["null_key"] == b"-ERR invalid key\r\n"
    assert outs["null_cmd"] == b"-ERR invalid command format\r\n"
    assert outs["null_burst"] == b"-ERR invalid max_burst\r\n"
    assert outs["null_ping"] == b"$-1\r\n"  # echoes null like asyncio


def test_native_concurrent_clients_share_limits():
    async def main():
        transport, metrics = make_transport()
        await transport.start()
        port = transport.bound_port

        async def client(n):
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            allowed = 0
            for _ in range(n):
                out = await resp_command(reader, writer, "THROTTLE",
                                         "shared", "20", "100", "3600")
                allowed += out.startswith(b"*5\r\n:1\r\n")
            writer.close()
            return allowed

        counts = await asyncio.gather(*[client(10) for _ in range(4)])
        await transport.stop()
        return counts

    counts = asyncio.run(main())
    assert sum(counts) == 20  # burst 20 across 40 attempts on 4 conns


def test_stop_wakes_parked_driver_promptly():
    """Drain-correct shutdown: with a huge linger the driver parks deep
    inside ws_next_batch — stop() must wake it via the C++ poison pill
    (running flag + condvar notify) and join within a bounded time, not
    sleep out the linger or silently leak the thread."""
    import time

    async def main():
        transport, _ = make_transport(max_linger_us=30_000_000)  # 30 s
        await transport.start()
        await asyncio.sleep(0.3)  # let the driver park in ws_next_batch
        t0 = time.monotonic()
        await transport.stop()
        elapsed = time.monotonic() - t0
        return elapsed, transport._driver

    elapsed, driver = asyncio.run(main())
    assert elapsed < 5.0, f"stop took {elapsed:.1f}s (linger not interrupted)"
    assert not driver.is_alive()


def test_native_http_health_reflects_supervisor_state():
    """The native HTTP wire layer serves /health from the pushed
    failure-domain state, not a hardcoded OK."""
    from throttlecrab_tpu.runtime import health_suffix
    from throttlecrab_tpu.server.native_http import NativeHttpTransport
    from throttlecrab_tpu.server.supervisor import SupervisedLimiter

    async def http_get(port, path):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(
            f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
        )
        await writer.drain()
        head = await asyncio.wait_for(
            reader.readuntil(b"\r\n\r\n"), timeout=5.0
        )
        length = 0
        for line in head.split(b"\r\n"):
            if line.lower().startswith(b"content-length:"):
                length = int(line.split(b":", 1)[1])
        body = await reader.readexactly(length)
        writer.close()
        return body

    async def main():
        metrics = Metrics()
        limiter = SupervisedLimiter(TpuRateLimiter(capacity=256))
        transport = NativeHttpTransport(
            "127.0.0.1", 0, limiter, metrics,
            batch_size=16, max_linger_us=500, now_fn=lambda: T0,
        )
        await transport.start()
        try:
            await asyncio.sleep(0.2)  # first _push_metrics ran
            ok_body = await http_get(transport.bound_port, "/health")
            # Force the state machine into degraded and push again.
            limiter._set_state("degraded")
            transport._push_metrics()
            degraded_body = await http_get(
                transport.bound_port, "/health"
            )
            return ok_body, degraded_body
        finally:
            await transport.stop()

    ok_body, degraded_body = asyncio.run(main())
    device = " " + health_suffix()
    assert ok_body == b"OK" + device.encode()
    assert degraded_body == b"degraded" + device.encode()
