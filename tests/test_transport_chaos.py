"""Connection-chaos soak: hostile client LIFECYCLES against a live server.

test_wire_fuzz covers hostile BYTES; this covers hostile TIMING — the
disconnect/abandon patterns real networks produce, thrown concurrently at
one server while a well-behaved workload runs. The server must (a) answer
every legitimate request correctly throughout, and (b) not leak: after the
storm, in-flight state drains to zero.

Chaos patterns (each from many concurrent connections):
  * pipeline-then-die: K valid requests, close without reading;
  * read-some-then-die: K requests, read a few responses, vanish;
  * half-close: K requests, FIN the write side, read everything (the
    finish-in-flight EOF path);
  * slow trickle: a valid frame delivered a few bytes at a time;
  * subscribe-then-die: switch the connection into streaming mode, then
    vanish (the worker-cancellation path).
"""

import asyncio
import random
import struct

from rio_tpu.protocol import SubscriptionRequest, decode_response, encode_subscribe_frame

from tests.test_aio_transport import _boot, _frame


async def _drain_close(writer):
    writer.close()
    try:
        await writer.wait_closed()
    except OSError:
        pass


async def _chaos_pipeline_die(host, port, rng):
    try:
        _, writer = await asyncio.open_connection(host, port)
    except OSError:
        return
    try:
        for i in range(rng.randrange(1, 12)):
            writer.write(_frame(f"chaos-{rng.random()}", i, delay_ms=rng.choice((0, 5))))
        await writer.drain()
    except OSError:
        pass
    await _drain_close(writer)


async def _chaos_read_some_die(host, port, rng):
    try:
        reader, writer = await asyncio.open_connection(host, port)
    except OSError:
        return
    try:
        k = rng.randrange(2, 10)
        for i in range(k):
            writer.write(_frame(f"chaos-{rng.random()}", i))
        await writer.drain()
        for _ in range(rng.randrange(0, k)):
            hdr = await asyncio.wait_for(reader.readexactly(4), 2)
            (ln,) = struct.unpack(">I", hdr)
            await asyncio.wait_for(reader.readexactly(ln), 2)
    except (OSError, asyncio.IncompleteReadError, asyncio.TimeoutError):
        pass
    await _drain_close(writer)


async def _chaos_half_close(host, port, rng):
    try:
        reader, writer = await asyncio.open_connection(host, port)
    except OSError:
        return
    try:
        k = rng.randrange(1, 8)
        for i in range(k):
            writer.write(_frame(f"chaos-{rng.random()}", i))
        await writer.drain()
        writer.write_eof()  # FIN; the server must still flush every response
        got = 0
        while got < k:
            hdr = await asyncio.wait_for(reader.readexactly(4), 5)
            (ln,) = struct.unpack(">I", hdr)
            raw = await asyncio.wait_for(reader.readexactly(ln), 5)
            assert decode_response(raw) is not None
            got += 1
    except (OSError, asyncio.IncompleteReadError, asyncio.TimeoutError):
        pass
    await _drain_close(writer)


async def _chaos_trickle(host, port, rng):
    try:
        _, writer = await asyncio.open_connection(host, port)
    except OSError:
        return
    try:
        frame = _frame(f"chaos-{rng.random()}", 1)
        for i in range(0, len(frame), 7):
            writer.write(frame[i : i + 7])
            await writer.drain()
            await asyncio.sleep(0.002)
    except OSError:
        pass
    await _drain_close(writer)


async def _chaos_subscribe_die(host, port, rng):
    try:
        reader, writer = await asyncio.open_connection(host, port)
    except OSError:
        return
    try:
        writer.write(_frame(f"chaos-{rng.random()}", 0))
        writer.write(
            encode_subscribe_frame(
                SubscriptionRequest("SleepyActor", f"chaos-{rng.random()}")
            )
        )
        await writer.drain()
        try:
            await asyncio.wait_for(reader.read(256), 0.1)
        except asyncio.TimeoutError:
            pass
    except OSError:
        pass
    await _drain_close(writer)


async def _legit_worker(host, port, n: int) -> None:
    """A well-behaved pipelined client that must see perfect FIFO echoes."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        for base in range(0, n, 4):
            tags = list(range(base, min(base + 4, n)))
            for t in tags:
                writer.write(_frame("legit", t, delay_ms=1 if t % 3 == 0 else 0))
            await writer.drain()
            for t in tags:
                hdr = await asyncio.wait_for(reader.readexactly(4), 10)
                (ln,) = struct.unpack(">I", hdr)
                raw = await asyncio.wait_for(reader.readexactly(ln), 10)
                resp = decode_response(raw)
                assert resp.error is None, resp.error
    finally:
        await _drain_close(writer)


async def _storm(host, port) -> None:
    rng = random.Random(0xC4A05)
    chaos = (
        _chaos_pipeline_die,
        _chaos_read_some_die,
        _chaos_half_close,
        _chaos_trickle,
        _chaos_subscribe_die,
    )
    for _wave in range(4):
        jobs = [
            asyncio.create_task(rng.choice(chaos)(host, port, rng))
            for _ in range(24)
        ] + [asyncio.create_task(_legit_worker(host, port, 24)) for _ in range(3)]
        results = await asyncio.gather(*jobs, return_exceptions=True)
        for r in results:
            assert not isinstance(r, BaseException), r
    # After the storm: a fresh connection still gets clean service.
    await _legit_worker(host, port, 8)


def test_server_survives_connection_chaos():
    async def run():
        server, task, host, port = await _boot()
        try:
            await _storm(host, port)
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(asyncio.wait_for(run(), 120))
