"""Seeded wire fuzz: a live server must survive arbitrary garbage bytes.

The transport probes that found real bugs in earlier rounds (garbage
frame kinds, oversized length headers, truncated msgpack) pinned as a
deterministic regression: batches of seeded-random malformed input are
thrown at a real server socket, and after every batch the server must
still answer a well-formed request on a FRESH connection. Mirrors the
reference's posture that a bad client must never take the node down
(the frame loop's error handling, ``rio-rs/src/service.rs:370-459``).
"""

from __future__ import annotations

import asyncio
import random
import struct

from tests.test_aio_transport import _boot, _frame

from rio_tpu.protocol import decode_response

_MAGIC_BAD = [
    b"",  # empty write then close
    b"\x00" * 4,  # zero-length frame header
    struct.pack(">I", 2**31) + b"\x02",  # absurd length prefix
    struct.pack(">I", 5) + b"\xff\xff\xff\xff\xff",  # unknown kind + junk
    struct.pack(">I", 1) + b"\x00",  # request kind, empty body
    struct.pack(">I", 3) + b"\x00\x91\xc0",  # truncated envelope msgpack
    b"GET / HTTP/1.1\r\nHost: x\r\n\r\n",  # wrong protocol entirely
]


def _random_garbage(rng: random.Random) -> bytes:
    n = rng.randrange(1, 64)
    body = bytes(rng.randrange(256) for _ in range(n))
    if rng.random() < 0.5:
        # Plausible header, garbage body — exercises the decode path, not
        # just the framer.
        return struct.pack(">I", len(body)) + body
    return body


async def _poke_garbage(host: str, port: int, payload: bytes) -> None:
    try:
        reader, writer = await asyncio.open_connection(host, port)
    except OSError:
        return
    try:
        writer.write(payload)
        await writer.drain()
        # Give the server a beat to react (error response or drop).
        try:
            await asyncio.wait_for(reader.read(64), 0.2)
        except asyncio.TimeoutError:
            pass
    except OSError:
        pass  # server dropped us mid-write: acceptable
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except OSError:
            pass


async def _valid_roundtrip(host: str, port: int, tag: int) -> None:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(_frame("fuzz-canary", tag))
        await writer.drain()
        hdr = await asyncio.wait_for(reader.readexactly(4), 5)
        (ln,) = struct.unpack(">I", hdr)
        raw = await asyncio.wait_for(reader.readexactly(ln), 5)
        assert decode_response(raw) is not None
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except OSError:
            pass


async def _fuzz_against(host: str, port: int) -> None:
    rng = random.Random(0xF022)
    for batch in range(8):
        payloads = list(_MAGIC_BAD) + [_random_garbage(rng) for _ in range(25)]
        await asyncio.gather(*[_poke_garbage(host, port, p) for p in payloads])
        # The node must still serve well-formed traffic.
        await _valid_roundtrip(host, port, tag=batch)


def test_server_survives_garbage_frames():
    async def run():
        server, task, host, port = await _boot()
        try:
            await _fuzz_against(host, port)
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(asyncio.wait_for(run(), 60))
