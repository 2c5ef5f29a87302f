"""Pipelined transport invariants (rio_tpu/aio.py).

The wire has no correlation ids (reference protocol contract), so the
whole design rests on two properties: the server writes responses in
exactly per-connection request order even though handlers run
concurrently, and the client matches inbound frames to pending roundtrips
FIFO — including when a roundtrip is cancelled mid-flight (its orphaned
response must be discarded, not delivered to the next waiter).
"""

import asyncio

import pytest

from rio_tpu import (
    AppData,
    LocalObjectPlacement,
    LocalStorage,
    Registry,
    Server,
    ServiceObject,
    handler,
    message,
)
from rio_tpu import aio
from rio_tpu.cluster.membership_protocol import LocalClusterProvider
from rio_tpu.codec import deserialize, serialize
from rio_tpu.protocol import (
    RequestEnvelope,
    decode_response,
    encode_request_frame,
)


@message(name="aio.Sleepy")
class Sleepy:
    tag: int = 0
    delay_ms: int = 0


@message(name="aio.Tagged")
class Tagged:
    tag: int = 0


class SleepyActor(ServiceObject):
    @handler
    async def run(self, msg: Sleepy, ctx: AppData) -> Tagged:
        if msg.delay_ms:
            await asyncio.sleep(msg.delay_ms / 1e3)
        return Tagged(tag=msg.tag)


async def _boot():
    members, placement = LocalStorage(), LocalObjectPlacement()
    server = Server(
        address="127.0.0.1:0",
        registry=Registry().add_type(SleepyActor),
        cluster_provider=LocalClusterProvider(members),
        object_placement_provider=placement,
    )
    await server.prepare()
    addr = await server.bind()
    task = asyncio.create_task(server.run())
    for _ in range(100):
        if await members.active_members():
            break
        await asyncio.sleep(0.02)
    host, _, port = addr.rpartition(":")
    return server, task, host, int(port)


def _frame(obj_id: str, tag: int, delay_ms: int = 0) -> bytes:
    return encode_request_frame(
        RequestEnvelope(
            "SleepyActor", obj_id, "aio.Sleepy",
            serialize(Sleepy(tag=tag, delay_ms=delay_ms)),
        )
    )


def test_fifo_order_with_out_of_order_completion():
    """Slow-then-fast pipelined requests: responses come back in request order.

    Distinct actor ids make the handlers truly concurrent (no shared
    per-object lock); the first (slow) handler finishes last, yet its
    response must be written first.
    """

    async def body():
        server, task, host, port = await _boot()
        try:
            conn = await aio.connect(host, port, 2.0)
            slow = asyncio.ensure_future(conn.roundtrip(_frame("a", 1, delay_ms=150)))
            await asyncio.sleep(0.01)  # ensure 'slow' is written first
            fast = asyncio.ensure_future(conn.roundtrip(_frame("b", 2, delay_ms=0)))
            r1, r2 = await asyncio.gather(slow, fast)
            t1 = deserialize(decode_response(r1).body, Tagged).tag
            t2 = deserialize(decode_response(r2).body, Tagged).tag
            assert (t1, t2) == (1, 2), "FIFO matching broke under reordering"
            conn.close()
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(body())


def test_cancelled_roundtrip_discards_orphan_response():
    """A response to a cancelled roundtrip must not shift later matches."""

    async def body():
        server, task, host, port = await _boot()
        try:
            conn = await aio.connect(host, port, 2.0)
            doomed = asyncio.ensure_future(conn.roundtrip(_frame("c", 7, delay_ms=80)))
            await asyncio.sleep(0.01)
            doomed.cancel()
            try:
                await doomed
            except asyncio.CancelledError:
                pass
            # The orphan (tag 7) arrives ~70ms from now; this roundtrip must
            # get ITS OWN response (tag 8), not the orphan.
            raw = await conn.roundtrip(_frame("d", 8, delay_ms=100))
            tag = deserialize(decode_response(raw).body, Tagged).tag
            assert tag == 8, f"orphan response leaked into the next waiter (tag={tag})"
            conn.close()
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(body())


def test_deep_pipeline_all_served_in_order():
    """Many in-flight requests on ONE connection, randomized handler delays."""

    async def body():
        server, task, host, port = await _boot()
        try:
            conn = await aio.connect(host, port, 2.0)
            n = 96  # deeper than ServerConnProtocol.MAX_CONCURRENT (64)
            futs = [
                asyncio.ensure_future(
                    conn.roundtrip(_frame(f"p{i}", i, delay_ms=(i * 7) % 23))
                )
                for i in range(n)
            ]
            raws = await asyncio.gather(*futs)
            tags = [deserialize(decode_response(r).body, Tagged).tag for r in raws]
            assert tags == list(range(n))
            conn.close()
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(body())


def test_eof_flushes_in_flight_responses():
    """Half-close after sending: pending handler responses still arrive."""

    async def body():
        server, task, host, port = await _boot()
        try:
            conn = await aio.connect(host, port, 2.0)
            fut = asyncio.ensure_future(conn.roundtrip(_frame("e", 5, delay_ms=80)))
            await asyncio.sleep(0.01)
            conn._transport.write_eof()  # we stop sending; still reading
            raw = await fut
            tag = deserialize(decode_response(raw).body, Tagged).tag
            assert tag == 5
            conn.close()
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(body())


def test_subscription_switch_flushes_pipeline_first():
    """A subscription request behind in-flight requests: all prior
    responses must be written (FIFO) before the stream takes over."""
    from rio_tpu.message_router import MessageRouter
    from rio_tpu.protocol import (
        SubscriptionRequest,
        decode_subresponse,
        encode_subscribe_frame,
    )

    async def body():
        server, task, host, port = await _boot()
        try:
            conn = await aio.connect(host, port, 2.0)
            slow = asyncio.ensure_future(conn.roundtrip(_frame("s1", 11, delay_ms=60)))
            await asyncio.sleep(0.01)
            conn.write(encode_subscribe_frame(SubscriptionRequest("SleepyActor", "s1")))
            raw = await slow  # the pending response still arrives first
            assert deserialize(decode_response(raw).body, Tagged).tag == 11
            # now in streaming mode: a publish reaches the wire
            await asyncio.sleep(0.05)  # let the server enter streaming mode
            router = server.app_data.get(MessageRouter)
            router.publish("SleepyActor", "s1", Tagged(tag=99))
            frame = await asyncio.wait_for(conn.read_frame(), 2.0)
            sub = decode_subresponse(frame)
            assert deserialize(sub.body, Tagged).tag == 99
            conn.close()
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(body())


def test_subscription_backpressure_bounds_server_memory():
    """A subscriber that stops reading must not grow server memory without
    bound: the streaming pump parks on pause_writing, the router's bounded
    per-subscriber queue drops OLDEST on overflow (broadcast-lag semantics,
    reference message_router.rs capacity 1000), and the stream stays
    healthy for fresh publishes once the client drains."""
    from rio_tpu.message_router import DEFAULT_CAPACITY, MessageRouter
    from rio_tpu.protocol import (
        SubscriptionRequest,
        decode_subresponse,
        encode_subscribe_frame,
    )

    async def body():
        server, task, host, port = await _boot()
        try:
            conn = await aio.connect(host, port, 2.0)
            conn.write(encode_subscribe_frame(SubscriptionRequest("SleepyActor", "bp")))
            await asyncio.sleep(0.1)  # server enters streaming mode
            # Stop the client from reading; shrink its receive window so
            # kernel buffers saturate quickly and pause_writing fires.
            import socket as _socket

            sock = conn._transport.get_extra_info("socket")
            sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF, 4096)
            conn._transport.pause_reading()

            router = server.app_data.get(MessageRouter)
            publish_count = 5 * DEFAULT_CAPACITY
            for i in range(publish_count):
                router.publish("SleepyActor", "bp", Tagged(tag=i))
            await asyncio.sleep(0.3)

            # Resume: what arrives is whatever squeezed through before the
            # stall plus at most the router's bounded queue — far less than
            # everything published (the overflow was dropped, not buffered).
            conn._transport.resume_reading()
            got = []
            try:
                while True:
                    frame = await asyncio.wait_for(conn.read_frame(), 1.0)
                    assert frame is not None
                    got.append(deserialize(decode_subresponse(frame).body, Tagged).tag)
                    if got and got[-1] == publish_count - 1:
                        break  # newest message delivered; backlog drained
            except asyncio.TimeoutError:
                raise AssertionError("stream never delivered the newest message")
            assert len(got) < publish_count  # lag dropped, not buffered
            assert got[-1] == publish_count - 1  # newest survives (drop-oldest)

            # The stream is still live for fresh publishes.
            router.publish("SleepyActor", "bp", Tagged(tag=999_999))
            frame = await asyncio.wait_for(conn.read_frame(), 2.0)
            assert deserialize(decode_subresponse(frame).body, Tagged).tag == 999_999
            conn.close()
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(body())


class _RecordingTransport:
    """asyncio.Transport stand-in recording pause/resume/write calls."""

    def __init__(self):
        self.paused = False
        self.pauses = 0
        self.resumes = 0
        self.writes = []
        self.closed = False

    def pause_reading(self):
        self.paused = True
        self.pauses += 1

    def resume_reading(self):
        self.paused = False
        self.resumes += 1

    def write(self, data):
        self.writes.append(data)

    def close(self):
        self.closed = True

    def is_closing(self):
        return self.closed


def test_server_inbound_backpressure_pauses_and_resumes_reads():
    """A pipelining flood beyond MAX_PENDING_FRAMES pauses the transport.

    MAX_CONCURRENT caps in-flight handlers but not buffered frames; without
    pause_reading a fast client grows server memory without bound: the
    transport must propagate TCP backpressure. Regression for the round-3
    advisor finding.
    """

    async def body():
        from rio_tpu.protocol import ResponseEnvelope

        gate = asyncio.Event()

        class _StubService:
            async def call(self, env):
                await gate.wait()
                return ResponseEnvelope.ok(b"")

        proto = aio.ServerConnProtocol(_StubService)
        transport = _RecordingTransport()
        proto.connection_made(transport)
        flood = proto.MAX_PENDING_FRAMES + 200
        payload = _frame("bp", 0)
        fed = 0
        while fed < flood and not transport.paused:
            n = min(50, flood - fed)
            proto.data_received(payload * n)  # a real kernel stops after pause
            fed += n
            await asyncio.sleep(0)
        assert transport.pauses >= 1, "flood never paused reads"
        assert fed < flood, "pause came only after the whole flood buffered"
        backlog = len(proto._queue) + len(proto._resp_q)
        assert backlog <= proto.MAX_PENDING_FRAMES + 50 + proto.MAX_CONCURRENT

        gate.set()  # handlers complete -> queue drains -> reads resume
        for _ in range(300):
            await asyncio.sleep(0)
            if transport.resumes and not proto._queue and not proto._resp_q:
                break
        assert transport.resumes >= 1, "drain never resumed reads"
        proto.data_received(payload * (flood - fed))  # post-resume remainder

        def frames_written():
            from rio_tpu.codec import FrameReader

            fr = FrameReader()
            return sum(len(fr.feed(w)) for w in transport.writes)

        for _ in range(300):
            await asyncio.sleep(0)
            if frames_written() == flood:
                break
        assert frames_written() == flood, "every buffered frame answered"
        proto.eof_received()
        await asyncio.sleep(0)
        proto.connection_lost(None)
        await asyncio.gather(proto._worker, return_exceptions=True)

    asyncio.run(body())




def _server_with_transport():
    return Server(
        address="127.0.0.1:0",
        registry=Registry().add_type(SleepyActor),
        cluster_provider=LocalClusterProvider(LocalStorage()),
        object_placement_provider=LocalObjectPlacement(),
        transport="asyncio",
    )


def _client_with_transport():
    from rio_tpu import Client

    return Client(LocalStorage(), transport="asyncio")


def _builder_with_transport():
    from rio_tpu.client import ClientBuilder

    return ClientBuilder().members_storage(LocalStorage()).transport("asyncio")


@pytest.mark.parametrize(
    "call, error",
    [
        pytest.param(_server_with_transport, TypeError, id="Server"),
        pytest.param(_client_with_transport, TypeError, id="Client"),
        pytest.param(_builder_with_transport, AttributeError, id="ClientBuilder"),
    ],
)
def test_transport_option_is_gone(call, error):
    """There is one transport: even the value that used to be the default
    is refused, by Python itself (no shim)."""
    with pytest.raises(error, match="transport"):
        call()
