"""C++ codec tests: wire parity with the Python codec, case by case and on
the frames of live traffic.

The native library must be byte-identical to the Python codec on every
envelope type (the two are interchangeable on the wire): it is the oracle
the served codec is held to, on hand-built envelopes and on every frame a
live server reads or writes (request/response, typed errors, redirects,
pub/sub, coalesced waves, traced and classified requests, refusals)."""

import asyncio

import pytest

from rio_tpu import AppData, Registry, ServiceObject, handler, message, wire_error
from rio_tpu import codec, native, protocol
from rio_tpu.message_router import MessageRouter

from .server_utils import Cluster, run_integration_test

lib = native.get()
pytestmark = pytest.mark.skipif(lib is None, reason="native library unavailable")


# ---------------------------------------------------------------------------
# Codec parity
# ---------------------------------------------------------------------------


def test_request_frame_parity():
    for ht, hid, mt, payload in [
        ("Svc", "obj-1", "Msg", b"\x01\x02payload"),
        ("", "", "", b""),
        ("x" * 40, "y" * 300, "z" * 70000, b"p" * 70000),
    ]:
        env = protocol.RequestEnvelope(ht, hid, mt, payload)
        assert protocol.encode_request_frame(env) == lib.encode_request_frame(
            ht.encode(), hid.encode(), mt.encode(), payload
        )
        # Python reference path must produce the same bytes.
        assert codec.frame(protocol.KIND_REQUEST + env.to_bytes()) == (
            lib.encode_request_frame(ht.encode(), hid.encode(), mt.encode(), payload)
        )


def test_traced_request_frame_parity():
    """The appended trace_ctx keeps byte parity in BOTH arities: untraced
    envelopes must match the legacy 4-element encoder (wire-append
    contract), traced ones the new 5-element entry point."""
    tid, sid = "a1" * 16, "b2" * 8
    for sampled in (True, False):
        env = protocol.RequestEnvelope("Svc", "obj-1", "Msg", b"pp", (tid, sid, sampled))
        assert protocol.encode_request_frame(env) == lib.encode_request_frame_traced(
            b"Svc", b"obj-1", b"Msg", b"pp", tid.encode(), sid.encode(), sampled
        )
    # Untraced stays on the legacy entry point, byte-identical.
    env = protocol.RequestEnvelope("Svc", "obj-1", "Msg", b"pp")
    assert protocol.encode_request_frame(env) == lib.encode_request_frame(
        b"Svc", b"obj-1", b"Msg", b"pp"
    )


def test_traced_decode_inbound_parity():
    tid, sid = "c3" * 16, "d4" * 8
    env = protocol.RequestEnvelope("Svc", "i", "M", b"xyz", (tid, sid, True))
    framed = protocol.encode_request_frame(env)
    assert lib.decode_inbound(framed[4:]) == (
        0, b"Svc", b"i", b"M", b"xyz", tid.encode(), sid.encode(), True,
    )
    # Legacy (untraced) frames keep the historical 5-tuple shape.
    legacy = protocol.encode_request_frame(protocol.RequestEnvelope("S", "i", "M", b"x"))
    assert lib.decode_inbound(legacy[4:]) == (0, b"S", b"i", b"M", b"x")
    # Python typed decode agrees.
    back = protocol.decode_inbound(framed[4:])
    assert back == env and back.trace_ctx == (tid, sid, True)


def test_response_frame_parity():
    ok = protocol.ResponseEnvelope.ok(b"hello")
    assert codec.frame(ok.to_bytes()) == lib.encode_response_ok_frame(b"hello")
    err = protocol.ResponseEnvelope.err(
        protocol.ResponseError.application(b"errbytes", "MyErr")
    )
    assert codec.frame(err.to_bytes()) == lib.encode_response_err_frame(
        5, b"MyErr", b"errbytes"
    )
    # body=None normalizes to bin0 so both encoders emit identical bytes.
    none_body = protocol.ResponseEnvelope.ok(None)
    assert codec.frame(none_body.to_bytes()) == lib.encode_response_ok_frame(b"")
    assert protocol.ResponseEnvelope.from_bytes(none_body.to_bytes()).body == b""
    # SERVER_BUSY (kind 8): the overload-shed error rides the same arm —
    # the C++ side treats kind as an opaque uint, so parity must hold with
    # no native change.
    busy = protocol.ResponseEnvelope.err(
        protocol.ResponseError.server_busy("inflight>256")
    )
    assert codec.frame(busy.to_bytes()) == lib.encode_response_err_frame(
        int(protocol.ErrorKind.SERVER_BUSY), b"inflight>256", b""
    )
    # DEADLINE_EXCEEDED (kind 9): the QoS doomed-work shed rides the same
    # opaque-uint arm, again with no native change.
    late = protocol.ResponseEnvelope.err(
        protocol.ResponseError.deadline_exceeded("budget spent in queue")
    )
    assert codec.frame(late.to_bytes()) == lib.encode_response_err_frame(
        int(protocol.ErrorKind.DEADLINE_EXCEEDED), b"budget spent in queue", b""
    )
    # Decoders agree with the Python ones.
    assert lib.decode_response(ok.to_bytes()) == (True, b"hello")
    assert lib.decode_response(err.to_bytes()) == (False, 5, b"MyErr", b"errbytes")
    assert lib.decode_response(busy.to_bytes()) == (False, 8, b"inflight>256", b"")
    assert lib.decode_response(late.to_bytes()) == (False, 9, b"budget spent in queue", b"")
    assert lib.decode_response(b"\x00garbage") is None


def test_subscription_frame_parity():
    sub = protocol.SubscriptionRequest("Svc", "id9")
    assert protocol.encode_subscribe_frame(sub) == lib.encode_subscribe_frame(
        b"Svc", b"id9"
    )
    ok = protocol.SubscriptionResponse(body=b"bb", message_type="T")
    assert codec.frame(ok.to_bytes()) == lib.encode_subresponse_ok_frame(b"T", b"bb")
    assert lib.decode_subresponse(ok.to_bytes()) == (True, b"T", b"bb")
    err = protocol.SubscriptionResponse(
        error=protocol.ResponseError.redirect("1.2.3.4:5")
    )
    assert codec.frame(err.to_bytes()) == lib.encode_subresponse_err_frame(
        1, b"1.2.3.4:5", b""
    )
    assert lib.decode_subresponse(err.to_bytes()) == (False, 1, b"1.2.3.4:5", b"")


def test_decode_inbound_parity():
    env = protocol.RequestEnvelope("Svc", "i", "M", b"xyz")
    framed = protocol.encode_request_frame(env)
    assert lib.decode_inbound(framed[4:]) == (0, b"Svc", b"i", b"M", b"xyz")
    sub = protocol.SubscriptionRequest("Svc", "j")
    framed = protocol.encode_subscribe_frame(sub)
    assert lib.decode_inbound(framed[4:]) == (1, b"Svc", b"j")
    assert lib.decode_inbound(b"\x07nope") is None
    # protocol.decode_inbound (native fast path) returns the typed envelopes
    back = protocol.decode_inbound(protocol.encode_request_frame(env)[4:])
    assert back == env


def test_command_frame_parity():
    """KIND_COMMAND (streams/sagas control plane) byte parity, both
    arities, plus the rc=2 decode shape mirroring requests."""
    if not lib.has_command:
        pytest.skip("prebuilt native lib predates command frames")
    env = protocol.CommandEnvelope("stream.publish", "orders", b"\x01\x02pay")
    assert protocol.encode_command_frame(env) == lib.encode_command_frame(
        b"stream.publish", b"orders", b"\x01\x02pay"
    )
    tid, sid = "e5" * 16, "f6" * 8
    for sampled in (True, False):
        traced = protocol.CommandEnvelope(
            "saga.start", "order-1", b"pp", (tid, sid, sampled)
        )
        assert protocol.encode_command_frame(traced) == lib.encode_command_frame_traced(
            b"saga.start", b"order-1", b"pp", tid.encode(), sid.encode(), sampled
        )
    # Decode: untraced 4-tuple, traced 7-tuple (trace triple appended,
    # symmetric with the request shapes).
    framed = protocol.encode_command_frame(env)
    assert lib.decode_inbound(framed[4:]) == (2, b"stream.publish", b"orders", b"\x01\x02pay")
    traced = protocol.CommandEnvelope("saga.start", "order-1", b"pp", (tid, sid, True))
    tframed = protocol.encode_command_frame(traced)
    assert lib.decode_inbound(tframed[4:]) == (
        2, b"saga.start", b"order-1", b"pp", tid.encode(), sid.encode(), True,
    )
    # Python typed decode agrees with both.
    back = protocol.decode_inbound(tframed[4:])
    assert type(back) is protocol.CommandEnvelope and back == traced


def test_qos_request_frame_parity():
    """The appended QoS fields (tenant/priority/deadline_ms, ISSUE 20) keep
    byte parity at every arity: default-field envelopes stay on the
    legacy/traced encoders byte-identical, classified ones match the new
    entry point with trailing-default truncation."""
    if not lib.has_qos:
        pytest.skip("prebuilt native lib predates QoS frames")
    tid, sid = "a7" * 16, "b8" * 8
    cases = [
        # (env, (tid, sid, sampled, tenant, priority, deadline_ms))
        (protocol.RequestEnvelope("S", "i", "M", b"p", tenant="bulk"),
         (b"", b"", -1, b"bulk", 0, 0)),
        (protocol.RequestEnvelope("S", "i", "M", b"p", priority=2),
         (b"", b"", -1, b"", 2, 0)),
        (protocol.RequestEnvelope("S", "i", "M", b"p", deadline_ms=1500),
         (b"", b"", -1, b"", 0, 1500)),
        (protocol.RequestEnvelope("S", "i", "M", b"p", tenant="t", priority=1,
                                  deadline_ms=99999),
         (b"", b"", -1, b"t", 1, 99999)),
        (protocol.RequestEnvelope("S", "i", "M", b"p", (tid, sid, True),
                                  tenant="iact", priority=3, deadline_ms=250),
         (tid.encode(), sid.encode(), 1, b"iact", 3, 250)),
        (protocol.RequestEnvelope("S", "i", "M", b"p", (tid, sid, False),
                                  tenant="iact"),
         (tid.encode(), sid.encode(), 0, b"iact", 0, 0)),
    ]
    for env, (t, s, sampled, tenant, prio, dl) in cases:
        assert protocol.encode_request_frame(env) == lib.encode_request_frame_qos(
            b"S", b"i", b"M", b"p", t, s, sampled, tenant, prio, dl
        ), env
    # All-default QoS fields: byte-identical to the pre-QoS layouts.
    env = protocol.RequestEnvelope("S", "i", "M", b"p", tenant="", priority=0,
                                   deadline_ms=0)
    assert protocol.encode_request_frame(env) == lib.encode_request_frame(
        b"S", b"i", b"M", b"p"
    )
    traced = protocol.RequestEnvelope("S", "i", "M", b"p", (tid, sid, True))
    assert protocol.encode_request_frame(traced) == lib.encode_request_frame_traced(
        b"S", b"i", b"M", b"p", tid.encode(), sid.encode(), True
    )


def test_qos_decode_inbound_parity():
    if not lib.has_qos:
        pytest.skip("prebuilt native lib predates QoS frames")
    tid, sid = "c9" * 16, "d0" * 8
    env = protocol.RequestEnvelope(
        "S", "i", "M", b"xyz", (tid, sid, True), tenant="bulk", priority=2,
        deadline_ms=750,
    )
    framed = protocol.encode_request_frame(env)
    assert lib.decode_inbound_qos(framed[4:]) == (
        0, b"S", b"i", b"M", b"xyz", tid.encode(), sid.encode(), True,
        b"bulk", 2, 750,
    )
    # Untraced-but-classified: the wire carries a nil trace slot; the
    # decoder reports sampled=None and empty trace spans.
    untr = protocol.RequestEnvelope("S", "i", "M", b"x", tenant="t", deadline_ms=9)
    assert lib.decode_inbound_qos(protocol.encode_request_frame(untr)[4:]) == (
        0, b"S", b"i", b"M", b"x", b"", b"", None, b"t", 0, 9,
    )
    # Legacy arities decode through the QoS entry point with defaults.
    legacy = protocol.encode_request_frame(protocol.RequestEnvelope("S", "i", "M", b"x"))
    assert lib.decode_inbound_qos(legacy[4:]) == (
        0, b"S", b"i", b"M", b"x", b"", b"", None, b"", 0, 0,
    )
    # Subscribe/command frames delegate to the legacy decoder unchanged.
    sub = protocol.encode_subscribe_frame(protocol.SubscriptionRequest("S", "j"))
    assert lib.decode_inbound_qos(sub[4:]) == (1, b"S", b"j")
    # Python typed decode agrees on every QoS field.
    back = protocol.decode_inbound(framed[4:])
    assert back == env and (back.tenant, back.priority, back.deadline_ms) == (
        "bulk", 2, 750,
    )


def test_native_frame_reader_parity():
    frames_in = [
        protocol.encode_request_frame(protocol.RequestEnvelope("A", "b", "C", b"d")),
        codec.frame(b""),
        codec.frame(b"x" * 100_000),
    ]
    stream = b"".join(frames_in)
    for chunk in (1, 3, 7, 4096):
        nat = native.NativeFrameReader(lib)
        py = codec.FrameReader()
        got_nat, got_py = [], []
        for i in range(0, len(stream), chunk):
            got_nat += nat.feed(stream[i : i + chunk])
            got_py += py.feed(stream[i : i + chunk])
        assert got_nat == got_py
        assert got_nat == [f[4:] for f in frames_in]


def test_native_frame_reader_oversize():
    from rio_tpu.errors import SerializationError

    nat = native.NativeFrameReader(lib)
    with pytest.raises(SerializationError):
        nat.feed(b"\xff\xff\xff\xff")


# ---------------------------------------------------------------------------
# The oracle on live frames (the shapes of test_client_server, tapped)
# ---------------------------------------------------------------------------


@message
class Ask:
    text: str = ""


@message
class Answer:
    text: str = ""
    times: int = 0


@message
class Publish:
    text: str = ""


@message
class Slow:
    delay_ms: int = 0


@wire_error
class NativeUnanswerable(Exception):
    pass


class NativeOracle(ServiceObject):
    def __init__(self):
        self.times = 0

    @handler
    async def ask(self, msg: Ask, ctx: AppData) -> Answer:
        if msg.text == "unanswerable":
            raise NativeUnanswerable(msg.text, 42)
        if msg.text == "panic":
            raise RuntimeError("boom")
        self.times += 1
        return Answer(text=f"echo:{msg.text}", times=self.times)

    @handler
    async def slow(self, msg: Slow, ctx: AppData) -> Answer:
        await asyncio.sleep(msg.delay_ms / 1000.0)
        self.times += 1
        return Answer(text="slow", times=self.times)

    @handler
    async def publish(self, msg: Publish, ctx: AppData) -> Answer:
        from rio_tpu.registry import type_id

        router = ctx.get(MessageRouter)
        router.publish(type_id(NativeOracle), self.id, Publish(text=f"pub:{msg.text}"))
        return Answer(text="published")


def build_registry() -> Registry:
    r = Registry()
    r.add_type(NativeOracle)
    return r


class _TapTransport:
    """What ``ServerConnProtocol`` writes, recorded one entry per write."""

    def __init__(self, inner, writes: list):
        self._inner, self._writes = inner, writes

    def write(self, data):
        self._writes.append(bytes(data))
        self._inner.write(data)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _Tap:
    """Both directions of every connection a server of this process accepts."""

    def __init__(self, monkeypatch):
        from rio_tpu import aio

        by_proto: dict = {}  # connection -> (reads, writes); the class has slots
        self.conns = by_proto.values()
        made, received = (
            aio.ServerConnProtocol.connection_made,
            aio.ServerConnProtocol.data_received,
        )

        def connection_made(proto, transport):
            by_proto[proto] = ([], [])
            made(proto, _TapTransport(transport, by_proto[proto][1]))

        def data_received(proto, data):
            by_proto[proto][0].append(bytes(data))
            received(proto, data)

        monkeypatch.setattr(aio.ServerConnProtocol, "connection_made", connection_made)
        monkeypatch.setattr(aio.ServerConnProtocol, "data_received", data_received)

    def frames(self):
        """``(inbound, responses, sub_responses)`` payloads of every
        connection, in wire order: a connection answers each inbound frame
        once, in order, until a subscribe frame turns it into a stream."""
        inbound, responses, subs = [], [], []
        for reads, writes in self.conns:
            got = codec.FrameReader().feed(b"".join(reads))
            sent = codec.FrameReader().feed(b"".join(writes))
            n = next(
                (i for i, p in enumerate(got) if p[:1] == protocol.KIND_SUBSCRIBE),
                len(got),
            )
            inbound += got
            responses += sent[:n]
            subs += sent[n:]
        return inbound, responses, subs

    def widest_write(self) -> int:
        return max(
            (len(codec.FrameReader().feed(w)) for _, ws in self.conns for w in ws),
            default=0,
        )


def _oracle_agrees_on_inbound(payload: bytes):
    """One inbound payload through both codecs; the decoded envelope or
    ``None`` when both refuse it."""
    got = lib.decode_inbound_qos(payload)
    try:
        env = protocol.decode_inbound(payload)
    except Exception:  # noqa: BLE001 - any refusal; the C++ side must refuse too
        assert got is None, got
        return None
    assert got is not None, env
    if type(env) is protocol.SubscriptionRequest:
        assert got == (1, env.handler_type.encode(), env.handler_id.encode())
        again = lib.encode_subscribe_frame(got[1], got[2])
        assert again == protocol.encode_subscribe_frame(env)
    else:
        assert type(env) is protocol.RequestEnvelope, env
        tid, sid, sampled = env.trace_ctx or ("", "", None)
        assert got == (
            0, env.handler_type.encode(), env.handler_id.encode(),
            env.message_type.encode(), env.payload, tid.encode(), sid.encode(),
            sampled, env.tenant.encode(), env.priority, env.deadline_ms,
        )
        if env.tenant or env.priority or env.deadline_ms:
            again = lib.encode_request_frame_qos(
                *got[1:7], -1 if sampled is None else int(sampled), *got[8:]
            )
        elif sampled is not None:
            again = lib.encode_request_frame_traced(*got[1:8])
        else:
            again = lib.encode_request_frame(*got[1:5])
        assert again == protocol.encode_request_frame(env)
    assert again == codec.frame(payload)
    return env


def _oracle_agrees_on_outbound(payload: bytes, *, sub: bool):
    """One server-written payload through both codecs; the decoded response."""
    if sub:
        resp, got = protocol.decode_subresponse(payload), lib.decode_subresponse(payload)
    else:
        resp, got = protocol.decode_response(payload), lib.decode_response(payload)
    err = resp.error
    if err is not None:
        assert got == (False, int(err.kind), err.detail.encode(), err.payload)
        encode = lib.encode_subresponse_err_frame if sub else lib.encode_response_err_frame
        again = encode(*got[1:])
    elif sub:
        assert got == (True, resp.message_type.encode(), resp.body)
        again = lib.encode_subresponse_ok_frame(*got[1:])
    else:
        assert got == (True, resp.body)
        again = lib.encode_response_ok_frame(got[1])
    assert again == codec.frame(payload) == codec.frame(resp.to_bytes())
    return resp


async def _dial(address: str):
    """A raw framed connection, for what a ``Client`` would not send."""
    from rio_tpu import aio

    host, _, port = address.rpartition(":")
    return await aio.connect(host, int(port), 2.0)


def _ask_frame(object_id: str, text: str) -> bytes:
    return protocol.encode_request_frame(
        protocol.RequestEnvelope(
            "NativeOracle", object_id, "Ask", codec.serialize(Ask(text=text))
        )
    )


async def _live_request_response(cluster: Cluster, tap: _Tap):
    client = cluster.client()
    out = await client.send(NativeOracle, "o1", Ask(text="hi"), returns=Answer)
    assert out == Answer(text="echo:hi", times=1)
    out = await client.send(NativeOracle, "o1", Ask(text="again"), returns=Answer)
    assert out.times == 2
    client.close()
    return {"requests": 2, "responses": 2}


async def _live_typed_error_and_panic(cluster: Cluster, tap: _Tap):
    client = cluster.client()
    with pytest.raises(NativeUnanswerable) as ei:
        await client.send(NativeOracle, "o", Ask(text="unanswerable"), returns=Answer)
    assert ei.value.args == ("unanswerable", 42)
    with pytest.raises(Exception):  # noqa: B017, PT011 - whatever a panic maps to
        await client.send(NativeOracle, "o", Ask(text="panic"), returns=Answer)
    out = await client.send(NativeOracle, "o", Ask(text="ok"), returns=Answer)
    assert out.text == "echo:ok"
    client.close()
    return {"requests": 3, "errors": {protocol.ErrorKind.APPLICATION}}


async def _live_redirect(cluster: Cluster, tap: _Tap):
    client = cluster.client()
    await client.send(NativeOracle, "r", Ask(text="seed"), returns=Answer)
    owner = await cluster.allocation_address("NativeOracle", "r")
    other = next(a for a in cluster.addresses if a != owner)
    conn = await _dial(other)
    raw = await conn.roundtrip(_ask_frame("r", "q"))
    assert protocol.decode_response(raw).error.detail == owner
    conn.close()
    # A fresh client with a cold cache follows the same redirect.
    c2 = cluster.client()
    for _ in range(4):
        await c2.send(NativeOracle, "r", Ask(text="q"), returns=Answer)
    client.close()
    c2.close()
    return {"requests": 6, "errors": {protocol.ErrorKind.REDIRECT}}


async def _live_pubsub(cluster: Cluster, tap: _Tap):
    client = cluster.client()
    # Allocate first so the subscription lands on the host.
    await client.send(NativeOracle, "caster", Ask(text="warm"), returns=Answer)
    stream = await client.subscribe(NativeOracle, "caster")
    got: list[str] = []
    ready = asyncio.Event()

    async def consume():
        async for item in stream:
            got.append(item.text)
            ready.set()
            if len(got) >= 2:
                return

    task = asyncio.create_task(consume())
    await asyncio.sleep(0.2)  # let the subscription attach
    await client.send(NativeOracle, "caster", Publish(text="a"), returns=Answer)
    await asyncio.wait_for(ready.wait(), 5)
    await client.send(NativeOracle, "caster", Publish(text="b"), returns=Answer)
    await asyncio.wait_for(task, 5)
    assert got == ["pub:a", "pub:b"]
    client.close()
    return {"requests": 3, "subs": 2}


async def _live_pipelined_wave(cluster: Cluster, tap: _Tap):
    """The HEAD response finishes last, so every later one parks behind it
    and the head's done-callback flushes the whole wave in one write."""
    client = cluster.client(pool_per_server=1)
    for i in range(16):
        await client.send(NativeOracle, f"w{i}", Ask(text="warm"), returns=Answer)
    outs = await asyncio.gather(
        client.send(NativeOracle, "w0", Slow(delay_ms=150), returns=Answer),
        *(
            client.send(NativeOracle, f"w{i}", Ask(text=f"m{i}"), returns=Answer)
            for i in range(1, 16)
        ),
    )
    assert outs[0].text == "slow"
    assert [o.text for o in outs[1:]] == [f"echo:m{i}" for i in range(1, 16)]
    client.close()
    assert tap.widest_write() == 16
    return {"requests": 32, "responses": 32}


_live_pipelined_wave.servers = 1  # the whole wave on one connection


async def _live_traced(cluster: Cluster, tap: _Tap):
    from rio_tpu import tracing

    client = cluster.client()
    token = tracing.adopt(("a1" * 16, "b2" * 8, True))
    try:
        await client.send(NativeOracle, "t", Ask(text="traced"), returns=Answer)
    finally:
        tracing.release(token)
    client.close()
    return {"requests": 1, "trace_ids": {"a1" * 16}}


async def _live_qos_tagged(cluster: Cluster, tap: _Tap):
    client = cluster.client(tenant="bulk", priority=2, deadline_ms=5000)
    await client.send(NativeOracle, "q", Ask(text="classified"), returns=Answer)
    await client.send(
        NativeOracle, "q", Ask(text="x"), returns=Answer, tenant="", priority=0, deadline_ms=0
    )
    client.close()
    return {"requests": 2, "tenants": {"bulk", ""}}


async def _live_unknown_kind(cluster: Cluster, tap: _Tap):
    conn = await _dial(cluster.addresses[0])
    bad = asyncio.ensure_future(conn.roundtrip(codec.frame(b"\x07nope")))
    good = await conn.roundtrip(_ask_frame("u", "after"))
    # The connection survives and stays aligned: refusal first, answer second.
    assert protocol.decode_response(await bad).error.kind == protocol.ErrorKind.NOT_SUPPORTED
    assert protocol.decode_response(good).error is None
    conn.close()
    return {"requests": 1, "refused": 1, "errors": {protocol.ErrorKind.NOT_SUPPORTED}}


@pytest.mark.parametrize(
    "scenario",
    [
        _live_request_response,
        _live_typed_error_and_panic,
        _live_redirect,
        _live_pubsub,
        _live_pipelined_wave,
        _live_traced,
        _live_qos_tagged,
        _live_unknown_kind,
    ],
    ids=lambda f: f.__name__.removeprefix("_live_"),
)
def test_oracle_agrees_on_live_frames(scenario, monkeypatch):
    """The C++ codec meets real traffic: every frame a live server reads
    or writes decodes through it to what ``protocol.py`` decodes, and
    re-encodes through it to the bytes that were on the socket."""
    tap = _Tap(monkeypatch)
    want: dict = {}

    async def body(cluster: Cluster):
        want.update(await scenario(cluster, tap))

    asyncio.run(
        run_integration_test(
            body,
            registry_builder=build_registry,
            num_servers=getattr(scenario, "servers", 2),
        )
    )
    inbound, responses, subs = tap.frames()
    envs = [_oracle_agrees_on_inbound(p) for p in inbound]
    resps = [_oracle_agrees_on_outbound(p, sub=False) for p in responses]
    sub_resps = [_oracle_agrees_on_outbound(p, sub=True) for p in subs]
    # What the scenario set out to put on the wire was on the tap.
    requests = [e for e in envs if type(e) is protocol.RequestEnvelope]
    assert len(requests) >= want["requests"]
    assert envs.count(None) == want.get("refused", 0)
    assert len(resps) >= want.get("responses", 0)
    assert len([r for r in sub_resps if r.error is None]) >= want.get("subs", 0)
    assert want.get("errors", set()) <= {r.error.kind for r in resps if r.error}
    assert want.get("trace_ids", set()) <= {e.trace_ctx[0] for e in requests if e.trace_ctx}
    assert want.get("tenants", set()) <= {e.tenant for e in requests}


def test_library_exports_no_engine():
    """The library is the codec and the frame reader, nothing else."""
    for name in ("rn_decode_inbound", "rn_encode_request_frame", "rn_reader_feed"):
        assert hasattr(lib._dll, name), name
    for name in ("rn_engine_create", "rn_engine_create_opt", "rn_engine_send"):
        assert not hasattr(lib._dll, name), name


def test_coalesced_egress_buffer_parity():
    """A coalesced egress wave — N complete length-prefixed response frames
    joined into ONE buffer (what `_flush_ready` now hands the engine, and
    what the engine's sendmsg gather puts on the socket) — must split back
    into exactly the same frames as N separate writes, in both frame
    readers. Coalescing may never be observable above the framing layer."""
    frames = [
        codec.frame(protocol.ResponseEnvelope.ok(b"r%d" % i).to_bytes())
        for i in range(9)
    ]
    frames.append(
        codec.frame(
            protocol.ResponseEnvelope.err(
                protocol.ResponseError.redirect("1.2.3.4:5")
            ).to_bytes()
        )
    )
    frames.append(lib.encode_response_ok_frame(b"x" * 70_000))
    frames.append(codec.frame(b""))  # empty payload mid-wave
    wave = b"".join(frames)
    expect = [f[4:] for f in frames]
    # Single joined feed.
    assert native.NativeFrameReader(lib).feed(wave) == expect
    assert codec.FrameReader().feed(wave) == expect
    # Chunked feed (waves split mid-frame by the kernel) stays in parity.
    for chunk in (1, 13, 1337):
        nat, py = native.NativeFrameReader(lib), codec.FrameReader()
        got_nat: list = []
        got_py: list = []
        for i in range(0, len(wave), chunk):
            got_nat += nat.feed(wave[i : i + chunk])
            got_py += py.feed(wave[i : i + chunk])
        assert got_nat == got_py == expect


def test_native_frame_reader_fuzz_parity():
    """Seeded fuzz: random valid frames interleaved with random garbage,
    fed in random chunk sizes — the C++ reader must match the Python
    reader byte for byte, including WHERE the oversize error fires
    (garbage bytes routinely parse as absurd length prefixes)."""
    import random

    from rio_tpu.errors import SerializationError

    rng = random.Random(0xBEEF)
    for _trial in range(25):
        parts = []
        for _ in range(rng.randrange(1, 12)):
            if rng.random() < 0.6:
                body = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 300)))
                parts.append(codec.frame(body))
            else:
                parts.append(bytes(rng.randrange(256) for _ in range(rng.randrange(1, 40))))
        stream = b"".join(parts)
        nat = native.NativeFrameReader(lib)
        py = codec.FrameReader()
        i = 0
        while i < len(stream):
            n = rng.randrange(1, 97)
            chunk = stream[i : i + n]
            i += n
            err_nat = err_py = False
            out_nat = out_py = None
            try:
                out_nat = nat.feed(chunk)
            except SerializationError:
                err_nat = True
            try:
                out_py = py.feed(chunk)
            except SerializationError:
                err_py = True
            assert err_nat == err_py, f"error divergence at byte {i}"
            if err_nat:
                break
            assert out_nat == out_py, f"frame divergence at byte {i}"
