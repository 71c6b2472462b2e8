"""The two feeders of ``Client.read`` (mqtt_tpu/clients.py): the direct
one (``_DirectFeed``: the broker's own protocol on the transport, the scan
inside the transport's read callback) and the stream one (``_read_stream``: a
``reader.read`` coroutine a socket read), held equal.

The same byte streams, cut at the same places, give the same deliveries in
the same order, the same bytes written back, the same counters and the
same count of socket reads; and the edges of a connection's life
(keepalive, errors, EOF, a stop from outside, the gate behind a staged
publish, flow control of the write side) end the same way on both. The
stream side is forced the way the WebSocket leg makes it: the connection
is handed a bare ``StreamReader`` that a pump feeds, so that no transport
feeds the reader the connection holds. CPU backend: equality and counts,
never a rate."""

import asyncio
import dataclasses
import random
import socket
import threading

import pytest

from mqtt_tpu import tracing
from mqtt_tpu.clients import (
    READ_HIGH_WATER,
    Client,
    ConnectionClosedError,
    _DirectFeed,
)
from mqtt_tpu.hooks import ON_PUBLISHED, Hook
from mqtt_tpu.listeners import Config as LConfig
from mqtt_tpu.listeners.tcp import TCP
from mqtt_tpu.listeners.websocket import Websocket
from mqtt_tpu.native import MAX_FRAMES_PER_SCAN
from mqtt_tpu.packets import (
    CONNACK,
    DISCONNECT,
    PINGREQ,
    PINGRESP,
    PUBACK,
    PUBLISH,
    FixedHeader,
    Packet,
    Subscription,
    encode_packet,
)
from mqtt_tpu.server import Options

from tests.test_batch_completion import staged_options, subscriber
from tests.test_ingest_run import drain, pubs
from tests.test_loop_ledger import arm_at_serve  # noqa: F401  (a fixture)
from tests.test_server import (
    Harness,
    connect_packet,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)

FEEDERS = ("direct", "stream")
PING = encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ)))
BYE = encode_packet(Packet(fixed_header=FixedHeader(type=DISCONNECT)))


@pytest.fixture(autouse=True)
def no_slice(monkeypatch):
    """``last_slice()`` is process-wide: every test starts without one."""
    monkeypatch.setattr(tracing, "_LAST_SLICE", None)


async def pump(raw: asyncio.StreamReader, fed: asyncio.StreamReader) -> None:
    """What the WebSocket leg's frame pump is to the connection: the only
    feeder of the reader it holds."""
    try:
        while True:
            data = await raw.read(65536)
            if not data:
                break
            fed.feed_data(data)
    except ConnectionError as e:
        fed.set_exception(e)
        return
    fed.feed_eof()


class FedHarness(Harness):
    """``Harness`` whose attached connections all sit on one feeder."""

    def __init__(self, feeder, options=None, allow=True):
        super().__init__(options, allow)
        self.feeder = feeder
        self.pumps = []
        self.attached = []  # the server side's Client of every attach

    async def attach(self):
        s1, s2 = socket.socketpair()
        s1.setblocking(False)
        s2.setblocking(False)
        client_reader, client_writer = await asyncio.open_connection(sock=s1)
        self._writers.append(client_writer)
        reader, writer = await asyncio.open_connection(sock=s2)
        loop = asyncio.get_running_loop()
        if self.feeder == "stream":
            fed = asyncio.StreamReader()
            self.pumps.append(loop.create_task(pump(reader, fed)))
            reader = fed
        cl = self.server.new_client(reader, writer, "t1", "", False)
        task = loop.create_task(self.server.attach_client(cl, "t1"))
        self.tasks.append(task)
        self.attached.append(cl)
        return client_reader, client_writer, task

    async def shutdown(self):
        await super().shutdown()
        for p in self.pumps:
            p.cancel()
        await asyncio.gather(*self.pumps, return_exceptions=True)


def on_feeder(cl: Client) -> str:
    """The feeder a served connection sits on, by what stands on its
    transport."""
    transport = getattr(cl.net.writer, "transport", None)  # a WebSocket's has none
    direct = transport is not None and isinstance(transport.get_protocol(), _DirectFeed)
    return "direct" if direct else "stream"


def packets_of(data: bytes):
    """The complete packets at the head of ``data``, one at a time."""
    i = 0
    while True:
        j, rem, shift = i + 1, 0, 0
        while True:
            if j >= len(data):
                return
            b = data[j]
            rem |= (b & 127) << shift
            shift += 7
            j += 1
            if not b & 128:
                break
        if j + rem > len(data):
            return
        yield data[i : j + rem]
        i = j + rem


def whole_frames(data: bytes) -> int:
    """The bytes at the head of ``data`` that are complete packets."""
    return sum(map(len, packets_of(data)))


def acks(ids):
    return b"".join(bytes((0x40, 2, i >> 8, i & 0xFF)) for i in ids)


def cut(data: bytes, places) -> list:
    places = [0, *places, len(data)]
    return [data[a:b] for a, b in zip(places, places[1:])]


def cut_at_random(data: bytes, pieces: int, seed: int) -> list:
    rng = random.Random(seed)
    return cut(data, sorted(rng.sample(range(1, len(data)), pieces - 1)))


MIXED = (
    pubs(0, 12, qos1_every=3)
    + PING
    + sub_packet(3, [Subscription(filter="t/3", qos=0)])
    + pubs(12, 20, qos1_every=4)
    + acks([500, 501, 502])
    + pub_packet("t/keep", b"kept", retain=True)
    + pubs(20, 40, qos1_every=5)
    + PING
)
WIDE = pub_packet("t/wide", b"y" * 200)  # a two-byte remaining length
BIG = pub_packet("t/big", bytes(range(256)) * 400)  # 102,400 B of payload
assert WIDE[1] & 128 and len(BIG) > 65536 + 8


@dataclasses.dataclass
class Case:
    """``writes``: the segments written to the connection's socket, each
    taken in before the next is written; ``joined``: the first of them
    rides behind CONNECT in one write."""

    writes: list
    joined: bool = False


CASES = {
    "frame_split_across_two_reads": Case(cut(MIXED, [len(pubs(0, 5)) + 4])),
    "split_inside_the_remaining_length": Case(
        cut(pubs(0, 3) + WIDE + pubs(3, 6), [len(pubs(0, 3)) + 2])
    ),
    "split_after_the_first_byte": Case(
        cut(pubs(0, 3) + WIDE + pubs(3, 6), [len(pubs(0, 3)) + 1])
    ),
    "a_64_frame_chunk": Case([pubs(0, 64, qos1_every=8)]),
    "more_frames_than_one_scan_takes": Case(
        [pubs(0, MAX_FRAMES_PER_SCAN + 44, qos1_every=50) + PING]
    ),
    "bytes_behind_connect": Case([pubs(0, 9, qos1_every=2) + PING], joined=True),
    "a_body_of_over_64k": Case(
        [pubs(0, 3) + BIG[:7], BIG[7:], pubs(3, 6, qos1_every=1)]
    ),
    "acks_alone_then_publishes": Case([acks(range(1, 9)), pubs(0, 4), acks([9])]),
    "cut_at_random_a": Case(cut_at_random(MIXED, 9, seed=36)),
    "cut_at_random_b": Case(cut_at_random(MIXED + WIDE + MIXED, 14, seed=1036)),
    "a_byte_at_a_time": Case(
        cut(pubs(0, 2, qos1_every=1), range(1, len(pubs(0, 2, qos1_every=1))))
    ),
}


def observe(feeder: str, case: Case):
    """Feed ``case`` to a fresh staged broker whose connections sit on
    ``feeder`` and write down all that can be seen of it."""

    async def scenario():
        h = FedHarness(feeder, staged_options(matcher_stage_latency_budget_ms=0))
        srv = h.server
        await srv.serve()
        sub_r, _sub_w = await subscriber(h, "sub", "#", qos=1)
        srv.matcher.flush()
        info, tele, ops, stage = srv.info, srv.telemetry, srv._ops, srv._stage

        def counters():
            return (
                info.bytes_received, info.packets_received, info.messages_received,
                info.bytes_sent, info.packets_sent, info.messages_sent,
                tele.outbound_bytes.value, tele.outbound_writes.value,
                ops.socket_reads, ops.socket_sends,
                ops.ingest_runs, ops.ingest_run_publishes, ops.ack_runs, ops.ack_run_acks,
            )

        writes = list(case.writes)
        if case.joined:
            before = counters()
            pub_r, pub_w, task = await h.attach()
            first = connect_packet("pub", 4) + writes.pop(0)
            pub_w.write(first)
            assert (await read_wire_packet(pub_r)).fixed_header.type == CONNACK
            written = first
        else:
            pub_r, pub_w, task = await h.connect("pub")
            before = counters()
            written = b""
        cl = h.attached[-1]
        assert {on_feeder(c) for c in h.attached} == {feeder}

        async def taken_in():
            """The bytes written so far have reached the frame loop, their
            publishes have fanned out, and nothing moves any more."""
            last = None
            for _ in range(4000):  # a first batch of a shape compiles
                now = counters()
                if (
                    task.done()
                    or info.bytes_received - before[0] >= whole_frames(written)
                ) and not cl._staged and now == last:
                    return
                last = now
                await asyncio.sleep(0.005)
            raise AssertionError("the segment was never taken in")

        await taken_in()
        for data in writes:
            pub_w.write(data)
            written += data
            await taken_in()
        back, back_closed = await drain(pub_r)
        delivered, _ = await drain(sub_r)
        prof = srv.profiler
        seen = {
            "written_back": back,
            "closed": (back_closed, cl.closed, task.done()),
            "stop_cause": repr(cl.stop_cause),
            "delivered": delivered,
            "counters": tuple(b - a for a, b in zip(before, counters())),
            "dropped": info.messages_dropped,
            "inflight": (info.inflight, len(cl.state.inflight)),
            "quota": cl.state.inflight.receive_quota,
            "pub_count": cl._pub_count,
            "out": (cl.state.out_bytes, cl.state.out_writes),
            "stage": (
                stage.admission_fallbacks, stage.order_held,
                stage.batch_completed, stage.adapter_completed,
            ),
            "direct_reads": ops.direct_reads,
            "socket_reads": ops.socket_reads,
            "noted": (prof.ingest_n, prof.ack_n) if prof.armed else None,
        }
        await srv.close()
        await h.shutdown()
        return seen

    return run(scenario())


@pytest.mark.parametrize("armed", [False, True], ids=["disarmed", "armed"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_the_two_feeders_cannot_be_told_apart(name, armed, arm_at_serve):
    arm, _made = arm_at_serve
    arm(armed)
    case = CASES[name]
    direct, stream = observe("direct", case), observe("stream", case)
    # the counter says which feeder served: every read or none
    assert direct.pop("direct_reads") == direct.pop("socket_reads") > 0
    assert stream.pop("direct_reads") == 0 and stream.pop("socket_reads") > 0
    assert direct == stream
    kinds = [pk[0] >> 4 for pk in packets_of(b"".join(case.writes))]
    assert direct["counters"][2] == kinds.count(PUBLISH)
    assert direct["closed"] == (False, False, False)
    if armed:
        # every publish booked as ingest; a scan of acks alone as acks
        assert direct["noted"][0] == kinds.count(PUBLISH)
        if name == "acks_alone_then_publishes":
            assert direct["noted"][1] == kinds.count(PUBACK) == 9
    if name == "a_body_of_over_64k":
        # the head, the body whole, the tail: three wake-ups on data
        assert direct["counters"][8] == 3
    if name == "more_frames_than_one_scan_takes":
        assert direct["written_back"].endswith(bytes((PINGRESP << 4, 0)))


# -- the edges of a connection's life, on both feeders ---------------------------

WILL = ("will/of", b"gone", 0)


async def connected(h, client_id, version=4, will=None, prepare=None):
    """``Harness.connect`` that hands back the server side's Client, with
    ``prepare(cl)`` run before its CONNECT is read."""
    r, w, task = await h.attach()
    cl = h.attached[-1]
    if prepare is not None:
        prepare(cl)
    w.write(connect_packet(client_id, version, will=will))
    assert (await read_wire_packet(r, version)).fixed_header.type == CONNACK
    assert on_feeder(cl) == h.feeder
    return r, w, task, cl


def quick_keepalive(seconds):
    """A keepalive a test can wait for: the deadline is armed at 1.5 x
    ``seconds`` wherever the connection arms it."""

    def prepare(cl):
        refresh = cl.refresh_deadline
        cl.refresh_deadline = lambda keepalive: refresh(seconds if keepalive else 0)

    return prepare


@pytest.mark.parametrize("feeder", FEEDERS)
def test_keepalive_frames_extend_it_partial_bytes_do_not(feeder):
    """[MQTT-3.1.2-24]: closed at 1.5 x the keepalive after the last
    complete packet, with ``asyncio.TimeoutError``, the will sent."""

    async def scenario():
        h = FedHarness(feeder)
        will_r, _w = await subscriber(h, "watch", "will/#")
        _r, w, task, cl = await connected(
            h, "ka", will=WILL, prepare=quick_keepalive(0.4)
        )
        loop = asyncio.get_running_loop()
        for _ in range(4):  # 0.6 s of deadline, a frame every 0.15 s
            await asyncio.sleep(0.15)
            w.write(PING)
        last_frame = loop.time()
        assert not task.done()
        partial = pub_packet("t/never", b"whole")
        for i in range(3):  # a trickle of one packet's bytes
            await asyncio.sleep(0.15)
            assert not task.done()
            w.write(partial[i : i + 1])
        await asyncio.wait_for(task, 2)
        assert 0.5 < loop.time() - last_frame < 1.5
        assert isinstance(cl.stop_cause, asyncio.TimeoutError)
        pk = await read_wire_packet(will_r)
        assert (pk.topic_name, bytes(pk.payload)) == WILL[:2]
        await h.shutdown()

    run(scenario())


@pytest.mark.parametrize("feeder", FEEDERS)
def test_keepalive_of_a_silent_connection_and_none_at_zero(feeder):
    async def scenario():
        h = FedHarness(feeder)
        _r, _w, task, cl = await connected(h, "silent", prepare=quick_keepalive(0.2))
        _r0, _w0, task0, cl0 = await connected(h, "forever", prepare=quick_keepalive(0))
        await asyncio.wait_for(task, 2)
        assert isinstance(cl.stop_cause, asyncio.TimeoutError)
        await asyncio.sleep(0.2)
        assert cl0._deadline is None and not task0.done()
        await h.shutdown()

    run(scenario())


class Poisoned(Hook):
    """Fails ``on_published`` for one payload: a completion's error."""

    def id(self):
        return "poisoned"

    def provides(self, b):
        return b == ON_PUBLISHED

    def on_published(self, cl, pk):
        if bytes(pk.payload) == b"poison":
            raise RuntimeError("poisoned")


def v5pubs(lo, hi):
    return pubs(lo, hi, version=5)


ENDINGS = {
    # a second CONNECT: the handler's error, a v5 DISCONNECT 0x82 written
    "handler_error": (
        v5pubs(0, 2) + connect_packet("again", 5) + v5pubs(2, 4),
        "0x82", 2,
    ),
    # a completion's error: the scan that held it is handled whole, the
    # frames past that scan are not
    "staged_error": (
        pub_packet("t/p", b"poison", version=5)
        + v5pubs(0, MAX_FRAMES_PER_SCAN - 1) + v5pubs(300, 310),
        "poisoned", MAX_FRAMES_PER_SCAN,
    ),
    "oversize_packet": (
        v5pubs(0, 2) + pub_packet("t/huge", b"x" * 2000, version=5) + v5pubs(2, 4),
        "0x95", 2,
    ),
    "malformed_length": (
        v5pubs(0, 2) + b"\x30\xff\xff\xff\xff\x01" + v5pubs(2, 4),
        "0x81", 2,
    ),
    "bad_header_byte": (
        v5pubs(0, 2) + b"\x00\xff\xff\xff\xff\x01" + v5pubs(2, 4),
        "0x81", 2,
    ),
}


def observe_ending(feeder, stream):
    async def scenario():
        opts = staged_options(matcher_stage_latency_budget_ms=0)
        opts.capabilities.maximum_packet_size = 1000
        h = FedHarness(feeder, opts)
        srv = h.server
        srv.add_hook(Poisoned())
        await srv.serve()
        sub_r, _sub_w = await subscriber(h, "sub", "#")
        srv.matcher.flush()
        r, w, task, cl = await connected(h, "pub", version=5, will=WILL)
        w.write(stream)
        await asyncio.wait_for(task, 10)
        for _ in range(2000):  # a first batch of a shape compiles
            if not cl._staged:
                break
            await asyncio.sleep(0.005)
        back, back_closed = await drain(r)
        delivered, _ = await drain(sub_r)
        seen = {
            "stop_cause": repr(cl.stop_cause),
            "written_back": back,
            "closed": (back_closed, cl.closed),
            "delivered": delivered,
            "received": (srv.info.messages_received, srv.info.packets_received),
            "will_sent": cl.properties.will.flag,
        }
        await srv.close()
        await h.shutdown()
        return seen

    return run(scenario())


@pytest.mark.parametrize("name", sorted(ENDINGS))
def test_an_error_ends_the_connection_the_same_way_and_nothing_after_it_is_scanned(name):
    stream, cause, handled = ENDINGS[name]
    direct, other = observe_ending("direct", stream), observe_ending("stream", stream)
    assert direct == other
    assert cause in direct["stop_cause"]
    assert direct["closed"] == (True, True)
    # the publishes before the fault and the will (which overtakes what
    # is still staged when a handler raises); none from behind the fault
    topics = [
        pk[4 : 4 + int.from_bytes(pk[2:4], "big")]
        for pk in packets_of(direct["delivered"])
    ]
    assert len(topics) == handled + 1 and topics.count(b"will/of") == 1
    assert direct["received"][0] == handled
    if name == "handler_error":
        assert direct["written_back"][:1] == bytes((DISCONNECT << 4,))
        assert direct["written_back"][2] == 0x82


@pytest.mark.parametrize("feeder", FEEDERS)
def test_peer_eof_and_a_stop_from_another_task(feeder):
    """The peer's EOF fails the read with ``ConnectionClosedError`` once
    what came before it is handled (the will goes out); ``cl.stop()``
    from outside ends the read too, and a DISCONNECT ends it cleanly."""

    async def scenario():
        h = FedHarness(feeder)
        will_r, _w = await subscriber(h, "watch", "#")
        # frames, then EOF in the same instant: the frames are handled
        _r, w, task, cl = await connected(h, "eof", will=WILL)
        w.write(pubs(0, 3))
        w.close()
        await asyncio.wait_for(task, 2)
        assert isinstance(cl.stop_cause, ConnectionClosedError)
        got = [(await read_wire_packet(will_r)).topic_name for _ in range(4)]
        assert got == ["t/0", "t/1", "t/2", "will/of"]
        # a stop from another task: the read ends, its cause stands
        _r, w2, task2, cl2 = await connected(h, "stopped", will=WILL)
        cause = RuntimeError("from outside")
        cl2.stop(cause)
        await asyncio.wait_for(task2, 2)
        assert cl2.stop_cause is cause
        assert (await read_wire_packet(will_r)).topic_name == "will/of"
        # a clean DISCONNECT, frames behind it never handled, no will
        r3, w3, task3, cl3 = await connected(h, "clean", will=WILL)
        w3.write(pubs(3, 4) + BYE + pubs(4, 6))
        await asyncio.wait_for(task3, 2)
        assert cl3.stop_cause is None or "0x00" in repr(cl3.stop_cause)
        assert (await read_wire_packet(will_r)).topic_name == "t/3"
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(read_wire_packet(will_r), 0.2)
        assert await r3.read() == b""
        await h.shutdown()

    run(scenario())


class Gated:
    """The stage's matcher behind a gate a test opens: batches resolve
    when it is set."""

    def __init__(self, inner):
        self.inner, self.gate = inner, threading.Event()

    def match_topics_async(self, topics, profile=None):
        resolve = self.inner.match_topics_async(topics, profile=profile)

        def gated():
            assert self.gate.wait(10)
            return resolve()

        return gated


@pytest.mark.parametrize("feeder", FEEDERS)
def test_the_gate_holds_bytes_in_order_and_pauses_the_transport(feeder, monkeypatch):
    """While a connection's publish is staged nothing of the connection
    is handled; what came meanwhile is handled after the completion, in
    order, in a later turn of the loop; past 128 KiB buffered the
    transport stops reading, and reads again once the gate has opened.
    Here the stream side keeps its own transport under its reader (the
    reader's pause is the bound that is compared): it is forced by
    answering ``_fed_transport`` with None."""
    if feeder == "stream":
        monkeypatch.setattr(Client, "_fed_transport", lambda self: None)

    async def scenario():
        h = FedHarness("direct", staged_options())
        srv = h.server
        await srv.serve()
        sub_r, _sub_w = await subscriber(h, "sub", "t/#")
        srv.matcher.flush()
        gated = srv._stage.matcher = Gated(srv._stage.matcher)
        r, w, task = await h.connect("pub")
        cl = h.attached[-1]
        assert on_feeder(cl) == feeder
        transport = cl.net.writer.transport
        turns = []
        loop = asyncio.get_running_loop()
        complete = srv._staged_completion

        def completion(*a, **kw):
            complete(*a, **kw)
            turns.append(("completed", srv.info.packets_received))
            loop.call_soon(lambda: turns.append(("next turn", srv.info.packets_received)))

        srv._staged_completion = completion
        w.write(pub_packet("t/1", b"one", qos=1, pid=7))
        assert (await read_wire_packet(r)).fixed_header.type == PUBACK
        assert cl._staged == 1 and cl._staged_waiter is not None
        received = srv.info.packets_received
        # behind the gate: a ping, then 160 KiB of publishes
        filler = pub_packet("t/fill", b"f" * 1000)
        n_fill = 160 * 1024 // len(filler) + 1
        w.write(PING + filler * n_fill + pub_packet("t/last", b"last"))
        for _ in range(400):
            if not transport.is_reading():
                break
            await asyncio.sleep(0.005)
        assert not transport.is_reading()  # paused past the high-water mark
        assert srv.info.packets_received == received and cl._staged == 1
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(r.readexactly(1), 0.05)
        if feeder == "direct":
            feed = transport.get_protocol()
            assert READ_HIGH_WATER < len(feed._rbuf) <= READ_HIGH_WATER + 256 * 1024
        gated.gate.set()
        assert bytes((await read_wire_packet(sub_r)).payload) == b"one"
        assert (await read_wire_packet(r)).fixed_header.type == PINGRESP
        got = [(await read_wire_packet(sub_r)).topic_name for _ in range(n_fill + 1)]
        assert got == ["t/fill"] * n_fill + ["t/last"]
        assert transport.is_reading()
        assert cl._staged == 0 and cl._staged_waiter is None
        # nothing of the connection was handled inside the completion
        # slice that opened the gate; the loop's next turn took it up
        assert turns[0] == ("completed", received)
        assert turns[1][0] == "next turn" and turns[1][1] > received
        await srv.close()
        await h.shutdown()

    run(scenario())


@pytest.mark.parametrize("feeder", FEEDERS)
def test_the_write_side_still_sees_its_flow_control(feeder):
    """``pause_writing`` / ``resume_writing`` reach the protocol that the
    connection's ``StreamWriter`` drains on, and ``connection_lost``
    resolves ``wait_closed``."""

    async def scenario():
        h = FedHarness(feeder)
        r, w, task, cl = await connected(h, "fc")
        writer = cl.net.writer
        protocol = writer.transport.get_protocol()
        protocol.pause_writing()
        drained = asyncio.get_running_loop().create_task(writer.drain())
        await asyncio.sleep(0.05)
        assert not drained.done()
        protocol.resume_writing()
        await asyncio.wait_for(drained, 1)
        # the write loop still delivers
        sub = sub_packet(1, [Subscription(filter="fc/#", qos=0)])
        w.write(sub + pub_packet("fc/x", b"echo"))
        await read_wire_packet(r)
        assert bytes((await read_wire_packet(r)).payload) == b"echo"
        w.close()
        await asyncio.wait_for(task, 2)
        await asyncio.wait_for(writer.wait_closed(), 2)
        await h.shutdown()

    run(scenario())


# -- the counter that says which feeder serves -----------------------------------


def test_direct_reads_by_listener(tmp_path, monkeypatch):
    """``direct_reads == socket_reads`` over a TCP and a TLS listener; a
    WebSocket connection's reads are the stream feeder's."""
    import ssl

    from mqtt_tpu.__main__ import cmd_genecc
    from tests.test_aux import _ws_client_frame

    monkeypatch.chdir(tmp_path)
    assert cmd_genecc(None) == 0
    server_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    server_ctx.load_cert_chain(
        str(tmp_path / "cert.ec.pem"), str(tmp_path / "cert-key.ec.pem")
    )
    client_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    client_ctx.load_verify_locations(str(tmp_path / "root.ec.pem"))

    async def scenario():
        h = Harness()
        srv = h.server
        for lst in (
            TCP(LConfig(type="tcp", id="tcp1", address="127.0.0.1:0")),
            TCP(LConfig(type="tcp", id="tls1", address="127.0.0.1:0", tls_config=server_ctx)),
        ):
            srv.add_listener(lst)
        await srv.serve()
        ops = srv._ops
        try:
            for lid, kw in (
                ("tcp1", {}),
                ("tls1", {"ssl": client_ctx, "server_hostname": "localhost"}),
            ):
                port = int(srv.listeners.get(lid).address().rsplit(":", 1)[1])
                reader, writer = await asyncio.open_connection("127.0.0.1", port, **kw)
                writer.write(connect_packet(lid, 4))
                assert (await read_wire_packet(reader)).fixed_header.type == CONNACK
                reads = ops.socket_reads
                writer.write(sub_packet(1, [Subscription(filter=lid + "/#", qos=0)]))
                await read_wire_packet(reader)
                writer.write(pub_packet(lid + "/x", b"over " + lid.encode()))
                pk = await read_wire_packet(reader)
                assert bytes(pk.payload) == b"over " + lid.encode()
                assert ops.socket_reads - reads == 2
                assert on_feeder(srv.clients.get(lid)) == "direct"
                writer.close()
            assert ops.direct_reads == ops.socket_reads == 4
            # MQTT over WebSocket: the de-framing pump feeds the reader
            ws = Websocket(LConfig(type="ws", id="ws1", address="127.0.0.1:0"))
            srv.add_listener(ws)
            await ws.init(srv.log)
            await ws.serve(srv.establish_connection)
            host, port = ws.address().rsplit(":", 1)
            reader, writer = await asyncio.open_connection(host, int(port))
            writer.write(
                b"GET /mqtt HTTP/1.1\r\n"
                b"Host: x\r\nUpgrade: websocket\r\nConnection: Upgrade\r\n"
                b"Sec-WebSocket-Key: dGhlIHNhbXBsZSBub25jZQ==\r\n"
                b"Sec-WebSocket-Version: 13\r\n\r\n"
            )
            await asyncio.wait_for(reader.readuntil(b"\r\n\r\n"), 3)
            writer.write(_ws_client_frame(connect_packet("ws", 4)))
            head = await asyncio.wait_for(reader.readexactly(2), 3)
            await asyncio.wait_for(reader.readexactly(head[1] & 0x7F), 3)
            writer.write(_ws_client_frame(PING))
            head = await asyncio.wait_for(reader.readexactly(2), 3)
            pong = await asyncio.wait_for(reader.readexactly(head[1] & 0x7F), 3)
            assert pong == bytes((PINGRESP << 4, 0))
            assert on_feeder(srv.clients.get("ws")) == "stream"
            assert ops.socket_reads == 5 and ops.direct_reads == 4
            writer.close()
        finally:
            await srv.close()
            await h.shutdown()

    run(scenario())


def test_a_scan_gated_connection_keeps_the_stream_feeder():
    """``Options.scan_coalesce``: the coalescer awaits across connections,
    so its connections read through the stream."""

    async def scenario():
        h = FedHarness("direct", Options(inline_client=True, scan_coalesce=True))
        r, w, _task = await h.connect("gated")
        ops = h.server._ops
        w.write(PING)
        assert (await read_wire_packet(r)).fixed_header.type == PINGRESP
        assert on_feeder(h.attached[-1]) == "stream"
        assert ops.socket_reads == 1 and ops.direct_reads == 0
        assert ops.scan_gate.scans >= 2  # the empty first scan, and the ping's
        await h.shutdown()

    run(scenario())
