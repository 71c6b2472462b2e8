"""The housekeeping tick's passes over the sessions that can have something
due (``clients.SessionsDue``), on the single loop and under loop shards:
a tick among thousands of idle sessions looks at the few that hold an
in-flight entry, have disconnected, or hold a backlog (``housekeep_visits``),
and what expires or is evicted, and when, is what a walk over every
session gave: an expired in-flight entry is dropped with ``on_qos_dropped``,
an expired session leaves with its delayed will, a slow consumer is
evicted under SHED. Beside them the governor's outbound backlog, a
running total the queues keep, against the sum over sessions."""

from __future__ import annotations

import asyncio
import socket
import time

import pytest

from mqtt_tpu import Options
from mqtt_tpu.hooks import ON_QOS_DROPPED, Hook
from mqtt_tpu.overload import SHED
from mqtt_tpu.packets import (
    CONNECT,
    ConnectParams,
    FixedHeader,
    Packet,
    Properties,
    Subscription,
    encode_packet,
)

from tests.test_server import Harness, pub_packet, read_wire_packet, run, sub_packet
from tests.test_shards import TIMEOUT, FabricHarness

IDLE = 2_000
# the most sessions one tick may look at here: the few due ones, and the
# live connections whose transport or queue a write or an enqueue marked
FEW = 8


class DropsSeen(Hook):
    def __init__(self):
        super().__init__()
        self.dropped = []

    def id(self):
        return "drops-seen"

    def provides(self, b):
        return b == ON_QOS_DROPPED

    def on_qos_dropped(self, cl, pk):
        self.dropped.append((cl.id, pk.packet_id))


def add_idle(srv, n=IDLE):
    """``n`` registered sessions with nothing due: no connection, nothing
    in flight, nothing queued."""
    for i in range(n):
        srv.clients.add_client(srv.new_client(None, None, "idle", f"idle-{i}", False))


async def on_loop_of(cl, fn):
    """``fn()`` on the shard loop that owns ``cl``, where its sweep runs."""

    async def call():
        return fn()

    return await asyncio.wrap_future(asyncio.run_coroutine_threadsafe(call(), cl.net.loop))


def tick(srv, now):
    """One tick's passes; the sessions they looked at."""
    before = srv._ops.housekeep_visits
    srv.housekeep(now)
    return srv._ops.housekeep_visits - before


async def inflight_case(h):
    """An unacknowledged QoS1 delivery of a persistent session: kept by a
    tick before its expiry, dropped with ``on_qos_dropped`` after it."""
    srv = h.server
    seen = DropsSeen()
    srv.add_hook(seen)
    r, w, _ = await h.connect("ifm", clean=False)
    await h.subscribe(r, w, 1, [Subscription(filter="if/t", qos=1)])
    add_idle(srv)
    srv.publish("if/t", b"x", False, 1)
    await read_wire_packet(r)  # delivered, never acknowledged
    cl = srv.clients.get("ifm")
    assert len(cl.state.inflight) == 1
    assert srv._due.inflight == {cl}
    visits = [tick(srv, int(time.time()))]
    assert len(cl.state.inflight) == 1 and seen.dropped == []
    visits.append(tick(srv, int(time.time()) + 2**33))
    assert len(cl.state.inflight) == 0
    assert [cid for cid, _pid in seen.dropped] == ["ifm", "ifm"]  # the client's and the server's
    assert srv._due.inflight == set()
    visits.append(tick(srv, int(time.time()) + 2**33))
    return visits


async def session_case(h):
    """A v5 session with a 60 s expiry and a will delayed 30 s: a tick
    before its expiry keeps both; after it the session leaves the
    registry and its will reaches the watcher, once."""
    srv = h.server
    sr, sw, _ = await h.connect("watcher")
    await h.subscribe(sr, sw, 1, [Subscription(filter="dl/t", qos=0)])
    reader, writer = await asyncio.open_connection("127.0.0.1", h.port)
    cp = ConnectParams(
        protocol_name=b"MQTT", clean=False, keepalive=30,
        client_identifier="doomed", will_flag=True,
        will_topic="dl/t", will_payload=b"gone",
    )
    cp.will_properties = Properties(will_delay_interval=30)
    pk = Packet(fixed_header=FixedHeader(type=CONNECT), protocol_version=5, connect=cp)
    pk.properties.session_expiry_interval = 60
    pk.properties.session_expiry_interval_flag = True
    writer.write(encode_packet(pk))
    await read_wire_packet(reader, 5)
    add_idle(srv)
    writer.close()  # abnormal disconnect: the will waits 30 s
    deadline = time.monotonic() + TIMEOUT
    while not len(srv.will_delayed) and time.monotonic() < deadline:
        await asyncio.sleep(0.02)
    doomed = srv.clients.get("doomed")
    assert doomed.closed and srv._due.stopped == {doomed}
    visits = [tick(srv, int(time.time()))]
    assert srv.clients.get("doomed") is doomed and len(srv.will_delayed) == 1
    visits.append(tick(srv, int(time.time()) + 2**33))
    assert srv.clients.get("doomed") is None and len(srv.will_delayed) == 0
    m = await read_wire_packet(sr)
    assert (m.topic_name, m.payload) == ("dl/t", b"gone")
    with pytest.raises(asyncio.TimeoutError):
        await asyncio.wait_for(read_wire_packet(sr), 0.3)
    assert srv._due.stopped == set()
    visits.append(tick(srv, int(time.time()) + 2**33))
    return visits


async def eviction_case(h):
    """A subscriber that stops reading under a flood: the sweep that runs
    where its loop lives starts its clock, and once SHED and the grace
    window have passed evicts it."""
    srv = h.server
    gov = srv.overload
    slow_r, slow_w, _ = await h.connect("stall")
    scl = srv.clients.get("stall")
    slow_w.transport.get_extra_info("socket").setsockopt(
        socket.SOL_SOCKET, socket.SO_RCVBUF, 4096
    )
    scl.net.writer.transport.get_extra_info("socket").setsockopt(
        socket.SOL_SOCKET, socket.SO_SNDBUF, 4096
    )
    await h.subscribe(slow_r, slow_w, 1, [Subscription(filter="e/#", qos=0)])
    slow_w.transport.pause_reading()
    add_idle(srv)
    _pr, pub_w, _ = await h.connect("epub")
    for _ in range(60):
        pub_w.write(pub_packet("e/x", b"x" * 32768))
    await pub_w.drain()
    await asyncio.sleep(0.3)
    assert scl in srv._due.backlog

    async def sweep():
        """The tick's passes, and the owning shard's sweep where one owns it."""
        visits = tick(srv, int(time.time()))
        if srv._fabric is not None:
            before = srv._ops.housekeep_visits
            srv._fabric.shard_of(scl.net.loop).evictions += await on_loop_of(
                scl, lambda: srv.sweep_clients_for_loop(scl.net.loop)
            )
            visits += srv._ops.housekeep_visits - before
        return visits

    visits = [await sweep()]
    assert scl.state.backlog_over_since is not None and gov.evictions == 0
    pressure = [2.0]
    gov.add_source("test", lambda: pressure[0])
    visits.append(await sweep())
    assert gov.state == SHED and gov.evictions == 0  # grace not elapsed
    await asyncio.sleep(0.15)
    visits.append(await sweep())
    assert gov.evictions == 1
    deadline = time.monotonic() + TIMEOUT
    while not scl.closed and time.monotonic() < deadline:
        await asyncio.sleep(0.02)
    assert scl.closed
    pressure[0] = 0.0
    return visits


CASES = {"inflight": inflight_case, "session": session_case, "eviction": eviction_case}


@pytest.mark.parametrize("shards", [1, 2], ids=["one_loop", "loop_shards_2"])
@pytest.mark.parametrize("case", list(CASES))
def test_a_tick_looks_at_the_due_sessions_and_expires_as_before(case, shards):
    async def scenario():
        h = await FabricHarness(
            shards=shards,
            inline_client=True,
            overload_eval_interval_ms=20.0,
            overload_eviction_grace_ms=100.0,
            overload_min_dwell_ms=50.0,
            overload_client_buffer_limit_bytes=4096,
        ).start()
        try:
            visits = await CASES[case](h)
            assert len(h.server.clients) > IDLE
        finally:
            await h.stop()
        return visits

    visits = run(scenario())
    assert visits and all(v <= FEW for v in visits), visits


def test_the_outbound_backlog_is_the_sum_over_the_registry():
    """The governor's outbound signal, a running total the queues keep,
    equals the sum of the registry's queue depths after an enqueue, a
    drain, a drop at a full queue, a session's leaving and one taken
    over."""

    async def scenario():
        h = await FabricHarness(shards=1).start()
        srv = h.server
        due = srv._due
        try:
            def held():
                total = sum(cl.state.outbound_qty for cl in srv.clients.get_all().values())
                srv._outbound_pressure()
                assert due.outbound == srv._outbound_backlog == total
                return total

            add_idle(srv, 3)
            a, b, c = (srv.clients.get(f"idle-{i}") for i in range(3))
            for _ in range(5):
                a.state.outbound.put_nowait(b"x")
            b.state.outbound.put_nowait(b"y")
            assert held() == 6
            assert await a.state.outbound.get() == b"x"
            assert held() == 5
            full = c.state.outbound
            for _ in range(full.maxsize):
                full.put_nowait(b"z")
            assert c in due.backlog  # a queue that filled is the sweep's
            with pytest.raises(asyncio.QueueFull):
                full.put_nowait(b"z")
            assert held() == 5 + full.maxsize
            srv.clients.delete("idle-2")  # leaves with its queue
            assert held() == 5 and c not in due.backlog
            # a session taken over: the new client's queue counts, the
            # old one's no longer does
            nb = srv.new_client(None, None, "idle", "idle-1", False)
            nb.state.outbound.put_nowait(b"n")
            nb.state.outbound.put_nowait(b"n")
            srv.clients.add_client(nb)
            assert held() == 4 + 2
            b.state.outbound.put_nowait(b"y")  # the old queue moves nothing now
            assert held() == 6
            srv.clients.add_client(nb)  # the same client again: counted once
            assert held() == 6
            while not a.state.outbound.empty():
                await a.state.outbound.get()
            assert held() == 2
        finally:
            await h.stop()

    run(scenario())


def test_a_session_taken_over_hands_its_entries_on():
    """A persistent session taken over with an unacknowledged delivery:
    the new client holds the entry and is the one the expiry pass looks
    at; the old one, out of the registry, is in no set."""

    async def scenario():
        h = Harness(Options(inline_client=True))
        srv = h.server
        seen = DropsSeen()
        srv.add_hook(seen)
        r1, w1, _ = await h.connect("dur", clean=False)
        w1.write(sub_packet(1, [Subscription(filter="tk/t", qos=1)]))
        await w1.drain()
        await read_wire_packet(r1)
        srv.publish("tk/t", b"keep", False, 1)
        await read_wire_packet(r1)
        old = srv.clients.get("dur")
        assert srv._due.inflight == {old}
        r2, _w2, _ = await h.connect("dur", clean=False)
        await read_wire_packet(r2)  # the entry, resent with DUP
        new = srv.clients.get("dur")
        assert new is not old and len(new.state.inflight) == 1
        assert srv._due.inflight == {new} and old.state.due is None
        assert tick(srv, int(time.time()) + 2**33) <= FEW
        assert len(new.state.inflight) == 0 and srv._due.inflight == set()
        assert {cid for cid, _pid in seen.dropped} == {"dur"}
        await h.shutdown()

    run(scenario())
