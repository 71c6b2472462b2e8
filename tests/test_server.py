"""End-to-end broker tests over in-memory socket pairs — the analog of the
reference's net.Pipe() scenarios (server_test.go): raw wire bytes in, exact
response packets out, for v3.1.1 and v5, plus hook-fake behavioral checks.
"""

import asyncio
import socket

import pytest

from mqtt_tpu import Capabilities, Options, Server
from mqtt_tpu.hooks import (
    ON_ACL_CHECK,
    ON_CONNECT,
    ON_CONNECT_AUTHENTICATE,
    ON_PACKET_READ,
    ON_PACKET_SENT,
    ON_PUBLISH,
    ON_QOS_DROPPED,
    Hook,
    Hooks,
)
from mqtt_tpu.hooks.auth import AllowHook
from mqtt_tpu.packets import (
    AUTH,
    CONNACK,
    CONNECT,
    DISCONNECT,
    PINGREQ,
    PINGRESP,
    PUBACK,
    PUBCOMP,
    PUBLISH,
    PUBREC,
    PUBREL,
    SUBACK,
    SUBSCRIBE,
    UNSUBACK,
    UNSUBSCRIBE,
    Code,
    ConnectParams,
    FixedHeader,
    Packet,
    Subscription,
    codes,
    decode_length,
    decode_packet,
    encode_packet,
)

TIMEOUT = 3.0


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=15))


def connect_packet(client_id="test", version=4, clean=True, keepalive=30, will=None):
    cp = ConnectParams(
        protocol_name=b"MQTT",
        clean=clean,
        keepalive=keepalive,
        client_identifier=client_id,
    )
    if will:
        cp.will_flag = True
        cp.will_topic = will[0]
        cp.will_payload = will[1]
        cp.will_qos = will[2] if len(will) > 2 else 0
        cp.will_retain = will[3] if len(will) > 3 else False
    return encode_packet(
        Packet(fixed_header=FixedHeader(type=CONNECT), protocol_version=version, connect=cp)
    )


async def read_wire_packet(reader, version=4):
    """Read one framed packet off the stream and decode it."""
    first = await asyncio.wait_for(reader.readexactly(1), TIMEOUT)
    buf = bytearray(first)
    while True:
        b = await asyncio.wait_for(reader.readexactly(1), TIMEOUT)
        buf += b
        if not (b[0] & 0x80):
            break
    remaining, _ = decode_length(bytes(buf), 1)
    if remaining:
        buf += await asyncio.wait_for(reader.readexactly(remaining), TIMEOUT)
    return decode_packet(bytes(buf), version)


class ObservingHook(Hook):
    """Provides ONE of ON_PACKET_ENCODE / ON_PACKET_SENT and changes
    nothing. A hook that observes encodes or sends is what takes a
    fan-out off the encode-once batched flush and onto the
    per-subscriber loop (``Server._fan_out``): the way a deployment
    reaches that path, so the way the tests do."""

    def __init__(self, event=ON_PACKET_SENT):
        super().__init__()
        self.event = event
        self.seen = 0

    def id(self):
        return "observing"

    def provides(self, b):
        return b == self.event

    def on_packet_encode(self, cl, pk):
        self.seen += 1
        return pk

    def on_packet_sent(self, cl, pk, b):
        self.seen += 1


class Harness:
    """One broker plus helpers to attach raw in-memory client connections."""

    def __init__(self, options=None, allow=True):
        self.server = Server(options or Options(inline_client=True))
        if allow:
            self.server.add_hook(AllowHook())
        self.tasks = []
        # every client-side writer is HELD for the harness's lifetime:
        # Python 3.12's StreamWriter.__del__ closes a dropped writer, so
        # a test that keeps only the reader would disconnect its client
        self._writers = []

    async def attach(self):
        """Create a socketpair; server side becomes an attached client."""
        s1, s2 = socket.socketpair()
        s1.setblocking(False)
        s2.setblocking(False)
        client_reader, client_writer = await asyncio.open_connection(sock=s1)
        self._writers.append(client_writer)
        server_reader, server_writer = await asyncio.open_connection(sock=s2)
        cl = self.server.new_client(server_reader, server_writer, "t1", "", False)
        task = asyncio.get_running_loop().create_task(self.server.attach_client(cl, "t1"))
        self.tasks.append(task)
        return client_reader, client_writer, task

    async def connect(self, client_id="test", version=4, expect_code=0, **kw):
        reader, writer, task = await self.attach()
        writer.write(connect_packet(client_id, version, **kw))
        await writer.drain()
        ack = await read_wire_packet(reader, version)
        assert ack.fixed_header.type == CONNACK
        assert ack.reason_code == expect_code, f"connack code {ack.reason_code:#x}"
        return reader, writer, task

    async def shutdown(self):
        for t in self.tasks:
            if not t.done():
                t.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)


class TestEstablishConnection:
    def test_connect_v4(self):
        async def scenario():
            h = Harness()
            reader, writer, task = await h.attach()
            writer.write(connect_packet("zen", 4))
            await writer.drain()
            raw = await asyncio.wait_for(reader.readexactly(4), TIMEOUT)
            assert raw == bytes.fromhex("20020000")  # exact CONNACK bytes
            writer.write(encode_packet(Packet(fixed_header=FixedHeader(type=DISCONNECT), protocol_version=4)))
            await writer.drain()
            await asyncio.wait_for(task, TIMEOUT)
            await h.shutdown()

        run(scenario())

    def test_connect_v5_properties(self):
        async def scenario():
            h = Harness()
            reader, writer, task = await h.connect("zen5", version=5)
            assert h.server.clients.get("zen5") is not None
            await h.shutdown()

        run(scenario())

    def test_first_packet_must_be_connect(self):
        async def scenario():
            h = Harness()
            reader, writer, task = await h.attach()
            writer.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await writer.drain()
            await asyncio.wait_for(task, TIMEOUT)  # connection dropped
            data = await asyncio.wait_for(reader.read(16), TIMEOUT)
            assert data == b""  # no CONNACK, just close
            await h.shutdown()

        run(scenario())

    def test_auth_default_deny(self):
        async def scenario():
            h = Harness(allow=False)  # no hooks: OR-default deny-all
            reader, writer, task = await h.attach()
            writer.write(connect_packet("nope", 4))
            await writer.drain()
            ack = await read_wire_packet(reader, 4)
            assert ack.fixed_header.type == CONNACK
            # v5 0x86 translates to v3 0x05 not-authorized (codes.go:141-148)
            assert ack.reason_code == 0x05
            await h.shutdown()

        run(scenario())

    def test_maximum_clients(self):
        async def scenario():
            opts = Options(capabilities=Capabilities(maximum_clients=0))
            h = Harness(opts)
            reader, writer, task = await h.attach()
            writer.write(connect_packet("late", 4))
            await writer.drain()
            ack = await read_wire_packet(reader, 4)
            assert ack.reason_code == 0x03  # v3 server unavailable
            await h.shutdown()

        run(scenario())

    def test_pingreq_pingresp(self):
        async def scenario():
            h = Harness()
            reader, writer, task = await h.connect("pinger")
            writer.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await writer.drain()
            resp = await read_wire_packet(reader)
            assert resp.fixed_header.type == PINGRESP
            await h.shutdown()

        run(scenario())


class TestPubSub:
    def test_subscribe_publish_roundtrip(self):
        async def scenario():
            h = Harness()
            sub_r, sub_w, _ = await h.connect("subber")
            sub_w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
                        protocol_version=4,
                        packet_id=11,
                        filters=[Subscription(filter="a/b/+", qos=0)],
                    )
                )
            )
            await sub_w.drain()
            suback = await read_wire_packet(sub_r)
            assert suback.fixed_header.type == SUBACK
            assert suback.reason_codes == b"\x00"

            pub_r, pub_w, _ = await h.connect("pubber")
            pub_w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBLISH),
                        protocol_version=4,
                        topic_name="a/b/c",
                        payload=b"hello",
                    )
                )
            )
            await pub_w.drain()
            msg = await read_wire_packet(sub_r)
            assert msg.fixed_header.type == PUBLISH
            assert msg.topic_name == "a/b/c"
            assert msg.payload == b"hello"
            await h.shutdown()

        run(scenario())

    def test_qos1_flow(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("q1")
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBLISH, qos=1),
                        protocol_version=4,
                        topic_name="q/1",
                        packet_id=7,
                        payload=b"x",
                    )
                )
            )
            await w.drain()
            ack = await read_wire_packet(r)
            assert ack.fixed_header.type == PUBACK
            assert ack.packet_id == 7
            await h.shutdown()

        run(scenario())

    def test_qos2_flow(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("q2")
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBLISH, qos=2),
                        protocol_version=4,
                        topic_name="q/2",
                        packet_id=9,
                        payload=b"x",
                    )
                )
            )
            await w.drain()
            rec = await read_wire_packet(r)
            assert rec.fixed_header.type == PUBREC and rec.packet_id == 9
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBREL, qos=1),
                        protocol_version=4,
                        packet_id=9,
                    )
                )
            )
            await w.drain()
            comp = await read_wire_packet(r)
            assert comp.fixed_header.type == PUBCOMP and comp.packet_id == 9
            await h.shutdown()

        run(scenario())

    def test_qos_downgrade_to_subscription(self):
        async def scenario():
            h = Harness()
            sub_r, sub_w, _ = await h.connect("downsub")
            sub_w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
                        protocol_version=4,
                        packet_id=1,
                        filters=[Subscription(filter="dn/t", qos=0)],
                    )
                )
            )
            await sub_w.drain()
            await read_wire_packet(sub_r)  # suback

            pr, pw, _ = await h.connect("downpub")
            pw.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBLISH, qos=1),
                        protocol_version=4,
                        topic_name="dn/t",
                        packet_id=3,
                        payload=b"m",
                    )
                )
            )
            await pw.drain()
            await read_wire_packet(pr)  # puback to publisher
            msg = await read_wire_packet(sub_r)
            assert msg.fixed_header.qos == 0  # min(sub 0, msg 1)
            await h.shutdown()

        run(scenario())

    def test_retained_delivered_on_subscribe(self):
        async def scenario():
            h = Harness()
            pr, pw, _ = await h.connect("retainer")
            pw.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBLISH, retain=True),
                        protocol_version=4,
                        topic_name="ret/t",
                        payload=b"keepme",
                    )
                )
            )
            await pw.drain()
            await asyncio.sleep(0.05)
            assert len(h.server.topics.retained) == 1

            sr, sw, _ = await h.connect("late-sub")
            sw.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
                        protocol_version=4,
                        packet_id=2,
                        filters=[Subscription(filter="ret/#", qos=0)],
                    )
                )
            )
            await sw.drain()
            suback = await read_wire_packet(sr)
            assert suback.fixed_header.type == SUBACK
            msg = await read_wire_packet(sr)
            assert msg.topic_name == "ret/t"
            assert msg.payload == b"keepme"
            assert msg.fixed_header.retain  # fwd_retained keeps the flag
            await h.shutdown()

        run(scenario())

    def test_unsubscribe(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("unsub")
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
                        protocol_version=4,
                        packet_id=4,
                        filters=[Subscription(filter="u/t", qos=0)],
                    )
                )
            )
            await w.drain()
            await read_wire_packet(r)
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=UNSUBSCRIBE, qos=1),
                        protocol_version=4,
                        packet_id=5,
                        filters=[Subscription(filter="u/t")],
                    )
                )
            )
            await w.drain()
            unsuback = await read_wire_packet(r)
            assert unsuback.fixed_header.type == UNSUBACK
            assert len(h.server.topics.subscribers("u/t").subscriptions) == 0
            await h.shutdown()

        run(scenario())


class TestSessionsAndWills:
    def test_session_takeover(self):
        async def scenario():
            h = Harness()
            r1, w1, t1 = await h.connect("dup", version=5, clean=False)
            r2, w2, t2 = await h.connect("dup", version=5, clean=False)
            # first client receives DISCONNECT(session taken over)
            pk = await read_wire_packet(r1, 5)
            assert pk.fixed_header.type == DISCONNECT
            assert pk.reason_code == 0x8E
            assert h.server.clients.get("dup") is not None
            await h.shutdown()

        run(scenario())

    def test_lwt_published_on_abnormal_disconnect(self):
        async def scenario():
            h = Harness()
            sr, sw, _ = await h.connect("watcher")
            sw.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
                        protocol_version=4,
                        packet_id=6,
                        filters=[Subscription(filter="lwt/t", qos=0)],
                    )
                )
            )
            await sw.drain()
            await read_wire_packet(sr)

            dr, dw, dt = await h.connect("dier", will=("lwt/t", b"gone", 0))
            dw.transport.abort()  # abrupt connection loss
            msg = await read_wire_packet(sr)
            assert msg.topic_name == "lwt/t"
            assert msg.payload == b"gone"
            await h.shutdown()

        run(scenario())

    def test_clean_disconnect_no_lwt(self):
        async def scenario():
            h = Harness()
            sr, sw, _ = await h.connect("watcher2")
            sw.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
                        protocol_version=4,
                        packet_id=6,
                        filters=[Subscription(filter="lwt2/t", qos=0)],
                    )
                )
            )
            await sw.drain()
            await read_wire_packet(sr)

            dr, dw, dt = await h.connect("polite", will=("lwt2/t", b"gone", 0))
            dw.write(encode_packet(Packet(fixed_header=FixedHeader(type=DISCONNECT), protocol_version=4)))
            await dw.drain()
            await asyncio.wait_for(dt, TIMEOUT)
            # no will should arrive; publish a sentinel to prove ordering
            h.server.publish("lwt2/t", b"sentinel", False, 0)
            msg = await read_wire_packet(sr)
            assert msg.payload == b"sentinel"
            await h.shutdown()

        run(scenario())


class TestInlineClient:
    def test_inline_pub_sub(self):
        async def scenario():
            h = Harness()
            got = []
            h.server.subscribe("in/+", 1, lambda cl, sub, pk: got.append(pk.topic_name))
            h.server.publish("in/x", b"v", False, 0)
            assert got == ["in/x"]
            h.server.unsubscribe("in/+", 1)
            h.server.publish("in/y", b"v", False, 0)
            assert got == ["in/x"]
            await h.shutdown()

        run(scenario())

    def test_inline_requires_option(self):
        from mqtt_tpu import InlineClientNotEnabledError

        s = Server(Options(inline_client=False))
        with pytest.raises(InlineClientNotEnabledError):
            s.publish("a", b"b", False, 0)
        with pytest.raises(InlineClientNotEnabledError):
            s.subscribe("a", 1, lambda *a: None)


class TestSysTopics:
    def test_sys_topics_retained(self):
        async def scenario():
            h = Harness()
            h.server.publish_sys_topics()
            pks = h.server.topics.messages("$SYS/#")
            topics = {p.topic_name for p in pks}
            assert "$SYS/broker/version" in topics
            assert "$SYS/broker/clients/connected" in topics
            assert "$SYS/broker/overload/state" in topics
            assert "$SYS/broker/telemetry/flight/ring_depth" in topics
            assert "$SYS/broker/predicates/rules" in topics
            if h.server.device_stats is not None:
                assert "$SYS/broker/devices/skew_ratio" in topics
            base = {
                t
                for t in topics
                if not t.startswith("$SYS/broker/overload/")
                and not t.startswith("$SYS/broker/telemetry/")
                and not t.startswith("$SYS/broker/predicates/")
                # device observatory rows scale with the device count
                and not t.startswith("$SYS/broker/devices/")
            }
            # the 20 of the reference's tree, the trie's three counts
            # and the two each of the ingest run and the ack run, the
            # direct feeder's reads, and the seven of the way out
            assert {
                "$SYS/broker/topics/particles",
                "$SYS/broker/topics/particle_maps",
                "$SYS/broker/topics/held",
                "$SYS/broker/ingest/runs",
                "$SYS/broker/ingest/run_publishes",
                "$SYS/broker/ingest/ack_runs",
                "$SYS/broker/ingest/ack_run_acks",
                "$SYS/broker/ingest/direct_reads",
                "$SYS/broker/egress/deliveries_flush",
                "$SYS/broker/egress/deliveries_cork",
                "$SYS/broker/egress/deliveries_queue",
                "$SYS/broker/egress/deliveries_dropped_full",
                "$SYS/broker/egress/cork_writes",
                "$SYS/broker/egress/cork_frames",
                "$SYS/broker/egress/cork_early_writes",
                "$SYS/broker/egress/socket_checks",
            } <= base
            assert len(base) == 36
            await h.shutdown()

        run(scenario())


class TestHooksDispatcher:
    def test_modifier_chain_order(self):
        hooks = Hooks()

        class Adder(Hook):
            def __init__(self, tag):
                super().__init__()
                self.tag = tag

            def id(self):
                return self.tag

            def provides(self, b):
                return b == ON_PACKET_READ

            def on_packet_read(self, cl, pk):
                pk.topic_name += self.tag
                return pk

        hooks.add(Adder("a"), None)
        hooks.add(Adder("b"), None)
        pk = hooks.on_packet_read(None, Packet(topic_name="x"))
        assert pk.topic_name == "xab"

    def test_reject_short_circuits(self):
        hooks = Hooks()

        class Rejecter(Hook):
            def id(self):
                return "rej"

            def provides(self, b):
                return b == ON_PUBLISH

            def on_publish(self, cl, pk):
                raise codes.ERR_REJECT_PACKET()

        hooks.add(Rejecter(), None)
        with pytest.raises(Code) as e:
            hooks.on_publish(None, Packet())
        assert e.value == codes.ERR_REJECT_PACKET

    def test_auth_or_semantics(self):
        hooks = Hooks()
        assert not hooks.on_connect_authenticate(None, Packet())  # default deny

        class Denier(Hook):
            def id(self):
                return "deny"

            def provides(self, b):
                return b in (ON_CONNECT_AUTHENTICATE, ON_ACL_CHECK)

        hooks.add(Denier(), None)
        assert not hooks.on_acl_check(None, "t", True)
        hooks.add(AllowHook(), None)
        assert hooks.on_connect_authenticate(None, Packet())
        assert hooks.on_acl_check(None, "t", True)


class TestInflight:
    def test_set_get_delete(self):
        from mqtt_tpu.inflight import Inflight

        i = Inflight()
        assert i.set(Packet(packet_id=1, created=10))
        assert not i.set(Packet(packet_id=1, created=11))
        assert i.get(1) is not None
        assert len(i) == 1
        assert i.delete(1)
        assert not i.delete(1)

    def test_quotas(self):
        from mqtt_tpu.inflight import Inflight

        i = Inflight()
        i.reset_receive_quota(2)
        i.decrease_receive_quota()
        i.decrease_receive_quota()
        i.decrease_receive_quota()  # floors at 0
        assert i.receive_quota == 0
        i.increase_receive_quota()
        assert i.receive_quota == 1
        for _ in range(5):
            i.increase_receive_quota()
        assert i.receive_quota == 2  # capped at maximum

    def test_get_all_sorted_and_immediate(self):
        from mqtt_tpu.inflight import Inflight

        i = Inflight()
        i.set(Packet(packet_id=1, created=30))
        i.set(Packet(packet_id=2, created=10))
        i.set(Packet(packet_id=3, created=20, expiry=-1))
        assert [p.packet_id for p in i.get_all(False)] == [2, 3, 1]
        nxt = i.next_immediate()
        assert nxt is not None and nxt.packet_id == 3

    def test_clone(self):
        from mqtt_tpu.inflight import Inflight

        i = Inflight()
        i.set(Packet(packet_id=5))
        c = i.clone()
        assert c.get(5) is not None
        c.delete(5)
        assert i.get(5) is not None


class TestRetainFlagRegression:
    def test_live_publish_after_retained_has_retain_cleared(self):
        """The trie-stored subscription must not keep fwd_retained_flag after
        retained delivery: a later LIVE publish with retain=1 must reach the
        subscriber with retain=0 [MQTT-3.3.1-12]."""

        async def scenario():
            h = Harness()
            pr, pw, _ = await h.connect("retainer2")
            pw.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBLISH, retain=True),
                        protocol_version=4,
                        topic_name="rf/t",
                        payload=b"old",
                    )
                )
            )
            await pw.drain()
            await asyncio.sleep(0.05)

            sr, sw, _ = await h.connect("flag-sub")
            sw.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
                        protocol_version=4,
                        packet_id=3,
                        filters=[Subscription(filter="rf/t", qos=0)],
                    )
                )
            )
            await sw.drain()
            await read_wire_packet(sr)  # suback
            retained = await read_wire_packet(sr)
            assert retained.fixed_header.retain  # retained replay keeps flag

            pw.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBLISH, retain=True),
                        protocol_version=4,
                        topic_name="rf/t",
                        payload=b"live",
                    )
                )
            )
            await pw.drain()
            live = await read_wire_packet(sr)
            assert live.payload == b"live"
            assert not live.fixed_header.retain  # [MQTT-3.3.1-12]
            await h.shutdown()

        run(scenario())


def sub_packet(pid, filters, version=4):
    return encode_packet(
        Packet(
            fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
            protocol_version=version,
            packet_id=pid,
            filters=filters,
        )
    )


def pub_packet(topic, payload, qos=0, pid=0, version=4, retain=False, props=None):
    pk = Packet(
        fixed_header=FixedHeader(type=PUBLISH, qos=qos, retain=retain),
        protocol_version=version,
        topic_name=topic,
        packet_id=pid,
        payload=payload,
    )
    if props is not None:
        pk.properties = props
    return encode_packet(pk)


class TestTopicAliases:
    def test_inbound_alias_resolves_empty_topic(self):
        """v5 publisher sets an alias then sends alias-only publishes; the
        subscriber sees the real topic both times (server.go:904-906)."""

        async def scenario():
            from mqtt_tpu.packets import Properties

            h = Harness()
            sr, sw, _ = await h.connect("alias-sub")
            sw.write(sub_packet(1, [Subscription(filter="al/t", qos=0)]))
            await sw.drain()
            await read_wire_packet(sr)

            pr, pw, _ = await h.connect("alias-pub", version=5)
            pw.write(
                pub_packet(
                    "al/t", b"one", version=5,
                    props=Properties(topic_alias=4, topic_alias_flag=True),
                )
            )
            pw.write(
                pub_packet(
                    "", b"two", version=5,
                    props=Properties(topic_alias=4, topic_alias_flag=True),
                )
            )
            await pw.drain()
            m1 = await read_wire_packet(sr)
            m2 = await read_wire_packet(sr)
            assert (m1.topic_name, m1.payload) == ("al/t", b"one")
            assert (m2.topic_name, m2.payload) == ("al/t", b"two")
            await h.shutdown()

        run(scenario())

    def test_outbound_alias_assigned_when_client_allows(self):
        """A v5 subscriber advertising topic_alias_maximum gets an alias on
        first delivery and an empty topic afterwards (server.go:1052-1061)."""

        async def scenario():
            from mqtt_tpu.packets import Properties

            h = Harness()
            reader, writer, task = await h.attach()
            cp = ConnectParams(
                protocol_name=b"MQTT", clean=True, keepalive=30,
                client_identifier="alias-out",
            )
            writer.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=CONNECT),
                        protocol_version=5,
                        properties=Properties(topic_alias_maximum=8),
                        connect=cp,
                    )
                )
            )
            await writer.drain()
            await read_wire_packet(reader, 5)
            writer.write(sub_packet(1, [Subscription(filter="ob/t", qos=0)], version=5))
            await writer.drain()
            await read_wire_packet(reader, 5)

            h.server.publish("ob/t", b"m1", False, 0)
            h.server.publish("ob/t", b"m2", False, 0)
            m1 = await read_wire_packet(reader, 5)
            m2 = await read_wire_packet(reader, 5)
            assert m1.topic_name == "ob/t" and m1.properties.topic_alias == 1
            assert m2.topic_name == "" and m2.properties.topic_alias == 1
            assert m2.payload == b"m2"
            await h.shutdown()

        run(scenario())


class TestQuotasAndLimits:
    def test_receive_maximum_disconnect(self):
        """Exceeding the in-flight receive quota with unacked QoS2 publishes
        disconnects with ErrReceiveMaximum (server.go:862-864)."""

        async def scenario():
            opts = Options(capabilities=Capabilities(receive_maximum=1))
            h = Harness(opts)
            reader, writer, task = await h.connect("greedy", version=5)
            writer.write(pub_packet("q/t", b"a", qos=2, pid=1, version=5))
            await writer.drain()
            rec = await read_wire_packet(reader, 5)
            assert rec.fixed_header.type == PUBREC
            # second QoS2 publish without completing the first
            writer.write(pub_packet("q/t", b"b", qos=2, pid=2, version=5))
            await writer.drain()
            disc = await read_wire_packet(reader, 5)
            assert disc.fixed_header.type == DISCONNECT
            assert disc.reason_code == codes.ERR_RECEIVE_MAXIMUM.code
            await h.shutdown()

        run(scenario())

    def test_maximum_packet_size_drops_oversized(self):
        """Messages larger than the client's maximum packet size are not
        delivered to it [MQTT-3.1.2-24] (clients.go:595-598)."""

        async def scenario():
            from mqtt_tpu.packets import Properties

            h = Harness()
            reader, writer, task = await h.attach()
            writer.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=CONNECT),
                        protocol_version=5,
                        properties=Properties(maximum_packet_size=25),
                        connect=ConnectParams(
                            protocol_name=b"MQTT", clean=True, keepalive=30,
                            client_identifier="small",
                        ),
                    )
                )
            )
            await writer.drain()
            await read_wire_packet(reader, 5)
            writer.write(sub_packet(1, [Subscription(filter="mx/t", qos=0)], version=5))
            await writer.drain()
            await read_wire_packet(reader, 5)

            h.server.publish("mx/t", b"x" * 100, False, 0)  # oversized: dropped
            h.server.publish("mx/t", b"ok", False, 0)
            m = await read_wire_packet(reader, 5)
            assert m.payload == b"ok"
            await h.shutdown()

        run(scenario())


class TestDelayedLWT:
    def test_will_delay_interval_defers_and_reconnect_cancels(self):
        """A v5 will with a delay interval is queued, published by the
        delayed-LWT tick, and cancelled by reconnection (server.go:1744-1758,
        [MQTT-3.1.3-9])."""

        async def scenario():
            import time as _time
            from mqtt_tpu.packets import Properties

            h = Harness()
            sr, sw, _ = await h.connect("lwt-watcher")
            sw.write(sub_packet(1, [Subscription(filter="dl/t", qos=0)]))
            await sw.drain()
            await read_wire_packet(sr)

            async def connect_with_delayed_will():
                reader, writer, task = await h.attach()
                cp = ConnectParams(
                    protocol_name=b"MQTT", clean=False, keepalive=30,
                    client_identifier="doomed", will_flag=True,
                    will_topic="dl/t", will_payload=b"gone",
                )
                cp.will_properties = Properties(will_delay_interval=30)
                writer.write(
                    encode_packet(
                        Packet(
                            fixed_header=FixedHeader(type=CONNECT),
                            protocol_version=5,
                            connect=cp,
                        )
                    )
                )
                await writer.drain()
                await read_wire_packet(reader, 5)
                return reader, writer, task

            reader, writer, task = await connect_with_delayed_will()
            writer.close()  # abnormal disconnect
            await asyncio.sleep(0.1)
            assert len(h.server.will_delayed) == 1

            # not yet due: nothing published
            h.server.send_delayed_lwt(int(_time.time()))
            with pytest.raises(asyncio.TimeoutError):
                await read_wire_packet(sr)

            # due: published to the watcher
            h.server.send_delayed_lwt(int(_time.time()) + 3600)
            m = await read_wire_packet(sr)
            assert (m.topic_name, m.payload) == ("dl/t", b"gone")
            assert len(h.server.will_delayed) == 0

            # reconnect cancels a re-queued delayed will [MQTT-3.1.3-9]
            reader, writer, task = await connect_with_delayed_will()
            writer.close()
            await asyncio.sleep(0.1)
            assert len(h.server.will_delayed) == 1
            reader, writer, task = await connect_with_delayed_will()
            assert len(h.server.will_delayed) == 0
            await h.shutdown()

        run(scenario())


class TestTakeover:
    def test_takeover_inherits_inflight_and_resends_dup(self):
        """Session takeover moves unacked QoS1 inflights to the new
        connection and resends them with DUP (server.go:561-603,
        clients.go:302-327)."""

        async def scenario():
            h = Harness()
            r1, w1, _ = await h.connect("dur", clean=False)
            w1.write(sub_packet(1, [Subscription(filter="tk/t", qos=1)]))
            await w1.drain()
            await read_wire_packet(r1)

            h.server.publish("tk/t", b"keep", False, 1)
            m = await read_wire_packet(r1)
            assert m.fixed_header.type == PUBLISH and m.fixed_header.qos == 1
            assert not m.fixed_header.dup

            # second connection with same id takes over without acking
            r2, w2, _ = await h.connect("dur", clean=False, expect_code=0)
            redo = await read_wire_packet(r2)
            assert redo.fixed_header.type == PUBLISH
            assert redo.payload == b"keep"
            assert redo.fixed_header.dup  # [MQTT-3.3.1-1] resend marks DUP
            await h.shutdown()

        run(scenario())

    def test_second_connect_is_protocol_violation(self):
        """A second CONNECT on a live connection disconnects the client
        (server.go:734-738, [MQTT-3.1.0-2])."""

        async def scenario():
            h = Harness()
            reader, writer, task = await h.connect("twice", version=5)
            writer.write(connect_packet("twice", 5))
            await writer.drain()
            disc = await read_wire_packet(reader, 5)
            assert disc.fixed_header.type == DISCONNECT
            assert disc.reason_code == codes.ERR_PROTOCOL_VIOLATION_SECOND_CONNECT.code
            await h.shutdown()

        run(scenario())


class TestSubscriptionOptions:
    def test_subscription_identifier_attached(self):
        """v5 subscription identifiers ride on delivered publishes, sorted
        [MQTT-3.3.4-3/4] (server.go:1033-1040)."""

        async def scenario():
            from mqtt_tpu.packets import Properties

            h = Harness()
            reader, writer, task = await h.connect("subid", version=5)
            pk = Packet(
                fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
                protocol_version=5,
                packet_id=2,
                properties=Properties(subscription_identifier=[7]),
                filters=[Subscription(filter="si/t", qos=0, identifier=7)],
            )
            writer.write(encode_packet(pk))
            await writer.drain()
            await read_wire_packet(reader, 5)

            h.server.publish("si/t", b"x", False, 0)
            m = await read_wire_packet(reader, 5)
            assert m.properties.subscription_identifier == [7]
            await h.shutdown()

        run(scenario())

    def test_no_local_suppresses_echo(self):
        """A no-local subscriber never receives its own publishes
        [MQTT-3.8.3-3] (server.go:1024-1026)."""

        async def scenario():
            h = Harness()
            reader, writer, task = await h.connect("nl", version=5)
            writer.write(
                sub_packet(1, [Subscription(filter="nl/t", qos=0, no_local=True)], version=5)
            )
            await writer.drain()
            await read_wire_packet(reader, 5)

            writer.write(pub_packet("nl/t", b"echo", version=5))
            await writer.drain()
            with pytest.raises(asyncio.TimeoutError):
                await read_wire_packet(reader, 5)

            # another client's publish still arrives
            h.server.publish("nl/t", b"other", False, 0)
            m = await read_wire_packet(reader, 5)
            assert m.payload == b"other"
            await h.shutdown()

        run(scenario())


class TestExpiryLoops:
    def test_clear_expired_retained_messages(self):
        async def scenario():
            import time as _time

            h = Harness()
            opts = h.server.options
            r, w, _ = await h.connect("ret", version=5)
            from mqtt_tpu.packets import Properties

            w.write(
                pub_packet(
                    "ex/t", b"v", version=5, retain=True,
                    props=Properties(message_expiry_interval=5),
                )
            )
            await w.drain()
            await asyncio.sleep(0.1)
            assert len(h.server.topics.retained) == 1
            h.server.clear_expired_retained_messages(int(_time.time()) + 60)
            assert len(h.server.topics.retained) == 0
            await h.shutdown()

        run(scenario())

    def test_clear_expired_clients(self):
        async def scenario():
            import time as _time

            h = Harness()
            # v4 with clean=False survives disconnect (server.go:484);
            # a v5 session with no expiry property would end immediately
            r, w, _ = await h.connect("mortal", clean=False)
            w.close()
            await asyncio.sleep(0.1)
            assert h.server.clients.get("mortal") is not None
            # session expiry defaults to the server maximum; far future expires
            h.server.clear_expired_clients(int(_time.time()) + 2 ** 33)
            assert h.server.clients.get("mortal") is None
            await h.shutdown()

        run(scenario())

    def test_clear_expired_inflights(self):
        async def scenario():
            import time as _time

            h = Harness()
            r, w, _ = await h.connect("ifm", clean=False)
            w.write(sub_packet(1, [Subscription(filter="if/t", qos=1)]))
            await w.drain()
            await read_wire_packet(r)
            h.server.publish("if/t", b"x", False, 1)
            await read_wire_packet(r)  # delivered, never acked
            cl = h.server.clients.get("ifm")
            assert len(cl.state.inflight) == 1
            h.server.clear_expired_inflights(int(_time.time()) + 2 ** 33)
            assert len(cl.state.inflight) == 0
            await h.shutdown()

        run(scenario())


class TestServerAPIs:
    def test_disconnect_client_sends_v5_disconnect(self):
        async def scenario():
            h = Harness()
            reader, writer, task = await h.connect("kickme", version=5)
            cl = h.server.clients.get("kickme")
            # error-class codes re-raise after stopping (mirrors the
            # reference's error return, server.go:1413-1437)
            with pytest.raises(Code):
                h.server.disconnect_client(cl, codes.ERR_ADMINISTRATIVE_ACTION)
            disc = await read_wire_packet(reader, 5)
            assert disc.fixed_header.type == DISCONNECT
            assert disc.reason_code == codes.ERR_ADMINISTRATIVE_ACTION.code
            await h.shutdown()

        run(scenario())

    def test_unsubscribe_client_clears_trie(self):
        async def scenario():
            h = Harness()
            reader, writer, task = await h.connect("unsub-all")
            writer.write(
                sub_packet(1, [Subscription(filter="ua/1", qos=0), Subscription(filter="ua/2", qos=0)])
            )
            await writer.drain()
            await read_wire_packet(reader)
            assert len(h.server.topics.subscribers("ua/1").subscriptions) == 1
            cl = h.server.clients.get("unsub-all")
            h.server.unsubscribe_client(cl)
            assert len(h.server.topics.subscribers("ua/1").subscriptions) == 0
            assert len(h.server.topics.subscribers("ua/2").subscriptions) == 0
            await h.shutdown()

        run(scenario())

    def test_inject_packet_publishes(self):
        async def scenario():
            h = Harness()
            reader, writer, task = await h.connect("inj-sub")
            writer.write(sub_packet(1, [Subscription(filter="in/t", qos=0)]))
            await writer.drain()
            await read_wire_packet(reader)
            cl = h.server.clients.get("inj-sub")
            h.server.inject_packet(
                cl,
                Packet(
                    fixed_header=FixedHeader(type=PUBLISH),
                    topic_name="in/t",
                    payload=b"injected",
                ),
            )
            m = await read_wire_packet(reader)
            assert m.payload == b"injected"
            await h.shutdown()

        run(scenario())


def unsub_packet(pid, filters, version=4):
    return encode_packet(
        Packet(
            fixed_header=FixedHeader(type=UNSUBSCRIBE, qos=1),
            protocol_version=version,
            packet_id=pid,
            filters=[Subscription(filter=f) for f in filters],
        )
    )


class TestCompatibilities:
    """The reference's compatibility-mode flags (server.go:86-93)."""

    def test_obscure_not_authorized_masks_suback_code(self):
        async def scenario():
            opts = Options()
            opts.capabilities.compatibilities.obscure_not_authorized = True
            h = Harness(opts, allow=False)

            class DenyACL(Hook):
                def id(self):
                    return "deny-acl"

                def provides(self, b):
                    return b in (ON_CONNECT_AUTHENTICATE, ON_ACL_CHECK)

                def on_connect_authenticate(self, cl, pk):
                    return True

                def on_acl_check(self, cl, topic, write):
                    return False

            h.server.add_hook(DenyACL())
            r, w, _ = await h.connect("obsc")
            w.write(sub_packet(1, [Subscription(filter="a/b", qos=0)]))
            await w.drain()
            ack = await read_wire_packet(r)
            assert ack.fixed_header.type == SUBACK
            assert ack.reason_codes == b"\x80"  # unspecified, NOT 0x87
            await h.shutdown()

        run(scenario())

    def test_not_authorized_suback_code_without_flag(self):
        async def scenario():
            h = Harness(allow=False)

            class DenyACL(Hook):
                def id(self):
                    return "deny-acl"

                def provides(self, b):
                    return b in (ON_CONNECT_AUTHENTICATE, ON_ACL_CHECK)

                def on_connect_authenticate(self, cl, pk):
                    return True

                def on_acl_check(self, cl, topic, write):
                    return False

            h.server.add_hook(DenyACL())
            r, w, _ = await h.connect("noobsc", version=5)
            w.write(sub_packet(1, [Subscription(filter="a/b", qos=0)], version=5))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.reason_codes == b"\x87"  # not authorized, unmasked
            await h.shutdown()

        run(scenario())

    def test_passive_client_disconnect_keeps_connection(self):
        async def scenario():
            opts = Options()
            opts.capabilities.compatibilities.passive_client_disconnect = True
            h = Harness(opts)
            r, w, _ = await h.connect("passive", version=5)
            cl = h.server.clients.get("passive")
            # an error-class disconnect writes DISCONNECT but must NOT stop
            # the client nor raise (server.go:1413-1437 passive mode)
            h.server.disconnect_client(cl, codes.ERR_KEEP_ALIVE_TIMEOUT)
            pk = await read_wire_packet(r, 5)
            assert pk.fixed_header.type == DISCONNECT
            assert not cl.closed
            await h.shutdown()

        run(scenario())

    def test_always_return_response_info(self):
        async def scenario():
            opts = Options()
            opts.capabilities.compatibilities.always_return_response_info = True
            h = Harness(opts)
            reader, writer, task = await h.attach()
            pk = Packet(
                fixed_header=FixedHeader(type=CONNECT),
                protocol_version=5,
                connect=ConnectParams(
                    protocol_name=b"MQTT",
                    clean=True,
                    keepalive=30,
                    client_identifier="ri",
                ),
            )
            pk.properties.request_response_info = 1
            writer.write(encode_packet(pk))
            await writer.drain()
            ack = await read_wire_packet(reader, 5)
            assert ack.fixed_header.type == CONNACK
            await h.shutdown()

        run(scenario())

    def test_no_inherited_properties_on_ack(self):
        async def scenario():
            opts = Options()
            opts.capabilities.compatibilities.no_inherited_properties_on_ack = True
            h = Harness(opts)
            r, w, _ = await h.connect("noinherit", version=5)
            pk = Packet(
                fixed_header=FixedHeader(type=PUBLISH, qos=1),
                protocol_version=5,
                topic_name="n/i",
                packet_id=3,
                payload=b"x",
            )
            from mqtt_tpu.packets import UserProperty
            pk.properties.user = [UserProperty("k", "v")]
            w.write(encode_packet(pk))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.fixed_header.type == PUBACK
            assert not ack.properties.user  # properties NOT inherited
            await h.shutdown()

        run(scenario())

    def test_restore_sys_info_on_restart(self):
        from mqtt_tpu.hooks.storage import SystemInfo as StoredSysInfo
        from mqtt_tpu.hooks import STORED_SYS_INFO as _SSI

        class SysStore(Hook):
            def id(self):
                return "sys-store"

            def provides(self, b):
                return b == _SSI

            def stored_sys_info(self):
                info = StoredSysInfo()
                info.info.version = "2.7.9"  # first NON-EMPTY wins (hooks.go:644)
                info.info.bytes_received = 777
                info.info.messages_received = 42
                return info

        async def scenario():
            opts = Options()
            opts.capabilities.compatibilities.restore_sys_info_on_restart = True
            h = Harness(opts)
            h.server.add_hook(SysStore())
            h.server.read_store()
            assert h.server.info.bytes_received == 777
            assert h.server.info.messages_received == 42
            await h.shutdown()

        run(scenario())


class TestSubscribeEdges:
    def test_shared_no_local_violation_code(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("snl", version=5)
            w.write(
                sub_packet(
                    1,
                    [Subscription(filter="$share/g/a", qos=0, no_local=True)],
                    version=5,
                )
            )
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.reason_codes[0] == 0x82  # protocol error [MQTT-3.8.3-4]
            await h.shutdown()

        run(scenario())

    def test_invalid_filter_reason_code(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("badf", version=5)
            w.write(sub_packet(1, [Subscription(filter="a/#/b", qos=0)], version=5))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.reason_codes[0] == codes.ERR_TOPIC_FILTER_INVALID.code
            await h.shutdown()

        run(scenario())

    def test_packet_id_in_use_suback(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("piu", version=5)
            cl = h.server.clients.get("piu")
            cl.state.inflight.set(
                Packet(fixed_header=FixedHeader(type=PUBLISH, qos=1), packet_id=9)
            )
            w.write(sub_packet(9, [Subscription(filter="a/b", qos=0)], version=5))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.reason_codes[0] == codes.ERR_PACKET_IDENTIFIER_IN_USE.code
            await h.shutdown()

        run(scenario())

    def test_granted_qos_capped_by_server_maximum(self):
        async def scenario():
            opts = Options()
            opts.capabilities.maximum_qos = 1
            h = Harness(opts)
            r, w, _ = await h.connect("qcap")
            w.write(sub_packet(1, [Subscription(filter="a/b", qos=2)]))
            await w.drain()
            ack = await read_wire_packet(r)
            assert ack.reason_codes == b"\x01"  # granted qos1, not qos2
            await h.shutdown()

        run(scenario())

    def test_subscription_counter_tracks_new_and_existing(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("cnt")
            w.write(sub_packet(1, [Subscription(filter="c/1", qos=0)]))
            await w.drain()
            await read_wire_packet(r)
            n1 = h.server.info.subscriptions
            w.write(sub_packet(2, [Subscription(filter="c/1", qos=1)]))  # resubscribe
            await w.drain()
            await read_wire_packet(r)
            assert h.server.info.subscriptions == n1  # not double counted
            await h.shutdown()

        run(scenario())

    def test_unsubscribe_decrements_counter_and_acks(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("uns", version=5)
            w.write(sub_packet(1, [Subscription(filter="u/1", qos=0)], version=5))
            await w.drain()
            await read_wire_packet(r, 5)
            n1 = h.server.info.subscriptions
            w.write(unsub_packet(2, ["u/1", "u/nope"], version=5))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.fixed_header.type == UNSUBACK
            assert ack.reason_codes == b"\x00\x11"  # success, no sub existed
            assert h.server.info.subscriptions == n1 - 1
            await h.shutdown()

        run(scenario())


class TestInflightQuotaEdges:
    def test_maximum_inflight_gate_drops_qos_publish(self):
        async def scenario():
            opts = Options()
            opts.capabilities.maximum_inflight = 1
            h = Harness(opts)
            sub_r, sub_w, _ = await h.connect("slow")
            sub_w.write(sub_packet(1, [Subscription(filter="g/#", qos=1)]))
            await sub_w.drain()
            await read_wire_packet(sub_r)
            cl = h.server.clients.get("slow")
            # occupy the single inflight slot
            cl.state.inflight.set(
                Packet(fixed_header=FixedHeader(type=PUBLISH, qos=1), packet_id=60000)
            )
            dropped0 = h.server.info.inflight_dropped
            pub_r, pub_w, _ = await h.connect("fast")
            pub_w.write(pub_packet("g/1", b"x", qos=1, pid=5))
            await pub_w.drain()
            await read_wire_packet(pub_r)  # publisher still gets PUBACK
            await asyncio.sleep(0.05)
            assert h.server.info.inflight_dropped == dropped0 + 1
            await h.shutdown()

        run(scenario())

    def test_packet_id_exhaustion_counts_and_hook(self):
        async def scenario():
            h = Harness()
            seen = []

            class IdHook(Hook):
                def id(self):
                    return "ids"

                def provides(self, b):
                    from mqtt_tpu.hooks import ON_PACKET_ID_EXHAUSTED

                    return b == ON_PACKET_ID_EXHAUSTED

                def on_packet_id_exhausted(self, cl, pk):
                    seen.append(cl.id)

            h.server.add_hook(IdHook())
            sub_r, sub_w, _ = await h.connect("exhaust")
            sub_w.write(sub_packet(1, [Subscription(filter="e/#", qos=1)]))
            await sub_w.drain()
            await read_wire_packet(sub_r)
            cl = h.server.clients.get("exhaust")
            # fill the entire id space
            caps_max = h.server.options.capabilities.maximum_packet_id
            for i in range(1, caps_max + 1):
                cl.state.inflight.set(
                    Packet(fixed_header=FixedHeader(type=PUBLISH, qos=1), packet_id=i)
                )
            # bypass the inflight-count gate so next_packet_id is reached
            h.server.options.capabilities.maximum_inflight = caps_max + 10
            pub_r, pub_w, _ = await h.connect("src")
            pub_w.write(pub_packet("e/1", b"x", qos=1, pid=5))
            await pub_w.drain()
            await read_wire_packet(pub_r)
            await asyncio.sleep(0.05)
            assert seen == ["exhaust"]
            await h.shutdown()

        run(scenario())

    def test_send_quota_zero_marks_immediate_resend(self):
        async def scenario():
            h = Harness()
            reader, writer, task = await h.attach()
            pk = Packet(
                fixed_header=FixedHeader(type=CONNECT),
                protocol_version=5,
                connect=ConnectParams(
                    protocol_name=b"MQTT",
                    clean=True,
                    keepalive=30,
                    client_identifier="quota1",
                ),
            )
            pk.properties.receive_maximum = 1  # client accepts 1 inflight
            writer.write(encode_packet(pk))
            await writer.drain()
            await read_wire_packet(reader, 5)
            writer.write(sub_packet(1, [Subscription(filter="q/#", qos=1)], version=5))
            await writer.drain()
            await read_wire_packet(reader, 5)

            pub_r, pub_w, _ = await h.connect("qsrc")
            pub_w.write(pub_packet("q/a", b"1", qos=1, pid=2))
            pub_w.write(pub_packet("q/b", b"2", qos=1, pid=3))
            await pub_w.drain()
            await read_wire_packet(pub_r)
            await read_wire_packet(pub_r)
            # first delivery consumed the quota; second is parked immediate
            out1 = await read_wire_packet(reader, 5)
            assert out1.fixed_header.type == PUBLISH
            cl = h.server.clients.get("quota1")
            await asyncio.sleep(0.05)
            assert cl.state.inflight.next_immediate() is not None
            # PUBACK frees quota -> the parked publish drains
            writer.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBACK),
                        protocol_version=5,
                        packet_id=out1.packet_id,
                    )
                )
            )
            await writer.drain()
            out2 = await read_wire_packet(reader, 5)
            assert out2.fixed_header.type == PUBLISH
            assert bytes(out2.payload) == b"2"
            await h.shutdown()

        run(scenario())

    def test_pubrel_unknown_id_gets_pubcomp_not_found(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("rel5", version=5)
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBREL, qos=1),
                        protocol_version=5,
                        packet_id=77,
                    )
                )
            )
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.fixed_header.type == PUBCOMP
            assert ack.reason_code == 0x92  # packet identifier not found
            await h.shutdown()

        run(scenario())

    def test_puback_unknown_id_is_ignored(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("ack4")
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBACK),
                        protocol_version=4,
                        packet_id=555,
                    )
                )
            )
            w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await w.drain()
            pk = await read_wire_packet(r)
            assert pk.fixed_header.type == PINGRESP  # connection healthy
            await h.shutdown()

        run(scenario())

    def test_receive_quota_restored_after_qos2_complete(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("q2q")
            cl = h.server.clients.get("q2q")
            quota0 = cl.state.inflight.receive_quota
            w.write(pub_packet("t/2", b"x", qos=2, pid=9))
            await w.drain()
            rec = await read_wire_packet(r)
            assert rec.fixed_header.type == PUBREC
            assert cl.state.inflight.receive_quota == quota0 - 1
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBREL, qos=1),
                        protocol_version=4,
                        packet_id=9,
                    )
                )
            )
            await w.drain()
            comp = await read_wire_packet(r)
            assert comp.fixed_header.type == PUBCOMP
            assert cl.state.inflight.receive_quota == quota0
            await h.shutdown()

        run(scenario())


class TestTakeoverEdges:
    def test_clean_takeover_discards_session(self):
        async def scenario():
            h = Harness()
            r1, w1, _ = await h.connect("td", clean=False)
            w1.write(sub_packet(1, [Subscription(filter="t/d", qos=1)]))
            await w1.drain()
            await read_wire_packet(r1)
            # reconnect CLEAN: subscriptions must be discarded
            r2, w2, _ = await h.connect("td", clean=True)
            await asyncio.sleep(0.05)
            subs = h.server.topics.subscribers("t/d")
            assert "td" not in subs.subscriptions
            await h.shutdown()

        run(scenario())

    def test_dirty_takeover_keeps_subscriptions_and_session_present(self):
        async def scenario():
            h = Harness()
            r1, w1, _ = await h.connect("tk", clean=False)
            w1.write(sub_packet(1, [Subscription(filter="t/k", qos=1)]))
            await w1.drain()
            await read_wire_packet(r1)
            reader, writer, task = await h.attach()
            writer.write(connect_packet("tk", 4, clean=False))
            await writer.drain()
            raw = await asyncio.wait_for(reader.readexactly(4), TIMEOUT)
            assert raw == bytes.fromhex("20020100")  # session present = 1
            subs = h.server.topics.subscribers("t/k")
            assert "tk" in subs.subscriptions
            await h.shutdown()

        run(scenario())

    def test_takeover_of_disconnected_session(self):
        async def scenario():
            h = Harness()
            r1, w1, t1 = await h.connect("gone", clean=False)
            w1.close()  # abnormal drop; session survives (non-clean)
            await asyncio.sleep(0.05)
            r2, w2, _ = await h.connect("gone", clean=False)
            await asyncio.sleep(0.05)
            cl = h.server.clients.get("gone")
            assert cl is not None and not cl.closed
            await h.shutdown()

        run(scenario())


class TestRetainEdges:
    def test_empty_payload_deletes_retained(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("ret")
            w.write(pub_packet("r/1", b"keep", retain=True))
            await w.drain()
            await asyncio.sleep(0.05)
            assert h.server.topics.retained.get("r/1") is not None
            assert h.server.info.retained == 1
            w.write(pub_packet("r/1", b"", retain=True))  # delete [MQTT-3.3.1-6]
            await w.drain()
            await asyncio.sleep(0.05)
            assert h.server.topics.retained.get("r/1") is None
            assert h.server.info.retained == 0
            await h.shutdown()

        run(scenario())

    def test_retain_available_zero_ignores_retain(self):
        async def scenario():
            opts = Options()
            opts.capabilities.retain_available = 0
            h = Harness(opts)
            r, w, _ = await h.connect("noret")
            w.write(pub_packet("r/2", b"x", retain=True))
            await w.drain()
            await asyncio.sleep(0.05)
            assert h.server.topics.retained.get("r/2") is None
            await h.shutdown()

        run(scenario())

    def test_retain_handling_1_skips_existing_subscription(self):
        async def scenario():
            h = Harness()
            pub_r, pub_w, _ = await h.connect("rp")
            pub_w.write(pub_packet("rh/1", b"x", retain=True))
            await pub_w.drain()
            r, w, _ = await h.connect("rh1", version=5)
            # retain_handling=1: send retained only if subscription is NEW
            w.write(
                sub_packet(
                    1,
                    [Subscription(filter="rh/1", qos=0, retain_handling=1)],
                    version=5,
                )
            )
            await w.drain()
            await read_wire_packet(r, 5)  # suback
            pk = await read_wire_packet(r, 5)
            assert pk.fixed_header.type == PUBLISH  # new sub -> retained sent
            # resubscribe: filter exists -> retained NOT sent again
            w.write(
                sub_packet(
                    2,
                    [Subscription(filter="rh/1", qos=0, retain_handling=1)],
                    version=5,
                )
            )
            await w.drain()
            ack2 = await read_wire_packet(r, 5)
            assert ack2.fixed_header.type == SUBACK
            w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await w.drain()
            nxt = await read_wire_packet(r, 5)
            assert nxt.fixed_header.type == PINGRESP  # no second retained
            await h.shutdown()

        run(scenario())

    def test_retain_handling_2_never_sends_retained(self):
        async def scenario():
            h = Harness()
            pub_r, pub_w, _ = await h.connect("rp2")
            pub_w.write(pub_packet("rh/2", b"x", retain=True))
            await pub_w.drain()
            r, w, _ = await h.connect("rh2c", version=5)
            w.write(
                sub_packet(
                    1,
                    [Subscription(filter="rh/2", qos=0, retain_handling=2)],
                    version=5,
                )
            )
            await w.drain()
            await read_wire_packet(r, 5)  # suback
            w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await w.drain()
            nxt = await read_wire_packet(r, 5)
            assert nxt.fixed_header.type == PINGRESP  # nothing retained sent
            await h.shutdown()

        run(scenario())

    def test_retain_as_published_preserves_flag(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("rap", version=5)
            w.write(
                sub_packet(
                    1,
                    [Subscription(filter="rap/#", qos=0, retain_as_published=True)],
                    version=5,
                )
            )
            await w.drain()
            await read_wire_packet(r, 5)
            pub_r, pub_w, _ = await h.connect("rapsrc")
            pub_w.write(pub_packet("rap/t", b"x", retain=True))
            await pub_w.drain()
            pk = await read_wire_packet(r, 5)
            assert pk.fixed_header.retain is True  # RAP keeps the flag
            await h.shutdown()

        run(scenario())

    def test_retained_qos_downgraded_to_subscription(self):
        async def scenario():
            h = Harness()
            pub_r, pub_w, _ = await h.connect("rqsrc")
            pub_w.write(pub_packet("rq/1", b"x", qos=1, pid=4, retain=True))
            await pub_w.drain()
            await read_wire_packet(pub_r)
            r, w, _ = await h.connect("rqsub")
            w.write(sub_packet(1, [Subscription(filter="rq/1", qos=0)]))
            await w.drain()
            await read_wire_packet(r)
            pk = await read_wire_packet(r)
            assert pk.fixed_header.type == PUBLISH
            assert pk.fixed_header.qos == 0  # min(sub 0, msg 1)
            await h.shutdown()

        run(scenario())

    def test_sys_topics_not_matched_by_top_level_wildcard(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("wild")
            w.write(sub_packet(1, [Subscription(filter="#", qos=0)]))
            await w.drain()
            await read_wire_packet(r)
            h.server.publish_sys_topics()
            w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await w.drain()
            pk = await read_wire_packet(r)
            assert pk.fixed_header.type == PINGRESP  # no $SYS leaked to '#'
            await h.shutdown()

        run(scenario())

    def test_sys_topics_delivered_to_explicit_subscriber(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("sysw")
            w.write(sub_packet(1, [Subscription(filter="$SYS/broker/uptime", qos=0)]))
            await w.drain()
            await read_wire_packet(r)
            h.server.publish_sys_topics()
            pk = await read_wire_packet(r)
            assert pk.topic_name == "$SYS/broker/uptime"
            await h.shutdown()

        run(scenario())


class TestPublishEdges:
    def test_publish_to_sys_topic_is_dropped(self):
        async def scenario():
            h = Harness()
            spy_r, spy_w, _ = await h.connect("spy")
            spy_w.write(sub_packet(1, [Subscription(filter="$SYS/#", qos=0)]))
            await spy_w.drain()
            await read_wire_packet(spy_r)
            r, w, _ = await h.connect("evil")
            w.write(pub_packet("$SYS/broker/uptime", b"hax"))
            w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await w.drain()
            await read_wire_packet(r)  # pingresp: publisher not disconnected
            spy_w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await spy_w.drain()
            pk = await read_wire_packet(spy_r)
            assert pk.fixed_header.type == PINGRESP  # $SYS publish dropped
            await h.shutdown()

        run(scenario())

    def test_inbound_alias_above_maximum_disconnects(self):
        async def scenario():
            opts = Options()
            opts.capabilities.topic_alias_maximum = 2
            h = Harness(opts)
            r, w, _ = await h.connect("alias5", version=5)
            pk = Packet(
                fixed_header=FixedHeader(type=PUBLISH),
                protocol_version=5,
                topic_name="a/t",
                payload=b"x",
            )
            pk.properties.topic_alias = 9  # above server maximum
            pk.properties.topic_alias_flag = True
            w.write(encode_packet(pk))
            await w.drain()
            out = await read_wire_packet(r, 5)
            assert out.fixed_header.type == DISCONNECT
            assert out.reason_code == codes.ERR_TOPIC_ALIAS_INVALID.code
            await h.shutdown()

        run(scenario())

    def test_v3_acl_deny_publish_disconnects(self):
        async def scenario():
            h = Harness(allow=False)

            class WriteDeny(Hook):
                def id(self):
                    return "write-deny"

                def provides(self, b):
                    return b in (ON_CONNECT_AUTHENTICATE, ON_ACL_CHECK)

                def on_connect_authenticate(self, cl, pk):
                    return True

                def on_acl_check(self, cl, topic, write):
                    return not write  # deny writes only

            h.server.add_hook(WriteDeny())
            r, w, task = await h.connect("v3deny")
            w.write(pub_packet("x/y", b"no", qos=1, pid=3))
            await w.drain()
            await asyncio.wait_for(task, TIMEOUT)  # v3: connection dropped
            await h.shutdown()

        run(scenario())

    def test_v5_acl_deny_qos1_acks_not_authorized(self):
        async def scenario():
            h = Harness(allow=False)

            class WriteDeny(Hook):
                def id(self):
                    return "write-deny"

                def provides(self, b):
                    return b in (ON_CONNECT_AUTHENTICATE, ON_ACL_CHECK)

                def on_connect_authenticate(self, cl, pk):
                    return True

                def on_acl_check(self, cl, topic, write):
                    return not write

            h.server.add_hook(WriteDeny())
            r, w, _ = await h.connect("v5deny", version=5)
            w.write(pub_packet("x/y", b"no", qos=1, pid=3, version=5))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.fixed_header.type == PUBACK
            assert ack.reason_code == codes.ERR_NOT_AUTHORIZED.code
            await h.shutdown()

        run(scenario())

    def test_qos2_duplicate_publish_acks_in_use(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("dup2", version=5)
            w.write(pub_packet("d/2", b"x", qos=2, pid=8, version=5))
            await w.drain()
            rec1 = await read_wire_packet(r, 5)
            assert rec1.fixed_header.type == PUBREC
            w.write(pub_packet("d/2", b"x", qos=2, pid=8, version=5))
            await w.drain()
            rec2 = await read_wire_packet(r, 5)
            assert rec2.fixed_header.type == PUBREC
            assert rec2.reason_code == codes.ERR_PACKET_IDENTIFIER_IN_USE.code
            await h.shutdown()

        run(scenario())

    def test_message_expiry_interval_rewritten_on_delivery(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("exp5", version=5)
            w.write(sub_packet(1, [Subscription(filter="ex/#", qos=0)], version=5))
            await w.drain()
            await read_wire_packet(r, 5)
            pub_r, pub_w, _ = await h.connect("expsrc", version=5)
            pk = Packet(
                fixed_header=FixedHeader(type=PUBLISH),
                protocol_version=5,
                topic_name="ex/1",
                payload=b"x",
            )
            pk.properties.message_expiry_interval = 300
            pub_w.write(encode_packet(pk))
            await pub_w.drain()
            out = await read_wire_packet(r, 5)
            # [MQTT-3.3.2-6]: remaining lifetime, <= original interval
            assert 0 < out.properties.message_expiry_interval <= 300
            await h.shutdown()

        run(scenario())


class TestDisconnectAndSessionEdges:
    def test_disconnect_with_will_message_sends_lwt(self):
        async def scenario():
            h = Harness()
            sub_r, sub_w, _ = await h.connect("lwtwatch")
            sub_w.write(sub_packet(1, [Subscription(filter="will/#", qos=0)]))
            await sub_w.drain()
            await read_wire_packet(sub_r)
            r, w, task = await h.connect(
                "willer", version=5, will=("will/us", b"bye")
            )
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=DISCONNECT),
                        protocol_version=5,
                        reason_code=0x04,  # disconnect WITH will message
                    )
                )
            )
            await w.drain()
            pk = await read_wire_packet(sub_r)
            assert pk.topic_name == "will/us"
            assert bytes(pk.payload) == b"bye"
            await h.shutdown()

        run(scenario())

    def test_disconnect_zero_to_nonzero_expiry_violation(self):
        async def scenario():
            h = Harness()
            r, w, task = await h.connect("zexp", version=5)
            pk = Packet(
                fixed_header=FixedHeader(type=DISCONNECT),
                protocol_version=5,
                reason_code=0,
            )
            pk.properties.session_expiry_interval = 60
            pk.properties.session_expiry_interval_flag = True
            w.write(encode_packet(pk))
            await w.drain()
            out = await read_wire_packet(r, 5)
            assert out.fixed_header.type == DISCONNECT  # [MQTT-3.1.2-23]
            assert out.reason_code == codes.ERR_PROTOCOL_VIOLATION_ZERO_NON_ZERO_EXPIRY.code
            await h.shutdown()

        run(scenario())

    def test_session_expiry_clamped_to_server_maximum(self):
        async def scenario():
            opts = Options()
            opts.capabilities.maximum_session_expiry_interval = 100
            h = Harness(opts)
            reader, writer, task = await h.attach()
            pk = Packet(
                fixed_header=FixedHeader(type=CONNECT),
                protocol_version=5,
                connect=ConnectParams(
                    protocol_name=b"MQTT",
                    clean=True,
                    keepalive=30,
                    client_identifier="clamp",
                ),
            )
            pk.properties.session_expiry_interval = 99999
            pk.properties.session_expiry_interval_flag = True
            writer.write(encode_packet(pk))
            await writer.drain()
            ack = await read_wire_packet(reader, 5)
            assert ack.fixed_header.type == CONNACK
            cl = h.server.clients.get("clamp")
            assert cl.properties.props.session_expiry_interval == 100
            await h.shutdown()

        run(scenario())

    def test_auth_packet_dispatches_hook(self):
        async def scenario():
            h = Harness()
            seen = []

            class AuthHook(Hook):
                def id(self):
                    return "auth-watch"

                def provides(self, b):
                    from mqtt_tpu.hooks import ON_AUTH_PACKET

                    return b == ON_AUTH_PACKET

                def on_auth_packet(self, cl, pk):
                    seen.append(pk.reason_code)
                    return pk

            h.server.add_hook(AuthHook())
            r, w, _ = await h.connect("auth5", version=5)
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=AUTH),
                        protocol_version=5,
                        reason_code=0x19,  # re-authenticate
                    )
                )
            )
            w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await w.drain()
            pk = await read_wire_packet(r, 5)
            assert pk.fixed_header.type == PINGRESP
            assert seen == [0x19]
            await h.shutdown()

        run(scenario())

    def test_unsubscribe_clears_shared_group_membership(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("shm", version=5)
            w.write(
                sub_packet(
                    1, [Subscription(filter="$share/g1/s/t", qos=0)], version=5
                )
            )
            await w.drain()
            await read_wire_packet(r, 5)
            assert h.server.topics.subscribers("s/t").shared
            w.write(unsub_packet(2, ["$share/g1/s/t"], version=5))
            await w.drain()
            await read_wire_packet(r, 5)
            assert not h.server.topics.subscribers("s/t").shared
            await h.shutdown()

        run(scenario())

    def test_shared_subscription_delivers_to_one_member(self):
        async def scenario():
            h = Harness()
            members = []
            for i in range(3):
                r, w, _ = await h.connect(f"gm{i}")
                w.write(
                    sub_packet(1, [Subscription(filter="$share/gg/x/y", qos=0)])
                )
                await w.drain()
                await read_wire_packet(r)
                members.append((r, w))
            pub_r, pub_w, _ = await h.connect("gpub")
            pub_w.write(pub_packet("x/y", b"once"))
            await pub_w.drain()
            await asyncio.sleep(0.1)
            got = 0
            for r, w in members:
                w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
                await w.drain()
                pk = await read_wire_packet(r)
                if pk.fixed_header.type == PUBLISH:
                    got += 1
                    await read_wire_packet(r)  # trailing pingresp
            assert got == 1  # exactly one group member receives it
            await h.shutdown()

        run(scenario())


class TestReferenceScenarioParity:
    """Edge scenarios ported from the reference suite that had no analog
    here yet (server_test.go: ZeroByteUsername, ServerKeepalive,
    ConnackFailureReason, AuthInvalidReason, PubrecInvalidReason,
    PubrelBadReason, SendLWTRetain, OnPublishAckErrorContinue,
    SubscribeWithRetain[DifferentFilter], BadFixedHeader)."""

    def test_zero_byte_username_is_valid(self):
        # server_test.go TestServerEstablishConnectionZeroByteUsernameIsValid
        async def scenario():
            h = Harness()
            reader, writer, task = await h.attach()
            cp = ConnectParams(
                protocol_name=b"MQTT",
                clean=True,
                keepalive=30,
                client_identifier="zbu",
                username_flag=True,
                username=b"",
            )
            writer.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=CONNECT),
                        protocol_version=5,
                        connect=cp,
                    )
                )
            )
            await writer.drain()
            ack = await read_wire_packet(reader, 5)
            assert ack.fixed_header.type == CONNACK
            assert ack.reason_code == 0  # [MQTT-3.1.3-11]
            await h.shutdown()

        run(scenario())

    def test_connack_carries_server_keepalive(self):
        # server_test.go TestServerSendConnackWithServerKeepalive
        async def scenario():
            h = Harness()

            class KeepaliveSetter(Hook):
                def id(self):
                    return "ka-set"

                def provides(self, b):
                    return b == ON_CONNECT

                def on_connect(self, cl, pk):
                    cl.state.server_keepalive = True

            h.server.add_hook(KeepaliveSetter())
            reader, writer, task = await h.attach()
            writer.write(connect_packet("kasrv", 5, keepalive=30))
            await writer.drain()
            ack = await read_wire_packet(reader, 5)
            assert ack.fixed_header.type == CONNACK
            assert ack.properties.server_keep_alive_flag  # [MQTT-3.1.2-21]
            assert ack.properties.server_keep_alive == 30
            await h.shutdown()

        run(scenario())

    def test_connack_failure_carries_reason_string(self):
        # server_test.go TestServerSendConnackFailureReason
        async def scenario():
            h = Harness(allow=False)  # default deny-all
            reader, writer, task = await h.attach()
            writer.write(connect_packet("noway", 5))
            await writer.drain()
            ack = await read_wire_packet(reader, 5)
            assert ack.fixed_header.type == CONNACK
            # connect-time auth failure maps to bad-username-or-password
            # (server.go:552 validateConnect)
            assert ack.reason_code == codes.ERR_BAD_USERNAME_OR_PASSWORD.code
            assert (
                ack.properties.reason_string
                == codes.ERR_BAD_USERNAME_OR_PASSWORD.reason
            )
            assert ack.session_present is False  # [MQTT-3.2.2-6]
            await h.shutdown()

        run(scenario())

    def test_auth_invalid_reason_code_disconnects(self):
        # server_test.go TestServerProcessPacketAuthInvalidReason
        async def scenario():
            h = Harness()
            r, w, task = await h.connect("badauth", version=5)
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=AUTH),
                        protocol_version=5,
                        reason_code=0x99,  # not one of 0x00/0x18/0x19
                    )
                )
            )
            await w.drain()
            out = await read_wire_packet(r, 5)
            assert out.fixed_header.type == DISCONNECT  # [MQTT-3.15.2-1]
            assert (
                out.reason_code
                == codes.ERR_PROTOCOL_VIOLATION_INVALID_REASON.code
            )
            await h.shutdown()

        run(scenario())

    def test_pubrec_invalid_reason_drops_outbound_qos2(self):
        # server_test.go TestServerProcessPacketPubrecInvalidReason
        async def scenario():
            h = Harness()
            dropped = []

            class DropWatch(Hook):
                def id(self):
                    return "drop-watch"

                def provides(self, b):
                    return b == ON_QOS_DROPPED

                def on_qos_dropped(self, cl, pk):
                    dropped.append(pk.packet_id)

            h.server.add_hook(DropWatch())
            r, w, _ = await h.connect("q2sub", version=5)
            w.write(sub_packet(1, [Subscription(filter="o/q2", qos=2)], version=5))
            await w.drain()
            await read_wire_packet(r, 5)
            pr, pw, _ = await h.connect("q2pub", version=5)
            pw.write(pub_packet("o/q2", b"x", qos=2, pid=5, version=5))
            await pw.drain()
            out = await read_wire_packet(r, 5)  # server->sub PUBLISH qos2
            assert out.fixed_header.type == PUBLISH
            assert out.fixed_header.qos == 2
            cl = h.server.clients.get("q2sub")
            assert len(cl.state.inflight) == 1
            # reply PUBREC with an error reason: flow must be abandoned
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBREC),
                        protocol_version=5,
                        packet_id=out.packet_id,
                        reason_code=0x80,
                    )
                )
            )
            w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await w.drain()
            nxt = await read_wire_packet(r, 5)
            assert nxt.fixed_header.type == PINGRESP  # no PUBREL was sent
            assert len(cl.state.inflight) == 0
            assert dropped == [out.packet_id]
            await h.shutdown()

        run(scenario())

    def test_pubrel_bad_reason_drops_inbound_qos2(self):
        # server_test.go TestServerProcessPacketPubrelBadReason
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("relbad", version=5)
            w.write(pub_packet("i/q2", b"x", qos=2, pid=9, version=5))
            await w.drain()
            rec = await read_wire_packet(r, 5)
            assert rec.fixed_header.type == PUBREC
            cl = h.server.clients.get("relbad")
            assert len(cl.state.inflight) == 1
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=PUBREL, qos=1),
                        protocol_version=5,
                        packet_id=9,
                        reason_code=0x83,  # error-class: MQTT5 4.13.2 ¶2
                    )
                )
            )
            w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await w.drain()
            nxt = await read_wire_packet(r, 5)
            assert nxt.fixed_header.type == PINGRESP  # no PUBCOMP was sent
            assert len(cl.state.inflight) == 0
            await h.shutdown()

        run(scenario())

    def test_lwt_retain_flag_stores_retained_message(self):
        # server_test.go TestServerSendLWTRetain
        async def scenario():
            h = Harness()
            r, w, task = await h.connect(
                "willret", version=5, will=("will/ret", b"gone", 1, True)
            )
            w.close()  # abnormal disconnect fires the will
            await asyncio.wait_for(task, TIMEOUT)
            msgs = h.server.topics.messages("will/ret")
            assert len(msgs) == 1  # [MQTT-3.1.2-14/-15]
            assert bytes(msgs[0].payload) == b"gone"
            assert msgs[0].fixed_header.retain
            await h.shutdown()

        run(scenario())

    def test_on_publish_error_v4_continues_delivery(self):
        # server_test.go TestServerProcessPublishOnPublishAckErrorContinue
        async def scenario():
            h = Harness()

            class Failer(Hook):
                def id(self):
                    return "pub-fail"

                def provides(self, b):
                    return b == ON_PUBLISH

                def on_publish(self, cl, pk):
                    raise codes.ERR_UNSPECIFIED_ERROR()

            h.server.add_hook(Failer())
            sr, sw, _ = await h.connect("v4watch")
            sw.write(sub_packet(1, [Subscription(filter="c/#", qos=0)]))
            await sw.drain()
            await read_wire_packet(sr)
            r, w, _ = await h.connect("v4pub")
            w.write(pub_packet("c/1", b"still"))
            await w.drain()
            out = await read_wire_packet(sr)  # v3: error falls through
            assert out.fixed_header.type == PUBLISH
            assert bytes(out.payload) == b"still"
            await h.shutdown()

        run(scenario())

    def test_on_publish_error_v5_qos1_acks_error_no_delivery(self):
        # server_test.go TestServerProcessPublishOnPublishAckErrorRWError
        async def scenario():
            h = Harness()

            class Failer(Hook):
                def id(self):
                    return "pub-fail5"

                def provides(self, b):
                    return b == ON_PUBLISH

                def on_publish(self, cl, pk):
                    raise codes.ERR_UNSPECIFIED_ERROR()

            h.server.add_hook(Failer())
            sr, sw, _ = await h.connect("v5watch", version=5)
            sw.write(sub_packet(1, [Subscription(filter="c5/#", qos=0)], version=5))
            await sw.drain()
            await read_wire_packet(sr, 5)
            r, w, _ = await h.connect("v5pub", version=5)
            w.write(pub_packet("c5/1", b"no", qos=1, pid=4, version=5))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.fixed_header.type == PUBACK
            assert ack.reason_code == codes.ERR_UNSPECIFIED_ERROR.code
            sw.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await sw.drain()
            nxt = await read_wire_packet(sr, 5)
            assert nxt.fixed_header.type == PINGRESP  # nothing was delivered
            await h.shutdown()

        run(scenario())

    def test_inline_subscribe_receives_retained(self):
        # server_test.go TestServerSubscribeWithRetain
        async def scenario():
            h = Harness()
            h.server.publish("ret/in", b"kept", True, 0)
            got = []
            h.server.subscribe(
                "ret/#", 7, lambda cl, sub, pk: got.append(bytes(pk.payload))
            )
            assert got == [b"kept"]  # [MQTT-3.8.4-4]
            await h.shutdown()

        run(scenario())

    def test_inline_subscribe_different_filter_gets_no_retained(self):
        # server_test.go TestServerSubscribeWithRetainDifferentFilter
        async def scenario():
            h = Harness()
            h.server.publish("ret/in2", b"kept", True, 0)
            got = []
            h.server.subscribe(
                "other/#", 7, lambda cl, sub, pk: got.append(bytes(pk.payload))
            )
            assert got == []
            await h.shutdown()

        run(scenario())

    def test_bad_connect_fixed_header_closes_connection(self):
        # server_test.go TestServerReadConnectionPacketBadFixedHeader
        async def scenario():
            h = Harness()
            reader, writer, task = await h.attach()
            writer.write(bytes([0x13, 0x00]))  # CONNECT with reserved flags set
            await writer.drain()
            await asyncio.wait_for(task, TIMEOUT)
            data = await asyncio.wait_for(reader.read(16), TIMEOUT)
            assert data == b""  # dropped before any CONNACK
            await h.shutdown()

        run(scenario())


class TestFastPublishPassthrough:
    """The QoS0 v4 frame passthrough must be byte- and counter-identical
    to the decode path, and must defer every case it cannot prove."""

    async def _roundtrip(self, h, extra_hook=None):
        if extra_hook is not None:
            h.server.add_hook(extra_hook)
        sr, sw, _ = await h.connect("fsub")
        sw.write(sub_packet(1, [Subscription(filter="fp/+", qos=0)]))
        await sw.drain()
        await read_wire_packet(sr)
        pr, pw, _ = await h.connect("fpub")
        frames = []
        for i in range(5):
            pw.write(pub_packet(f"fp/{i}", f"payload-{i}".encode()))
        await pw.drain()
        for i in range(5):
            pk = await read_wire_packet(sr)
            frames.append((pk.topic_name, bytes(pk.payload), pk.fixed_header.retain))
        stats = (h.server.info.messages_received, h.server.info.messages_sent)
        return frames, stats

    def test_fast_and_slow_paths_deliver_identical_bytes_and_counters(self):
        async def scenario():
            fast_h = Harness()
            fast_frames, fast_stats = await self._roundtrip(fast_h)
            await fast_h.shutdown()

            class SlowForcer(Hook):
                """Providing ON_PUBLISH disables the passthrough."""

                def id(self):
                    return "slow-forcer"

                def provides(self, b):
                    return b == ON_PUBLISH

                def on_publish(self, cl, pk):
                    return pk

            slow_h = Harness()
            slow_frames, slow_stats = await self._roundtrip(slow_h, SlowForcer())
            await slow_h.shutdown()

            assert fast_frames == slow_frames
            assert fast_stats == slow_stats
            await asyncio.sleep(0)

        run(scenario())

    def test_mixed_version_targets_fast_v4_slow_v5(self):
        async def scenario():
            h = Harness()
            r4, w4, _ = await h.connect("v4t")
            w4.write(sub_packet(1, [Subscription(filter="mx/#", qos=0)]))
            await w4.drain()
            await read_wire_packet(r4)
            r5, w5, _ = await h.connect("v5t", version=5)
            w5.write(sub_packet(1, [Subscription(filter="mx/#", qos=0)], version=5))
            await w5.drain()
            await read_wire_packet(r5, 5)
            pr, pw, _ = await h.connect("mixpub")
            pw.write(pub_packet("mx/a", b"both"))
            await pw.drain()
            pk4 = await read_wire_packet(r4)
            pk5 = await read_wire_packet(r5, 5)
            assert bytes(pk4.payload) == bytes(pk5.payload) == b"both"
            assert pk4.topic_name == pk5.topic_name == "mx/a"
            await h.shutdown()

        run(scenario())

    def test_no_local_suppressed_on_fast_path(self):
        async def scenario():
            h = Harness()
            # a v5 session subscribes with no_local, then is taken over by
            # a v4 connection (subscriptions inherited): the v4 publisher
            # IS eligible for the passthrough, so the no_local origin
            # check must fire inside the fast dispatcher itself
            r5, w5, _ = await h.connect("selfpub", version=5, clean=False)
            w5.write(
                sub_packet(
                    1,
                    [Subscription(filter="nl/#", qos=0, no_local=True)],
                    version=5,
                )
            )
            await w5.drain()
            await read_wire_packet(r5, 5)
            r, w, _ = await h.connect("selfpub", version=4, clean=False)
            assert h.server.topics.subscribers("nl/x").subscriptions  # inherited
            w.write(pub_packet("nl/x", b"echo"))
            w.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await w.drain()
            nxt = await read_wire_packet(r)
            assert nxt.fixed_header.type == PINGRESP  # no echo delivered
            await h.shutdown()

        run(scenario())

    def test_acl_denied_fast_publish_drops_silently(self):
        async def scenario():
            h = Harness(allow=False)  # OR-auth: AllowHook would override

            class DenyPub(Hook):
                def id(self):
                    return "deny-pub"

                def provides(self, b):
                    return b in (ON_CONNECT_AUTHENTICATE, ON_ACL_CHECK)

                def on_connect_authenticate(self, cl, pk):
                    return True

                def on_acl_check(self, cl, topic, write):
                    return not (write and topic.startswith("secret/"))

            h.server.add_hook(DenyPub())
            sr, sw, _ = await h.connect("aclsub")
            sw.write(sub_packet(1, [Subscription(filter="#", qos=0)]))
            await sw.drain()
            await read_wire_packet(sr)
            pr, pw, _ = await h.connect("aclpub")
            pw.write(pub_packet("secret/x", b"no"))
            pw.write(pub_packet("open/x", b"yes"))
            pw.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await pw.drain()
            assert (await read_wire_packet(pr)).fixed_header.type == PINGRESP
            out = await read_wire_packet(sr)
            assert out.topic_name == "open/x"  # denied topic never arrived
            await h.shutdown()

        run(scenario())

    def test_wildcard_and_dollar_topics_defer_to_slow_path(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("oddpub")
            # publishing to a wildcard topic surfaces through the decode
            # path (the passthrough must defer it), which for v4 drops
            # the connection without a reply
            w.write(pub_packet("bad/+/topic", b"x"))
            await w.drain()
            data = await asyncio.wait_for(r.read(16), TIMEOUT)
            assert data == b""  # connection closed by the broker
            await h.shutdown()

        run(scenario())

    def test_padded_varint_publish_defers_to_decode_path(self):
        """A non-minimal remaining-length varint must NOT be relayed
        verbatim: the decode path re-encodes the frame minimally."""

        async def scenario():
            h = Harness()
            sr, sw, _ = await h.connect("vsub")
            sw.write(sub_packet(1, [Subscription(filter="pv/#", qos=0)]))
            await sw.drain()
            await read_wire_packet(sr)
            pr, pw, _ = await h.connect("vpub")
            body = b"\x00\x04pv/a" + b"x"
            pw.write(bytes([0x30, 0x80 | len(body), 0x00]) + body)
            await pw.drain()
            raw_first = await asyncio.wait_for(sr.readexactly(2), TIMEOUT)
            assert raw_first[1] == len(body)  # minimal single-byte varint
            rest = await asyncio.wait_for(sr.readexactly(raw_first[1]), TIMEOUT)
            pk = decode_packet(bytes(raw_first + rest), 4)
            assert pk.topic_name == "pv/a" and bytes(pk.payload) == b"x"
            await h.shutdown()

        run(scenario())


class TestMoreReferenceScenarios:
    def test_on_publish_reject_packet_silently_ignores(self):
        # server_test.go TestServerProcessPublishOnMessageRecvRejected:
        # ErrRejectPacket from on_publish drops the message with no error
        async def scenario():
            h = Harness()

            class Rejecter(Hook):
                def id(self):
                    return "rejector"

                def provides(self, b):
                    return b == ON_PUBLISH

                def on_publish(self, cl, pk):
                    if pk.topic_name.startswith("reject/"):
                        raise codes.ERR_REJECT_PACKET()
                    return pk

            h.server.add_hook(Rejecter())
            sr, sw, _ = await h.connect("rsub")
            sw.write(sub_packet(1, [Subscription(filter="#", qos=0)]))
            await sw.drain()
            await read_wire_packet(sr)
            pr, pw, _ = await h.connect("rpub")
            pw.write(pub_packet("reject/x", b"no"))
            pw.write(pub_packet("pass/x", b"yes"))
            pw.write(encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ))))
            await pw.drain()
            # publisher not disconnected (silent drop)
            assert (await read_wire_packet(pr)).fixed_header.type == PINGRESP
            out = await read_wire_packet(sr)
            assert out.topic_name == "pass/x"  # rejected one never delivered
            await h.shutdown()

        run(scenario())

    def test_server_close_fires_on_stopped_and_sets_done(self):
        # server_test.go TestServerClose
        async def scenario():
            h = Harness()
            stopped = []

            class StopWatch(Hook):
                def id(self):
                    return "stop-watch"

                def provides(self, b):
                    from mqtt_tpu.hooks import ON_STOPPED

                    return b == ON_STOPPED

                def on_stopped(self):
                    stopped.append(True)

            h.server.add_hook(StopWatch())
            r, w, task = await h.connect("closer")
            await h.server.close()
            assert stopped == [True]
            assert h.server.done.is_set()
            await h.shutdown()

        run(scenario())

    def test_sys_info_tick_republishes_uptime(self):
        # server_test.go TestServerEventLoop analog: the $SYS publication
        # refreshes uptime and fires the OnSysInfoTick hook
        async def scenario():
            h = Harness()
            ticks = []

            class TickWatch(Hook):
                def id(self):
                    return "tick-watch"

                def provides(self, b):
                    from mqtt_tpu.hooks import ON_SYS_INFO_TICK

                    return b == ON_SYS_INFO_TICK

                def on_sys_info_tick(self, info):
                    ticks.append(info.uptime)

            h.server.add_hook(TickWatch())
            # pretend 5s of uptime: rewind the MONOTONIC anchor (uptime is
            # clock-step immune now — rewinding wall-clock `started` would
            # not move it, by design; see system.Info.uptime_now)
            h.server.info._mono_started -= 5
            h.server.publish_sys_topics()
            assert ticks and ticks[0] >= 5
            msgs = {p.topic_name: p for p in h.server.topics.messages("$SYS/#")}
            assert int(bytes(msgs["$SYS/broker/uptime"].payload)) >= 5
            await h.shutdown()

        run(scenario())


class TestProtocolEdges:
    def test_subscribe_without_filters_is_protocol_violation(self):
        # server_test.go TestServerProcessPacketSubscribeInvalid
        async def scenario():
            h = Harness()
            r, w, task = await h.connect("nofilt", version=5)
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
                        protocol_version=5,
                        packet_id=3,
                        filters=[],
                    )
                )
            )
            await w.drain()
            out = await read_wire_packet(r, 5)
            assert out.fixed_header.type == DISCONNECT  # [MQTT-3.10.3-2]
            await h.shutdown()

        run(scenario())

    def test_unsubscribe_without_filters_is_protocol_violation(self):
        # server_test.go TestServerProcessPacketUnsubscribeInvalid
        async def scenario():
            h = Harness()
            r, w, task = await h.connect("nounfilt", version=5)
            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=UNSUBSCRIBE, qos=1),
                        protocol_version=5,
                        packet_id=4,
                        filters=[],
                    )
                )
            )
            await w.drain()
            out = await read_wire_packet(r, 5)
            assert out.fixed_header.type == DISCONNECT
            await h.shutdown()

        run(scenario())

    def test_unsubscribe_nonexistent_filter_acks_no_subscription_existed(self):
        async def scenario():
            h = Harness()
            r, w, _ = await h.connect("unx", version=5)
            w.write(unsub_packet(5, ["never/was"], version=5))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.fixed_header.type == UNSUBACK
            assert ack.reason_codes[0] == codes.CODE_NO_SUBSCRIPTION_EXISTED.code
            await h.shutdown()

        run(scenario())

    def test_connack_advertises_reduced_maximum_qos(self):
        # SendConnack capability surface [MQTT-3.2.2-9]
        async def scenario():
            opts = Options(capabilities=Capabilities(maximum_qos=1))
            h = Harness(opts)
            reader, writer, task = await h.attach()
            writer.write(connect_packet("qcap", 5))
            await writer.drain()
            ack = await read_wire_packet(reader, 5)
            assert ack.fixed_header.type == CONNACK
            assert ack.properties.maximum_qos_flag
            assert ack.properties.maximum_qos == 1
            await h.shutdown()

        run(scenario())

    def test_inline_subscribe_invalid_filter_raises(self):
        from mqtt_tpu.packets import Code

        async def scenario():
            h = Harness()
            with pytest.raises(Code):
                h.server.subscribe("bad/#/deep", 1, lambda *a: None)
            with pytest.raises(Code):
                h.server.unsubscribe("bad/#/deep", 1)
            await h.shutdown()

        run(scenario())

    def test_serve_propagates_read_store_failure(self):
        # server_test.go TestServerServeReadStoreFailure
        async def scenario():
            h = Harness()

            class BadStore(Hook):
                def id(self):
                    return "bad-store"

                def provides(self, b):
                    from mqtt_tpu.hooks import STORED_CLIENTS

                    return b == STORED_CLIENTS

                def stored_clients(self):
                    raise RuntimeError("store corrupted")

            h.server.add_hook(BadStore())
            with pytest.raises(RuntimeError, match="store corrupted"):
                await h.server.serve()
            await h.shutdown()

        run(scenario())
