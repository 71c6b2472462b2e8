"""The ingest run against the per-frame path (server.ingest_run,
clients.read): the same byte stream fed to a connection whose run gate is
open and to one where a do-nothing hook providing ON_PACKET_READ keeps it
shut gives the same parked packets field for field, the same bytes
written back, the same counters, the same deliveries in the same order,
the same error and disconnect. CPU backend: equality and counts, never a
rate."""

import asyncio
import dataclasses

import pytest

from mqtt_tpu.hooks import (
    ON_ACL_CHECK,
    ON_CONNECT_AUTHENTICATE,
    ON_PACKET_PROCESSED,
    ON_PACKET_READ,
    ON_PUBLISH,
    Hook,
)
from mqtt_tpu.packets import (
    PUBACK,
    PUBLISH,
    FixedHeader,
    Packet,
    Properties,
    Subscription,
    UserProperty,
    encode_packet,
)

from tests.test_batch_completion import run_echo, staged_options, subscriber
from tests.test_server import Harness, pub_packet, run, sub_packet

NOW = 1_790_000_000.0  # both sides stamp ``created`` from one clock


class ReadsPackets(Hook):
    """Takes every packet as read and changes nothing: shuts the gate."""

    def id(self):
        return "reads-packets"

    def provides(self, b):
        return b == ON_PACKET_READ


class SeesPublishes(Hook):
    """A do-nothing ``on_publish``: added in mid-stream, it shuts the
    gate at the next run."""

    def id(self):
        return "sees-publishes"

    def provides(self, b):
        return b == ON_PUBLISH


class Door(Hook):
    """Authenticates everyone; the write ACL refuses ``deny`` topics and
    stops the client that publishes to a ``stop`` topic (the publish
    itself is let through); every ACL question and every
    ``on_packet_processed`` of a PUBLISH is written down."""

    def __init__(self):
        super().__init__()
        self.asked = []
        self.processed = []

    def id(self):
        return "door"

    def provides(self, b):
        return b in (ON_CONNECT_AUTHENTICATE, ON_ACL_CHECK, ON_PACKET_PROCESSED)

    def on_connect_authenticate(self, cl, pk):
        return True

    def on_acl_check(self, cl, topic, write):
        if write:
            self.asked.append(topic)
            if "stop" in topic:
                cl.stop()
        return "deny" not in topic

    def on_packet_processed(self, cl, pk, err):
        if pk.fixed_header.type == PUBLISH:
            self.processed.append(
                (bytes(pk.payload), pk.origin, pk.created, type(err).__name__)
            )


def raw_publish(first_byte, body):
    assert len(body) < 128
    return bytes((first_byte, len(body))) + body


def pubs(lo, hi, qos1_every=0, version=4):
    """Frames ``lo``..``hi``-1, QoS1 (packet id i + 1) every n-th."""
    return b"".join(
        pub_packet(f"t/{i}", b"m%d" % i, qos=1, pid=i + 1, version=version)
        if qos1_every and i % qos1_every == 0
        else pub_packet(f"t/{i}", b"m%d" % i, version=version)
        for i in range(lo, hi)
    )


def other(fixed_header, **fields):
    return encode_packet(
        Packet(fixed_header=fixed_header, protocol_version=4, **fields)
    )


V5_PROPS = Properties(
    user=[UserProperty("k", "v")], content_type="text/plain",
    message_expiry_interval=60,
)


@dataclasses.dataclass
class Case:
    """``writes``: the byte strings written to the publisher's socket,
    each settled before the next; ``between``: called with (server,
    client, index) after write ``index`` settled; ``prepare``: called
    with (server, client) before the first; ``took``: the publishes the
    open side's runs take."""

    writes: list
    version: int = 4
    options: dict = dataclasses.field(default_factory=dict)
    client_id: str = "pub"
    prepare: object = None
    between: object = None
    took: int = 0


def shed_every_third(srv, cl):
    calls = []

    def admit(client):
        calls.append(client.id)
        return len(calls) % 3 != 0

    srv.overload.admit = admit


def no_quota(srv, cl):
    cl.state.inflight.receive_quota = 0


def id_in_use(srv, cl):
    cl.state.inflight.set(
        Packet(fixed_header=FixedHeader(type=PUBACK), packet_id=9)
    )
    srv.info.inflight += 1


def stop_the_stage(srv, cl):
    srv._stage._stopping = True


def add_on_publish(srv, cl, index):
    if index == 0:
        srv.add_hook(SeesPublishes())


CASES = {
    "qos0_only": Case([pubs(0, 40)], took=40),
    "one_in_eight_qos1": Case(
        [pubs(0, 64, qos1_every=8)], took=64,
        options={"telemetry_sample": 4},
    ),
    "qos1_dup": Case(
        [pubs(0, 3) + raw_publish(0x3A, b"\x00\x03t/d\x00\x07dup") + pubs(4, 8)],
        took=8,
    ),
    "qos2_in_mid_scan": Case(
        [pubs(0, 5) + pub_packet("t/q2", b"two", qos=2, pid=77) + pubs(6, 12)],
        took=11,
    ),
    "retained_in_mid_scan": Case(
        [pubs(0, 5) + pub_packet("t/keep", b"kept", retain=True) + pubs(6, 12)],
        took=11,
    ),
    "puback_in_mid_scan": Case(
        [pubs(0, 5, qos1_every=2)
         + other(FixedHeader(type=PUBACK), packet_id=4242)
         + pubs(6, 12, qos1_every=2)],
        took=11,
    ),
    "subscribe_in_mid_scan": Case(
        [pubs(0, 5) + sub_packet(3, [Subscription(filter="t/3", qos=0)])
         + pubs(6, 12)],
        took=11,
    ),
    "wildcard_topic": Case([pubs(0, 6) + pub_packet("t/+", b"w") + pubs(7, 9)], took=6),
    "dollar_topics": Case(
        [pubs(0, 3) + pub_packet("$SYS/x", b"sys") + pub_packet("$other/x", b"o")
         + pubs(5, 8)],
        took=6,
    ),
    "bad_utf8": Case(
        [pubs(0, 6) + raw_publish(0x30, b"\x00\x03t/\xffbad") + pubs(7, 9)], took=6
    ),
    "nul_in_topic": Case(
        [pubs(0, 2) + raw_publish(0x30, b"\x00\x03t\x00xnul") + pubs(3, 5)], took=2
    ),
    "truncated_topic": Case(
        [pubs(0, 6) + raw_publish(0x30, b"\x00\x09t/") + pubs(7, 9)], took=6
    ),
    "empty_topic": Case([pubs(0, 2) + raw_publish(0x30, b"\x00\x00p")], took=2),
    "qos1_without_id": Case(
        [pubs(0, 4) + raw_publish(0x32, b"\x00\x03t/z\x00\x00p") + pubs(5, 7)], took=4
    ),
    "qos1_frame_ends_in_its_id": Case(
        [pubs(0, 4) + raw_publish(0x32, b"\x00\x03t/z\x01")], took=4
    ),
    "dup_at_qos0": Case(
        [pubs(0, 4) + raw_publish(0x38, b"\x00\x03t/zp") + pubs(5, 7)], took=4
    ),
    "acl_refuses_qos0": Case(
        [pubs(0, 4) + pub_packet("t/deny", b"no") + pubs(5, 9)], took=9
    ),
    "acl_refuses_qos1": Case(
        [pubs(0, 4, qos1_every=2) + pub_packet("t/deny", b"no", qos=1, pid=50)
         + pubs(5, 9)],
        took=5,
    ),
    "receive_quota_0": Case([pubs(0, 6)], prepare=no_quota, took=0),
    "packet_id_in_use": Case(
        [pubs(0, 4) + pub_packet("t/again", b"re", qos=1, pid=9) + pubs(5, 9)],
        prepare=id_in_use, took=8,
    ),
    "governor_sheds": Case(
        [pubs(0, 24, qos1_every=4)], prepare=shed_every_third, took=24
    ),
    "v5_without_properties": Case(
        [pubs(0, 12, qos1_every=4, version=5)], version=5, took=0
    ),
    "v5_with_properties": Case(
        [b"".join(
            pub_packet(f"t/{i}", b"m%d" % i, version=5, props=V5_PROPS)
            for i in range(12)
        )],
        version=5, took=0,
    ),
    "tenant_client": Case(
        [pubs(0, 12, qos1_every=4)], client_id="cidA", took=0,
        options={
            "tenancy": True, "tenants": {"acme": {}},
            "tenant_users": {"cidA": "acme", "sub": "acme"},
        },
    ),
    "on_publish_hook_added_in_mid_stream": Case(
        [pubs(0, 10, qos1_every=5), pubs(10, 20, qos1_every=5)],
        between=add_on_publish, took=10,
    ),
    "client_closed_in_mid_run": Case(
        [pubs(0, 4) + pub_packet("t/stop", b"last") + pubs(5, 9)], took=5
    ),
    "stage_cap_crossed": Case(
        [pubs(0, 24, qos1_every=6)], took=24,
        options={"overload_stage_max_pending": 8},
    ),
    "stopping_stage": Case([pubs(0, 10, qos1_every=3)], prepare=stop_the_stage, took=10),
    "run_of_one": Case([pubs(0, 1), pubs(1, 2, qos1_every=1)], took=2),
}


async def drain(reader, idle=0.15):
    """Everything the socket gives until it is idle or closed."""
    got = bytearray()
    while True:
        try:
            data = await asyncio.wait_for(reader.read(65536), idle)
        except asyncio.TimeoutError:
            return bytes(got), False
        if not data:
            return bytes(got), True
        got += data


def observe(case, shut):
    """Feed ``case`` to a fresh broker and write down all that can be
    seen of it."""

    async def scenario():
        h = Harness(
            staged_options(matcher_stage_latency_budget_ms=0, **case.options),
            allow=False,
        )
        srv = h.server
        door = Door()
        srv.add_hook(door)
        if shut:
            srv.add_hook(ReadsPackets())
        await srv.serve()
        stage = srv._stage
        parked = []
        park_many = stage.park_many

        def spy(items):
            parked.extend(
                (topic, entry.pk, entry.clock) for topic, entry in items
            )
            park_many(items)

        stage.park_many = spy
        sub_r, _sub_w = await subscriber(h, "sub", "#", qos=1)
        srv.matcher.flush()
        pub_r, pub_w, task = await h.connect(case.client_id, version=case.version)
        cl = next(  # a tenant's client is registered under a scoped id
            c for c in srv.clients.get_all().values()
            if c.id.endswith(case.client_id)
        )
        if case.prepare is not None:
            case.prepare(srv, cl)
        info, tele, ops = srv.info, srv.telemetry, srv._ops
        before = (
            info.bytes_received, info.packets_received, info.messages_received,
            info.bytes_sent, info.packets_sent, info.messages_sent,
            tele._n, tele.outbound_bytes.value, tele.outbound_writes.value,
        )
        sends = ops.socket_sends
        written = 0
        for index, data in enumerate(case.writes):
            pub_w.write(data)
            written += len(data)
            for _ in range(2000):  # a first batch of a shape compiles
                if (
                    task.done() or info.bytes_received - before[0] >= written
                ) and stage.batch_completed >= len(parked):
                    break
                await asyncio.sleep(0.005)
            if case.between is not None:
                case.between(srv, cl, index)
        back, back_closed = await drain(pub_r)
        delivered, _ = await drain(sub_r)
        after = (
            info.bytes_received, info.packets_received, info.messages_received,
            info.bytes_sent, info.packets_sent, info.messages_sent,
            tele._n, tele.outbound_bytes.value, tele.outbound_writes.value,
        )
        seen = {
            "socket_sends": ops.socket_sends - sends,
            "written_back": back,
            "closed": (back_closed, cl.closed, task.done()),
            "stop_cause": repr(cl.stop_cause),
            "delivered": delivered,
            "counters": tuple(b - a for a, b in zip(before, after)),
            "dropped": info.messages_dropped,
            "inflight": (info.inflight, len(cl.state.inflight)),
            "quota": cl.state.inflight.receive_quota,
            "pub_count": cl._pub_count,
            "out": (cl.state.out_bytes, cl.state.out_writes),
            "asked": door.asked,
            "processed": door.processed,
            "stage": (
                stage.admission_fallbacks, stage.order_held,
                stage.batch_completed, stage.adapter_completed,
            ),
            "parked": parked,
            "took": ops.ingest_run_publishes,
            "runs": ops.ingest_runs,
        }
        await srv.close()
        await h.shutdown()
        return seen

    return run(scenario())


def packet_views(parked):
    """What a reader of a parked packet can see of it, the untouched
    packet's equality first."""
    views = []
    for topic, pk, clock in parked:
        views.append((
            topic, repr(pk.copy(True)), repr(pk.copy(False)), repr(pk),
            None if clock is None else (
                type(clock).__name__, [name for name, _ in clock.stages]
            ),
        ))
    return views


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_run_and_the_per_frame_path_cannot_be_told_apart(name, monkeypatch):
    monkeypatch.setattr("mqtt_tpu.server.time.time", lambda: NOW)
    case = CASES[name]
    run_side = observe(case, shut=False)
    frame_side = observe(case, shut=True)
    # the mechanism engaged where the gate lets it, and only there
    assert frame_side.pop("took") == 0 and frame_side.pop("runs") == 0
    took, runs = run_side.pop("took"), run_side.pop("runs")
    assert took == case.took, (took, runs)
    assert (runs > 0) == (took > 0)
    a, b = run_side.pop("parked"), frame_side.pop("parked")
    assert [pk for _, pk, _ in a] == [pk for _, pk, _ in b]  # field for field
    va, vb = packet_views(a), packet_views(b)
    assert va == vb
    # a run's fallbacks complete as one slice where the per-frame path's
    # complete one by one: fewer writes to the subscriber, never more
    assert run_side.pop("socket_sends") <= frame_side.pop("socket_sends")
    assert run_side == frame_side
    if name == "one_in_eight_qos1":
        # 8 acks left as one write, the 1-in-4 clock draw kept its beat
        assert run_side["written_back"] == b"".join(
            bytes((0x40, 2, 0, i + 1)) for i in range(0, 64, 8)
        )
        assert sum(v[4] is not None for v in va) == 16
    if name == "stage_cap_crossed":
        fallbacks, held, completed, _ = run_side["stage"]
        assert fallbacks == held == 24 - 8 and completed == 24


@pytest.mark.parametrize("shut", [False, True], ids=["run", "per_frame"])
def test_a_run_that_crosses_the_cap_keeps_order_against_the_reference(shut):
    """stresser's echo loop with the stage's backlog cut below one socket
    read (8 against 16-frame writes): admitted members, then held ones,
    each connection's messages back once and in the order sent
    (``benchmark/reference.py``), by the run and by the per-frame path."""
    chunk, rounds = 16, 3
    verdict, delivered, n = run_echo(
        32, 4, chunk, rounds, hooks=[ReadsPackets()] if shut else [],
        overload_stage_max_pending=8,
    )
    assert verdict["errors"] == 0 and verdict["misordered"] == 0, verdict
    assert delivered == 4 * chunk * rounds
    assert n["peak"] <= 8 and n["completed"] == 4 * chunk * rounds
    assert chunk - 8 <= n["held"] <= n["fallbacks"]
    assert (n["took"] == 0) == shut
