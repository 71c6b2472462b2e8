"""The ack run against the per-frame path (server.ack_run, clients.read,
Inflight.acknowledge): the same byte stream fed to a connection whose run
gate is open and to one where a do-nothing hook providing ON_QOS_COMPLETE
keeps it shut leaves the same in-flight map, send quota and counters, and
the same bytes written back in the same order. CPU backend: equality and
counts, never a rate."""

import asyncio
import dataclasses

import pytest

from mqtt_tpu.hooks import (
    ON_PACKET_PROCESSED,
    ON_PACKET_READ,
    ON_QOS_COMPLETE,
    Hook,
)
from mqtt_tpu.native import MAX_FRAMES_PER_SCAN, frame_scan
from mqtt_tpu.packets import (
    CONNACK,
    DISCONNECT,
    PINGREQ,
    PUBACK,
    PUBLISH,
    FixedHeader,
    Packet,
    Properties,
    UserProperty,
    encode_packet,
)

from tests.test_batch_completion import staged_options
from tests.test_fanout_wide import reference, serve_fanout
from tests.test_ingest_run import drain
from tests.test_server import (
    Harness,
    Options,
    connect_packet,
    pub_packet,
    read_wire_packet,
    run,
)

NOW = 1_790_000_000.0  # both sides stamp ``created`` from one clock


class Sees(Hook):
    """Provides one event, counts its PUBACKs and changes nothing: shuts
    the gate."""

    def __init__(self, event):
        super().__init__()
        self.event = event
        self.acks = 0

    def id(self):
        return "sees-%d" % self.event

    def provides(self, b):
        return b == self.event

    def _note(self, pk):
        if pk.fixed_header.type == PUBACK:
            self.acks += 1

    def on_packet_read(self, cl, pk):
        self._note(pk)
        return pk

    def on_packet_processed(self, cl, pk, err):
        self._note(pk)

    def on_qos_complete(self, cl, pk):
        self._note(pk)


def ack(pid, version, props):
    return encode_packet(Packet(
        fixed_header=FixedHeader(type=PUBACK), protocol_version=version,
        packet_id=pid, properties=props,
    ))


def acks(ids):
    return b"".join(bytes((0x40, 2, pid >> 8, pid & 0xFF)) for pid in ids)


def other(type_, version=4):
    return encode_packet(
        Packet(fixed_header=FixedHeader(type=type_), protocol_version=version)
    )


def fill(ids, parked=(), quota=None):
    """A ``prepare``: the session holds a delivered QoS1 publish under
    each of ``ids`` and one waiting for send quota (``expiry`` -1) under
    each of ``parked``; ``quota`` is (send quota, its maximum)."""

    def prepare(srv, cl):
        inflight = cl.state.inflight
        for n, pid in enumerate(list(ids) + list(parked)):
            pk = Packet(
                fixed_header=FixedHeader(type=PUBLISH, qos=1),
                protocol_version=cl.properties.protocol_version,
                topic_name="d/%d" % pid, payload=b"p%d" % pid, packet_id=pid,
                created=int(NOW) + n,
            )
            if pid in parked:
                pk.expiry = -1
            assert inflight.set(pk)
            srv.info.inflight += 1
        if quota is not None:
            inflight.reset_send_quota(quota[1])
            inflight.send_quota = quota[0]

    return prepare


@dataclasses.dataclass
class Case:
    """``writes``: the byte strings written to the socket, each settled
    before the next; ``prepare``: called with (server, client) before
    the first; ``took`` / ``runs``: the PUBACK frames the open side's
    runs take, and the runs."""

    writes: list
    prepare: object
    version: int = 4
    staged: bool = False
    clean: bool = True
    took: int = 0
    runs: int = 0


USER = Properties(user=[UserProperty("k", "v")])

CASES = {
    "forty_acks_one_scan": Case(
        [acks(range(1, 41))], fill(range(1, 41)), took=40, runs=1
    ),
    "unknown_id": Case(
        [acks([1, 2, 999, 3, 0, 4])], fill(range(1, 6)), took=6, runs=1
    ),
    "one_id_twice": Case(
        [acks([1, 2, 2, 3, 1])], fill(range(1, 6)), took=5, runs=1
    ),
    "v5_bare_acks_raise_the_quota": Case(
        [acks(range(1, 21))], fill(range(1, 31), quota=(2, 25)), version=5,
        took=20, runs=1,
    ),
    "v5_quota_at_its_maximum": Case(
        [acks(range(1, 6))], fill(range(1, 9), quota=(7, 8)), version=5,
        took=5, runs=1,
    ),
    "v5_reason_and_properties_inside": Case(
        [acks([1, 2]) + b"\x40\x03\x00\x03\x10" + acks([4])
         + ack(5, 5, props=USER) + acks([6])],
        fill(range(1, 9), quota=(0, 16)), version=5, took=4, runs=3,
    ),
    # an inbound QoS1 publish under an id that a delivery holds takes
    # the id over, and the delivery's PUBACK then finds nothing and frees
    # no quota: an ack taken ahead of the publish before it would
    "publish_puback_pingreq_interleaved": Case(
        [pub_packet("t/a", b"a", qos=1, pid=3) + acks([3, 4])
         + other(PINGREQ) + pub_packet("t/b", b"b", qos=1, pid=3)
         + pub_packet("t/c", b"c") + acks([5]) + pub_packet("t/d", b"d")
         + other(PINGREQ) + acks([6, 7, 8])],
        fill(range(3, 12), quota=(0, 16)), staged=True, took=6, runs=3,
    ),
    # each ack frees quota for one waiting entry, resent before the
    # next ack is looked at: the run leaves such a session alone
    "v5_quota_0_three_parked": Case(
        [acks([1, 2, 3, 4, 5])],
        fill(range(1, 6), parked=(11, 12, 13), quota=(0, 5)), version=5,
    ),
    "parked_without_a_quota": Case(
        [acks([1, 2, 3])], fill(range(1, 4), parked=(11, 12)), took=3, runs=1
    ),
    "stretch_cut_by_the_frame_limit": Case(
        [acks(range(1, MAX_FRAMES_PER_SCAN + 11))],
        fill(range(1, MAX_FRAMES_PER_SCAN + 21)),
        took=MAX_FRAMES_PER_SCAN + 10, runs=2,
    ),
    "client_closed_in_mid_scan": Case(
        [acks([1, 2]) + other(DISCONNECT) + acks([3, 4])],
        fill(range(1, 7)), clean=False, took=2, runs=1,
    ),
    "ack_split_across_reads": Case(
        [acks([1, 2]) + acks([3])[:3], acks([3])[3:] + acks([4])],
        # the missing byte comes with what follows it (a small rest is
        # not read alone: Client._missing_bytes)
        fill(range(1, 7)), took=4, runs=2,
    ),
}


def observe(case, shut):
    """Feed ``case`` to a fresh broker, with ``shut`` (a hook, or None)
    added, and write down all that can be seen of it."""

    async def scenario():
        opts = (
            staged_options(matcher_stage_latency_budget_ms=0)
            if case.staged else Options(inline_client=True)
        )
        h = Harness(opts)
        srv = h.server
        if shut is not None:
            srv.add_hook(shut)
        await srv.serve()
        if case.staged:
            srv.matcher.flush()
        reader, writer, task = await h.attach()
        writer.write(connect_packet("sub", case.version, clean=case.clean))
        assert (await read_wire_packet(reader, case.version)).fixed_header.type == CONNACK
        cl = srv.clients.get("sub")
        inflight = cl.state.inflight
        case.prepare(srv, cl)
        info, ops = srv.info, srv._ops
        before = (info.bytes_received, info.packets_received, info.inflight)
        sent = bytearray()
        for data in case.writes:
            writer.write(data)
            sent += data
            # the whole frames written so far
            written = frame_scan(sent, max_frames=4096, max_packet_size=0)[1]
            for _ in range(2000):  # a first batch of a shape compiles
                if task.done() or (
                    info.bytes_received - before[0] >= written and not cl._staged
                ):
                    break
                await asyncio.sleep(0.005)
        back, back_closed = await drain(reader)
        seen = {
            "written_back": back,
            "closed": (back_closed, cl.closed, task.done()),
            "map": [(pid, pk.expiry < 0) for pid, pk in inflight.internal.items()],
            "quota": (inflight.send_quota, inflight.maximum_send_quota),
            "info": (
                info.bytes_received - before[0],
                info.packets_received - before[1],
                info.inflight - before[2],
            ),
            "took": ops.ack_run_acks,
            "runs": ops.ack_runs,
        }
        await srv.close()
        await h.shutdown()
        return seen

    return run(scenario())


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_ack_run_and_the_per_frame_path_cannot_be_told_apart(name, monkeypatch):
    monkeypatch.setattr("mqtt_tpu.server.time.time", lambda: NOW)
    case = CASES[name]
    run_side = observe(case, None)
    frame_side = observe(case, Sees(ON_QOS_COMPLETE))
    # the mechanism engaged where the gate lets it, and only there
    assert frame_side.pop("took") == 0 and frame_side.pop("runs") == 0
    assert (run_side.pop("took"), run_side.pop("runs")) == (case.took, case.runs)
    assert run_side == frame_side
    # and the case did what its name says
    acked, _, gone = run_side["info"]
    back = run_side["written_back"]
    if name == "v5_quota_0_three_parked":
        # five acks, three resends in the order parked, quota left for two
        assert [pid for pid, _ in run_side["map"]] == []
        assert run_side["quota"] == (2, 5) and gone == -8
        assert back.index(b"p11") < back.index(b"p12") < back.index(b"p13")
        assert back.count(b"d/1") == 3
    if name == "v5_bare_acks_raise_the_quota":
        assert run_side["quota"] == (22, 25) and len(run_side["map"]) == 10
    if name == "v5_quota_at_its_maximum":
        assert run_side["quota"] == (8, 8)
    if name == "parked_without_a_quota":
        assert run_side["map"] == [(11, True), (12, True)] and back == b""
    if name == "publish_puback_pingreq_interleaved":
        # both publishes acknowledged, each before the PINGRESP behind
        # it; six acks, five of them of an id still in flight
        assert back == (b"\x40\x02\x00\x03" + b"\xd0\x00") * 2
        assert run_side["quota"] == (5, 16)
        assert [pid for pid, _ in run_side["map"]] == [9, 10, 11]
    if name == "client_closed_in_mid_scan":
        assert run_side["closed"][1] and acked == 10
    if name == "stretch_cut_by_the_frame_limit":
        assert len(run_side["map"]) == 10


@pytest.mark.parametrize(
    "event", [ON_PACKET_READ, ON_PACKET_PROCESSED, ON_QOS_COMPLETE],
    ids=["on_packet_read", "on_packet_processed", "on_qos_complete"],
)
def test_a_hook_that_is_shown_pubacks_sees_every_one(event):
    case = CASES["unknown_id"]
    hook = Sees(event)
    frame_side = observe(case, hook)
    run_side = observe(case, None)
    assert frame_side.pop("took") == 0 and frame_side.pop("runs") == 0
    assert run_side.pop("took") == 6 and run_side.pop("runs") == 1
    assert run_side == frame_side
    # a completion for each id that was in flight, a packet for each frame
    assert hook.acks == (4 if event == ON_QOS_COMPLETE else 6)


def test_the_gate_is_shut_for_an_inline_and_for_a_closed_client():
    async def scenario():
        h = Harness()
        srv = h.server
        await srv.serve()
        rbuf = bytearray(acks([1, 2, 3]))
        frames, _consumed, _err = frame_scan(rbuf, max_frames=8, max_packet_size=0)
        assert len(frames) == 3
        inline = srv.inline_client
        fill([1, 2, 3])(srv, inline)
        assert srv.ack_run(inline, rbuf, frames, 0, 0) == -3
        reader, writer, task = await h.connect("sub")
        cl = srv.clients.get("sub")
        fill([1, 2, 3])(srv, cl)
        cl.stop()
        assert srv.ack_run(cl, rbuf, frames, 0, 0) == -3
        assert len(inline.state.inflight) == len(cl.state.inflight) == 3
        assert srv._ops.ack_runs == srv._ops.ack_run_acks == 0
        # and open for the same client while it was open: from frame 1 on
        reader, writer, task = await h.connect("sub2")
        cl = srv.clients.get("sub2")
        fill([1, 2, 3])(srv, cl)
        before = srv.info.bytes_received, srv.info.packets_received
        assert srv.ack_run(cl, rbuf, frames, 1, 4) == 2
        assert list(cl.state.inflight.internal) == [1]
        assert srv.info.bytes_received - before[0] == 8
        assert srv.info.packets_received - before[1] == 2
        assert (srv._ops.ack_runs, srv._ops.ack_run_acks) == (1, 2)
        await srv.close()
        await h.shutdown()

    run(scenario())


def test_the_counts_reach_the_slice_metrics_and_sys():
    async def scenario():
        h = Harness()
        srv = h.server
        await srv.serve()
        reader, writer, task = await h.connect("sub")
        cl = srv.clients.get("sub")
        fill(range(1, 9))(srv, cl)
        writer.write(acks(range(1, 6)) + other(PINGREQ) + acks([6, 7]))
        assert (await read_wire_packet(reader)).fixed_header.type == 13
        for _ in range(400):
            if srv._ops.ack_run_acks == 7:
                break
            await asyncio.sleep(0.005)
        counts = srv._slice_counters()
        assert counts["ack_run_acks"] == 7 and 2 <= counts["ack_runs"] <= 7
        text = srv.telemetry.registry.exposition()
        assert "mqtt_tpu_ack_run_acks_total 7" in text
        assert f"mqtt_tpu_ack_runs_total {counts['ack_runs']}" in text
        srv.publish_sys_topics()
        got = {
            p.topic_name: bytes(p.payload)
            for p in srv.topics.messages("$SYS/broker/ingest/#")
            if "ack" in p.topic_name
        }
        assert got == {
            "$SYS/broker/ingest/ack_run_acks": b"7",
            "$SYS/broker/ingest/ack_runs": str(counts["ack_runs"]).encode(),
        }
        await srv.close()
        await h.shutdown()

    run(scenario())


@pytest.mark.parametrize("seed", [34, 2**31 + 34, 3_000_000_019])
def test_a_served_broadcast_is_acknowledged_by_the_run(seed):
    """``fanout-5-1000`` at the rehearse size over loopback TCP (50
    subscribers of one ``<root>/+`` filter at QoS1, 5 publishers x 8
    frames, every delivery acknowledged): the sockets saw what the plain
    reference says, each delivery once and in its publisher's order; no
    session holds an entry at the end; and every PUBACK came in by a run,
    a read's stretch of them at a time."""
    served = serve_fanout(seed, publishes=8)
    verdict = reference.compare_deliveries(served["expected"], served["received"])
    assert verdict["errors"] == 0 and verdict["misordered"] == 0, verdict
    assert served["due"] == served["acked"] == 5 * 8 * 50
    assert served["inflight_left"] == 0 and served["info_inflight"] == 0
    d = served["delta"]
    assert d["ack_run_acks"] == served["due"]
    # a subscriber answers a corked write of deliveries in one go, so a
    # read holds a stretch of acks: some 50 runs for the 2,000 here
    assert 1 <= d["ack_runs"] <= served["acked"] // 2
