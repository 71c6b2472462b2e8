"""Chaos/robustness suite for the broker-wide overload control plane
(mqtt_tpu.overload): the NORMAL -> THROTTLE -> SHED governor, bounded
staging admission, THROTTLE read-pausing, SHED 0x97 shedding,
slow-consumer eviction, tiered cluster forward shedding, and the seeded
publish-storm drills (mqtt_tpu.faults.StormPlan / drive_storm).

The storm acceptance drill: offered load far above sustainable, staging
pending depth and aggregate outbound backlog stay below their caps,
admitted QoS1 traffic is delivered exactly once with bounded latency,
shed publishes get v5 reason 0x97, the slow consumer is evicted with
DISCONNECT 0x97, and the governor returns to NORMAL within the
hysteresis window once the storm stops — all visible through the
$SYS/broker/overload/* gauges.
"""

import asyncio
import logging
import os
import time

import pytest

from mqtt_tpu import Options, Server
from mqtt_tpu.faults import FaultPlan, FaultyMatcher, StormPlan, drive_storm
from mqtt_tpu.overload import (
    NORMAL,
    SHED,
    THROTTLE,
    OverloadConfig,
    OverloadGovernor,
)
from mqtt_tpu.packets import DISCONNECT, PINGREQ, PUBACK, PUBLISH, SUBACK
from mqtt_tpu.packets import FixedHeader, Packet, Subscription, encode_packet
from mqtt_tpu.staging import MatchStage, Parked
from mqtt_tpu.topics import SYS_PREFIX, Subscribers

from tests.test_server import (
    Harness,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)


class FakeClock:
    def __init__(self) -> None:
        self.t = 100.0

    def __call__(self) -> float:
        return self.t


def make_governor(**kw):
    clock = FakeClock()
    kw.setdefault("eval_interval_s", 0.0)
    kw.setdefault("min_dwell_s", 1.0)
    gov = OverloadGovernor(OverloadConfig(**kw), clock=clock)
    pressure = [0.0]
    gov.add_source("test", lambda: pressure[0])
    return gov, clock, pressure


class StubClient:
    def __init__(self, cid="c1"):
        self.id = cid
        self._pub_epoch = -1
        self._pub_count = 0


# -- unit: governor state machine -------------------------------------------


class TestGovernorStateMachine:
    def test_hysteresis_bands_and_dwell(self):
        gov, clock, pressure = make_governor()
        assert gov.evaluate() == NORMAL

        pressure[0] = 0.75  # above throttle_enter (0.70): escalate now
        assert gov.evaluate() == THROTTLE
        pressure[0] = 0.60  # inside the band (exit 0.50): hold
        clock.t += 5
        assert gov.evaluate() == THROTTLE

        pressure[0] = 0.95  # above shed_enter (0.90): escalate now
        assert gov.evaluate() == SHED
        pressure[0] = 0.60  # below shed_exit (0.65) but dwell not elapsed
        assert gov.evaluate() == SHED
        clock.t += 2  # dwell elapsed; 0.60 >= throttle_exit: step down one
        assert gov.evaluate() == THROTTLE

        pressure[0] = 0.10
        assert gov.evaluate() == THROTTLE  # just entered: dwell again
        clock.t += 2
        assert gov.evaluate() == NORMAL
        assert gov.transitions == 4

    def test_shed_exits_straight_to_normal_when_quiet(self):
        gov, clock, pressure = make_governor()
        pressure[0] = 1.5
        assert gov.evaluate() == SHED
        pressure[0] = 0.0
        clock.t += 2
        assert gov.evaluate() == NORMAL

    def test_escalation_ignores_dwell(self):
        gov, clock, pressure = make_governor(min_dwell_s=60.0)
        pressure[0] = 0.8
        assert gov.evaluate() == THROTTLE
        pressure[0] = 0.99  # straight up, no dwell for escalation
        assert gov.evaluate() == SHED

    def test_eval_interval_rate_limits_lazy_calls(self):
        gov, clock, pressure = make_governor(eval_interval_s=1.0)
        pressure[0] = 2.0
        gov.evaluate(force=True)
        assert gov.state == SHED
        pressure[0] = 0.0
        clock.t += 10
        e0 = gov.epoch
        gov.evaluate()  # interval elapsed: runs, window rolled
        e1 = gov.epoch
        assert e1 != e0
        gov.evaluate()  # within the interval: no-op
        assert gov.epoch == e1

    def test_failing_source_reads_as_zero(self):
        gov, clock, pressure = make_governor()

        def boom():
            raise RuntimeError("signal died")

        gov.add_source("bad", boom)
        pressure[0] = 0.2
        assert gov.evaluate() == NORMAL
        assert gov.signal_pressures["bad"] == 0.0

    def test_admit_quota_per_window(self):
        gov, clock, pressure = make_governor(
            shed_quota=2, eval_interval_s=1000.0, quota_window_s=10.0
        )
        cl = StubClient()
        pressure[0] = 2.0
        gov.evaluate(force=True)
        assert gov.state == SHED
        assert gov.admit(cl) and gov.admit(cl)
        assert not gov.admit(cl)  # third in the window sheds
        assert gov.sheds == 1
        # sampling again within the same wall-clock window must NOT
        # refill the budget
        gov.evaluate(force=True)
        assert not gov.admit(cl)
        clock.t += 10  # the window rolls on the clock
        gov.evaluate(force=True)
        assert gov.admit(cl)
        # another client has its own budget
        assert gov.admit(StubClient("c2"))

    def test_admit_always_true_outside_shed(self):
        gov, clock, pressure = make_governor(shed_quota=1)
        cl = StubClient()
        for _ in range(10):
            assert gov.admit(cl)
        assert gov.sheds == 0

    def test_read_delay_only_for_over_quota_publishers(self):
        gov, clock, pressure = make_governor(
            publish_quota=5, throttle_delay_s=0.033, eval_interval_s=1000.0
        )
        cl = StubClient()
        pressure[0] = 0.8
        gov.evaluate(force=True)
        assert gov.state == THROTTLE
        assert gov.read_delay(cl) == 0.0  # first call syncs the window
        cl._pub_count = 3
        assert gov.read_delay(cl) == 0.0  # under quota
        cl._pub_count = 50
        assert gov.read_delay(cl) == pytest.approx(0.033)
        assert gov.throttled == 1
        pressure[0] = 0.0
        clock.t += 5
        gov.evaluate(force=True)
        assert gov.read_delay(cl) == 0.0  # NORMAL again

    def test_evict_due_requires_shed_and_grace(self):
        gov, clock, pressure = make_governor(eviction_grace_s=2.0)
        t0 = clock.t
        clock.t += 5
        assert not gov.evict_due(t0)  # NORMAL: never
        pressure[0] = 2.0
        gov.evaluate(force=True)
        assert gov.evict_due(t0)  # SHED + grace expired
        assert not gov.evict_due(clock.t - 0.5)  # within grace
        assert not gov.evict_due(None)

    def test_qos0_forward_fraction_tiers(self):
        gov, clock, pressure = make_governor(
            qos0_forward_throttle_fraction=0.5,
            qos0_forward_shed_fraction=0.25,
        )
        assert gov.qos0_forward_fraction() == 1.0
        pressure[0] = 0.8
        gov.evaluate(force=True)
        assert gov.qos0_forward_fraction() == 0.5
        pressure[0] = 2.0
        gov.evaluate(force=True)
        assert gov.qos0_forward_fraction() == 0.25

    def test_gauges_shape(self):
        gov, clock, pressure = make_governor()
        pressure[0] = 0.95
        gov.evaluate(force=True)
        g = gov.gauges()
        assert g["state"] == SHED and g["state_code"] == 2
        assert g["pressure"] == pytest.approx(0.95)
        assert g["signal/test"] == pytest.approx(0.95)
        assert g["peak/test"] == pytest.approx(0.95)
        for key in ("sheds", "evictions", "throttled", "transitions"):
            assert key in g


class TestOptionNormalization:
    def test_inverted_bands_and_zero_caps_are_repaired(self):
        o = Options(
            overload_throttle_enter=0.5,
            overload_throttle_exit=0.9,  # inverted
            overload_shed_enter=0.3,  # below throttle_enter
            overload_shed_exit=0.8,  # inverted
            overload_stage_max_pending=0,
            overload_max_outbound_backlog=-5,
            overload_eval_interval_ms=0,
            overload_publish_quota=0,
            overload_shed_quota=-1,
        )
        o.ensure_defaults()
        assert o.overload_throttle_exit <= o.overload_throttle_enter
        assert o.overload_shed_exit <= o.overload_shed_enter
        assert o.overload_shed_enter >= o.overload_throttle_enter
        assert o.overload_stage_max_pending > 0
        assert o.overload_max_outbound_backlog > 0
        assert o.overload_eval_interval_ms > 0
        assert o.overload_publish_quota > 0
        assert o.overload_shed_quota > 0


# -- unit: bounded staging admission ----------------------------------------


class TestBoundedStagingAdmission:
    def test_overflow_resolves_via_host_walk(self):
        async def scenario():
            hits = []

            def host(topic):
                hits.append(topic)
                return Subscribers()

            stage = MatchStage(None, host, max_pending=3)
            # arm submission without starting the collector, so parked
            # entries stay parked and the bound is observable
            stage._wake = asyncio.Event()
            parked = [stage.submit(f"t/{i}") for i in range(3)]
            assert all(not f.done() for f in parked)
            done = []
            for i, f in enumerate(parked):
                f.add_done_callback(lambda _f, i=i: done.append(i))
            over = stage.submit("t/over")
            # walked on the host NOW, outside the device's backlog; its
            # completion waits its turn behind the three parked before it
            assert hits == ["t/over"]
            assert not over.done()
            over.add_done_callback(lambda _f: done.append("over"))
            assert stage.admission_fallbacks == stage.order_held == 1
            assert stage.peak_pending == 3
            assert stage.pending_depth == 4  # the held member rides along
            assert stage.pressure() == pytest.approx(1.0)
            # a parker that has nothing else in the stage overtakes
            # nothing: its fallback completes inside park()
            entry = Parked(lambda es, rs, t=0: done.append("alone"))
            entry.alone = True
            stage.park("t/alone", entry)
            assert done == ["alone"] and stage.order_held == 1
            await stage.stop()  # drains the parked entries via host walk
            assert all(f.done() for f in parked) and over.done()
            await asyncio.sleep(0)  # the futures' callbacks
            assert done == ["alone", 0, 1, 2, "over"]
            assert hits == ["t/over", "t/alone", "t/0", "t/1", "t/2"]

        run(scenario())

    def test_deadline_aware_admission(self):
        async def scenario():
            stage = MatchStage(
                None,
                lambda t: Subscribers(),
                latency_budget_s=0.1,
                max_pending=1000,
            )
            stage._wake = asyncio.Event()
            stage._queue = asyncio.Queue(maxsize=8)
            stage._ewma_s = 0.05
            # depth 1 (no queue backlog): projected 0.05 < 0.2 deadline
            f1 = stage.submit("a")
            assert not f1.done()
            for _ in range(4):
                stage._queue.put_nowait(None)
            # projected wait (1 + 4) * 0.05 = 0.25 > 2 x 0.1: host walk,
            # held behind "a" (it joins the order, PR 27)
            f2 = stage.submit("b")
            assert not f2.done()
            assert stage.admission_fallbacks == stage.order_held == 1
            # a held member is no device work: the projected wait of the
            # next submission does not count it
            assert stage._held_pending == 1 and stage.pending_depth == 2
            stage._queue = None
            await stage.stop()
            assert f1.done() and f2.done()

        run(scenario())

    def test_no_adaptation_means_no_deadline(self):
        async def scenario():
            stage = MatchStage(
                None, lambda t: Subscribers(), latency_budget_s=None,
                max_pending=10,
            )
            stage._wake = asyncio.Event()
            stage._ewma_s = 99.0
            assert not stage._past_deadline()
            f = stage.submit("x")
            assert not f.done()
            await stage.stop()

        run(scenario())


# -- unit: tiered cluster forward shedding ----------------------------------


class _FakeTransport:
    def __init__(self, buffered: int) -> None:
        self.buffered = buffered
        self.aborted = False

    def get_write_buffer_size(self) -> int:
        return self.buffered

    def abort(self) -> None:
        self.aborted = True


class _FakeWriter:
    def __init__(self, buffered: int) -> None:
        self.transport = _FakeTransport(buffered)
        self.sent = []

    def write(self, data: bytes) -> None:
        self.sent.append(data)


class TestClusterTieredShedding:
    def _cluster(self, tmp_path):
        from mqtt_tpu.cluster import Cluster
        from mqtt_tpu.topics import TopicsIndex

        class FakeServer:
            pass

        srv = FakeServer()
        srv.topics = TopicsIndex()
        gov, clock, pressure = make_governor()
        srv.overload = gov
        c = Cluster(srv, 0, 2, str(tmp_path))
        return c, gov, pressure

    def test_qos0_sheds_at_reduced_cap_while_shedding(self, tmp_path):
        from mqtt_tpu.cluster import _T_FRAME, _T_PACKET, Cluster

        c, gov, pressure = self._cluster(tmp_path)
        # 40% of the buffer used: fine in NORMAL, over the 25% SHED tier
        w = _FakeWriter(int(0.4 * Cluster.MAX_PEER_BUFFER))
        assert c._send_nowait(1, w, _T_FRAME, b"f", qos=0)
        pressure[0] = 2.0
        gov.evaluate(force=True)
        assert not c._send_nowait(1, w, _T_FRAME, b"f", qos=0)
        assert c.shed_qos0_forwards == 1
        assert c.dropped_forwards == 1
        assert gov.sheds == 1
        # QoS>0 keeps the FULL cap: same buffer passes
        assert c._send_nowait(1, w, _T_PACKET, b"p", qos=1)
        # ...until the full cap, where it drops but is NOT a shed
        w2 = _FakeWriter(Cluster.MAX_PEER_BUFFER + 1)
        assert not c._send_nowait(1, w2, _T_PACKET, b"p", qos=1)
        assert c.shed_qos0_forwards == 1  # unchanged

    def test_control_traffic_never_sheds(self, tmp_path):
        from mqtt_tpu.cluster import _T_PRESENCE, Cluster

        c, gov, pressure = self._cluster(tmp_path)
        pressure[0] = 2.0
        gov.evaluate(force=True)
        w = _FakeWriter(int(2 * Cluster.MAX_PEER_BUFFER))
        assert c._send_nowait(1, w, _T_PRESENCE, b"s")  # over every tier
        assert w.sent
        # only a wedged link (8x) closes it
        w3 = _FakeWriter(9 * Cluster.MAX_PEER_BUFFER)
        assert not c._send_nowait(1, w3, _T_PRESENCE, b"s")
        assert w3.transport.aborted

    def test_buffer_pressure_signal(self, tmp_path):
        from mqtt_tpu.cluster import Cluster

        c, gov, pressure = self._cluster(tmp_path)
        assert c._buffer_pressure() == 0.0
        c._writers[1] = _FakeWriter(Cluster.MAX_PEER_BUFFER // 2)
        c._writers[2] = _FakeWriter(Cluster.MAX_PEER_BUFFER // 4)
        assert c._buffer_pressure() == pytest.approx(0.5)


# -- e2e helpers -------------------------------------------------------------


def storm_options(**kw):
    return Options(
        inline_client=True,
        device_matcher=True,
        matcher_stage_window_ms=1.0,
        matcher_opts={"max_levels": 4, "background": False},
        overload_stage_max_pending=kw.pop("max_pending", 32),
        overload_throttle_enter=kw.pop("throttle_enter", 0.30),
        overload_throttle_exit=kw.pop("throttle_exit", 0.10),
        overload_shed_enter=kw.pop("shed_enter", 0.45),
        overload_shed_exit=kw.pop("shed_exit", 0.20),
        overload_eval_interval_ms=kw.pop("eval_ms", 30.0),
        overload_min_dwell_ms=kw.pop("dwell_ms", 100.0),
        overload_publish_quota=kw.pop("publish_quota", 100_000),
        overload_shed_quota=kw.pop("shed_quota", 5),
        overload_eviction_grace_ms=kw.pop("grace_ms", 200.0),
        **kw,
    )


async def collect_acks(reader, want: int, out: dict) -> None:
    """Read ``want`` PUBACKs off one v5 publisher stream into
    ``out[packet_id] = (reason_code, arrival_time)``."""
    got = 0
    while got < want:
        pk = await asyncio.wait_for(read_wire_packet(reader, 5), 10)
        if pk.fixed_header.type == PUBACK:
            out[pk.packet_id] = (pk.reason_code, time.perf_counter())
            got += 1


def qos1_tags(schedule):
    """payload tag (s<p>-<m>) per QoS1 message, in packet-id order."""
    return [p.split(b"|", 1)[0] for (_s, _t, p, q) in schedule if q]


class DeliveryCollector:
    """Reads the healthy subscriber CONCURRENTLY with the storm (it must
    keep draining, or its own transport backlog would make it a slow
    consumer); records delivered payload tags and first-arrival times."""

    def __init__(self, reader, writer=None) -> None:
        self.got: list = []
        self.seen_at: dict = {}
        # held for the collector's lifetime: Python 3.12's
        # StreamWriter.__del__ CLOSES a dropped writer, which would
        # disconnect the subscriber the moment its creator returns
        self._writer = writer
        self._done = asyncio.Event()
        self._task = asyncio.ensure_future(self._run(reader))

    async def _run(self, reader) -> None:
        while True:
            try:
                pk = await asyncio.wait_for(read_wire_packet(reader), 0.8)
            except asyncio.TimeoutError:
                if self._done.is_set():
                    return  # storm over and the stream went quiet
                continue
            if pk.fixed_header.type != PUBLISH:
                continue
            tag = bytes(pk.payload).split(b"|", 1)[0]
            self.seen_at.setdefault(tag, time.perf_counter())
            self.got.append(tag)

    async def finish(self) -> list:
        self._done.set()
        await self._task
        return self.got

    def admitted_latencies(self, admitted: set, ack_times: dict) -> list:
        """Admitted-QoS1 fan-out latency: PUBACK arrival (admission is
        decided before the ack is written) to subscriber delivery — the
        broker's own latency, free of client-side socket queueing."""
        return sorted(
            self.seen_at[tag] - ack_times[tag]
            for tag in admitted
            if tag in self.seen_at and tag in ack_times
        )


async def run_publish_storm(h, plan, slow_consumer=False, sub_filter="storm/#"):
    """Drive one seeded storm through a Harness broker: a healthy
    wildcard subscriber (drained live by a DeliveryCollector), optionally
    a never-reading slow consumer, N v5 publishers with ack collectors.
    Returns (admitted_tags, shed_tags, ack_times, collector, slow_conn)."""
    sub_r, sub_w, _ = await h.connect("sub")
    sub_w.write(sub_packet(1, [Subscription(filter=sub_filter, qos=0)]))
    await sub_w.drain()
    assert (await read_wire_packet(sub_r)).fixed_header.type == SUBACK
    slow_conn = None
    if slow_consumer:
        slow_r, slow_w, _ = await h.connect("slowpoke", version=5)
        slow_w.write(
            sub_packet(2, [Subscription(filter="storm/#", qos=0)], version=5)
        )
        await slow_w.drain()
        assert (await read_wire_packet(slow_r, 5)).fixed_header.type == SUBACK
        # shrink both kernel buffers toward their floors so the unread
        # backlog lands in the server's TRANSPORT buffer, where the
        # overload sweep's watermark can see it (AF_UNIX queues data on
        # the RECEIVER's buffer, so the victim's rcvbuf matters most)
        import socket as _socket

        srv_sock = h.server.clients.get("slowpoke").net.writer.get_extra_info(
            "socket"
        )
        if srv_sock is not None:
            srv_sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_SNDBUF, 4096)
        cli_sock = slow_w.get_extra_info("socket")
        if cli_sock is not None:
            cli_sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF, 4096)
        # a truly stalled consumer: its receive window stays closed, so
        # nothing drains into the client-side StreamReader either
        slow_w.transport.pause_reading()
        slow_conn = (slow_r, slow_w)
    h.server.matcher.flush()
    collector = DeliveryCollector(sub_r, sub_w)

    schedules = plan.schedule()
    writers, acks, ack_tasks = [], [], []
    for p in range(plan.publishers):
        r, w, _ = await h.connect(f"storm-p{p}", version=5)
        writers.append(w)
        want = sum(1 for (_s, _t, _pl, q) in schedules[p] if q)
        out = {}
        acks.append(out)
        ack_tasks.append(asyncio.ensure_future(collect_acks(r, want, out)))

    await drive_storm(writers, plan)
    await asyncio.gather(*ack_tasks)

    admitted, shed, ack_times = set(), set(), {}
    for p in range(plan.publishers):
        tags = qos1_tags(schedules[p])
        for pid, (reason, t_ack) in acks[p].items():
            tag = tags[pid - 1]
            if reason == 0x97:
                shed.add(tag)
            else:
                admitted.add(tag)
                ack_times[tag] = t_ack
    return admitted, shed, ack_times, collector, slow_conn


async def await_normal(gov, timeout_s=6.0):
    deadline = time.monotonic() + timeout_s
    while gov.state != NORMAL and time.monotonic() < deadline:
        gov.evaluate(force=True)
        await asyncio.sleep(0.05)
    return gov.state


# -- e2e: the storm acceptance drill ----------------------------------------


class TestPublishStorm:
    def test_storm_sheds_gracefully_and_recovers(self):
        """Offered load far above what the (slowed) stage sustains:
        pending depth stays at/below its cap, some QoS1 publishes get
        0x97, every ADMITTED QoS1 publish is delivered exactly once, no
        shed one leaks, and the governor walks back to NORMAL — all
        asserted through the $SYS gauges too."""

        async def scenario():
            h = Harness(storm_options())
            # a uniformly slow device: every dispatch takes ~20ms, so the
            # storm outruns the pipeline and pressure builds (seeded,
            # replayable; slow must NOT trip the breaker)
            h.server.matcher = FaultyMatcher(
                h.server.matcher, FaultPlan(seed=5, slow_rate=1.0, slow_s=0.02)
            )
            await h.server.serve()
            gov = h.server.overload

            plan = StormPlan(
                seed=42, publishers=5, msgs_per_publisher=60,
                topic_space=8, qos1_fraction=0.5,
            )
            admitted, shed, ack_times, collector, _ = await run_publish_storm(
                h, plan
            )
            assert shed, "the storm never shed: offered load too low"
            assert admitted, "everything shed: admission collapsed"
            delivered = await collector.finish()
            lat = collector.admitted_latencies(admitted, ack_times)
            # every admitted QoS1 message exactly once, no shed leak
            from collections import Counter

            counts = Counter(delivered)
            for tag in admitted:
                assert counts[tag] == 1, (tag, counts[tag])
            for tag in shed:
                assert counts[tag] == 0, f"shed {tag} was delivered"
            # admitted-traffic fan-out p99 stays bounded (stage budget is
            # 250ms; generous CI allowance)
            if lat:
                assert lat[max(0, int(len(lat) * 0.99) - 1)] < 3.0

            # backlogs stayed within their configured caps
            stage = h.server._stage
            assert stage.peak_pending <= stage.max_pending
            peak_out = gov.peak_pressures.get("outbound", 0.0)
            assert peak_out <= 1.0
            assert gov.sheds >= len(shed)

            # the governor returns to NORMAL within the hysteresis window
            assert await await_normal(gov) == NORMAL

            # ...and the whole story is visible in $SYS
            h.server.publish_sys_topics()
            retained = h.server.topics.retained

            def gauge(name):
                pk = retained.get(SYS_PREFIX + "/broker/overload/" + name)
                return None if pk is None else pk.payload.decode()

            assert gauge("state") == NORMAL
            assert int(gauge("sheds")) >= len(shed)
            assert int(gauge("transitions")) >= 1
            assert int(gauge("stage_peak_pending")) <= stage.max_pending
            assert gauge("evictions") is not None
            assert gauge("signal/staging") is not None

            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_slow_consumer_evicted_with_0x97(self):
        """SHED posture + a consumer whose outbound queue stays full past
        the grace window => DISCONNECT 0x97 Quota Exceeded and a freed
        backlog (the eviction gauge counts it)."""

        async def scenario():
            opts = Options(
                inline_client=True,
                overload_eval_interval_ms=20.0,
                overload_eviction_grace_ms=100.0,
                overload_min_dwell_ms=50.0,
                # tiny transport-buffer watermark: a non-reading peer
                # crosses it as soon as the socket buffer is full
                overload_client_buffer_limit_bytes=4096,
            )
            h = Harness(opts)
            await h.server.serve()
            gov = h.server.overload

            slow_r, slow_w, _ = await h.connect("slowpoke", version=5)
            slow_w.write(
                sub_packet(1, [Subscription(filter="e/#", qos=0)], version=5)
            )
            await slow_w.drain()
            assert (await read_wire_packet(slow_r, 5)).fixed_header.type == SUBACK

            pub_r, pub_w, _ = await h.connect("pub")
            # ~1.3MB of fan-out the victim never reads: the socketpair
            # buffer fills and the rest parks in the transport buffer
            payload = b"x" * 32768
            for i in range(40):
                pub_w.write(pub_packet("e/x", payload))
            await pub_w.drain()
            await asyncio.sleep(0.2)
            h.server.sweep_overload()  # observes the over-limit backlog
            cl = h.server.clients.get("slowpoke")
            assert cl.state.backlog_over_since is not None

            # force SHED (the signal a real storm would provide)
            pressure = [2.0]
            gov.add_source("test", lambda: pressure[0])
            h.server.sweep_overload()
            assert gov.state == SHED
            assert gov.evictions == 0  # grace not elapsed yet
            await asyncio.sleep(0.15)  # grace (100ms) expires
            h.server.sweep_overload()

            assert gov.evictions == 1
            assert h.server.clients.get("slowpoke").closed
            # the victim sees DISCONNECT 0x97 after the queued publishes
            while True:
                pk = await asyncio.wait_for(read_wire_packet(slow_r, 5), 10)
                if pk.fixed_header.type == DISCONNECT:
                    assert pk.reason_code == 0x97
                    break

            # recovery: pressure gone, governor returns to NORMAL
            pressure[0] = 0.0
            assert await await_normal(gov) == NORMAL
            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_throttle_pauses_over_quota_publisher(self):
        """THROTTLE: a publisher past its window quota gets its reads
        paused (counted in the throttled gauge); an idle client does
        not."""

        async def scenario():
            opts = Options(
                inline_client=True,
                overload_publish_quota=5,
                overload_throttle_delay_ms=20.0,
                # freeze automatic window rolls: the test drives epochs
                overload_eval_interval_ms=60_000.0,
            )
            h = Harness(opts)
            await h.server.serve()
            gov = h.server.overload
            pressure = [0.8]
            gov.add_source("test", lambda: pressure[0])
            gov.evaluate(force=True)
            assert gov.state == THROTTLE

            pub_r, pub_w, _ = await h.connect("pub")
            # sync this client's quota window with one cheap round trip
            pub_w.write(
                encode_packet(
                    Packet(fixed_header=FixedHeader(type=PINGREQ), protocol_version=4)
                )
            )
            await pub_w.drain()
            await read_wire_packet(pub_r)

            deadline = time.monotonic() + 8
            while gov.throttled == 0 and time.monotonic() < deadline:
                pub_w.write(
                    b"".join(pub_packet("t/x", b"p") for _ in range(10))
                )
                await pub_w.drain()
                await asyncio.sleep(0.05)
            assert gov.throttled >= 1
            cl = h.server.clients.get("pub")
            assert cl._pub_count > 5

            await h.server.close()
            await h.shutdown()

        run(scenario())


# -- slow-marked: the sustained 10x storm (chaos smoke) ----------------------


@pytest.mark.slow
class TestSustainedStorm:
    def test_sustained_storm_10x(self):
        """The full acceptance drill at sustained scale: a seeded storm
        whose offered rate is >= 10x the admitted (sustainable) rate,
        with a slow consumer in the blast radius. Caps hold, admitted
        QoS1 delivery is exact, sheds carry 0x97, the slow consumer is
        evicted, and the governor recovers to NORMAL."""

        async def scenario():
            # a STICKY shed posture: the exit band sits near zero, the
            # dwell is long (NORMAL dips between pressure waves are what
            # admit excess traffic), evaluation is frequent (short dips),
            # and the batch cap is small so the pipeline cannot amortize
            # the whole blast into a handful of device batches — together
            # these keep the offered:admitted ratio >= 10x measurable
            h = Harness(
                storm_options(
                    shed_quota=1,
                    shed_enter=0.30,
                    shed_exit=0.02,
                    throttle_enter=0.15,
                    throttle_exit=0.01,
                    eval_ms=25.0,
                    dwell_ms=2000.0,
                    grace_ms=300.0,
                    overload_client_buffer_limit_bytes=8192,
                    overload_quota_window_ms=100.0,
                    matcher_stage_max_batch=64,
                )
            )
            h.server.matcher = FaultyMatcher(
                h.server.matcher, FaultPlan(seed=9, slow_rate=1.0, slow_s=0.05)
            )
            await h.server.serve()
            gov = h.server.overload
            # pin the stage to tiny batches: sustainable service is then
            # ~8 topics / 50ms = 160 msg/s, an order of magnitude under
            # the blast — the 10x-over-sustainable operating point
            stage = h.server._stage
            stage.min_batch = stage.max_batch = stage._batch_cap = 8

            msgs = int(os.environ.get("STORM_MSGS", "1500"))
            # small payloads keep the BLAST fast (big ones throttle the
            # publishers themselves below the pipeline's sustainable
            # rate, and the governor then legitimately recovers mid-run)
            plan = StormPlan(
                seed=1207, publishers=8, msgs_per_publisher=msgs,
                topic_space=16, qos1_fraction=0.5, payload_pad=64,
            )
            t0 = time.perf_counter()
            # the healthy subscriber watches ONE publisher's subtree: the
            # oracle stays exact over that slice while the subscriber
            # itself stays comfortably inside its drain budget (a sub on
            # the full 8-publisher blast would legitimately become a
            # slow consumer on this shared event loop)
            admitted, shed, ack_times, collector, slow_conn = (
                await run_publish_storm(
                    h, plan, slow_consumer=True, sub_filter="storm/p0/#"
                )
            )
            storm_s = time.perf_counter() - t0
            offered = plan.publishers * msgs
            offered_rate = offered / storm_s
            admitted_qos1 = len(admitted)
            delivered = await collector.finish()
            admitted_p0 = {t for t in admitted if t.startswith(b"s0-")}
            shed_p0 = {t for t in shed if t.startswith(b"s0-")}
            lat = collector.admitted_latencies(admitted_p0, ack_times)

            from collections import Counter

            counts = Counter(delivered)
            assert admitted_p0, "publisher 0 had nothing admitted"
            for tag in admitted_p0:
                assert counts[tag] == 1
            for tag in shed_p0:
                assert counts[tag] == 0

            # 10x: the blast offered at least 10x what was admitted
            assert offered >= 10 * admitted_qos1, (
                f"offered={offered} admitted_qos1={admitted_qos1} "
                f"rate={offered_rate:.0f}/s in {storm_s:.1f}s"
            )
            # bounded backlogs under the sustained blast
            stage = h.server._stage
            assert stage.peak_pending <= stage.max_pending
            assert gov.peak_pressures.get("outbound", 0.0) <= 1.0
            # admitted-traffic fan-out p99 stays bounded
            if lat:
                assert lat[max(0, int(len(lat) * 0.99) - 1)] < 3.0
            # the slow consumer's unread backlog (transport buffer far
            # past the watermark) costs it eviction under SHED; if the
            # storm's own sweeps didn't catch it, hold the posture long
            # enough for the grace window — the backlog is still there
            if gov.evictions == 0:
                hold = [1.0]
                gov.add_source("hold", lambda: hold[0])
                gov.evaluate(force=True)
                h.server.sweep_overload()
                await asyncio.sleep(0.35)
                h.server.sweep_overload()
                hold[0] = 0.0
            slow_r, slow_w = slow_conn
            slow_w.transport.resume_reading()  # the victim reads its fate
            saw_disconnect = False
            try:
                while True:
                    pk = await asyncio.wait_for(read_wire_packet(slow_r, 5), 3)
                    if pk.fixed_header.type == DISCONNECT:
                        saw_disconnect = pk.reason_code == 0x97
                        break
            except (asyncio.TimeoutError, asyncio.IncompleteReadError):
                pass
            assert gov.evictions >= 1
            victim = h.server.clients.get("slowpoke")
            assert saw_disconnect or victim is None or victim.closed

            assert await await_normal(gov, timeout_s=10.0) == NORMAL
            h.server.publish_sys_topics()
            state = h.server.topics.retained.get(
                SYS_PREFIX + "/broker/overload/state"
            )
            assert state is not None and state.payload.decode() == NORMAL

            await h.server.close()
            await h.shutdown()

        asyncio.run(asyncio.wait_for(scenario(), timeout=300))
