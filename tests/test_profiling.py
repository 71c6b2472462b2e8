"""The host observability planes that stay on with telemetry
(mqtt_tpu.utils.locked and the write paths' counters): lock-plane
wait/hold math, fan-out amplification accounting against a known
fan-out, and the lock families on /metrics.
"""

import asyncio
import threading
import time

import pytest

from mqtt_tpu import Options
from mqtt_tpu.packets import SUBACK, Subscription
from mqtt_tpu.utils.locked import (
    DEFAULT_PLANE,
    InstrumentedLock,
    LockPlane,
    LockedMap,
)

from tests.test_server import (
    Harness,
    ObservingHook,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)

TIMEOUT = 5


class TestLockPlane:
    def test_disarmed_lock_records_nothing(self):
        plane = LockPlane()
        lk = InstrumentedLock("topics_trie", plane=plane)
        with lk:
            pass
        st = plane.stats("topics_trie")
        assert st.acquisitions == 0 and st.hold_hist.count == 0

    def test_armed_uncontended_hold_math(self):
        plane = LockPlane()
        plane.arm()
        lk = InstrumentedLock("clients", plane=plane)
        for _ in range(5):
            with lk:
                pass
        st = plane.stats("clients")
        assert st.acquisitions == 5
        assert st.contended == 0
        assert st.hold_hist.count == 5
        assert st.wait_hist.count == 0  # wait histogram only on contention
        assert st.hold_s > 0.0

    def test_contended_wait_is_measured(self):
        plane = LockPlane()
        plane.arm()
        lk = InstrumentedLock("flight_ring", plane=plane)
        entered = threading.Event()
        release = threading.Event()

        def holder():
            with lk:
                entered.set()
                release.wait(TIMEOUT)

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        assert entered.wait(TIMEOUT)
        waited = [0.0]

        def contender():
            t0 = time.perf_counter()
            with lk:
                waited[0] = time.perf_counter() - t0

        c = threading.Thread(target=contender, daemon=True)
        c.start()
        time.sleep(0.05)  # let the contender actually block
        release.set()
        t.join(TIMEOUT)
        c.join(TIMEOUT)
        st = plane.stats("flight_ring")
        assert st.acquisitions == 2
        assert st.contended == 1
        assert st.wait_hist.count == 1
        # the measured wait must be in the ballpark of the real block
        assert st.wait_s == pytest.approx(waited[0], rel=0.5, abs=0.05)
        assert st.wait_s >= 0.04

    def test_rlock_reentry_times_outermost_only(self):
        plane = LockPlane()
        plane.arm()
        lk = InstrumentedLock("topics_trie", rlock=True, plane=plane)
        with lk:
            with lk:
                with lk:
                    pass
        st = plane.stats("topics_trie")
        assert st.acquisitions == 1
        assert st.hold_hist.count == 1

    def test_top_contended_and_wait_share(self):
        plane = LockPlane()
        hot = plane.stats("clients")
        cold = plane.stats("retained")
        hot.wait_s = 3.0
        hot.acquisitions = 10
        cold.wait_s = 1.0
        cold.acquisitions = 10
        top = plane.top_contended(2)
        assert [t["name"] for t in top] == ["clients", "retained"]
        assert plane.wait_share("clients") == pytest.approx(0.75)
        assert plane.wait_share("retained") == pytest.approx(0.25)

    def test_same_name_shares_stats_and_reset(self):
        plane = LockPlane()
        plane.arm()
        a = InstrumentedLock("trace_ring", plane=plane)
        b = InstrumentedLock("trace_ring", plane=plane)
        with a:
            pass
        with b:
            pass
        assert plane.stats("trace_ring").acquisitions == 2
        plane.reset()
        assert plane.stats("trace_ring").acquisitions == 0

    def test_arm_refcounting(self):
        plane = LockPlane()
        plane.arm()
        plane.arm()
        plane.disarm()
        assert plane.enabled  # second holder still live
        plane.disarm()
        assert not plane.enabled

    def test_disarm_mid_hold_keeps_depth_coherent(self):
        """Disarming while a thread HOLDS the lock must still unwind the
        re-entrancy depth on release, or stats go silently blind after a
        later re-arm."""
        plane = LockPlane()
        plane.arm()
        lk = InstrumentedLock("overload_governor", plane=plane)
        lk.acquire()  # depth 0 -> 1 while armed
        plane.disarm()
        lk.release()  # disarmed: must STILL decrement depth
        plane.arm()
        with lk:
            pass
        st = plane.stats("overload_governor")
        assert st.acquisitions == 2  # the re-armed acquire was outermost
        assert st.hold_hist.count == 1  # mid-hold disarm skipped its observe

    def test_reset_zeroes_in_place_for_live_locks(self):
        """reset() must zero the records live locks already hold, not
        replace them — otherwise pre-reset locks keep feeding orphans
        while top_contended reads fresh zeroed copies."""
        plane = LockPlane()
        plane.arm()
        lk = InstrumentedLock("clients", plane=plane)
        with lk:
            pass
        st_before = plane.stats("clients")
        plane.reset()
        assert st_before.acquisitions == 0
        with lk:
            pass
        assert plane.stats("clients") is st_before
        assert plane.stats("clients").acquisitions == 1
        assert plane.top_contended(3)[0]["acquisitions"] == 1

    def test_named_locked_map_instruments(self):
        plane_was = DEFAULT_PLANE.enabled
        DEFAULT_PLANE.arm()
        try:
            base = DEFAULT_PLANE.stats("retained").acquisitions
            m = LockedMap(name="retained")
            m.add("k", 1)
            assert m.get("k") == 1
            assert DEFAULT_PLANE.stats("retained").acquisitions >= base + 2
        finally:
            DEFAULT_PLANE.disarm()
            assert DEFAULT_PLANE.enabled == plane_was or DEFAULT_PLANE.enabled

    def test_non_blocking_acquire_contract(self):
        plane = LockPlane()
        plane.arm()
        lk = InstrumentedLock("matcher_breaker", plane=plane)
        got = lk.acquire(blocking=False)
        assert got
        results = []

        def try_it():
            results.append(lk.acquire(blocking=False))

        t = threading.Thread(target=try_it, daemon=True)
        t.start()
        t.join(TIMEOUT)
        assert results == [False]
        lk.release()


# -- topic sketch ------------------------------------------------------------


class TestFanoutAmplification:
    def test_qos1_fanout_encodes_once_per_variant(self):
        """QoS1 publish to N same-variant QoS1 subscribers: the batched
        fan-out (ISSUE 13) encodes the wire frame ONCE and patches each
        target's packet id at flush — encodes == variants == 1,
        deliveries == N, amplification ~1 (the exact waste ROADMAP
        item 3 named, eliminated). Every subscriber still receives a
        distinct, valid packet id."""

        async def scenario():
            h = Harness(Options(inline_client=True, telemetry_sample=1))
            subs = []
            n = 4
            for i in range(n):
                r, w, _ = await h.connect(f"s{i}", version=5)
                w.write(
                    sub_packet(
                        1, [Subscription(filter="amp/t", qos=1)], version=5
                    )
                )
                await w.drain()
                assert (await read_wire_packet(r, 5)).fixed_header.type == SUBACK
                subs.append((r, w))
            pr, pw, _ = await h.connect("pub", version=5)
            pw.write(pub_packet("amp/t", b"x", qos=1, pid=9, version=5))
            await pw.drain()
            for r, _w in subs:
                pk = await read_wire_packet(r, 5)
                assert pk.topic_name == "amp/t"
                assert pk.fixed_header.qos == 1
                # a real per-target id was patched over the shared
                # encode (ids are per-client spaces [MQTT-2.2.1])
                assert pk.packet_id > 0
            tele = h.server.telemetry
            assert h.server.info.messages_received == 1
            assert tele.publish_encodes.value == 1
            assert tele.fanout_variants.value == 1
            assert tele.fanout_deliveries.value == n
            assert tele.outbound_bytes.value > 0
            await h.shutdown()

        run(scenario())

    def test_qos1_fanout_under_observing_hook_encodes_per_target(self):
        """A hook that observes sends takes the fan-out onto the
        per-subscriber encode path — kept as the differential oracle
        for the batched path."""

        async def scenario():
            h = Harness(Options(inline_client=True, telemetry_sample=1))
            h.server.add_hook(ObservingHook())
            subs = []
            n = 4
            for i in range(n):
                r, w, _ = await h.connect(f"s{i}", version=5)
                w.write(
                    sub_packet(
                        1, [Subscription(filter="amp/t", qos=1)], version=5
                    )
                )
                await w.drain()
                assert (await read_wire_packet(r, 5)).fixed_header.type == SUBACK
                subs.append((r, w))
            pr, pw, _ = await h.connect("pub", version=5)
            pw.write(pub_packet("amp/t", b"x", qos=1, pid=9, version=5))
            await pw.drain()
            for r, _w in subs:
                pk = await read_wire_packet(r, 5)
                assert pk.topic_name == "amp/t"
                assert pk.fixed_header.qos == 1
            tele = h.server.telemetry
            assert tele.publish_encodes.value == n
            assert tele.fanout_deliveries.value == n
            assert tele.fanout_variants.value == 0
            await h.shutdown()

        run(scenario())

    def test_qos0_v5_fanout_encodes_once_per_variant(self):
        """QoS0 publish to N shareable v5 subscribers: ONE encode per
        (version, retain) variant, N deliveries — the flat-amplification
        shape of the batched path."""

        async def scenario():
            h = Harness(Options(inline_client=True, telemetry_sample=1))
            subs = []
            n = 4
            for i in range(n):
                r, w, _ = await h.connect(f"s{i}", version=5)
                w.write(
                    sub_packet(
                        1, [Subscription(filter="amp/t", qos=0)], version=5
                    )
                )
                await w.drain()
                assert (await read_wire_packet(r, 5)).fixed_header.type == SUBACK
                subs.append((r, w))
            pr, pw, _ = await h.connect("pub", version=5)
            pw.write(pub_packet("amp/t", b"x", version=5))
            await pw.drain()
            for r, _w in subs:
                pk = await read_wire_packet(r, 5)
                assert pk.topic_name == "amp/t"
            tele = h.server.telemetry
            inbound = h.server.info.messages_received
            assert tele.publish_encodes.value / inbound == pytest.approx(1.0)
            assert tele.fanout_deliveries.value / inbound == pytest.approx(n)
            await h.shutdown()

        run(scenario())

    def test_v4_shared_frame_encodes_once(self):
        """The same fan-out with v4 subscribers rides the shared-frame
        fast path: deliveries == N but the frame is never re-encoded
        (encodes == 0 on the passthrough leg — the inbound bytes ARE the
        outbound bytes), which is exactly the flat-amplification shape
        ROADMAP item 3 wants from the decode path too."""

        async def scenario():
            h = Harness(Options(inline_client=True, telemetry_sample=1))
            subs = []
            n = 3
            for i in range(n):
                r, w, _ = await h.connect(f"s{i}", version=4)
                w.write(sub_packet(1, [Subscription(filter="amp/t", qos=0)]))
                await w.drain()
                assert (await read_wire_packet(r)).fixed_header.type == SUBACK
                subs.append((r, w))
            pr, pw, _ = await h.connect("pub", version=4)
            pw.write(pub_packet("amp/t", b"x"))
            await pw.drain()
            for r, _w in subs:
                pk = await read_wire_packet(r)
                assert pk.topic_name == "amp/t"
            tele = h.server.telemetry
            inbound = h.server.info.messages_received
            assert tele.publish_encodes.value == 0
            assert tele.fanout_deliveries.value / inbound == pytest.approx(n)
            # per-client mirrors saw the writes
            total_writes = sum(
                cl.state.out_writes
                for cl in h.server.clients.get_all().values()
            )
            assert total_writes >= n
            await h.shutdown()

        run(scenario())

    def test_sys_fanout_excluded_from_amplification(self):
        """$SYS housekeeping republishes every interval with no inbound
        publish behind it — it must not count toward the encode/delivery
        amplification the ROADMAP item 3 gate watches."""

        async def scenario():
            h = Harness(Options(inline_client=True, telemetry_sample=1))
            r, w, _ = await h.connect("sys-watcher", version=4)
            w.write(sub_packet(1, [Subscription(filter="$SYS/#", qos=0)]))
            await w.drain()
            assert (await read_wire_packet(r)).fixed_header.type == SUBACK
            tele = h.server.telemetry
            before = (tele.publish_encodes.value, tele.fanout_deliveries.value)
            h.server.publish_sys_topics()
            # drain a few delivered $SYS publishes so the write loop ran
            for _ in range(3):
                pk = await read_wire_packet(r)
                assert pk.topic_name.startswith("$SYS")
            await asyncio.sleep(0)
            assert (
                tele.publish_encodes.value,
                tele.fanout_deliveries.value,
            ) == before
            await h.shutdown()

        run(scenario())

# -- lock metrics on /metrics ------------------------------------------------


class TestLockMetricsExposition:
    def test_lock_families_render_and_accumulate(self):
        async def scenario():
            from mqtt_tpu.telemetry import check_exposition

            h = Harness(Options(inline_client=True, telemetry_sample=1))
            plane = h.server.telemetry.lock_plane
            assert plane is not None
            plane.arm()  # Harness never serve()s, so arm explicitly
            try:
                r, w, _ = await h.connect("s0", version=4)
                w.write(sub_packet(1, [Subscription(filter="lm/#", qos=0)]))
                await w.drain()
                assert (await read_wire_packet(r)).fixed_header.type == SUBACK
                pr, pw, _ = await h.connect("pub", version=4)
                pw.write(pub_packet("lm/a", b"x"))
                await pw.drain()
                await read_wire_packet(r)
                text = h.server.telemetry.exposition()
                assert check_exposition(text) > 0
                assert 'mqtt_tpu_lock_wait_seconds_bucket{lock="clients"' in text
                assert 'mqtt_tpu_lock_hold_seconds_count{lock="clients"}' in text
                line = next(
                    l
                    for l in text.splitlines()
                    if l.startswith(
                        'mqtt_tpu_lock_acquisitions_total{lock="clients"}'
                    )
                )
                assert int(float(line.rsplit(" ", 1)[1])) > 0
            finally:
                plane.disarm()
            await h.shutdown()

        run(scenario())
