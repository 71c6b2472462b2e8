"""The host hot-path observatory (mqtt_tpu.profiling +
mqtt_tpu.utils.locked): sampler determinism under a seeded synthetic
thread workload, the collapsed-stack and trace-event exports + their
validators, lock-plane wait/hold math, fan-out amplification accounting
against a known fan-out, space-saving sketch accuracy bounds, and the
GET /profile HTTP matrix.
"""

import asyncio
import json
import threading
import time

import pytest

from mqtt_tpu import Options, Server
from mqtt_tpu.listeners import Config as LConfig, HTTPStats
from mqtt_tpu.packets import SUBACK, Subscription
from mqtt_tpu.profiling import (
    SamplingProfiler,
    TopicSketch,
    check_collapsed,
)
from mqtt_tpu.tracing import check_trace_events
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


# -- deterministic sampler: synthetic frames ---------------------------------


class _FakeCode:
    def __init__(self, name, filename):
        self.co_name = name
        self.co_filename = filename


class _FakeFrame:
    """A minimal stand-in for an interpreter frame: f_code/f_lineno/f_back."""

    def __init__(self, name, lineno, back=None, filename="synthetic.py"):
        self.f_code = _FakeCode(name, filename)
        self.f_lineno = lineno
        self.f_back = back


def _stack(*names):
    """Build a frame chain; names given root-first, returns the LEAF."""
    frame = None
    for i, name in enumerate(names):
        frame = _FakeFrame(name, 10 + i, back=frame)
    return frame


class TestSamplerDeterminism:
    def _profiler(self, frames_by_sweep):
        """A profiler fed a scripted sequence of _current_frames dicts
        and a scripted clock — fully deterministic."""
        sweeps = iter(frames_by_sweep)
        t = [0.0]

        def clock():
            t[0] += 0.005
            return t[0]

        return SamplingProfiler(
            hz=100.0, frames_fn=lambda: next(sweeps), clock=clock
        )

    def test_collapsed_aggregation_and_counts(self):
        leaf = _stack("serve", "fan_out", "encode")
        p = self._profiler([{1: leaf}, {1: leaf}, {1: leaf}])
        for _ in range(3):
            p.sample_once()
        txt = p.collapsed()
        assert check_collapsed(txt) == 1  # one distinct stack
        line = txt.strip()
        assert line.endswith(" 3")
        # root-first order: serve;fan_out;encode
        assert line.index("serve") < line.index("fan_out") < line.index("encode")
        assert "(synthetic.py:" in line
        assert p.samples == 3 and p.thread_samples == 3

    def test_distinct_stacks_and_thread_names(self):
        a = _stack("loop", "read")
        b = _stack("loop", "write")
        p = self._profiler([{1: a, 2: b}, {1: a, 2: b}])
        p.sample_once()
        p.sample_once()
        txt = p.collapsed()
        assert check_collapsed(txt) == 2
        # unnamed tids fall back to a stable synthetic thread name
        assert "thread-1;" in txt and "thread-2;" in txt

    def test_own_thread_never_sampled(self):
        own = threading.get_ident()
        leaf = _stack("me")
        p = self._profiler([{own: leaf, 99: leaf}])
        assert p.sample_once() == 1  # only the foreign thread
        assert "me" in p.collapsed()

    def test_stack_cap_counts_drops(self):
        p = SamplingProfiler(
            hz=10, frames_fn=lambda: {}, clock=time.perf_counter, max_stacks=16
        )
        for i in range(40):
            p._agg[("t", (f"f{i}",))] = 1  # simulate 16-cap overflow input
        # cap enforcement happens on the sample path:
        sweeps = iter([{7: _stack(f"g{i}")} for i in range(40)])
        p2 = SamplingProfiler(hz=10, frames_fn=lambda: next(sweeps), max_stacks=16)
        for _ in range(40):
            p2.sample_once()
        assert len(p2._agg) == 16
        assert p2.dropped_stacks == 24

    def test_trace_events_merge_consecutive_samples(self):
        """Three identical samples then a divergence at depth 1 become
        one long span per shared frame plus split spans below it."""
        a = _stack("root", "walk")
        b = _stack("root", "encode")
        p = self._profiler([{5: a}, {5: a}, {5: b}])
        for _ in range(3):
            p.sample_once()
        doc = p.trace_events()
        assert check_trace_events(doc) > 0
        names = [e["name"] for e in doc["traceEvents"]]
        roots = [e for e in doc["traceEvents"] if "root" in e["name"]]
        assert len(roots) == 1  # merged across all three samples
        assert any("walk" in n for n in names)
        assert any("encode" in n for n in names)
        walk = next(e for e in doc["traceEvents"] if "walk" in e["name"])
        root = roots[0]
        assert root["dur"] >= walk["dur"]

    def test_live_thread_sampling_lands_known_function(self):
        """A real (non-scripted) sweep over a live thread parked in a
        distinctively-named function finds that function."""
        ev = threading.Event()

        def profiling_target_fn():
            ev.wait(TIMEOUT)

        t = threading.Thread(target=profiling_target_fn, daemon=True, name="px")
        t.start()
        try:
            p = SamplingProfiler(hz=100)
            time.sleep(0.02)  # let the worker reach the wait
            p.sample_once()
            txt = p.collapsed()
            assert "profiling_target_fn" in txt
            assert "px;" in txt
        finally:
            ev.set()
            t.join(TIMEOUT)

    def test_start_stop_thread_lifecycle(self):
        p = SamplingProfiler(hz=200)
        p.start()
        try:
            deadline = time.monotonic() + TIMEOUT
            while p.samples == 0 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert p.samples > 0
        finally:
            p.stop()
        assert p._thread is None


# -- validators --------------------------------------------------------------


class TestCheckCollapsed:
    def test_accepts_valid(self):
        good = "main;f (x.py:1);g (x.py:2) 5\nother;h (y.py:3) 1\n"
        assert check_collapsed(good) == 2

    def test_rejects_bad_count(self):
        with pytest.raises(ValueError):
            check_collapsed("main;f 0\n")
        with pytest.raises(ValueError):
            check_collapsed("main;f notanumber\n")

    def test_rejects_empty_frame_and_empty_doc(self):
        with pytest.raises(ValueError):
            check_collapsed("main;;f 3\n")
        with pytest.raises(ValueError):
            check_collapsed("\n\n")

    def test_profile_trace_export_passes_trace_checker(self):
        p = SamplingProfiler(hz=100, frames_fn=lambda: {3: _stack("a", "b")})
        p.sample_once()
        n = check_trace_events(json.dumps(p.trace_events()))
        assert n >= 2  # one span per open frame depth


# -- lock plane --------------------------------------------------------------


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


class TestTopicSketch:
    def test_exact_when_under_capacity(self):
        sk = TopicSketch(k=16)
        for i in range(10):
            for _ in range(i + 1):
                sk.observe(f"t/{i}")
        top = sk.top(3)
        assert top[0] == {"topic": "t/9", "count": 10, "err": 0}
        assert sk.tracked == 10
        assert sk.evictions == 0
        assert sk.total == sum(range(1, 11))

    def test_space_saving_error_bounds(self):
        """Every tracked count is within `err` of the true count, and a
        topic whose true count exceeds min_count is guaranteed tracked
        (the Metwally guarantees the compaction sizing relies on)."""
        import random

        rng = random.Random(7)
        sk = TopicSketch(k=32)
        true: dict = {}
        # zipf-ish: a few hot topics, a long cold tail
        for _ in range(5000):
            if rng.random() < 0.6:
                t = f"hot/{rng.randrange(8)}"
            else:
                t = f"cold/{rng.randrange(800)}"
            true[t] = true.get(t, 0) + 1
            sk.observe(t)
        tracked = {d["topic"]: d for d in sk.top(32)}
        for topic, d in tracked.items():
            assert true[topic] <= d["count"], "sketch must never undercount"
            assert d["count"] - d["err"] <= true[topic]
        floor = sk.min_count()
        for topic, n in true.items():
            if n > floor:
                assert topic in tracked, (topic, n, floor)

    def test_avg_hits_is_a_lower_bound(self):
        sk = TopicSketch(k=8)
        for _ in range(40):
            sk.observe("hot")
        for i in range(10):
            sk.observe(f"cold/{i}")
        true_avg = 50 / 11
        assert 0 < sk.avg_hits_per_topic() <= true_avg + 1e-9

    def test_counters_and_top_after_one_observation(self):
        sk = TopicSketch(k=8)
        sk.observe("a")
        assert sk.total == 1 and sk.tracked == 1
        assert sk.admissions == 1 and sk.evictions == 0
        assert sk.top(5)[0]["topic"] == "a"


# -- amplification accounting vs a known fan-out -----------------------------


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

    def test_sketch_observes_sampled_topics(self):
        async def scenario():
            h = Harness(Options(inline_client=True, telemetry_sample=1))
            r, w, _ = await h.connect("s0", version=4)
            w.write(sub_packet(1, [Subscription(filter="sk/#", qos=0)]))
            await w.drain()
            assert (await read_wire_packet(r)).fixed_header.type == SUBACK
            pr, pw, _ = await h.connect("pub", version=4)
            for i in range(6):
                pw.write(pub_packet(f"sk/{i % 2}", b"x"))
            await pw.drain()
            for _ in range(6):
                await read_wire_packet(r)
            sk = h.server.topic_sketch
            assert sk is not None
            assert sk.total == 6
            tops = {d["topic"] for d in sk.top(4)}
            assert tops == {"sk/0", "sk/1"}
            await h.shutdown()

        run(scenario())


# -- HTTP matrix -------------------------------------------------------------


async def _http(host, port, path, method="GET"):
    reader, writer = await asyncio.open_connection(host, int(port))
    writer.write(f"{method} {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    data = await asyncio.wait_for(reader.read(262144), TIMEOUT)
    writer.close()
    return data


class TestProfileHttpMatrix:
    def test_profile_matrix_and_formats(self):
        async def scenario():
            h = Harness(Options(inline_client=True))
            # make sure the profiler has at least one aggregated stack
            ev = threading.Event()

            def profile_http_probe_fn():
                ev.wait(TIMEOUT)

            t = threading.Thread(
                target=profile_http_probe_fn, daemon=True, name="probe"
            )
            t.start()
            await asyncio.sleep(0.02)
            h.server.host_profiler.sample_once()
            st = HTTPStats(
                LConfig(type="sysinfo", id="s", address="127.0.0.1:0"),
                h.server.info,
                telemetry=h.server.telemetry,
            )
            await st.init(h.server.log)
            host, port = st.address().rsplit(":", 1)
            # GET /profile: collapsed text, no-store
            data = await _http(host, port, "/profile")
            head, body = data.split(b"\r\n\r\n", 1)
            assert head.startswith(b"HTTP/1.1 200")
            assert b"Cache-Control: no-store" in head
            assert b"text/plain" in head
            assert check_collapsed(body.decode()) > 0
            assert b"profile_http_probe_fn" in body
            # trace format: Perfetto-loadable
            data = await _http(host, port, "/profile?format=trace")
            head, body = data.split(b"\r\n\r\n", 1)
            assert head.startswith(b"HTTP/1.1 200")
            assert b"application/json" in head
            assert check_trace_events(json.loads(body.decode())) > 0
            # non-GET on the KNOWN path: 405 with Allow
            post = await _http(host, port, "/profile", "POST")
            assert post.startswith(b"HTTP/1.1 405") and b"Allow: GET" in post
            ev.set()
            t.join(TIMEOUT)
            await st.close(lambda _: None)
            await h.shutdown()

        run(scenario())

    def test_profile_404_without_profiler(self):
        async def scenario():
            h = Harness(Options(inline_client=True, profile=False))
            assert h.server.host_profiler is None
            st = HTTPStats(
                LConfig(type="sysinfo", id="s", address="127.0.0.1:0"),
                h.server.info,
                telemetry=h.server.telemetry,
            )
            await st.init(h.server.log)
            host, port = st.address().rsplit(":", 1)
            assert (await _http(host, port, "/profile")).startswith(
                b"HTTP/1.1 404"
            )
            # 404 wins over 405 when the surface does not exist at all
            assert (await _http(host, port, "/profile", "POST")).startswith(
                b"HTTP/1.1 404"
            )
            await st.close(lambda _: None)
            await h.shutdown()

        run(scenario())

    def test_profile_404_without_telemetry(self):
        async def scenario():
            h = Harness(Options(inline_client=True, telemetry=False))
            st = HTTPStats(
                LConfig(type="sysinfo", id="s", address="127.0.0.1:0"),
                h.server.info,
                telemetry=h.server.telemetry,
            )
            await st.init(h.server.log)
            host, port = st.address().rsplit(":", 1)
            assert (await _http(host, port, "/profile")).startswith(
                b"HTTP/1.1 404"
            )
            await st.close(lambda _: None)
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

    def test_trigger_dump_writes_profile_sibling(self, tmp_path):
        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    telemetry_dump_dir=str(tmp_path),
                    telemetry_dump_min_interval_ms=0.0,
                )
            )
            h.server.host_profiler.sample_once()
            h.server.telemetry.trigger_dump("test_reason")
            h.server.telemetry.recorder.join_writer()
            names = sorted(p.name for p in tmp_path.iterdir())
            assert any(n.startswith("flight_") for n in names), names
            profs = [n for n in names if n.startswith("profile_")]
            assert profs, names
            assert profs[0].endswith(".txt")
            await h.shutdown()

        run(scenario())
