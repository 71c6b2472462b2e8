"""Chaos suite for the degradation manager (mqtt_tpu.resilience) and the
worker-mesh link hardening (mqtt_tpu.cluster), driven by the seeded fault
injector (mqtt_tpu.faults).

Covers: breaker state machine + backoff determinism, the guard pool's
wedged-worker accounting, every injectable fault class (hang / error /
issue_error / corrupt / slow) resolving bit-identical to the host-trie
oracle within the watchdog budget, automatic half-open recovery, the
end-to-end staged broker under seeded chaos with $SYS gauge assertions,
and mesh peer-link kill/stall with reconnect + presence resync.
"""

import asyncio
import random
import threading
import time

import pytest

from mqtt_tpu import Options, Server
from mqtt_tpu.faults import (
    CHAOS_CLIENT,
    FaultPlan,
    FaultyMatcher,
    sever_peer_link,
)
from mqtt_tpu.hooks.chaos import ChaosHook, ChaosOptions
from mqtt_tpu.ops.matcher import subscribers_equal
from mqtt_tpu.packets import PUBLISH, SUBACK, Subscription
from mqtt_tpu.resilience import (
    CLOSED,
    OPEN,
    Backoff,
    BreakerConfig,
    CircuitBreaker,
    GuardPool,
    GuardTimeout,
    ResilientMatcher,
)
from mqtt_tpu.topics import SYS_PREFIX, Subscribers, TopicsIndex

from tests.test_server import (
    Harness,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)


class HostBatchMatcher:
    """A 'device' matcher that actually walks the host trie — the perfect
    substrate for fault injection: healthy dispatches are bit-identical
    to the oracle by construction, so any divergence IS the fault."""

    def __init__(self, index: TopicsIndex) -> None:
        self.index = index
        self.dispatches = 0

    def match_topics_async(self, topics):
        self.dispatches += 1
        index = self.index

        def resolve():
            return [
                index.subscribers(t) if t else Subscribers() for t in topics
            ]

        return resolve

    def close(self) -> None:
        pass


def small_index() -> TopicsIndex:
    ti = TopicsIndex()
    ti.subscribe("alice", Subscription(filter="a/+", qos=1))
    ti.subscribe("bob", Subscription(filter="a/b"))
    ti.subscribe("carol", Subscription(filter="c/#"))
    return ti


def fast_config(**kw) -> BreakerConfig:
    kw.setdefault("failure_threshold", 3)
    kw.setdefault("watchdog_s", 0.2)
    kw.setdefault("probe_backoff_s", 0.03)
    kw.setdefault("probe_backoff_max_s", 0.2)
    kw.setdefault("probe_jitter", 0.0)
    kw.setdefault("probe_successes", 1)
    kw.setdefault("verify_sample", 8)
    kw.setdefault("seed", 7)
    return BreakerConfig(**kw)


def oracle(ti, topics):
    return [ti.subscribers(t) if t else Subscribers() for t in topics]


def assert_oracle(ti, topics, results):
    want = oracle(ti, topics)
    assert len(results) == len(want)
    for r, w in zip(results, want):
        assert subscribers_equal(r, w)


# -- unit: backoff + breaker state machine ----------------------------------


class TestBackoff:
    def test_deterministic_growth_and_cap(self):
        a = Backoff(initial=0.1, maximum=1.0, jitter=0.2, seed=42)
        b = Backoff(initial=0.1, maximum=1.0, jitter=0.2, seed=42)
        seq_a = [a.next() for _ in range(8)]
        seq_b = [b.next() for _ in range(8)]
        assert seq_a == seq_b  # same seed, same schedule
        # grows geometrically and respects the cap (+20% jitter headroom)
        assert seq_a[0] < seq_a[2] < seq_a[4]
        assert all(d <= 1.0 * 1.2 + 1e-9 for d in seq_a)
        a.reset()
        assert a.next() <= 0.1 * 1.2 + 1e-9

    def test_huge_attempt_counts_do_not_overflow(self):
        """Regression: factor**attempts overflowed a float before min()
        could cap it, killing the re-dial loop after a ~day-long outage."""
        a = Backoff(initial=0.05, maximum=2.0, jitter=0.0)
        for _ in range(1200):
            assert a.next() <= 2.0

    def test_jitter_desyncs_seeds(self):
        seqs = {
            tuple(round(Backoff(0.1, 1.0, seed=s).next(), 6) for _ in range(4))
            for s in range(5)
        }
        assert len(seqs) > 1  # different seeds do not re-dial in lockstep


class TestCircuitBreaker:
    def make(self, **kw):
        t = [0.0]
        kw.setdefault("backoff", Backoff(initial=1.0, maximum=8.0, jitter=0.0))
        br = CircuitBreaker(clock=lambda: t[0], **kw)
        return br, t

    def test_trips_after_consecutive_failures_only(self):
        br, _ = self.make(failure_threshold=3)
        br.record_failure()
        br.record_failure()
        br.record_success()  # success resets the consecutive count
        br.record_failure()
        br.record_failure()
        assert br.state == CLOSED and br.allow()
        br.record_failure()
        assert br.state == OPEN and not br.allow()
        assert br.trips == 1

    def test_half_open_probe_cycle_and_backoff_growth(self):
        br, t = self.make(failure_threshold=1, probe_successes=2)
        br.record_failure("hang")
        assert br.state == OPEN
        assert not br.acquire_probe()  # backoff (1.0s) not elapsed
        delay1 = br.seconds_until_probe()
        t[0] = 1.5
        assert br.acquire_probe()
        assert br.state == "half_open"
        br.record_probe_failure("error")  # probe failed: re-open + backoff
        assert br.state == OPEN
        assert br.seconds_until_probe() > delay1  # 2.0s > 1.0s
        t[0] = 10.0
        assert br.acquire_probe()
        br.record_probe_success()  # 1 of 2: fast-follow probe, still open
        assert br.state == OPEN
        t[0] = 20.0
        assert br.acquire_probe()
        br.record_probe_success()
        assert br.state == CLOSED and br.allow()
        d = br.as_dict()
        assert d["trips"] == 2 and d["probes"] == 3
        assert d["failures_hang"] == 1 and d["failures_error"] == 1

    def test_single_probe_slot(self):
        br, t = self.make(failure_threshold=1)
        br.record_failure()
        t[0] = 5.0
        assert br.acquire_probe()
        assert not br.acquire_probe()  # slot already claimed
        assert br.acquire_probe(force=True)  # tests/ops override

    def test_stale_live_outcomes_cannot_claim_the_probe_slot(self):
        """A batch issued before the trip resolving during HALF_OPEN must
        not count as the probe's outcome in either direction."""
        br, t = self.make(failure_threshold=1, probe_successes=1)
        br.record_failure()
        t[0] = 5.0
        assert br.acquire_probe()  # HALF_OPEN, slot held
        br.record_success()  # stale live batch resolves fine...
        assert br.state == "half_open"  # ...but the breaker stays probing
        assert not br.acquire_probe()  # and the slot stays claimed
        br.record_failure("hang")  # stale live failure mid-probe
        assert br.state == "half_open"  # no spurious re-trip
        assert br.probe_failures == 0
        br.record_probe_success()  # only the probe's verdict closes it
        assert br.state == CLOSED


class TestGuardPool:
    def test_hang_is_abandoned_and_capacity_recovers(self):
        pool = GuardPool(workers=1)
        release = threading.Event()
        task = pool.submit(lambda: (release.wait(5), "late")[1])
        with pytest.raises(GuardTimeout):
            task.wait(0.05)
        pool.report_wedged(task)  # spawns the substitute worker
        assert pool.wedged == 1
        # the substitute serves new work while the first call is wedged
        assert pool.submit(lambda: "fresh").wait(2) == "fresh"
        release.set()  # the hung call returns; its worker retires
        deadline = time.monotonic() + 2
        while pool.wedged and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.wedged == 0
        pool.close()

    def test_completion_racing_the_wedge_report_is_not_counted(self):
        """Regression: a call finishing between the GuardTimeout raise
        and report_wedged must not skew the wedge count negative or
        spawn a spurious replacement."""
        pool = GuardPool(workers=1)
        release = threading.Event()
        task = pool.submit(lambda: (release.wait(5), "late")[1])
        with pytest.raises(GuardTimeout):
            task.wait(0.05)
        release.set()  # completes BEFORE the caller reports the wedge
        task._done.wait(2)
        pool.report_wedged(task)
        assert pool.wedged == 0  # not a wedge: nothing counted
        assert pool.submit(lambda: "still-served").wait(2) == "still-served"
        assert pool.live_unwedged == 1  # and no spurious extra worker
        pool.close()

    def test_wedges_past_the_cap_bound_threads_and_recover(self):
        """Regression: past MAX_WEDGED the pool stopped spawning while
        abandoned workers still retired, so capacity bled to zero with
        no recovery path. Now thread growth is hard-bounded AND capacity
        returns once hung calls come back (workers beyond the spawn cap
        keep serving instead of retiring)."""
        pool = GuardPool(workers=1)
        pool.MAX_WEDGED = 2  # shrink the cap for the test
        releases = []
        for _ in range(4):  # wedge past the cap
            ev = threading.Event()
            releases.append(ev)
            task = pool.submit(lambda ev=ev: ev.wait(10))
            with pytest.raises(GuardTimeout):
                task.wait(0.1)
            pool.report_wedged(task)
        assert pool.wedged == 4
        # bounded: 1 original + MAX_WEDGED replacements, all now stuck
        # (the 4th 'wedge' is a queued abandon) — the probe path reads
        # this and stops burning threads
        assert pool.live_unwedged <= 0
        for ev in releases:  # the 'link heals': hung calls return
            ev.set()
        deadline = time.monotonic() + 3
        while pool.wedged and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.wedged == 0
        # capacity recovered without ever exceeding the thread bound
        assert pool.submit(lambda: "after").wait(2) == "after"
        assert pool.live_unwedged >= 1
        pool.close()

    def test_exceptions_ferry_to_the_waiter(self):
        pool = GuardPool(workers=1)
        task = pool.submit(lambda: 1 / 0)
        with pytest.raises(ZeroDivisionError):
            task.wait(2)
        pool.close()


# -- unit: the degradation manager over injected faults ----------------------


class TestResilientMatcherFaults:
    TOPICS = ["a/b", "a/x", "c/d/e", "nope"]

    def build(self, plan: FaultPlan, **cfg):
        ti = small_index()
        inner = HostBatchMatcher(ti)
        faulty = FaultyMatcher(inner, plan)
        rm = ResilientMatcher(faulty, ti, fast_config(**cfg))
        return ti, inner, faulty, rm

    def test_dispatch_error_falls_back_and_trips(self):
        ti, inner, faulty, rm = self.build(
            FaultPlan(at={0: "error", 1: "error", 2: "error"})
        )
        try:
            for _ in range(3):
                assert_oracle(ti, self.TOPICS, rm.match_topics(self.TOPICS))
            assert rm.breaker.state == OPEN
            assert rm.breaker.failure_kinds.get("error") == 3
            # OPEN: matching never touches the device (host route only)
            seen = inner.dispatches
            assert_oracle(ti, self.TOPICS, rm.match_topics(self.TOPICS))
            assert inner.dispatches == seen
            assert rm.fallback_batches >= 1
        finally:
            rm.close()

    def test_issue_error_is_survived(self):
        ti, _inner, _faulty, rm = self.build(
            FaultPlan(at={0: "issue_error"}), failure_threshold=1
        )
        try:
            assert_oracle(ti, self.TOPICS, rm.match_topics(self.TOPICS))
            assert rm.breaker.state == OPEN
        finally:
            rm.close()

    def test_hang_is_bounded_by_watchdog(self):
        ti, _inner, faulty, rm = self.build(
            FaultPlan(at={0: "hang"}, hang_s=10.0), failure_threshold=1
        )
        try:
            t0 = time.monotonic()
            results = rm.match_topics(self.TOPICS)
            elapsed = time.monotonic() - t0
            assert elapsed < 2.0, "publish futures must not wedge"
            assert_oracle(ti, self.TOPICS, results)
            assert rm.breaker.failure_kinds.get("hang") == 1
            assert rm.pool.wedged == 1
            faulty.release.set()  # un-wedge; the worker retires
            deadline = time.monotonic() + 2
            while rm.pool.wedged and time.monotonic() < deadline:
                time.sleep(0.01)
            assert rm.pool.wedged == 0
        finally:
            faulty.release.set()
            rm.close()

    def test_cold_compile_does_not_run_the_watchdog(self):
        """Cold compile is set-up, not a hang: a dispatch that overruns
        the budget only by the time the process spent in
        first-signature jit calls is NOT abandoned (ISSUE 21)."""
        ti = small_index()
        clock = {"s": 0.0}

        class CompilingMatcher(HostBatchMatcher):
            def match_topics_async(self, topics):
                resolve = super().match_topics_async(topics)

                def slow():
                    t0 = time.monotonic()
                    while time.monotonic() - t0 < 0.5:  # 5x the budget
                        time.sleep(0.01)
                        clock["s"] = time.monotonic() - t0
                    return resolve()

                return slow

        rm = ResilientMatcher(
            CompilingMatcher(ti), ti, fast_config(watchdog_s=0.1),
            compile_clock=lambda: clock["s"],
        )
        try:
            assert_oracle(ti, self.TOPICS, rm.match_topics(self.TOPICS))
            assert rm.breaker.failures == 0 and rm.pool.wedged == 0
            assert rm.fallback_batches == 0
        finally:
            rm.close()

    def test_compile_grace_is_bounded(self, monkeypatch):
        """A wedged compiler is still a hang: the exclusion stops at
        COMPILE_GRACE_S."""
        import mqtt_tpu.resilience as res

        monkeypatch.setattr(res, "COMPILE_GRACE_S", 0.1)
        ti, _inner, faulty, rm = self.build(
            FaultPlan(at={0: "hang"}, hang_s=10.0),
            failure_threshold=1, watchdog_s=0.1,
        )
        t0 = time.monotonic()
        rm._compile_clock = lambda: time.monotonic() - t0  # "compiling" forever
        try:
            results = rm.match_topics(self.TOPICS)
            assert time.monotonic() - t0 < 2.0
            assert_oracle(ti, self.TOPICS, results)
            assert rm.breaker.failure_kinds.get("hang") == 1
        finally:
            faulty.release.set()
            rm.close()

    def test_corrupt_result_caught_by_differential_rewalk(self):
        ti, _inner, _faulty, rm = self.build(
            FaultPlan(at={0: "corrupt"}), failure_threshold=1
        )
        try:
            results = rm.match_topics(self.TOPICS)
            # the falsified entry must NOT leak to fan-out
            assert_oracle(ti, self.TOPICS, results)
            for r in results:
                assert CHAOS_CLIENT not in r.subscriptions
            assert rm.breaker.failure_kinds.get("corrupt") == 1
            assert rm.breaker.state == OPEN
        finally:
            rm.close()

    def test_slow_link_does_not_trip(self):
        ti, _inner, _faulty, rm = self.build(
            FaultPlan(at={0: "slow"}, slow_s=0.05), watchdog_s=1.0
        )
        try:
            assert_oracle(ti, self.TOPICS, rm.match_topics(self.TOPICS))
            assert rm.breaker.state == CLOSED
            assert rm.breaker.failures == 0
        finally:
            rm.close()

    def test_automatic_half_open_recovery(self):
        """Trip the breaker, then let the BACKGROUND probe thread verify
        health and close it — no live traffic involved."""
        ti, inner, _faulty, rm = self.build(
            FaultPlan(at={0: "error", 1: "error", 2: "error"})
        )
        try:
            for _ in range(3):
                rm.match_topics(self.TOPICS)
            assert rm.breaker.state == OPEN
            deadline = time.monotonic() + 5
            while rm.breaker.state != CLOSED and time.monotonic() < deadline:
                time.sleep(0.02)
            assert rm.breaker.state == CLOSED, rm.breaker.as_dict()
            assert rm.breaker.probes >= 1
            # re-admitted: live traffic reaches the device again
            seen = inner.dispatches
            assert_oracle(ti, self.TOPICS, rm.match_topics(self.TOPICS))
            assert inner.dispatches == seen + 1
        finally:
            rm.close()

    def test_probe_now_requires_verified_health(self):
        """A probe against a STILL-corrupting device must not close the
        breaker (re-admission requires verified healthy matches)."""
        ti, _inner, _faulty, rm = self.build(
            # every dispatch corrupts, forever
            FaultPlan(corrupt_rate=1.0),
            failure_threshold=1,
            probe_backoff_s=30.0,  # keep the background prober out of it
            probe_backoff_max_s=60.0,
        )
        try:
            rm.match_topics(self.TOPICS)  # trips
            assert rm.breaker.state == OPEN
            assert rm.probe_now() is False
            assert rm.breaker.state == OPEN
            assert rm.breaker.probe_failures >= 1
        finally:
            rm.close()

    def test_churn_between_resolve_and_verify_is_not_corruption(self):
        """A SUBSCRIBE landing after the device resolve makes the live
        host walk legitimately diverge from a CORRECT device result; the
        differential check must treat that as indeterminate, not trip
        the breaker as 'corrupt'."""

        class ChurningMatcher(HostBatchMatcher):
            def match_topics_async(self, topics):
                resolver = super().match_topics_async(topics)

                def resolve():
                    results = resolver()  # correct at resolve time
                    # post-resolve churn: a new subscriber on a matched
                    # filter, before the verify step can run
                    self.index.subscribe(
                        f"late{self.dispatches}", Subscription(filter="a/+")
                    )
                    return results

                return resolve

        ti = small_index()
        rm = ResilientMatcher(
            ChurningMatcher(ti), ti, fast_config(failure_threshold=1)
        )
        try:
            for _ in range(3):
                rm.match_topics(["a/b", "a/x"])
            assert rm.breaker.state == CLOSED, rm.breaker.as_dict()
            assert "corrupt" not in rm.breaker.failure_kinds
        finally:
            rm.close()

    def test_seeded_fault_schedule_is_replayable(self):
        kinds = ["hang", "error", "corrupt", "slow", None]
        draws1 = [FaultPlan(seed=3, error_rate=0.3, slow_rate=0.2).draw(i) for i in range(64)]
        draws2 = [FaultPlan(seed=3, error_rate=0.3, slow_rate=0.2).draw(i) for i in range(64)]
        assert draws1 == draws2
        assert any(d is not None for d in draws1)
        assert all(d in kinds for d in draws1)


# -- end-to-end: staged broker under seeded chaos ----------------------------


N_PUBS = 8
MSGS_EACH = 6


def chaos_options(**kw):
    return Options(
        inline_client=True,
        device_matcher=True,
        matcher_stage_window_ms=2.0,
        matcher_opts={"max_levels": 4, "background": False},
        # fast, deterministic breaker: any fault trips; probes every
        # ~40ms verify against the host walk and close after 1 success
        breaker_failure_threshold=1,
        breaker_watchdog_ms=kw.pop("watchdog_ms", 1500.0),
        breaker_probe_backoff_ms=40.0,
        breaker_probe_backoff_max_ms=200.0,
        breaker_probe_jitter=0.0,
        breaker_probe_successes=1,
        breaker_verify_sample=8,
        **kw,
    )


async def _read_sys_gauge(h, topic):
    pk = h.server.topics.retained.get(SYS_PREFIX + topic)
    return None if pk is None else pk.payload.decode()


class TestBrokerChaos:
    def test_staged_broker_survives_seeded_fault_storm(self):
        """The acceptance drill: dispatch hang/exception/corrupt/slow at
        seeded random points under live publish traffic. Delivery stays
        bit-identical to the host-trie oracle (every message exactly
        once), no publish future outlives the watchdog budget, and the
        breaker demonstrably trips OPEN and recovers through half-open
        probes — asserted via the $SYS gauges."""

        async def scenario():
            h = Harness(chaos_options())
            await h.server.serve()

            sub_r, sub_w, _ = await h.connect("sub")
            sub_w.write(sub_packet(1, [Subscription(filter="c/#", qos=0)]))
            await sub_w.drain()
            assert (await read_wire_packet(sub_r)).fixed_header.type == SUBACK
            h.server.matcher.flush()

            pubs = []
            for i in range(N_PUBS):
                _, w, _ = await h.connect(f"pub{i}")
                pubs.append(w)

            # warm the dispatch path (first-batch compile must not eat
            # the watchdog budget), then arm chaos at seeded random
            # dispatch indices — replayable from the seed alone
            pubs[0].write(pub_packet("c/warm/up", b"w0"))
            await pubs[0].drain()
            pk = await asyncio.wait_for(read_wire_packet(sub_r), 10)
            assert pk.topic_name == "c/warm/up"

            rng = random.Random(1207)
            idxs = sorted(rng.sample(range(1, 24), 5))
            kinds = ["hang", "error", "corrupt", "slow", "error"]
            chaos = ChaosHook()
            chaos.init(
                ChaosOptions(
                    server=h.server,
                    seed=1207,
                    hang_s=3.0,
                    slow_s=0.02,
                    at=dict(zip(idxs, kinds)),
                )
            )
            chaos.install(h.server)

            async def publish_all(i, w):
                for m in range(MSGS_EACH):
                    w.write(pub_packet(f"c/p{i}/x", f"m{i}-{m}".encode()))
                    await w.drain()
                    await asyncio.sleep(0.004)  # spread across batches

            await asyncio.gather(
                *(publish_all(i, w) for i, w in enumerate(pubs))
            )

            # the oracle: the wildcard subscriber receives EVERY message
            # exactly once, each read bounded (nothing wedges past the
            # watchdog + pipeline depth)
            expect = {
                (f"c/p{i}/x", f"m{i}-{m}".encode())
                for i in range(N_PUBS)
                for m in range(MSGS_EACH)
            }
            got = []
            for _ in range(len(expect)):
                pk = await asyncio.wait_for(read_wire_packet(sub_r), 10)
                assert pk.fixed_header.type == PUBLISH
                got.append((pk.topic_name, bytes(pk.payload)))
            assert set(got) == expect, "lost deliveries"
            assert len(got) == len(expect), "duplicated deliveries"
            for topic, payload in got:
                assert CHAOS_CLIENT not in topic  # corrupt never leaked

            # the breaker tripped on the injected faults...
            assert chaos.injected, "chaos never fired"
            br = h.server.matcher.breaker
            assert br.trips >= 1, br.as_dict()
            # ...and recovers through half-open probes
            deadline = time.monotonic() + 8
            while br.state != CLOSED and time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            assert br.state == CLOSED, br.as_dict()
            assert br.probes >= 1

            # state transitions are visible through the $SYS gauges
            h.server.publish_sys_topics()
            state = await _read_sys_gauge(h, "/broker/matcher/breaker/state")
            trips = await _read_sys_gauge(h, "/broker/matcher/breaker/trips")
            fb = await _read_sys_gauge(
                h, "/broker/matcher/breaker/fallback_batches"
            )
            assert state == CLOSED
            assert trips is not None and int(trips) >= 1
            assert fb is not None and int(fb) >= 1

            chaos.uninstall()
            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_breaker_open_serves_from_host_with_no_device_calls(self):
        """With the device permanently dark (every dispatch hangs), the
        broker keeps serving within the watchdog bound and the $SYS
        gauges show the degradation."""

        async def scenario():
            h = Harness(chaos_options(watchdog_ms=200.0))
            await h.server.serve()

            sub_r, sub_w, _ = await h.connect("sub")
            sub_w.write(sub_packet(1, [Subscription(filter="d/+", qos=0)]))
            await sub_w.drain()
            await read_wire_packet(sub_r)
            h.server.matcher.flush()

            chaos = ChaosHook()
            chaos.init(
                ChaosOptions(server=h.server, hang_rate=1.0, hang_s=30.0)
            )
            chaos.install(h.server)

            pub_r, pub_w, _ = await h.connect("pub")
            t0 = time.monotonic()
            for m in range(6):
                pub_w.write(pub_packet("d/x", f"k{m}".encode()))
                await pub_w.drain()
                pk = await asyncio.wait_for(read_wire_packet(sub_r), 10)
                assert bytes(pk.payload) == f"k{m}".encode()
            # 6 round trips: the first eats one watchdog (200ms); OPEN
            # ones are instant host walks
            assert time.monotonic() - t0 < 8.0
            # degraded: OPEN, or HALF_OPEN while a (doomed) probe runs
            assert h.server.matcher.breaker.state != CLOSED
            assert h.server.matcher.breaker.trips >= 1
            assert h.server.matcher.fallback_batches >= 1

            chaos.faulty.release.set()  # let wedged workers retire
            chaos.uninstall()
            await h.server.close()
            await h.shutdown()

        run(scenario())


# -- worker mesh: peer-link kill + reconnect + presence resync ---------------


class TestMeshLinkChaos:
    def test_peer_kill_reconnect_and_presence_resync(self, tmp_path):
        """Sever a live mesh link mid-traffic: the dial side reconnects
        with backoff, presence replays in full on reattach (including
        filters subscribed DURING the outage), and cross-worker delivery
        resumes. Reconnects surface in the $SYS gauge counters."""
        from mqtt_tpu.cluster import Cluster

        async def wait_until(cond, timeout=5.0, what=""):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if cond():
                    return
                await asyncio.sleep(0.02)
            raise AssertionError(f"timeout waiting for {what}")

        async def scenario():
            hA = Harness()
            hB = Harness()
            await hA.server.serve()
            await hB.server.serve()
            cA = Cluster(hA.server, 0, 2, str(tmp_path))
            cB = Cluster(hB.server, 1, 2, str(tmp_path))
            await cA.start()
            await cB.start()
            await wait_until(
                lambda: cA.peer_count == 1 and cB.peer_count == 1,
                what="mesh up",
            )

            # subscriber on A; publisher on B reaches it across the mesh
            sub_r, sub_w, _ = await hA.connect("subA")
            sub_w.write(sub_packet(1, [Subscription(filter="m/+", qos=0)]))
            await sub_w.drain()
            await read_wire_packet(sub_r)
            await wait_until(
                lambda: cB._interested_peers("m/1"), what="presence at B"
            )
            pub_r, pub_w, _ = await hB.connect("pubB")
            pub_w.write(pub_packet("m/1", b"pre-kill"))
            await pub_w.drain()
            pk = await asyncio.wait_for(read_wire_packet(sub_r), 5)
            assert bytes(pk.payload) == b"pre-kill"

            # KILL the link mid-traffic (connection reset, as a crashed
            # worker would present)
            assert sever_peer_link(cB, 0)
            await wait_until(
                lambda: cB.peer_count == 0 or cA.peer_count == 0,
                what="link down observed",
            )
            # a filter subscribed DURING the outage: its presence message
            # is unsendable now and must arrive via the reattach replay
            sub_w.write(sub_packet(2, [Subscription(filter="n/+", qos=0)]))
            await sub_w.drain()
            await read_wire_packet(sub_r)

            # the dial side heals the link with backoff...
            await wait_until(
                lambda: cA.peer_count == 1 and cB.peer_count == 1,
                what="mesh reconnect",
            )
            assert cB.reconnects_total >= 1  # B dials worker 0
            # ...and the full presence replay converges B's interest map
            await wait_until(
                lambda: cB._interested_peers("n/5"),
                what="outage-subscribed presence resync",
            )
            pub_w.write(pub_packet("n/5", b"post-heal"))
            await pub_w.drain()
            pk = await asyncio.wait_for(read_wire_packet(sub_r), 5)
            assert bytes(pk.payload) == b"post-heal"

            await cA.stop()
            await cB.stop()
            await hA.server.close()
            await hB.server.close()
            await hA.shutdown()
            await hB.shutdown()

        run(scenario())

    def test_qos_forward_drop_is_counted_not_silent(self, tmp_path):
        """The documented known-limit: QoS>0 forwards drop at the
        peer-buffer cap — per peer and per class, never silently."""
        from mqtt_tpu.cluster import _T_PACKET, Cluster
        from mqtt_tpu.packets import FixedHeader, Packet

        class WedgedTransport:
            def get_write_buffer_size(self):
                return Cluster.MAX_PEER_BUFFER + 1

            def abort(self):
                pass

        class WedgedWriter:
            transport = WedgedTransport()

            def write(self, data):
                raise AssertionError("a wedged peer must not be written")

        async def scenario():
            h = Harness()
            c = Cluster(h.server, 0, 2, str(tmp_path))
            c._writers[1] = WedgedWriter()
            c._apply_presence(1, "x/y", True, False)

            pk = Packet(
                fixed_header=FixedHeader(type=PUBLISH, qos=1),
                protocol_version=5,
            )
            pk.topic_name = "x/y"
            pk.payload = b"hello"
            pk.packet_id = 9
            c.forward_packet(pk)

            assert c.dropped_forwards == 1
            assert c.dropped_by_peer == {1: 1}
            assert c.dropped_qos_forwards == 1
            # a QoS0 drop counts in the totals but not the QoS>0 class
            assert c._send_nowait(1, c._writers[1], _T_PACKET, b"x") is False
            assert c.dropped_forwards == 2
            assert c.dropped_qos_forwards == 1
            await h.shutdown()

        run(scenario())

    def test_presence_wake_from_foreign_thread(self, tmp_path):
        """Satellite regression: a trie mutation from an embedder thread
        must not lose the presence wake (the wake routes through
        call_soon_threadsafe when off-loop)."""
        from mqtt_tpu.cluster import Cluster

        async def scenario():
            h = Harness()
            await h.server.serve()
            c = Cluster(h.server, 0, 1, str(tmp_path))
            await c.start()

            def embedder():
                h.server.topics.subscribe(
                    "thread-cli", Subscription(filter="t/h/r")
                )

            t = threading.Thread(target=embedder)
            t.start()
            t.join()
            deadline = time.monotonic() + 3
            while c._pending_presence and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            assert not c._pending_presence, "presence wake was lost"
            await c.stop()
            await h.server.close()
            await h.shutdown()

        run(scenario())


# -- slow chaos smoke (make chaos-smoke) -------------------------------------


@pytest.mark.slow
class TestChaosSmoke:
    def test_rate_driven_fault_storm_long(self):
        """The long randomized drill: rate-driven seeded faults across
        hundreds of dispatches under sustained traffic; delivery stays
        exactly-once against the oracle and the breaker ends CLOSED."""

        async def scenario():
            h = Harness(chaos_options(watchdog_ms=800.0))
            await h.server.serve()
            sub_r, sub_w, _ = await h.connect("sub")
            sub_w.write(sub_packet(1, [Subscription(filter="s/#", qos=0)]))
            await sub_w.drain()
            await read_wire_packet(sub_r)
            h.server.matcher.flush()

            pubs = []
            for i in range(4):
                _, w, _ = await h.connect(f"p{i}")
                pubs.append(w)
            pubs[0].write(pub_packet("s/warm", b"w"))
            await pubs[0].drain()
            await asyncio.wait_for(read_wire_packet(sub_r), 10)

            chaos = ChaosHook()
            chaos.init(
                ChaosOptions(
                    server=h.server,
                    seed=99,
                    hang_rate=0.04,
                    error_rate=0.08,
                    corrupt_rate=0.05,
                    slow_rate=0.1,
                    hang_s=2.0,
                    slow_s=0.01,
                )
            )
            chaos.install(h.server)

            n_msgs = 50
            async def publish_all(i, w):
                for m in range(n_msgs):
                    w.write(pub_packet(f"s/{i}/t", f"{i}.{m}".encode()))
                    await w.drain()
                    await asyncio.sleep(0.003)

            await asyncio.gather(*(publish_all(i, w) for i, w in enumerate(pubs)))

            expect = {
                (f"s/{i}/t", f"{i}.{m}".encode())
                for i in range(4)
                for m in range(n_msgs)
            }
            got = []
            for _ in range(len(expect)):
                pk = await asyncio.wait_for(read_wire_packet(sub_r), 15)
                got.append((pk.topic_name, bytes(pk.payload)))
            assert set(got) == expect and len(got) == len(expect)

            br = h.server.matcher.breaker
            deadline = time.monotonic() + 10
            while br.state != CLOSED and time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            assert br.state == CLOSED, br.as_dict()
            assert chaos.injected

            chaos.faulty.release.set()
            chaos.uninstall()
            await h.server.close()
            await h.shutdown()

        run(scenario())
