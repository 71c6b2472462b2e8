"""The publish staging loop: device_matcher=True end-to-end through the
real broker (SURVEY.md §7 stage 4).

Covers: >=100 concurrent publishers fanning out through batched device
matches with correct per-subscriber delivery, proof that matching was
batched (not one device round trip per publish on the event loop), QoS1
ack-before-fan-out ordering, $SYS/broker/matcher observability topics,
and stage shutdown draining via the host walk.
"""

import asyncio
import threading
import time

import pytest

from mqtt_tpu import Options, Server
from mqtt_tpu.packets import PUBLISH, SUBACK, FixedHeader, Packet, Subscription
from mqtt_tpu.staging import MatchStage
from mqtt_tpu.topics import SYS_PREFIX, Subscribers

from tests.test_server import (
    Harness,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)

N_PUBLISHERS = 100
MSGS_EACH = 2


def staged_options(**kw):
    return Options(
        inline_client=True,
        device_matcher=True,
        # tight window keeps the test fast while still coalescing the
        # concurrent publishers into real batches
        matcher_stage_window_ms=kw.pop("window_ms", 5.0),
        matcher_opts={"max_levels": 4, "background": False},
        **kw,
    )


class TestStagedBroker:
    def test_hundred_concurrent_publishers_fan_out(self):
        async def scenario():
            h = Harness(staged_options())
            await h.server.serve()  # starts the stage (no listeners bound)
            assert h.server._stage is not None

            # one wildcard subscriber + one exact subscriber
            sub_r, sub_w, _ = await h.connect("sub-wild")
            sub_w.write(sub_packet(1, [Subscription(filter="t/#", qos=0)]))
            await sub_w.drain()
            assert (await read_wire_packet(sub_r)).fixed_header.type == SUBACK
            sub2_r, sub2_w, _ = await h.connect("sub-exact")
            sub2_w.write(sub_packet(1, [Subscription(filter="t/p7/x", qos=0)]))
            await sub2_w.drain()
            assert (await read_wire_packet(sub2_r)).fixed_header.type == SUBACK

            # fold the subscription overlay so the device index (not the
            # host overlay route) serves the publish matches
            h.server.matcher.flush()

            pubs = []
            for i in range(N_PUBLISHERS):
                r, w, _ = await h.connect(f"pub{i}")
                pubs.append((r, w))

            async def publish_all(i, w):
                for m in range(MSGS_EACH):
                    w.write(pub_packet(f"t/p{i}/x", f"m{i}-{m}".encode()))
                    await w.drain()

            await asyncio.gather(*(publish_all(i, w) for i, (_, w) in enumerate(pubs)))

            # the wildcard subscriber receives every message
            got = set()
            for _ in range(N_PUBLISHERS * MSGS_EACH):
                pk = await read_wire_packet(sub_r)
                assert pk.fixed_header.type == PUBLISH
                got.add((pk.topic_name, bytes(pk.payload)))
            assert len(got) == N_PUBLISHERS * MSGS_EACH
            # the exact subscriber receives only its topic, in order
            for m in range(MSGS_EACH):
                pk = await read_wire_packet(sub2_r)
                assert pk.topic_name == "t/p7/x"
                assert bytes(pk.payload) == f"m7-{m}".encode()

            # matching really was batched: far fewer device batches than
            # published messages (no per-publish round trip on the loop)
            stats = h.server.matcher.stats
            assert stats.topics >= N_PUBLISHERS * MSGS_EACH
            assert stats.batches < stats.topics / 2, (
                f"batches={stats.batches} topics={stats.topics}: staging "
                "did not coalesce"
            )
            # the folded index really served from the device: the publish
            # topics matched post-flush must not all have host-routed
            assert stats.host_fallbacks < stats.topics, stats.as_dict()

            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_qos1_ack_precedes_fan_out_and_sys_topics(self):
        async def scenario():
            h = Harness(staged_options())
            await h.server.serve()

            sub_r, sub_w, _ = await h.connect("sub")
            sub_w.write(sub_packet(1, [Subscription(filter="q/+", qos=1)]))
            await sub_w.drain()
            await read_wire_packet(sub_r)

            pub_r, pub_w, _ = await h.connect("pub")
            pub_w.write(pub_packet("q/1", b"hello", qos=1, pid=9))
            await pub_w.drain()
            ack = await read_wire_packet(pub_r)  # PUBACK written sync
            assert ack.packet_id == 9
            out = await read_wire_packet(sub_r)
            assert out.topic_name == "q/1" and bytes(out.payload) == b"hello"

            # $SYS matcher observability
            h.server.publish_sys_topics()
            retained = h.server.topics.retained
            batches = retained.get(SYS_PREFIX + "/broker/matcher/batches")
            assert batches is not None and int(batches.payload) >= 1
            assert retained.get(SYS_PREFIX + "/broker/matcher/fallback_ratio") is not None

            await h.server.close()
            await h.shutdown()

        run(scenario())


class TestMatchStageUnit:
    def test_stage_error_falls_back_to_host(self):
        class BoomMatcher:
            def match_topics_async(self, topics):
                raise RuntimeError("boom")

        async def scenario():
            hits = []

            def host(topic):
                hits.append(topic)
                return Subscribers()

            stage = MatchStage(BoomMatcher(), host, window_s=0.001)
            stage.start()
            subs = await stage.submit("a/b")
            assert isinstance(subs, Subscribers)
            assert hits == ["a/b"]
            await stage.stop()

        run(scenario())

    def test_stage_stop_drains_pending_via_host(self):
        class NeverMatcher:
            def match_topics_async(self, topics):
                def resolve():
                    raise RuntimeError("resolver exploded")

                return resolve

        async def scenario():
            stage = MatchStage(
                NeverMatcher(), lambda t: Subscribers(), window_s=0.001
            )
            stage.start()
            fut = stage.submit("x/y")
            subs = await asyncio.wait_for(fut, 5)
            assert isinstance(subs, Subscribers)
            await stage.stop()
            # post-stop submissions resolve immediately via the host walk
            fut2 = stage.submit("x/z")
            assert fut2.done()

        run(scenario())


class TestCancelledCallerFutures:
    def test_cancelled_mid_window_leaks_nothing(self):
        """A client disconnecting during the accumulation window cancels
        its staged futures: the collector must prune them (no device
        work for dead callers), the drainer and _fallback_all must not
        raise InvalidStateError, and nothing leaks in _pending/_queue."""

        import threading

        class GatedMatcher:
            def __init__(self):
                self.calls = []
                self.release = threading.Event()

            def match_topics_async(self, topics):
                self.calls.append(list(topics))

                def resolve():
                    self.release.wait(5)
                    return [Subscribers() for _ in topics]

                return resolve

        async def scenario():
            m = GatedMatcher()
            stage = MatchStage(
                m, lambda t: Subscribers(), window_s=0.05, max_inflight=2
            )
            stage.start()
            futs = [stage.submit(f"c/{i}") for i in range(6)]
            for f in futs[:3]:
                f.cancel()  # disconnect during the window
            await asyncio.sleep(0.1)  # window elapses, batch dispatches
            assert m.calls and len(m.calls[0]) == 3  # cancelled pruned
            m.release.set()
            results = await asyncio.gather(*futs[3:])
            assert all(isinstance(r, Subscribers) for r in results)
            assert stage._pending == []

            # cancel AFTER dispatch (in-flight): the drainer must skip
            # the cancelled future without InvalidStateError
            m.release.clear()
            late = stage.submit("c/late")
            await asyncio.sleep(0.08)  # dispatched, resolver gated
            late.cancel()
            m.release.set()
            await asyncio.sleep(0.1)
            assert stage._queue.empty()
            await stage.stop()

        run(scenario())

    def test_stop_with_cancelled_pending_is_clean(self):
        """_fallback_all over a mix of live and cancelled futures: the
        cancelled ones are skipped (no InvalidStateError), the live ones
        resolve via the host walk."""

        async def scenario():
            stage = MatchStage(None, lambda t: Subscribers())
            stage._wake = asyncio.Event()  # park without a collector
            futs = [stage.submit(f"x/{i}") for i in range(4)]
            futs[0].cancel()
            futs[2].cancel()
            await stage.stop()
            assert futs[1].done() and futs[3].done()
            assert isinstance(futs[1].result(), Subscribers)
            assert isinstance(futs[3].result(), Subscribers)

        run(scenario())


class TestCrossLoopResolution:
    def test_fallback_rejection_marshals_to_submitter_loop(self):
        """Regression for the brokerlint R12 finding fixed in PR 19: a
        fallback used to fail the waiter's future INLINE on whatever
        thread ran it, scheduling its done-callbacks cross-thread. An
        entry completes on the loop that parked it (``_hand_over``'s
        marshal). The submitter loop runs in DEBUG mode here, so the old
        inline shape trips asyncio's non-thread-safe-operation check and
        the test fails loudly if the marshal seam regresses."""
        import threading

        from mqtt_tpu.staging import Parked, _set_futures

        class Boom(Exception):
            pass

        def exploding_host(topic):
            raise Boom(topic)

        loop_b = asyncio.new_event_loop()
        loop_b.set_debug(True)
        t = threading.Thread(
            target=loop_b.run_forever, name="submitter-loop", daemon=True
        )
        t.start()
        try:

            async def park():
                entry = Parked(_set_futures)
                entry.loop = asyncio.get_running_loop()
                entry.fut = entry.loop.create_future()
                return entry

            entry = asyncio.run_coroutine_threadsafe(park(), loop_b).result(5)
            rej = MatchStage(None, exploding_host)
            # the old code raises RuntimeError (non-thread-safe op) here
            rej._fallback_all([("x/y", entry)])

            async def reap():
                try:
                    await entry.fut
                except Boom:
                    return threading.get_ident()
                raise AssertionError("future resolved without the host error")

            # the rejection completed ON the submitter's loop thread
            assert (
                asyncio.run_coroutine_threadsafe(reap(), loop_b).result(5)
                == t.ident
            )

            # the success leg rides the same seam
            entry2 = asyncio.run_coroutine_threadsafe(park(), loop_b).result(5)
            ok = MatchStage(None, lambda t: Subscribers())
            ok._fallback_all([("x/z", entry2)])

            async def reap_ok():
                return await entry2.fut

            assert isinstance(
                asyncio.run_coroutine_threadsafe(reap_ok(), loop_b).result(5),
                Subscribers,
            )
            assert rej.adapter_completed == ok.adapter_completed == 1
        finally:
            loop_b.call_soon_threadsafe(loop_b.stop)
            t.join(5)
            loop_b.close()

    def test_inject_packet_parks_without_a_task(self):
        """``server.inject_packet`` of a staged PUBLISH used to spawn a
        fan-out task (brokerlint R13 wanted it tracked, PR 19). It now
        parks the publish like one read from a socket: no task is made,
        the publish fans out with its batch, and the stage counts it as
        completed by the batch callback, not through a future."""

        async def scenario():
            h = Harness(staged_options())
            await h.server.serve()
            sub_r, sub_w, _ = await h.connect("inj-sub")
            sub_w.write(sub_packet(1, [Subscription(filter="in/t", qos=0)]))
            await sub_w.drain()
            await read_wire_packet(sub_r)
            h.server.matcher.flush()
            cl = h.server.clients.get("inj-sub")
            stage = h.server._stage
            tasks_before = set(h.server.listeners.client_tasks)
            all_before = asyncio.all_tasks()
            assert h.server.inject_packet(
                cl,
                Packet(
                    fixed_header=FixedHeader(type=PUBLISH),
                    topic_name="in/t",
                    payload=b"injected",
                ),
            ) is None
            assert set(h.server.listeners.client_tasks) == tasks_before
            assert asyncio.all_tasks() == all_before, "no task a publish"
            assert stage.pending_depth == 1 and cl._staged == 1
            pk = await read_wire_packet(sub_r)
            assert bytes(pk.payload) == b"injected"
            assert cl._staged == 0
            assert stage.batch_completed == 1
            assert stage.batch_completions == 1
            assert stage.adapter_completed == 0
            await h.server.close()
            await h.shutdown()

        run(scenario())


class TestAdaptiveWindow:
    def test_window_headroom_scales_with_queue_depth(self):
        """Regression (ADVICE r5): _observe_service budgets depth x
        service, so _window must too — with a deep queue the pipeline can
        be over budget while one batch's service is not, and the
        collector must stop adding window sleep on top."""

        async def scenario():
            stage = MatchStage(
                None,
                lambda t: Subscribers(),
                window_s=0.01,
                latency_budget_s=0.1,
            )
            stage._ewma_s = 0.04  # one batch: comfortably under budget
            assert stage._window() > 0.0  # no queue yet: depth 1
            stage._queue = asyncio.Queue(maxsize=8)
            for _ in range(3):
                stage._queue.put_nowait(None)
            # effective latency = depth(4) x 0.04 = 0.16 > 0.1 budget:
            # the window collapses instead of sleeping on top of it
            assert stage._window() == 0.0
            stage._queue.get_nowait()
            stage._queue.get_nowait()
            stage._queue.get_nowait()
            # depth 1 x 0.04 leaves headroom again
            assert stage._window() > 0.0

        run(scenario())


class TestColdCompileIsSetUp:
    """A batch whose drain overlapped a first-signature jit call (the
    compile clock moved) is set-up, not a service-time sample: it must
    reach neither the EWMA nor the deadline-aware admission test. At
    the parent commit a cold broker answered most of its first burst
    from the host trie (ISSUE 21)."""

    class ColdMatcher:
        """First batch 'compiles' (slow, clock advances); the rest are
        fast and block until released, so a backlog parks behind them."""

        def __init__(self) -> None:
            self.clock = 0.0
            self.batches = 0
            self.release = threading.Event()

        def match_topics_async(self, topics):
            self.batches += 1
            first = self.batches == 1

            def resolve():
                if first:
                    time.sleep(0.15)
                    self.clock += 0.15  # a KernelWatch first-signature call
                else:
                    self.release.wait(5)
                return [Subscribers() for _ in topics]

            return resolve

    def _run(self, compile_aware: bool):
        async def scenario():
            m = self.ColdMatcher()
            host_walks = []

            def host(topic):
                host_walks.append(topic)
                return Subscribers()

            stage = MatchStage(
                m,
                host,
                window_s=0.001,
                latency_budget_s=0.05,  # the cold batch is 3x this
                min_batch=1,
                compile_clock=(lambda: m.clock) if compile_aware else (lambda: 0.0),
            )
            stage.start()
            await stage.submit("cold/1")  # the compile batch
            # a burst right behind it: batches park behind the blocked
            # resolver, so admission consults _past_deadline
            futs = []
            for i in range(40):
                futs.append(stage.submit(f"warm/{i}"))
                await asyncio.sleep(0.001)
            m.release.set()
            await asyncio.wait_for(asyncio.gather(*futs), 10)
            out = (stage.admission_fallbacks, stage.compile_tainted_batches,
                   stage._ewma_s, len(host_walks))
            await stage.stop()
            return out

        return run(scenario())

    def test_compile_batch_stays_out_of_the_controller(self):
        fallbacks, tainted, ewma, host_walks = self._run(compile_aware=True)
        assert tainted == 1
        assert fallbacks == 0 and host_walks == 0
        assert ewma < 0.15  # the cold 150 ms never entered the estimate

    def test_blind_stage_reproduces_the_cold_fallbacks(self):
        """The control: with a clock that never moves, the same run reads
        the compile as load and refuses work (the parent's behaviour)."""
        fallbacks, tainted, _ewma, host_walks = self._run(compile_aware=False)
        assert tainted == 0
        assert fallbacks > 0 and host_walks == fallbacks


class TestSingleConnectionPipelining:
    def test_one_client_burst_coalesces(self):
        """All publishes in one socket write must reach the stage before
        the read loop blocks on any of them (clients.py scan batching)."""

        async def scenario():
            h = Harness(staged_options())
            await h.server.serve()
            sub_r, sub_w, _ = await h.connect("sub")
            sub_w.write(sub_packet(1, [Subscription(filter="b/#", qos=0)]))
            await sub_w.drain()
            await read_wire_packet(sub_r)
            h.server.matcher.flush()

            pub_r, pub_w, _ = await h.connect("pub")
            burst = b"".join(
                pub_packet(f"b/{i}", f"x{i}".encode()) for i in range(50)
            )
            pub_w.write(burst)  # ONE socket write, 50 publishes
            await pub_w.drain()

            for i in range(50):
                pk = await read_wire_packet(sub_r)
                assert pk.topic_name == f"b/{i}"  # order preserved

            stats = h.server.matcher.stats
            assert stats.batches <= 5, stats.as_dict()  # coalesced, not 50
            await h.server.close()
            await h.shutdown()

        run(scenario())


def _parks(many, n, cap, parked_before, first_alone, second_loop):
    """Park ``n`` entries behind ``parked_before`` on a stage of
    ``max_pending`` ``cap`` whose collector does not run, one ``park()``
    each or in one ``park_many()``, from the stage's loop or from a
    second one; return all that can be seen of it afterwards."""
    from mqtt_tpu.staging import Parked

    hits, done = [], []

    def host(topic):
        hits.append(topic)
        return Subscribers()

    def complete(entries, results, t_set_ns=0):
        done.extend(e.pk for e in entries)

    def park_them(stage):
        items = []
        for i in range(n):
            entry = Parked(complete, pk=f"t/{i}")
            entry.alone = first_alone and i == 0
            items.append((f"t/{i}", entry))
        if many:
            stage.park_many(items)
        else:
            for topic, entry in items:
                stage.park(topic, entry)
        return [entry for _, entry in items]

    async def scenario():
        stage = MatchStage(None, host, max_pending=cap)
        stage._wake = asyncio.Event()  # armed, no collector: entries stay
        stage._loop = asyncio.get_running_loop()
        for i in range(parked_before):
            stage.park(f"before/{i}", Parked(complete, pk=f"before/{i}"))
        stage._wake.clear()
        if second_loop:
            loop_b = asyncio.new_event_loop()
            t = threading.Thread(target=loop_b.run_forever, daemon=True)
            t.start()

            async def on_b():
                return park_them(stage)

            entries = await asyncio.wrap_future(
                asyncio.run_coroutine_threadsafe(on_b(), loop_b)
            )
            assert all(e.loop is loop_b for e in entries)
            await asyncio.sleep(0.01)  # the marshalled wake-up
        else:
            entries = park_them(stage)
        seen = {
            "pending": [
                (topic, e.held is not None) for topic, e in stage._pending
            ],
            "held_pending": stage._held_pending,
            "peak": stage.peak_pending,
            "fallbacks": stage.admission_fallbacks,
            "order_held": stage.order_held,
            "walked": list(hits),
            "done_inside": list(done),
            "woken": stage._wake.is_set(),
        }
        await stage.stop()
        if second_loop:
            done_on_b = asyncio.run_coroutine_threadsafe(asyncio.sleep(0), loop_b)
            await asyncio.wrap_future(done_on_b)
            loop_b.call_soon_threadsafe(loop_b.stop)
            t.join(5)
            loop_b.close()
        seen["done"] = sorted(done, key=lambda pk: pk.startswith("t/"))
        seen["walked_by_stop"] = hits[len(seen["walked"]):]
        return seen

    return run(scenario())


PARK_RUNS = {
    # name: (n, cap, parked before, the first entry is alone)
    "one": (1, 8192, 0, True),
    "one_behind_others": (1, 8192, 3, False),
    "one_refused_alone": (1, 4, 4, True),
    "one_refused_held": (1, 4, 4, False),
    "sixty_four": (64, 8192, 0, True),
    "straddles_the_cap": (64, 40, 0, True),
    "straddles_the_cap_behind_others": (64, 40, 10, False),
    "all_refused_first_alone": (5, 2, 2, True),
}


@pytest.mark.parametrize("second_loop", [False, True], ids=["stage_loop", "second_loop"])
@pytest.mark.parametrize("name", sorted(PARK_RUNS))
def test_park_many_is_park_once_an_item(name, second_loop):
    """``park_many(items)`` leaves what ``park()`` called once an item
    leaves: the same ``_pending`` (admitted members, then held ones, in
    order), ``peak_pending``, ``admission_fallbacks``, ``order_held``,
    ``alone`` completions inside the call, the collector woken, and the
    same completions in the same order when the stage stops."""
    n, cap, before, alone = PARK_RUNS[name]
    one_by_one = _parks(False, n, cap, before, alone, second_loop)
    at_once = _parks(True, n, cap, before, alone, second_loop)
    assert at_once == one_by_one
    room = max(0, cap - before)
    assert at_once["fallbacks"] == max(0, n - room)
    assert [held for _, held in at_once["pending"][before:]] == (
        [False] * min(n, room)
        + [True] * (max(0, n - room) - (alone and room == 0))
    )
    assert at_once["woken"] == bool(at_once["pending"][before:])
    assert len(at_once["done"]) == before + n
