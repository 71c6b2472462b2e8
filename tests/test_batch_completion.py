"""Batch-granular completion (mqtt_tpu.staging / server._complete_staged):
a device batch completes as a batch. The stage parks entries, not
futures; the drain loop calls the entries' completion once a slice with
the batch's entries and results in submit order; the server fans a slice
out under one read of the client registry; a connection waits once a
socket read for its own publishes. CPU backend: order, counts and
exactly-once, never a rate."""

import asyncio
import contextlib
import random
import threading
import time

import pytest

from mqtt_tpu import Options, staging
from mqtt_tpu.hooks import ON_PACKET_PROCESSED, ON_PUBLISHED, Hook
from mqtt_tpu.packets import (
    CONNACK,
    ERR_UNSPECIFIED_ERROR,
    PINGREQ,
    PINGRESP,
    PUBACK,
    PUBLISH,
    SUBACK,
    FixedHeader,
    Packet,
    Subscription,
    encode_packet,
)
from mqtt_tpu.staging import MatchStage, Parked
from mqtt_tpu.topics import Subscribers

from tests.test_server import (
    TIMEOUT,
    Harness,
    connect_packet,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)


def load_benchmark_module(name):
    """A module of ``benchmark/`` by path: the plain reference and the
    deployments live beside the benchmark and import nothing of the
    program."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + name.replace("/", "_"),
        os.path.join(root, "benchmark", name + ".py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def staged_options(**kw):
    return Options(
        inline_client=True,
        device_matcher=True,
        matcher_stage_window_ms=kw.pop("window_ms", 2.0),
        matcher_opts={"max_levels": 4, "background": False},
        **kw,
    )


class Tagged(Subscribers):
    """A result that names the topic it answers."""

    __slots__ = ("topic", "via")

    def __init__(self, topic, via):
        super().__init__()
        self.topic, self.via = topic, via


class GateMatcher:
    """Resolves each batch when ``release`` is set; ``fail`` raises in
    the issue leg or in the resolver."""

    def __init__(self, fail=None):
        self.fail = fail
        self.batches = []
        self.release = threading.Event()
        self.release.set()
        self.issued = threading.Event()

    def match_topics_async(self, topics, profile=None):
        self.batches.append(list(topics))
        self.issued.set()
        if self.fail == "issue":
            raise RuntimeError("no device")

        def resolve():
            assert self.release.wait(10)
            if self.fail == "resolve":
                raise RuntimeError("sync failed")
            return [Tagged(t, "device") for t in topics]

        return resolve


class Recorder:
    """A completion that writes down every call it gets."""

    def __init__(self):
        self.calls = []  # (thread id, loop, [topics], [results' via])
        self.between = 0  # loop callbacks that ran between two calls

    def __call__(self, entries, results, t_set_ns=0):
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        self.calls.append(
            (threading.get_ident(), loop, [e.pk for e in entries],
             [(r.topic, r.via) for r in results])
        )
        if loop is not None:
            loop.call_soon(self._tick)

    def _tick(self):
        self.between += 1

    @property
    def order(self):
        return [t for _tid, _loop, topics, _r in self.calls for t in topics]


def park(stage, rec, topic):
    entry = Parked(rec)
    entry.pk = topic  # the recorder reads the topic back off the entry
    stage.park(topic, entry)
    return entry


def host(topic):
    return Tagged(topic, "host")


async def until(cond, what, timeout_s=TIMEOUT):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, what
        await asyncio.sleep(0.002)


class TestStageCompletion:
    def test_submit_order_across_batches_and_slices(self, monkeypatch):
        """20 publishes, batches of 8, slices of 3: the completion sees
        them in park order, never more than a slice a call, each call's
        results its own entries', and the loop runs between two slices
        of one batch."""
        monkeypatch.setattr(staging, "COMPLETION_SLICE", 3)

        async def scenario():
            m = GateMatcher()
            stage = MatchStage(
                m, host, window_s=0.001, max_batch=8, latency_budget_s=None
            )
            stage.start()
            rec = Recorder()
            topics = [f"o/{i}" for i in range(20)]
            for t in topics:
                park(stage, rec, t)
            await until(lambda: len(rec.order) == 20, "all completed")
            assert rec.order == topics
            assert [len(b) for b in m.batches] == [8, 8, 4]
            assert [len(c[2]) for c in rec.calls] == [3, 3, 2, 3, 3, 2, 3, 1]
            for _tid, _loop, got, results in rec.calls:
                assert results == [(t, "device") for t in got]
            assert stage.batch_completed == 20
            assert stage.batch_completions == len(rec.calls) == 8
            assert stage.adapter_completed == 0
            # a yield to the loop between the slices of one batch: the
            # recorder's call_soon ticks ran before the last call did
            assert rec.between >= 5
            await stage.stop()

        run(scenario())

    @pytest.mark.parametrize(
        "klass", ["admission", "stop", "issue_error", "resolve_error"]
    )
    def test_fallback_reaches_the_completion_exactly_once(self, klass):
        from mqtt_tpu.telemetry import Telemetry

        async def scenario():
            tel = Telemetry(sample=0)
            fail = {"issue_error": "issue", "resolve_error": "resolve"}.get(klass)
            m = GateMatcher(fail=fail)
            stage = MatchStage(
                m, host, window_s=0.001, max_pending=4, telemetry=tel,
                latency_budget_s=None,
            )
            rec = Recorder()
            if klass == "admission":
                stage._wake = asyncio.Event()  # armed, never drained
                topics = [f"a/{i}" for i in range(6)]
                for t in topics:
                    park(stage, rec, t)
                # the two past max_pending were walked on the host inside
                # park() and wait their turn behind the four before them
                assert rec.order == []
                assert stage.admission_fallbacks == stage.order_held == 2
                assert stage.peak_pending == 4 and stage.pending_depth == 6
                await stage.stop()
                expect_fallbacks = {"admission": 2, "stop": 4}
                assert rec.order == topics
            else:
                stage.start()
                if klass == "stop":
                    m.release.clear()  # the first batch hangs in its sync
                topics = [f"f/{i}" for i in range(4)]
                for t in topics:
                    park(stage, rec, t)
                if klass == "stop":
                    await until(m.issued.is_set, "batch issued")
                    await asyncio.sleep(0.02)
                    await stage.stop()
                    m.release.set()
                else:
                    await until(lambda: len(rec.order) == 4, "fell back")
                    await stage.stop()
                expect_fallbacks = {klass: 4}
                assert rec.order == topics
            # exactly once, and by the host walk
            assert len(rec.order) == len(set(rec.order))
            for _tid, _loop, got, results in rec.calls:
                assert results == [(t, "host") for t in got]
            for k, c in tel.fallback.items():
                assert int(c.value) == expect_fallbacks.get(k, 0), k
            assert stage.batch_completed == len(rec.order)

        run(scenario())

    def test_fallback_with_nothing_parked_completes_inside_park(self):
        """A parker with nothing else in the stage overtakes nothing: its
        admission fallback completes inside ``park()`` and is no held
        member, while the stage is full of other parkers' publishes."""

        async def scenario():
            stage = MatchStage(
                GateMatcher(), host, max_pending=2, latency_budget_s=None
            )
            stage._wake = asyncio.Event()  # armed, never drained
            rec = Recorder()
            for t in ("x/0", "x/1"):
                park(stage, rec, t)
            entry = Parked(rec)
            entry.pk, entry.alone = "x/alone", True
            stage.park("x/alone", entry)
            assert rec.order == ["x/alone"]
            assert rec.calls[0][3] == [("x/alone", "host")]
            assert stage.admission_fallbacks == 1 and stage.order_held == 0
            assert stage.pending_depth == 2
            await stage.stop()
            assert rec.order == ["x/alone", "x/0", "x/1"]

        run(scenario())

    def test_held_members_keep_their_place_and_never_reach_the_matcher(
        self, monkeypatch
    ):
        """Ten publishes against a device backlog of four, while the
        first batch hangs in its sync: the six not admitted are walked on
        the host at once, the matcher sees the four admitted only, and
        the completion sees all ten in park order, each held member with
        its host result, under one ``mqtt/order.hold`` a batch."""

        records = []
        profile = staging.BatchProfile
        monkeypatch.setattr(
            staging, "BatchProfile",
            lambda: records.append(profile()) or records[-1],
        )

        async def scenario():
            m = GateMatcher()
            m.release.clear()
            walked = []

            def walk(topic):
                walked.append(topic)
                return host(topic)

            stage = MatchStage(
                m, walk, window_s=0.001, max_batch=3, max_pending=4,
                latency_budget_s=None,
            )
            stage.start()
            rec = Recorder()
            topics = [f"h/{i}" for i in range(10)]
            for t in topics:
                park(stage, rec, t)
            assert walked == topics[4:] and rec.order == []
            assert stage.admission_fallbacks == stage.order_held == 6
            assert stage.peak_pending == 4
            # no depth the admission test reckons with counts them
            assert stage.pressure() == pytest.approx(1.0)
            m.release.set()
            await until(lambda: len(rec.order) == 10, "all completed")
            assert rec.order == topics
            assert m.batches == [topics[:3], topics[3:4]]
            via = [v for _tid, _loop, _t, results in rec.calls for _, v in results]
            assert via == ["device"] * 4 + ["host"] * 6
            assert stage.batch_completed == 10
            # batches of 3: [0 1 2] [3 h h] [h h h] [h]
            assert [r.topics for r in records] == [3, 1, 0, 0]
            assert [r.hold_n for r in records] == [0, 2, 3, 1]
            for r in records[1:]:
                assert r.hold[0] <= r.hold[1] and r.hold_sum_ns >= 0
                names = [name for name, *_ in r.spans()]
                assert names[0] == "mqtt/batch" and "mqtt/order.hold" in names
            await stage.stop()

        run(scenario())

    def test_issue_error_waits_behind_the_batch_in_its_sync(self):
        """Batch N fails at issue while batch N-1 hangs in its sync: N is
        walked on the host at once and completes after N-1."""

        class FailsSecond(GateMatcher):
            def match_topics_async(self, topics, profile=None):
                self.fail = "issue" if self.batches else None
                return super().match_topics_async(topics, profile=profile)

        async def scenario():
            from mqtt_tpu.telemetry import Telemetry

            tel = Telemetry(sample=0)
            m = FailsSecond()
            m.release.clear()
            stage = MatchStage(
                m, host, window_s=0.001, max_batch=3, latency_budget_s=None,
                telemetry=tel,
            )
            stage.start()
            rec = Recorder()
            topics = [f"i/{i}" for i in range(6)]
            for t in topics:
                park(stage, rec, t)
            await until(lambda: stage.order_held == 3, "batch 2 fell back")
            assert len(m.batches) == 2 and rec.order == []
            m.release.set()
            await until(lambda: len(rec.order) == 6, "all completed")
            assert rec.order == topics
            via = [v for _tid, _loop, _t, results in rec.calls for _, v in results]
            assert via == ["device"] * 3 + ["host"] * 3
            assert int(tel.fallback["issue_error"].value) == 3
            assert stage.inflight_batches == 0 and stage._held_batches == 0
            await stage.stop()

        run(scenario())

    @pytest.mark.parametrize("where", ["pending", "queued"])
    def test_stop_with_held_members_completes_each_once_in_order(self, where):
        async def scenario():
            m = GateMatcher()
            m.release.clear()
            stage = MatchStage(
                m, host, window_s=0.001, max_batch=2, max_pending=3,
                latency_budget_s=None, pipeline_depth=2,
            )
            rec = Recorder()
            topics = [f"s/{i}" for i in range(9)]
            if where == "pending":
                stage._wake = asyncio.Event()  # armed, never drained
                for t in topics:
                    park(stage, rec, t)
            else:
                stage.start()
                for t in topics:
                    park(stage, rec, t)
                # one batch hangs in its sync, one waits in the queue, the
                # collector holds a third, the rest is in _pending
                await until(lambda: len(m.batches) >= 2, "queued")
            assert stage.order_held == 6 and rec.order == []
            await stage.stop()
            m.release.set()
            assert rec.order == topics  # none lost, none twice, in order
            assert stage.batch_completed == 9

        run(scenario())

    @pytest.mark.parametrize("where", ["pending", "queued", "between_slices"])
    def test_stop_with_parked_entries_loses_no_publish(self, where, monkeypatch):
        monkeypatch.setattr(staging, "COMPLETION_SLICE", 2)

        async def scenario():
            m = GateMatcher()
            stage = MatchStage(
                m, host, window_s=0.001, max_batch=4, latency_budget_s=None,
                pipeline_depth=2,
            )
            rec = Recorder()
            topics = [f"s/{i}" for i in range(12)]
            if where == "pending":
                stage._wake = asyncio.Event()
                for t in topics:
                    park(stage, rec, t)
            else:
                stage.start()
                if where == "queued":
                    m.release.clear()
                for t in topics:
                    park(stage, rec, t)
                if where == "queued":
                    # one batch hangs in its sync, more wait in the queue
                    # and in _pending behind it
                    await until(lambda: len(m.batches) >= 2, "queued")
                else:
                    # stop() lands at the yield after a batch's first slice
                    await until(lambda: len(rec.order) >= 2, "first slice")
            await stage.stop()
            m.release.set()
            assert sorted(rec.order) == sorted(topics)
            assert len(rec.order) == 12  # none twice
            if where == "between_slices":
                assert rec.order[:4] == topics[:4]  # the cut batch, in order

        run(scenario())

    def test_entries_parked_from_a_second_loop_complete_there(self):
        """One batch holds entries of two loops: each loop's share is
        completed on it, in order, by one hand-over."""

        async def scenario():
            stage = MatchStage(
                GateMatcher(), host, window_s=0.02, latency_budget_s=None
            )
            stage.start()
            here = asyncio.get_running_loop()
            loop2 = asyncio.new_event_loop()
            t = threading.Thread(target=loop2.run_forever, daemon=True)
            t.start()
            rec = Recorder()
            try:
                async def park_there():
                    for i in range(5):
                        park(stage, rec, f"shard/{i}")

                for i in range(3):
                    park(stage, rec, f"main/{i}")
                asyncio.run_coroutine_threadsafe(park_there(), loop2).result(5)
                await until(lambda: len(rec.order) == 8, "both loops completed")
                by_loop = {loop: topics for _tid, loop, topics, _r in rec.calls}
                assert by_loop == {
                    here: [f"main/{i}" for i in range(3)],
                    loop2: [f"shard/{i}" for i in range(5)],
                }
                tids = {loop: tid for tid, loop, _t, _r in rec.calls}
                assert tids[loop2] == t.ident != tids[here]
                assert len(stage.matcher.batches) == 1  # one batch, two loops
                await stage.stop()
            finally:
                loop2.call_soon_threadsafe(loop2.stop)
                t.join(5)
                loop2.close()

        run(scenario())

    def test_adapter_and_batch_path_resolve_alike(self):
        """``submit()`` is the same pipeline: one batch holds both kinds,
        each future gets what the batch completion gets."""

        async def scenario():
            stage = MatchStage(
                GateMatcher(), host, window_s=0.005, latency_budget_s=None
            )
            stage.start()
            rec = Recorder()
            rng = random.Random(26)
            topics = [f"d/{rng.randrange(50)}/{i}" for i in range(40)]
            futs = []
            for t in topics:
                futs.append(stage.submit(t))
                park(stage, rec, t)
            got = await asyncio.gather(*futs)
            await until(lambda: len(rec.order) == 40, "batch path done")
            assert [(r.topic, r.via) for r in got] == [
                pair for _tid, _loop, _t, results in rec.calls for pair in results
            ]
            assert rec.order == topics
            assert stage.adapter_completed == stage.batch_completed == 40
            await stage.stop()

        run(scenario())


class ProcessedHook(Hook):
    """Records on_packet_processed; fails on_published for one payload."""

    def __init__(self):
        super().__init__()
        self.processed = []

    def id(self):
        return "processed"

    def provides(self, b):
        return b in (ON_PUBLISHED, ON_PACKET_PROCESSED)

    def on_published(self, cl, pk):
        if bytes(pk.payload) == b"poison":
            raise ERR_UNSPECIFIED_ERROR()

    def on_packet_processed(self, cl, pk, err):
        if pk.fixed_header.type == PUBLISH:
            self.processed.append((cl.id, bytes(pk.payload), err))


async def subscriber(h, client_id, flt, qos=0):
    r, w, _ = await h.connect(client_id)
    w.write(sub_packet(1, [Subscription(filter=flt, qos=qos)]))
    await w.drain()
    assert (await read_wire_packet(r)).fixed_header.type == SUBACK
    return r, w


def run_echo(seed, n_clients, chunk, rounds, hooks=(), **options):
    """stresser's echo loop (``benchmark/deployments/stresser.py``) over
    loopback TCP: ``n_clients`` connections, each writing ``rounds``
    chunks of ``chunk`` frames to its own topic and reading each chunk
    back before the next, on a broker with ``hooks`` added. Returns the
    plain reference's verdict on what the sockets saw
    (``benchmark/reference.py``), the count of deliveries, and the
    broker's counts read before it closed."""
    reference = load_benchmark_module("reference")
    stresser = load_benchmark_module("deployments/stresser")
    params = {
        "clients": n_clients,
        "offline_subscriptions": [["ops-dashboard", "$SYS/#"]],
    }
    plan = stresser.plan(params, seed, None)
    subs = plan["subscriptions"]

    async def client(port, k):
        cid, flt, qos = subs[plan["live"][k]]
        r, w = await asyncio.open_connection("127.0.0.1", port)
        w.write(connect_packet(cid))
        assert (await read_wire_packet(r)).fixed_header.type == CONNACK
        w.write(sub_packet(1, [Subscription(filter=flt, qos=qos)]))
        assert (await read_wire_packet(r)).fixed_header.type == SUBACK
        return r, w

    async def echo(r, w, k, received):
        topics = stresser.topics(params, seed, k)
        got = received[subs[plan["live"][k]][0]] = []
        for n in range(rounds):
            w.write(b"".join(
                pub_packet(next(topics), b"%d:%d" % (k, n * chunk + i))
                for i in range(chunk)
            ))
            for _ in range(chunk):
                pk = await read_wire_packet(r)
                pub, seq = bytes(pk.payload).split(b":")
                got.append(reference.pack_delivery(
                    int(pub), int(seq), pk.fixed_header.qos,
                    int(pk.fixed_header.dup),
                    reference.topic_tag(pk.topic_name.encode()),
                ))

    async def scenario():
        from mqtt_tpu.listeners import Config as LConfig
        from mqtt_tpu.listeners.tcp import TCP

        h = Harness(staged_options(matcher_stage_latency_budget_ms=0, **options))
        srv = h.server
        for hook in hooks:
            srv.add_hook(hook)
        srv.add_listener(TCP(LConfig(type="tcp", id="t", address="127.0.0.1:0")))
        await srv.serve()
        port = int(srv.listeners.get("t").address().rsplit(":", 1)[1])
        staging.bulk_register(
            srv.topics,
            ((c, Subscription(filter=f, qos=q)) for c, f, q in subs[n_clients:]),
        )
        conns = [await client(port, k) for k in range(n_clients)]
        srv.matcher.flush()
        received: dict = {}
        sends = srv._ops.socket_sends
        await asyncio.gather(
            *(echo(r, w, k, received) for k, (r, w) in enumerate(conns))
        )
        stage = srv._stage
        counts = {
            "held": stage.order_held, "fallbacks": stage.admission_fallbacks,
            "peak": stage.peak_pending, "completed": stage.batch_completed,
            "sends": srv._ops.socket_sends - sends,
            "took": srv._ops.ingest_run_publishes,
            "loops": len({
                srv.clients.get(subs[row][0]).net.loop for row in plan["live"]
            }),
            "exposition": srv.telemetry.registry.exposition(),
        }
        for _r, w in conns:
            w.close()
        await srv.close()
        await h.shutdown()
        return received, counts

    received, counts = run(scenario())
    live = reference.FilterSet(subs[row] for row in plan["live"])
    sent = (
        (k, seq, topic, 0)
        for k in range(n_clients)
        for seq, topic in zip(
            range(chunk * rounds), stresser.topics(params, seed, k)
        )
    )
    verdict = reference.compare_deliveries(
        reference.expected_deliveries(live, sent), received
    )
    return verdict, sum(len(v) for v in received.values()), counts


class TestServedPath:
    def test_order_across_batches_and_slices_and_the_counter(self, monkeypatch):
        """Two publishers, 30 publishes each in one socket write, batches
        of 8 and slices of 3: the subscriber sees each publisher's
        messages in order; every publish left the stage through the batch
        callback and none through a future, and no task was made."""
        monkeypatch.setattr(staging, "COMPLETION_SLICE", 3)

        async def scenario():
            h = Harness(
                staged_options(
                    matcher_stage_max_batch=8, matcher_stage_latency_budget_ms=0
                )
            )
            await h.server.serve()
            sub_r, _sub_w = await subscriber(h, "sub", "t/#")
            h.server.matcher.flush()
            pubs = [await h.connect(f"pub{k}") for k in range(2)]
            tasks_before = asyncio.all_tasks()
            for k, (_r, w, _t) in enumerate(pubs):
                w.write(
                    b"".join(
                        pub_packet(f"t/{k}/{i}", f"{k}:{i}".encode())
                        for i in range(30)
                    )
                )
            seen = {0: [], 1: []}
            for _ in range(60):
                pk = await read_wire_packet(sub_r)
                k, i = bytes(pk.payload).decode().split(":")
                seen[int(k)].append(int(i))
            assert seen == {0: list(range(30)), 1: list(range(30))}
            assert asyncio.all_tasks() == tasks_before  # no task a publish
            stage = h.server._stage
            assert stage.batch_completed == 60
            assert stage.adapter_completed == 0
            assert stage.batch_completions >= 60 // 3
            assert h.server.matcher.stats.batches >= 60 // 8
            text = h.server.telemetry.registry.exposition()
            assert 'mqtt_tpu_stage_completed_total{path="batch"} 60' in text
            assert 'mqtt_tpu_stage_completed_total{path="adapter"} 0' in text
            assert "mqtt_tpu_stage_completion_calls_total" in text
            await h.server.close()
            await h.shutdown()

        run(scenario())

    @pytest.mark.parametrize("seed", [27, 2003, 2**31 + 7])
    def test_fallbacks_keep_each_publishers_order_against_the_reference(
        self, seed
    ):
        """stresser's shape over loopback TCP with the stage's backlog cut
        below one socket read (8 against 16-frame writes): in every read
        the frames past the eighth fall back while the first eight sit in
        the stage. What the sockets saw equals the plain reference
        (``benchmark/reference.py``): every message back, once, in the
        order sent."""
        chunk, rounds = 16, 3
        verdict, delivered, n = run_echo(
            seed, 4, chunk, rounds, overload_stage_max_pending=8
        )
        assert verdict["errors"] == 0 and verdict["misordered"] == 0, verdict
        assert delivered == 4 * chunk * rounds
        assert f"mqtt_tpu_stage_order_held_total {n['held']}" in n["exposition"]
        # the frames of a read past the backlog fell back: behind frames
        # of their own connection they joined its order (at least the
        # first read's last eight); a connection with nothing in the
        # stage had its fallbacks completed inside park()
        assert n["peak"] <= 8 and n["completed"] == 4 * chunk * rounds
        assert chunk - 8 <= n["held"] <= n["fallbacks"]

    def test_one_failing_publish_closes_only_its_connection(self):
        """A publish whose completion raises: its connection is closed,
        the error reaches on_packet_processed, and the other publishes of
        the same slice are delivered."""

        async def scenario():
            h = Harness(staged_options(window_ms=50.0))
            hook = ProcessedHook()
            h.server.add_hook(hook)
            await h.server.serve()
            sub_r, _sub_w = await subscriber(h, "sub", "t/#")
            h.server.matcher.flush()
            _ar, aw, atask = await h.connect("bad", version=5)
            _br, bw, btask = await h.connect("good")
            # one 50 ms window: all three land in one batch, one slice
            bw.write(pub_packet("t/g", b"g1"))
            aw.write(pub_packet("t/a", b"poison", version=5))
            bw.write(pub_packet("t/g", b"g2"))
            await bw.drain()
            await aw.drain()
            got = [bytes((await read_wire_packet(sub_r)).payload) for _ in range(3)]
            # the poisoned publish was fanned out too: on_published (which
            # failed) runs after the fan-out
            assert sorted(got) == [b"g1", b"g2", b"poison"]
            await asyncio.wait_for(atask, TIMEOUT)  # its connection ended
            assert not btask.done()
            errs = {(cid, p): e for cid, p, e in hook.processed}
            assert errs[("bad", b"poison")] == ERR_UNSPECIFIED_ERROR
            assert errs[("good", b"g1")] is None and errs[("good", b"g2")] is None
            # the survivor still publishes
            bw.write(pub_packet("t/g", b"g3"))
            await bw.drain()
            assert bytes((await read_wire_packet(sub_r)).payload) == b"g3"
            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_next_scan_waits_for_the_last_staged_publish(self):
        """A connection's next socket read is not taken before its staged
        publishes have fanned out: a PINGREQ sent behind a publish is
        answered only after the publish's batch resolved; another
        connection is served meanwhile."""

        async def scenario():
            h = Harness(staged_options())
            await h.server.serve()
            sub_r, _sub_w = await subscriber(h, "sub", "t/#")
            h.server.matcher.flush()
            gate = threading.Event()
            inner = h.server._stage.matcher

            class Gated:
                def match_topics_async(self, topics, profile=None):
                    resolve = inner.match_topics_async(topics, profile=profile)

                    def gated():
                        assert gate.wait(10)
                        return resolve()

                    return gated

            h.server._stage.matcher = Gated()
            pub_r, pub_w, _ = await h.connect("pub")
            other_r, other_w, _ = await h.connect("other")
            ping = encode_packet(Packet(fixed_header=FixedHeader(type=PINGREQ)))
            pub_w.write(pub_packet("t/1", b"one", qos=1, pid=7))
            await pub_w.drain()
            assert (await read_wire_packet(pub_r)).fixed_header.type == PUBACK
            cl = h.server.clients.get("pub")
            assert cl._staged == 1 and cl._staged_waiter is not None
            pub_w.write(ping)
            await pub_w.drain()
            other_w.write(ping)
            await other_w.drain()
            assert (await read_wire_packet(other_r)).fixed_header.type == PINGRESP
            with pytest.raises(asyncio.TimeoutError):
                await asyncio.wait_for(pub_r.readexactly(1), 0.1)
            gate.set()
            assert bytes((await read_wire_packet(sub_r)).payload) == b"one"
            assert (await read_wire_packet(pub_r)).fixed_header.type == PINGRESP
            assert cl._staged == 0 and cl._staged_waiter is None
            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_close_with_parked_publishes_delivers_them(self):
        """``Server.close()`` stops the stage with publishes parked in it:
        each is completed by the host walk and delivered."""

        async def scenario():
            h = Harness(staged_options(window_ms=200.0))
            await h.server.serve()
            sub_r, _sub_w = await subscriber(h, "sub", "t/#")
            h.server.matcher.flush()
            _r, w, _ = await h.connect("pub")
            w.write(b"".join(pub_packet(f"t/{i}", b"%d" % i) for i in range(5)))
            await w.drain()
            stage = h.server._stage
            await until(lambda: stage.pending_depth == 5, "parked")
            await stage.stop()
            got = [bytes((await read_wire_packet(sub_r)).payload) for _ in range(5)]
            assert got == [b"%d" % i for i in range(5)]
            assert stage.batch_completed == 5
            assert int(h.server.telemetry.fallback["stop"].value) == 5
            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_adapter_and_batch_path_deliver_alike(self):
        """Differential, one seeded mix: broker A takes it over a socket
        (parked entries, batch completion); broker B resolves each
        publish through ``submit()``'s future and fans it out as the
        synchronous callers do. Every subscriber receives the same
        messages in the same order."""
        rng = random.Random(2026)
        filters = {
            "w1": ("m/+/x", 0), "w2": ("m/#", 1), "e1": ("m/3/x", 1),
            "e2": ("m/5/y", 0), "s1": ("$share/g/m/+/y", 0), "none": ("q/#", 0),
        }
        mix = [
            (f"m/{rng.randrange(8)}/{rng.choice('xy')}", b"p%d" % i, rng.randrange(2))
            for i in range(80)
        ]

        async def broker():
            h = Harness(staged_options(matcher_stage_max_batch=16))
            await h.server.serve()
            readers = {}
            for cid, (flt, qos) in filters.items():
                readers[cid] = (await subscriber(h, cid, flt, qos))[0]
            h.server.matcher.flush()
            return h, readers

        async def drain(readers):
            out = {}
            for cid, r in readers.items():
                got = []
                while True:
                    try:
                        pk = await asyncio.wait_for(read_wire_packet(r), 0.2)
                    except asyncio.TimeoutError:
                        break
                    got.append((pk.topic_name, bytes(pk.payload), pk.fixed_header.qos))
                out[cid] = got
            return out

        async def scenario():
            ha, ra = await broker()
            pub_r, pub_w, _ = await ha.connect("pub")
            for n, (topic, payload, qos) in enumerate(mix):
                pub_w.write(pub_packet(topic, payload, qos=qos, pid=n + 1))
            await pub_w.drain()
            stage = ha.server._stage
            await until(lambda: stage.batch_completed == len(mix), "fanned out")
            via_batch = await drain(ra)
            assert stage.adapter_completed == 0
            await ha.server.close()
            await ha.shutdown()

            hb, rb = await broker()
            stage = hb.server._stage
            for topic, payload, qos in mix:
                pk = Packet(
                    fixed_header=FixedHeader(type=PUBLISH, qos=qos),
                    topic_name=topic, payload=payload, origin="pub",
                )
                hb.server._fan_out(pk, await stage.submit(topic))
            via_adapter = await drain(rb)
            assert stage.adapter_completed == len(mix)
            assert stage.batch_completed == 0
            await hb.server.close()
            await hb.shutdown()
            return via_batch, via_adapter

        via_batch, via_adapter = run(scenario())
        assert via_batch == via_adapter
        assert sum(len(v) for v in via_batch.values()) > len(mix)
        assert via_batch["none"] == []

    def test_registry_is_read_once_a_slice(self):
        """The slice's targets resolve under one acquisition of the
        ``clients`` lock: its acquisition count moves by the completion
        calls, not by the matched subscribers."""
        from mqtt_tpu.utils.locked import DEFAULT_PLANE

        async def scenario():
            h = Harness(staged_options(window_ms=20.0))
            await h.server.serve()
            readers = [
                (await subscriber(h, f"s{i}", "t/#"))[0] for i in range(6)
            ]
            h.server.matcher.flush()
            _r, w, _ = await h.connect("pub")
            DEFAULT_PLANE.arm()
            try:
                stats = DEFAULT_PLANE.stats("clients")
                before = stats.acquisitions
                w.write(b"".join(pub_packet(f"t/{i}", b"x") for i in range(10)))
                await w.drain()
                for r in readers:
                    for _ in range(10):
                        await read_wire_packet(r)
                stage = h.server._stage
                assert stage.batch_completed == 10
                # 60 matched subscribers; one read a completion call (a
                # housekeeping tick inside the window may add its own few)
                taken = stats.acquisitions - before
                assert stage.batch_completions <= taken < 30
            finally:
                DEFAULT_PLANE.disarm()
            await h.server.close()
            await h.shutdown()

        run(scenario())


def test_locked_map_present_reads_under_one_acquisition():
    from mqtt_tpu.utils.locked import DEFAULT_PLANE, LockedMap

    m = LockedMap(name="present_probe")
    for i in range(5):
        m.add(f"k{i}", i)
    DEFAULT_PLANE.arm()
    try:
        stats = DEFAULT_PLANE.stats("present_probe")
        before = stats.acquisitions
        assert m.present(["k1", "zz", "k4", "k1"]) == {"k1": 1, "k4": 4}
        assert m.present(iter(["zz"])) == {}
        assert m.present({"k0": None}) == {"k0": 0}
        assert stats.acquisitions - before == 3
    finally:
        DEFAULT_PLANE.disarm()


class TestScanWrites:
    """What a connection's handlers write during one socket read leaves
    as one transport write (``Client._cork``), in order."""

    def test_a_reads_acks_leave_as_one_write_in_order(self):
        async def scenario():
            h = Harness(staged_options())
            await h.server.serve()
            pub_r, pub_w, _ = await h.connect("pub")
            cl = h.server.clients.get("pub")
            writes = []
            inner = cl.net.writer.write

            def spy(data):
                writes.append(bytes(data))
                return inner(data)

            cl.net.writer.write = spy
            pub_w.write(
                b"".join(
                    pub_packet(f"t/{i}", b"x", qos=1, pid=10 + i) for i in range(6)
                )
            )
            await pub_w.drain()
            acks = [await read_wire_packet(pub_r) for _ in range(6)]
            assert [a.fixed_header.type for a in acks] == [PUBACK] * 6
            assert [a.packet_id for a in acks] == [10 + i for i in range(6)]
            assert len(writes) == 1 and len(writes[0]) == 6 * 4
            assert cl._cork is None  # between reads nothing is held back
            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_disconnect_written_in_a_read_goes_out_before_the_close(self):
        """A v5 protocol error behind a QoS1 publish in one read: the
        PUBACK and the DISCONNECT both reach the client, in that order,
        before EOF."""
        from mqtt_tpu.packets import DISCONNECT

        async def scenario():
            h = Harness()
            r, w, task = await h.connect("v5", version=5)
            # a second CONNECT: the handler raises a protocol error, which
            # for a v5 client writes a DISCONNECT and stops the client
            bad = connect_packet("v5", 5)
            w.write(pub_packet("a/b", b"ok", qos=1, pid=3, version=5) + bad)
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.fixed_header.type == PUBACK and ack.packet_id == 3
            bye = await read_wire_packet(r, 5)
            assert bye.fixed_header.type == DISCONNECT
            assert await asyncio.wait_for(r.read(16), TIMEOUT) == b""
            await asyncio.wait_for(task, TIMEOUT)
            await h.shutdown()

        run(scenario())

    def test_own_delivery_stays_behind_the_ack(self):
        """No stage: the fan-out runs inside the read. A publisher
        subscribed to its own topic gets the PUBACK of a publish before
        the publish itself: a direct socket write may not overtake the
        packets its read has corked."""

        async def scenario():
            h = Harness()
            r, w = await subscriber(h, "self", "own/#", qos=0)
            w.write(
                pub_packet("own/1", b"a", qos=1, pid=1)
                + pub_packet("own/2", b"b", qos=1, pid=2)
            )
            await w.drain()
            got = [await read_wire_packet(r) for _ in range(4)]
            kinds = [
                (p.fixed_header.type, p.packet_id or bytes(p.payload)) for p in got
            ]
            assert sorted(kinds, key=str) == sorted(
                [(PUBACK, 1), (PUBACK, 2), (PUBLISH, b"a"), (PUBLISH, b"b")], key=str
            )
            assert kinds.index((PUBACK, 1)) < kinds.index((PUBLISH, b"a"))
            assert kinds.index((PUBACK, 2)) < kinds.index((PUBLISH, b"b"))
            assert kinds.index((PUBLISH, b"a")) < kinds.index((PUBLISH, b"b"))
            await h.shutdown()

        run(scenario())


# -- the cork's second opener: a completion slice ---------------------------


class SliceRig:
    """A broker whose completion slices are run by hand: subscribers on
    socketpairs, one log of every call that reaches a socket (the native
    flush's fds, a transport write's bytes) and of every publish's end
    (``on_published``), in the order they happened."""

    def __init__(self, monkeypatch, options=None, allow=True):
        import mqtt_tpu.native as native

        self.h = Harness(options, allow)
        self.server = self.h.server
        self.log = []
        self.fd_owner = {}
        rig = self

        class Marks(ProcessedHook):
            def on_published(self, cl, pk):
                rig.log.append(("published", bytes(pk.payload)))
                if rig.after_publish is not None:
                    rig.after_publish(bytes(pk.payload))

        self.after_publish = None
        self.hook = Marks()
        self.server.add_hook(self.hook)
        inner = native.fan_flush

        def flush_spy(fds, data, id_off=-1, pids=None):
            self.log.append(("flush", [self.fd_owner.get(fd, fd) for fd in fds]))
            return inner(fds, data, id_off, pids)

        monkeypatch.setattr(native, "fan_flush", flush_spy)

    async def join(
        self, client_id, *filters, version=4, qos=0, props=None, clean=True
    ):
        """Connect (``props``: the CONNECT's properties), subscribe,
        and spy on the server side's transport writes. ``filters``: a
        filter, (filter, subscription id), or a Subscription."""
        if props is None:
            r, w, task = await self.h.connect(
                client_id, version=version, clean=clean
            )
        else:
            r, w, task = await self.h.attach()
            cp = Packet(
                fixed_header=FixedHeader(type=1), protocol_version=version,
                properties=props,
            )
            cp.connect.protocol_name = b"MQTT"
            cp.connect.clean = True
            cp.connect.keepalive = 30
            cp.connect.client_identifier = client_id
            w.write(encode_packet(cp))
            assert (await read_wire_packet(r, version)).fixed_header.type == CONNACK
        for n, flt in enumerate(filters):
            sub = (
                flt if isinstance(flt, Subscription)
                else Subscription(filter=flt[0], qos=qos, identifier=flt[1])
                if isinstance(flt, tuple)
                else Subscription(filter=flt, qos=qos)
            )
            w.write(self.subscribe_packet(n + 1, sub, version))
            await w.drain()
            assert (await read_wire_packet(r, version)).fixed_header.type == SUBACK
        cl = self.server.clients.get(client_id)
        self.fd_owner[cl.net.writer.get_extra_info("socket").fileno()] = client_id
        inner = cl.net.writer.write

        def spy(data):
            self.log.append(("write", client_id, bytes(data)))
            return inner(data)

        cl.net.writer.write = spy
        return cl, r, w, task

    @staticmethod
    def subscribe_packet(pid, sub, version):
        if not sub.identifier:
            return sub_packet(pid, [sub], version=version)
        from mqtt_tpu.packets import SUBSCRIBE, Properties

        return encode_packet(
            Packet(
                fixed_header=FixedHeader(type=SUBSCRIBE, qos=1),
                protocol_version=version,
                packet_id=pid,
                filters=[sub],
                properties=Properties(subscription_identifier=[sub.identifier]),
            )
        )

    def slice_of(self, origin, publishes, qos=0):
        """One slice as the stage hands it over: ``(topic, payload)``
        or ``(topic, payload, qos)`` publishes of ``origin`` in submit
        order, each with the host trie's answer."""
        entries, results = [], []
        for topic, payload, *own in publishes:
            pk = Packet(
                fixed_header=FixedHeader(type=PUBLISH, qos=own[0] if own else qos),
                protocol_version=4, topic_name=topic, payload=payload,
                origin=origin.id,
            )
            entries.append(Parked(None, None, None, None, origin, pk))
            results.append(self.server.topics.subscribers(topic))
        return entries, results

    def run_slice(self, origin, publishes, qos=0):
        del self.log[:]
        self.server._complete_staged(*self.slice_of(origin, publishes, qos))
        return list(self.log)

    def writes(self, client_id):
        return [e[2] for e in self.log if e[0] == "write" and e[1] == client_id]


def frames(publishes, version=4):
    return [pub_packet(t, p, version=version) for t, p in publishes]


class TestSliceWrites:
    """A slice's deliveries to a socket it targets more than once leave
    as one write when the slice ends; everything else as before."""

    def test_64_publishes_to_one_socket_are_one_write_in_submit_order(
        self, monkeypatch
    ):
        async def scenario():
            rig = SliceRig(monkeypatch)
            pub, *_ = await rig.join("pub")
            sub, sub_r, *_ = await rig.join("sub", "t/#")
            pubs = [(f"t/{i % 5}", b"m%02d" % i) for i in range(64)]
            log = rig.run_slice(pub, pubs)
            assert rig.writes("sub") == [b"".join(frames(pubs))]
            assert [e for e in log if e[0] == "flush"] == []
            # the write came after the slice's last publish was done
            assert log[-1][0] == "write" and log[-2] == ("published", b"m63")
            assert sub._cork is None  # nothing is held past the slice
            got = [bytes((await read_wire_packet(sub_r)).payload) for _ in pubs]
            assert got == [p for _t, p in pubs]
            await rig.h.shutdown()

        run(scenario())

    def test_a_socket_targeted_once_is_written_at_its_own_publish(
        self, monkeypatch
    ):
        async def scenario():
            rig = SliceRig(monkeypatch)
            pub, *_ = await rig.join("pub")
            _once, once_r, *_ = await rig.join("once", "t/a")
            _wide, wide_r, *_ = await rig.join("wide", "t/#")
            pubs = [("t/a", b"a"), ("t/b", b"b1"), ("t/b", b"b2"), ("t/c", b"c")]
            log = rig.run_slice(pub, pubs)
            # "once" goes out through the native flush inside publish "a";
            # "wide", targeted four times, in one write at the slice's end
            assert log.index(("flush", ["once"])) < log.index(("published", b"a"))
            assert rig.writes("once") == []
            assert rig.writes("wide") == [b"".join(frames(pubs))]
            assert log.index(("published", b"c")) < len(log) - 1
            assert bytes((await read_wire_packet(once_r)).payload) == b"a"
            for _t, p in pubs:
                assert bytes((await read_wire_packet(wide_r)).payload) == p
            await rig.h.shutdown()

        run(scenario())

    def test_no_repeat_no_cork(self, monkeypatch):
        """A broadcast to sockets each targeted once: today's one native
        flush a publish over all of them, no transport write."""

        async def scenario():
            rig = SliceRig(monkeypatch)
            pub, *_ = await rig.join("pub")
            for k in range(3):
                await rig.join(f"s{k}", "t/one")
            log = rig.run_slice(pub, [("t/one", b"x"), ("q/none", b"y")])
            assert [e for e in log if e[0] != "published"] == [
                ("flush", ["s0", "s1", "s2"])
            ]
            await rig.h.shutdown()

        run(scenario())

    def test_qos1_frames_in_a_cork_carry_their_own_packet_ids(self, monkeypatch):
        async def scenario():
            rig = SliceRig(monkeypatch)
            pub, *_ = await rig.join("pub")
            sub, sub_r, *_ = await rig.join("sub", "t/#", qos=1)
            pubs = [(f"t/{i}", b"q%d" % i) for i in range(5)]
            rig.run_slice(pub, pubs, qos=1)
            assert len(rig.writes("sub")) == 1
            got = [await read_wire_packet(sub_r) for _ in pubs]
            assert [bytes(p.payload) for p in got] == [p for _t, p in pubs]
            assert [p.fixed_header.qos for p in got] == [1] * 5
            pids = [p.packet_id for p in got]
            assert len(set(pids)) == 5 and 0 not in pids
            inflight = {p.packet_id: bytes(p.payload) for p in sub.state.inflight.get_all(False)}
            assert inflight == {p.packet_id: bytes(p.payload) for p in got}
            assert rig.server.info.inflight == 5
            await rig.h.shutdown()

        run(scenario())

    @pytest.mark.parametrize("slow", ["identifier", "alias"])
    def test_slow_path_delivery_arrives_behind_the_collected_frames(
        self, monkeypatch, slow
    ):
        """A delivery that needs a rewrite of its own (a subscription
        identifier; an outbound topic alias) rides the outbound queue,
        which drains after the slice's flush: the socket sees submit
        order either way."""

        async def scenario():
            from mqtt_tpu.packets import Properties

            rig = SliceRig(monkeypatch)
            pub, *_ = await rig.join("pub")
            if slow == "identifier":
                sub, r, w, _ = await rig.join("sub", "a/#", ("b/#", 7), version=5)
            else:
                sub, r, w, _ = await rig.join(
                    "sub", "+/#", version=5, props=Properties(topic_alias_maximum=8)
                )
            pubs = [("a/1", b"1"), ("a/2", b"2"), ("b/1", b"3"), ("a/3", b"4")]
            rig.run_slice(pub, pubs)
            assert sub._cork is None
            got = [await read_wire_packet(r, 5) for _ in pubs]
            assert [bytes(p.payload) for p in got] == [b"1", b"2", b"3", b"4"]
            if slow == "identifier":
                assert [p.properties.subscription_identifier for p in got] == [
                    [], [], [7], []
                ]
            else:
                assert [p.properties.topic_alias for p in got] == [1, 2, 3, 4]
            await rig.h.shutdown()

        run(scenario())

    def test_a_socket_corked_by_its_own_read_takes_the_delivery_into_it(self):
        """No stage: the fan-out runs inside the read. A publisher that
        hears its own topic gets acks and deliveries of one read as ONE
        write, in the order they were made."""

        async def scenario():
            h = Harness()
            r, w = await subscriber(h, "self", "own/#", qos=0)
            cl = h.server.clients.get("self")
            writes = []
            inner = cl.net.writer.write

            def spy(data):
                writes.append(bytes(data))
                return inner(data)

            cl.net.writer.write = spy
            sends = h.server._ops.socket_sends
            w.write(b"".join(
                pub_packet(f"own/{i}", b"%d" % i, qos=1, pid=i + 1) for i in range(3)
            ))
            await w.drain()
            got = [await read_wire_packet(r) for _ in range(6)]
            kinds = [
                (p.fixed_header.type, p.packet_id or bytes(p.payload)) for p in got
            ]
            assert kinds == [
                (PUBACK, 1), (PUBLISH, b"0"), (PUBACK, 2), (PUBLISH, b"1"),
                (PUBACK, 3), (PUBLISH, b"2"),
            ]
            assert len(writes) == 1
            assert h.server._ops.socket_sends - sends == 1
            await h.shutdown()

        run(scenario())

    @pytest.mark.parametrize("how", ["closes", "evicted"])
    def test_a_socket_that_goes_mid_slice_loses_nothing_written_before(
        self, monkeypatch, how
    ):
        async def scenario():
            from mqtt_tpu.packets import DISCONNECT, ERR_QUOTA_EXCEEDED, Code

            rig = SliceRig(monkeypatch)
            pub, *_ = await rig.join("pub")
            sub, sub_r, _w, sub_task = await rig.join("sub", "t/#", version=5)
            _other, other_r, *_ = await rig.join("other", "t/#")

            def after(payload):
                if payload != b"2":
                    return
                if how == "closes":
                    sub.stop()
                else:
                    try:
                        rig.server.disconnect_client(sub, ERR_QUOTA_EXCEEDED)
                    except Code:
                        pass

            rig.after_publish = after
            pubs = [("t/x", b"%d" % i) for i in range(1, 6)]
            rig.run_slice(pub, pubs)  # raises nothing
            assert [e for _c, _p, e in rig.hook.processed] == [None] * 5
            got = [await read_wire_packet(sub_r, 5) for _ in range(2)]
            assert [bytes(p.payload) for p in got] == [b"1", b"2"]
            if how == "evicted":
                bye = await read_wire_packet(sub_r, 5)
                assert bye.fixed_header.type == DISCONNECT
                assert bye.reason_code == ERR_QUOTA_EXCEEDED.code
            assert await asyncio.wait_for(sub_r.read(16), TIMEOUT) == b""
            assert sub._cork is None
            # the socket beside it got all five, as one write
            assert rig.writes("other") == [b"".join(frames(pubs))]
            await asyncio.wait_for(sub_task, TIMEOUT)
            await rig.h.shutdown()

        run(scenario())

    def test_a_short_write_at_the_flush_finishes_through_the_transport(
        self, monkeypatch
    ):
        """A joined write larger than the socket's buffer: the transport
        keeps the rest and finishes it in order; a later slice finds the
        transport busy and takes the outbound queue behind it."""
        import socket as socketlib

        async def scenario():
            rig = SliceRig(monkeypatch)
            pub, *_ = await rig.join("pub")
            sub, sub_r, *_ = await rig.join("sub", "t/#")
            sock = sub.net.writer.get_extra_info("socket")
            sock.setsockopt(socketlib.SOL_SOCKET, socketlib.SO_SNDBUF, 4096)
            first = [(f"t/{i}", bytes([65 + i]) * 3000) for i in range(20)]
            rig.run_slice(pub, first)
            assert len(rig.writes("sub")) == 1
            assert sub.net.writer.transport.get_write_buffer_size() > 0
            second = [("t/z", b"tail-%d" % i) for i in range(3)]
            log = rig.run_slice(pub, second)
            assert [e for e in log if e[0] != "published"] == []  # queued
            for _t, p in first + second:
                assert bytes((await read_wire_packet(sub_r)).payload) == p
            await rig.h.shutdown()

        run(scenario())

    def test_the_byte_cap_flushes_a_cork_early(self, monkeypatch):
        import mqtt_tpu.clients as clients_mod

        async def scenario():
            monkeypatch.setattr(clients_mod, "CORK_MAX_BYTES", 300)
            rig = SliceRig(monkeypatch)
            pub, *_ = await rig.join("pub")
            sub, sub_r, *_ = await rig.join("sub", "t/#")
            pubs = [(f"t/{i}", b"%02d" % i * 32) for i in range(30)]
            rig.run_slice(pub, pubs)
            writes = rig.writes("sub")
            size = len(frames(pubs)[0])
            assert len(writes) > 3 and b"".join(writes) == b"".join(frames(pubs))
            assert all(len(w) < 300 + size for w in writes)
            assert all(len(w) >= 300 for w in writes[:-1])
            assert sub._cork is None
            for _t, p in pubs:
                assert bytes((await read_wire_packet(sub_r)).payload) == p
            await rig.h.shutdown()

        run(scenario())

    def test_sends_count_sends_and_frames_count_frames(self, monkeypatch):
        async def scenario():
            rig = SliceRig(monkeypatch)
            srv = rig.server
            pub, *_ = await rig.join("pub")
            sub, *_ = await rig.join("sub", "t/#")
            once, *_ = await rig.join("once", "t/7")
            before = (
                srv._ops.socket_sends, srv.info.packets_sent, srv.info.messages_sent,
                srv.info.bytes_sent, srv.telemetry.fanout_deliveries.value,
                sub.state.out_writes, once.state.out_writes,
                srv._slice_counters()["deliveries"],
            )
            pubs = [(f"t/{i}", b"p%02d" % i) for i in range(64)]
            rig.run_slice(pub, pubs)
            nbytes = sum(len(f) for f in frames(pubs)) + len(frames([pubs[7]])[0])
            after = (
                srv._ops.socket_sends, srv.info.packets_sent, srv.info.messages_sent,
                srv.info.bytes_sent, srv.telemetry.fanout_deliveries.value,
                sub.state.out_writes, once.state.out_writes,
                srv._slice_counters()["deliveries"],
            )
            assert [a - b for a, b in zip(after, before)] == [
                2, 65, 65, nbytes, 65, 64, 1, 65,
            ]
            await rig.h.shutdown()

        run(scenario())

    def test_the_slices_flush_is_counted_as_fan_out_time(self, monkeypatch):
        """While a profiler session keeps the batch, the joined writes at
        the slice's end add to the fan-out's busy time
        (``loop_us_per_pub.fanout``) and to no publish's count."""

        class Profiler:
            armed = False  # no live session: the sends go untimed

            def __init__(self):
                self.fanouts, self.flushes = 0, []

            def note_fanout(self, set_ns, start_ns, done_ns):
                assert set_ns == 1 and done_ns >= start_ns
                self.fanouts += 1

            def note_slice_flush(self, busy_ns):
                self.flushes.append(busy_ns)

            def annotation(self, name, **args):
                # one socket's cork, the slice's two deliveries in it
                assert name == "mqtt/loop.flush" and args == {"sends": 1, "frames": 2}
                return contextlib.nullcontext()

        async def scenario():
            rig = SliceRig(monkeypatch)
            prof = rig.server.profiler = Profiler()
            pub, *_ = await rig.join("pub")
            await rig.join("sub", "t/#")
            rig.server._complete_staged(
                *rig.slice_of(pub, [("t/1", b"a"), ("q/1", b"b")]), 1
            )
            assert (prof.fanouts, prof.flushes) == (2, [])  # nothing corked
            rig.server._complete_staged(
                *rig.slice_of(pub, [("t/1", b"a"), ("t/2", b"b")]), 1
            )
            assert prof.fanouts == 4 and len(prof.flushes) == 1
            assert prof.flushes[0] > 0
            await rig.h.shutdown()

        run(scenario())

    @pytest.mark.parametrize("error", [RuntimeError, KeyboardInterrupt])
    def test_a_failing_publish_still_flushes_every_open_cork(
        self, monkeypatch, error
    ):
        """An exception in one publish's fan-out is that publish's (the
        slice goes on); one that nothing may swallow leaves the slice at
        once. Either way every cork the slice opened is written and
        closed before ``_complete_staged`` returns."""

        async def scenario():
            rig = SliceRig(monkeypatch)
            srv = rig.server
            pub, *_ = await rig.join("pub")
            a, a_r, *_ = await rig.join("a", "t/#")
            b, b_r, *_ = await rig.join("b", "t/+")
            inner = srv._fan_out

            def failing(pk, *args):
                if bytes(pk.payload) == b"boom":
                    raise error("fan-out failed")
                return inner(pk, *args)

            monkeypatch.setattr(srv, "_fan_out", failing)
            pubs = [("t/1", b"1"), ("t/2", b"2"), ("t/3", b"boom"), ("t/4", b"4")]
            if error is RuntimeError:
                rig.run_slice(pub, pubs)
                want = [pubs[0], pubs[1], pubs[3]]
            else:
                with pytest.raises(KeyboardInterrupt):
                    rig.run_slice(pub, pubs)
                want = pubs[:2]
            assert a._cork is None and b._cork is None
            for cid, r in (("a", a_r), ("b", b_r)):
                assert rig.writes(cid) == [b"".join(frames(want))]
                for _t, p in want:
                    assert bytes((await read_wire_packet(r)).payload) == p
            await rig.h.shutdown()

        run(scenario())


# -- what a socket is, kept for the slice (clients.SliceSocket) --------------


class AclGate(Hook):
    """Allows everything but the ``(client id, topic)`` pairs in
    ``denied``; ``during`` is called inside every subscriber-side check."""

    def __init__(self, denied=()):
        super().__init__()
        self.denied = set(denied)
        self.during = None

    def id(self):
        return "acl-gate"

    def provides(self, b):
        from mqtt_tpu.hooks import ON_ACL_CHECK, ON_CONNECT_AUTHENTICATE

        return b in (ON_CONNECT_AUTHENTICATE, ON_ACL_CHECK)

    def on_connect_authenticate(self, cl, pk):
        return True

    def on_acl_check(self, cl, topic, write):
        if not write and self.during is not None:
            self.during(cl, topic)
        return (cl.id, topic) not in self.denied


def counts(srv, clients):
    """Every count a delivery moves, as it stands."""
    from mqtt_tpu.server import _EGRESS_COUNTERS

    ops, info = srv._ops, srv.info
    c = {k: getattr(ops, k) for k in _EGRESS_COUNTERS + ("socket_sends",)}
    c.update(
        bytes_sent=info.bytes_sent, packets_sent=info.packets_sent,
        messages_sent=info.messages_sent, inflight=info.inflight,
        dropped=info.messages_dropped,
        deliveries=srv.telemetry.fanout_deliveries.value,
        outbound_bytes=srv.telemetry.outbound_bytes.value,
        outbound_writes=srv.telemetry.outbound_writes.value,
    )
    for cl in clients:
        c["out:" + cl.id] = (cl.state.out_bytes, cl.state.out_writes)
        c["quota:" + cl.id] = cl.state.inflight.send_quota
        c["ids:" + cl.id] = sorted(
            (p.packet_id, bytes(p.payload)) for p in cl.state.inflight.get_all(False)
        )
    return c


async def drained(srv, readers):
    """Everything the sockets were sent, a socket: the outbound queues
    first (their write loops need the event loop), then each socket
    until it is quiet."""
    for _ in range(200):
        if not any(cl.state.outbound_qty for cl in srv.clients.get_all().values()):
            break
        await asyncio.sleep(0.005)

    async def one(reader):
        got = b""
        while True:
            try:
                chunk = await asyncio.wait_for(reader.read(1 << 16), 0.05)
            except asyncio.TimeoutError:
                return got
            if not chunk:
                return got
            got += chunk

    return dict(zip(readers, await asyncio.gather(*map(one, readers.values()))))


def delta(after, before):
    """What moved between two ``counts``; the in-flight ids as they are."""
    moved = {}
    for k, v in after.items():
        if isinstance(v, tuple):
            moved[k] = tuple(x - y for x, y in zip(v, before[k]))
        else:
            moved[k] = v if isinstance(v, list) else v - before[k]
    return moved


async def served_mix(monkeypatch, seed, mode):
    """One seeded mix of subscribers and publishes through a broker of
    its own. ``mode``: ``slice`` (the publishes as ONE completion slice),
    ``single`` (as one slice a publish, nothing between them), ``read``
    (one slice, no socket given a record: every delivery reads its
    socket, as before ISSUE 38). Returns the bytes each socket got, in
    order, and the counts the publishes moved."""
    from mqtt_tpu.packets import Properties

    rng = random.Random(seed)
    topics = ["m/a/x", "m/b/x", "m/a/y", "m/c/x", "q/none"]
    gate = AclGate({(rng.choice(["a4", "c5", "e1"]), rng.choice(topics[:4]))})
    rig = SliceRig(monkeypatch, allow=False)
    srv = rig.server
    srv.add_hook(gate)
    if mode == "read":
        # the slice corks as ever and keeps no record
        monkeypatch.setattr(srv, "_session_shares_frames", lambda props: False)
    q = lambda: rng.randrange(2)  # noqa: E731
    roster = [
        ("pub", 5, None, [Subscription(filter="m/#", qos=q(), no_local=True)]),
        ("nl", 5, None, [Subscription(filter="m/#", qos=q(), no_local=True)]),
        ("a4", 4, None, [Subscription(filter="m/#", qos=q())]),
        ("b4", 4, None, [Subscription(filter="m/+/x", qos=1)]),
        ("c5", 5, None, [Subscription(filter="m/#", qos=q())]),
        # a plain subscription and one with an identifier, one socket
        ("d5", 5, None, [
            Subscription(filter="m/a/#", qos=q()),
            Subscription(filter="m/b/#", qos=q(), identifier=7),
        ]),
        ("al", 5, Properties(topic_alias_maximum=8), [Subscription(filter="m/#", qos=q())]),
        ("mx", 5, Properties(maximum_packet_size=4096), [Subscription(filter="m/+/x", qos=q())]),
    ] + [
        (f"e{k}", 4 + k % 2, None, [Subscription(filter=topics[k], qos=q())])
        for k in range(4)
    ]
    clients, readers = [], {}
    for cid, version, props, filters in roster:
        cl, r, _w, _t = await rig.join(cid, *filters, version=version, props=props)
        clients.append(cl)
        readers[cid] = r
    pubs = [
        (rng.choice(topics), b"%03d" % i + bytes(rng.randrange(40)), rng.randrange(2))
        for i in range(rng.randrange(24, 64))
    ]
    before = counts(srv, clients)
    if mode == "single":
        for one in pubs:
            rig.run_slice(clients[0], [one])
    else:
        rig.run_slice(clients[0], pubs)
    assert all(cl._cork is None and cl._slice is None for cl in clients)
    got = await drained(srv, readers)
    moved = delta(counts(srv, clients), before)
    await rig.h.shutdown()
    return got, moved


class TestASliceReadsASocketOnce:
    """ISSUE 38: a completion slice keeps a record of each ready socket
    it corked, and a delivery to it is one append. Nothing a socket
    receives and nothing that is counted may differ for it."""

    @pytest.mark.parametrize("seed", [38, 3801, 380017, 2**31 + 38, 4099, 77])
    def test_a_slice_of_n_equals_n_slices_of_one(self, monkeypatch, seed):
        """The same publishes as one slice, as a slice each, and as one
        slice that reads every socket at every delivery: every socket
        gets the same bytes in the same order, and the counts agree
        (between the first two all but the route, which is the point: a
        socket hit once a slice is written at its publish)."""

        async def scenario():
            sliced, n_sliced = await served_mix(monkeypatch, seed, "slice")
            single, n_single = await served_mix(monkeypatch, seed, "single")
            read, n_read = await served_mix(monkeypatch, seed, "read")
            assert sliced == single == read
            assert sum(map(len, sliced.values())) > 2000
            # against the slice that reads a socket a delivery: every
            # count but the reads themselves
            checks = n_sliced.pop("socket_checks"), n_read.pop("socket_checks")
            assert n_sliced == n_read
            assert checks[0] < checks[1]
            # against a slice a publish: nothing is corked there, so
            # the routes and the writes differ and nothing else
            routes = ("deliveries_flush", "deliveries_cork", "deliveries_queue")
            assert sum(n_sliced[k] for k in routes) == sum(n_single[k] for k in routes)
            assert n_single["cork_writes"] == n_single["deliveries_cork"] == 0
            assert n_sliced["cork_frames"] == n_sliced["deliveries_cork"] > 0
            aside = routes + (
                "cork_writes", "cork_frames", "cork_early_writes", "socket_sends",
            )
            for k in aside + ("socket_checks",):
                n_sliced.pop(k, None), n_single.pop(k, None)
            assert n_sliced == n_single

        run(scenario())

    def test_one_read_a_socket_a_slice(self, monkeypatch):
        """``socket_checks``: one for 64 publishes to one socket, N for
        N sockets hit once."""

        async def scenario():
            rig = SliceRig(monkeypatch)
            ops = rig.server._ops
            pub, *_ = await rig.join("pub")
            sub, *_ = await rig.join("sub", "t/#")
            for k in range(5):
                await rig.join(f"s{k}", "one/shot")
            at, wrote = ops.socket_checks, sub.state.out_writes
            rig.run_slice(pub, [(f"t/{i}", b"%d" % i) for i in range(64)])
            assert ops.socket_checks - at == 1
            assert ops.deliveries_cork == 64 == sub.state.out_writes - wrote
            at = ops.socket_checks
            rig.run_slice(pub, [("one/shot", b"x")])
            assert ops.socket_checks - at == 5 and ops.deliveries_flush == 5
            await rig.h.shutdown()

        run(scenario())

    @pytest.mark.parametrize(
        "case",
        ["byte_cap", "closed_by_a_hook", "closed_in_its_own_acl_call",
         "queue_not_empty", "slow_path_between", "queued_in_its_own_acl_call"],
    )
    def test_what_ends_a_record_early(self, monkeypatch, case):
        """Whatever else puts a frame on the socket's way out, or takes
        the socket away, ends the record: the next delivery reads the
        socket afresh. ``other``, beside it, always gets the whole
        slice as one write."""
        import mqtt_tpu.clients as clients_mod

        async def scenario():
            from mqtt_tpu.packets import Properties

            if case == "byte_cap":
                monkeypatch.setattr(clients_mod, "CORK_MAX_BYTES", 300)
            gate = AclGate()
            rig = SliceRig(monkeypatch, allow=False)
            srv, ops = rig.server, rig.server._ops
            srv.add_hook(gate)
            pub, *_ = await rig.join("pub")
            filters = ("t/#", ("s/#", 9)) if case == "slow_path_between" else ("t/#",)
            sub, sub_r, *_ = await rig.join("sub", *filters, version=5)
            other, other_r, *_ = await rig.join("other", "+/#")
            readers = {"sub": sub_r, "other": other_r}
            pubs = [(f"t/{i}", b"%02d" % i * 16) for i in range(12)]
            ahead = b""
            want = [p for _t, p in pubs]
            if case == "closed_by_a_hook":
                rig.after_publish = lambda p: p == pubs[4][1] and sub.stop()
                want = want[:5]
            elif case == "closed_in_its_own_acl_call":
                gate.during = lambda cl, t: (cl, t) == (sub, "t/5") and sub.stop()
                want = want[:5]
            elif case == "queue_not_empty":
                ahead = pub_packet("was/queued", b"first", version=5)
                assert srv._enqueue_frame(sub, ahead, lambda: None)
            elif case == "slow_path_between":
                pubs[5] = ("s/5", pubs[5][1])
            elif case == "queued_in_its_own_acl_call":
                ahead = pub_packet("from/the/hook", b"hook", version=5)
                gate.during = lambda cl, t: (cl, t) == (sub, "t/5") and (
                    srv._enqueue_frame(sub, ahead, lambda: None)
                )
            before = counts(srv, [sub, other])
            rig.run_slice(pub, pubs)
            assert sub._slice is None and other._slice is None
            assert b"".join(rig.writes("other")) == b"".join(frames(pubs))
            assert case == "byte_cap" or len(rig.writes("other")) == 1
            got = await drained(srv, readers)
            n = delta(counts(srv, [sub, other]), before)
            sent = b"".join(frames([(t, p) for t, p in pubs if p in want], 5))
            if case == "slow_path_between":
                sent = sent.replace(
                    pub_packet("s/5", pubs[5][1], version=5),
                    encode_packet(Packet(
                        fixed_header=FixedHeader(type=PUBLISH), protocol_version=5,
                        topic_name="s/5", payload=pubs[5][1],
                        properties=Properties(subscription_identifier=[9]),
                    )),
                )
            if case == "queue_not_empty":
                assert got["sub"] == ahead + sent  # everything behind it
                # one read as the slice corks it, one a delivery; one for other
                assert n["deliveries_queue"] == 12 and n["socket_checks"] == 1 + 12 + 1
            elif case == "queued_in_its_own_acl_call":
                cut = len(b"".join(frames(pubs[:5], 5)))
                assert got["sub"] == sent[:cut] + ahead + sent[cut:]
                # five into the cork, seven behind the hook's frame
                assert (n["deliveries_cork"], n["deliveries_queue"]) == (12 + 5, 7)
            else:
                assert got["sub"] == sent
            if case == "byte_cap":
                size = len(frames(pubs[:1], 5)[0])
                early = len(rig.writes("sub")) - 1
                assert early >= 1
                assert n["cork_early_writes"] == early + len(rig.writes("other")) - 1
                assert all(300 <= len(w) < 300 + size for w in rig.writes("sub")[:-1])
                # one read each record, then one a delivery
                assert 2 < n["socket_checks"] < 2 * 12
            elif case.startswith("closed"):
                # the five before went out as the socket closed; the
                # seven after were dropped, as a closed socket's are
                assert n["out:sub"] == (len(sent), 5)
                assert n["deliveries_cork"] == 12 + 5 and n["dropped"] == 0
            elif case == "slow_path_between":
                # five into the cork, the sixth and all behind it queued
                assert (n["deliveries_cork"], n["deliveries_queue"]) == (12 + 5, 6)
                assert n["out:sub"] == (len(sent), 12)
            assert n["out:other"] == (len(b"".join(frames(pubs))), 12)
            assert n["messages_sent"] == n["out:sub"][1] + 12
            await rig.h.shutdown()

        run(scenario())

    @pytest.mark.parametrize(
        "case",
        ["offline_session_qos0", "offline_session_qos1",
         "closed_in_its_own_acl_call", "queued_in_its_own_acl_call"],
    )
    def test_a_socket_that_is_gone_costs_what_it_did(self, monkeypatch, case, caplog):
        """A session that outlives its socket stays in the registry, its
        dead writer with it: hit twice a slice it is corked as ever, but
        is no socket to keep a record of, and a delivery to it ends at
        ``closed``, before any QoS bookkeeping. So does the delivery
        whose own ACL call closes the socket. Against the same slice
        with no records (every delivery reads its socket): the same
        bytes, in-flight ids, send quota and counts, and no warning."""
        import logging

        qos = 0 if case.endswith("qos0") else 1

        async def served(records):
            gate = AclGate()
            rig = SliceRig(monkeypatch, allow=False)
            srv = rig.server
            srv.add_hook(gate)
            if not records:
                monkeypatch.setattr(srv, "_session_shares_frames", lambda props: False)
            pub, *_ = await rig.join("pub")
            sub, sub_r, sub_w, sub_task = await rig.join(
                "sub", "t/#", qos=1, clean=False
            )
            live, live_r, *_ = await rig.join("live", "t/#", qos=1)
            readers = {"live": live_r}
            if case.startswith("offline"):
                sub_w.close()
                await asyncio.wait_for(sub_task, TIMEOUT)
                assert sub.closed and sub.net.writer is not None
                assert srv.clients.get("sub") is sub  # the session stays
            else:
                readers["sub"] = sub_r
                if case.startswith("closed"):
                    gate.during = lambda cl, t: (cl, t) == (sub, "t/5") and sub.stop()
                else:
                    ahead = pub_packet("from/the/hook", b"hook")
                    gate.during = lambda cl, t: (cl, t) == (sub, "t/5") and (
                        srv._enqueue_frame(sub, ahead, lambda: None)
                    )
            pubs = [(f"t/{i}", b"%02d" % i) for i in range(12)]
            before = counts(srv, [sub, live])
            with caplog.at_level(logging.WARNING):
                caplog.clear()
                rig.run_slice(pub, pubs, qos=qos)
                assert caplog.records == []
            assert sub._slice is None and sub._cork is None
            if case.startswith("offline"):
                assert rig.writes("sub") == []  # nothing into a dead transport
            got = await drained(srv, readers)
            moved = delta(counts(srv, [sub, live]), before)
            await rig.h.shutdown()
            return got, moved

        async def scenario():
            kept, n_kept = await served(True)
            read, n_read = await served(False)
            assert kept == read and len(kept["live"]) > 12 * 6
            checks = n_kept.pop("socket_checks"), n_read.pop("socket_checks")
            assert n_kept == n_read
            if case.startswith("offline"):
                # one read a corked socket, against one a delivery to live
                assert checks == (2, 12)
                assert n_kept["ids:sub"] == [] and n_kept["quota:sub"] == 0
                assert n_kept["out:sub"] == (0, 0)
                assert n_kept["deliveries_cork"] == 12 == n_kept["messages_sent"]
            elif case.startswith("closed"):
                # five booked and sent, the sixth stopped at `closed`
                assert len(n_kept["ids:sub"]) == 5 and n_kept["inflight"] == 5 + 12

        run(scenario())

    def test_another_shards_record_is_left_to_its_loop(self, monkeypatch):
        """Under the shard fabric a socket's record belongs to the slice
        of the loop that owns the socket: a fan-out on another loop goes
        on marshalling its delivery there."""

        async def scenario():
            from mqtt_tpu.clients import SliceSocket

            rig = SliceRig(monkeypatch)
            srv = rig.server
            pub, *_ = await rig.join("pub")
            sub, *_ = await rig.join("sub", "t/#")
            elsewhere = asyncio.new_event_loop()
            try:
                monkeypatch.setattr(srv, "_fabric", object())
                sub.net.loop = elsewhere  # another shard's socket ...
                sub._cork = bytearray()
                rec = sub._slice = SliceSocket(sub)  # ... in its own slice
                rig.run_slice(pub, [("t/1", b"a")])
                assert rec.n == 0 and not rec.cork and sub._slice is rec
                assert len(elsewhere._ready) == 1  # marshalled, as ever
            finally:
                sub._cork = sub._slice = None
                elsewhere.close()
            await rig.h.shutdown()

        run(scenario())


class TestEchoServed:
    """ISSUE 28's served check: stresser's echo loop over loopback TCP
    against the plain reference, with and without the loop shard fabric
    (where a remote shard's sockets are written on their own loop,
    outside any slice)."""

    @pytest.mark.parametrize("shards", [1, 3])
    @pytest.mark.parametrize("seed", [28, 4099, 2**31 + 11])
    def test_every_message_back_in_order_exactly_once(self, seed, shards):
        n_clients, chunk, rounds = 20, 64, 3
        total = n_clients * chunk * rounds
        verdict, delivered, n = run_echo(
            seed, n_clients, chunk, rounds, loop_shards=shards
        )
        assert verdict["errors"] == 0 and verdict["misordered"] == 0, verdict
        assert delivered == total
        if shards == 1:
            # one loop owns every socket: a 64-frame chunk leaves in a few
            # writes (a count, not a rate), where the parent made 3,840
            assert n["loops"] == 1 and n["sends"] < total // 8
        else:
            assert n["loops"] > 1
