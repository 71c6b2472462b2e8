"""The slice a profiler session leaves behind (mqtt_tpu.tracing): while
``jax.profiler`` traces, every staged device batch keeps its span tree
and the session is bracketed by two snapshots; ``last_slice()`` returns
it once the session has ended. CPU backend throughout: boundaries and
counts, never a rate."""

import asyncio
import gc
import glob
import os
import time

import pytest

import jax

from mqtt_tpu import tracing
from mqtt_tpu.ops.matcher import TpuMatcher
from mqtt_tpu.packets import Subscription
from mqtt_tpu.staging import MatchStage
from mqtt_tpu.telemetry import Telemetry
from mqtt_tpu.topics import TopicsIndex
from mqtt_tpu.tracing import (
    BUSY_SPANS,
    BatchProfile,
    DeviceProfiler,
    Gen2Pauses,
    TraceSlice,
    thread_group,
)

from tests.test_compact import build_index
from tests.test_ops_matcher import canon
from tests.test_server import run

CHAIN = (
    "submit_first_ns", "formed_ns", "issue_start_ns", "tokenize",
    "h2d_dispatch", "d2h_sync", "resolve", "deliver",
)


@pytest.fixture(autouse=True)
def no_slice(monkeypatch):
    """``last_slice()`` is process-wide: every test starts without one."""
    monkeypatch.setattr(tracing, "_LAST_SLICE", None)


def start_session(tmp_path) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)


def staged(index, profiler, telemetry=None, **kw):
    m = TpuMatcher(index, max_levels=4)
    m.profiler = profiler
    m.rebuild()
    if profiler is not None:
        profiler.matcher_stats = m.stats
    stage = MatchStage(
        m, index.subscribers, window_s=0.001, profiler=profiler,
        telemetry=telemetry, **kw,
    )
    return m, stage


async def bursts(stage, index, topics, size):
    for i in range(0, len(topics), size):
        chunk = topics[i : i + size]
        got = await asyncio.gather(*[stage.submit(t) for t in chunk])
        for t, subs in zip(chunk, got):
            assert canon(subs) == canon(index.subscribers(t))
        await asyncio.sleep(0.005)


def boundaries(rec: BatchProfile) -> list:
    out = []
    for slot in CHAIN:
        v = getattr(rec, slot)
        if slot == "submit_first_ns" and rec.wait_n == 0:
            continue  # parked before the session: no submit was stamped
        assert v is not None, slot
        out += list(v) if isinstance(v, tuple) else [v]
    return out


def one_session(tmp_path, n_topics=120, burst=40):
    """A few staged batches inside a start_trace / stop_trace pair."""
    index, topic_gen = build_index(71)
    prof = DeviceProfiler()
    m, stage = staged(index, prof)
    topics = [topic_gen() for _ in range(n_topics)]
    submits: dict = {}

    async def scenario():
        stage.start()
        await bursts(stage, index, topics[:burst], burst)  # before: not kept
        assert tracing.last_slice() is None and not prof.armed
        start_session(tmp_path)
        try:
            await bursts(stage, index, topics, burst)
            # the members' own submit instants, for the stage.wait sums
            futs = []
            for t in topics[:burst]:
                futs.append(stage.submit(t))
                entry = stage._pending[-1][1]  # what submit() just parked
                assert entry.fut is futs[-1]
                submits[id(entry)] = entry.submit_ns
            await asyncio.gather(*futs)
            await asyncio.sleep(0.02)  # the heartbeat gets to run
        finally:
            jax.profiler.stop_trace()
        await bursts(stage, index, topics[:burst], burst)  # notices the end
        await stage.stop()

    run(scenario())
    return prof, m, list(submits.values())


class TestSlice:
    def test_one_session_yields_one_slice(self, tmp_path):
        prof, m, last_submits = one_session(tmp_path)
        sl = tracing.last_slice()
        assert isinstance(sl, TraceSlice)
        assert not prof.armed and prof._kept == []
        done = [r for r in sl.batches if r.deliver is not None]
        assert len(done) >= 4
        # A and B bracket the kept batches' formation, and count them
        assert sl.a["t_ns"] < sl.b["t_ns"]
        topics = sum(r.topics for r in done)
        assert topics == 120 + 40
        assert sl.b["topics"] - sl.a["topics"] == topics
        for r in done:
            assert r.kept and r.seq is not None
            assert sl.a["t_ns"] <= r.formed_ns <= sl.b["t_ns"]
        # the batch that noticed the session was parked before it (no
        # submit stamped); every later member was parked inside it
        assert done[0].wait_n == 0 and done[0].submit_first_ns is None
        assert [r.wait_n for r in done[1:]] == [r.topics for r in done[1:]]
        # the session's last batch: stage.wait is the members' own waits
        last = done[-1]
        assert last.topics == last.wait_n == len(last_submits)
        assert last.wait_sum_ns == sum(last.formed_ns - t for t in last_submits)
        assert last.submit_first_ns == min(last_submits)
        # the in-flight union the duty-cycle fold keeps, cut to the slice
        assert 0 < sl.b["inflight_s"] - sl.a["inflight_s"] <= (
            sl.b["t_ns"] - sl.a["t_ns"]
        ) / 1e9

    def test_boundaries_monotone_and_children_inside_root(self, tmp_path):
        one_session(tmp_path)
        sl = tracing.last_slice()
        seqs = set()
        for r in sl.batches:
            ts = boundaries(r)
            assert ts == sorted(ts), (r.seq, ts)
            # the queue's own chain: issue returned before sync started
            assert r.issue_start_ns <= r.issue_end_ns <= r.sync_start_ns
            assert r.sync_start_ns <= r.d2h_sync[0]
            spans = r.spans()
            names = [s[0] for s in spans]
            assert names[0] == "mqtt/batch"
            assert set(names) == {
                "mqtt/batch", "mqtt/issue.handoff", "mqtt/pipeline.wait",
                *BUSY_SPANS.values(),
                *(["mqtt/stage.wait"] if r.wait_n else []),
            }
            _, t0, t1, args = spans[0]
            assert args["topics"] == r.topics and args["bucket"] >= r.topics
            for name, s0, s1, a in spans:
                assert t0 <= s0 <= s1 <= t1, name
                assert a["batch"] == r.seq  # one identifier per tree
            # the windows the duty-cycle fold reads are the same stamps
            assert r.dispatch == (r.tokenize[0] / 1e9, r.h2d_dispatch[1] / 1e9)
            assert r.d2h == (r.d2h_sync[0] / 1e9, r.d2h_sync[1] / 1e9)
            # every member's result was in hand at one instant, the
            # hand-over's first, inside the deliver span
            assert r.set_sum_ns > 0 and r.set_sum_ns % r.topics == 0
            assert r.deliver[0] <= r.set_sum_ns // r.topics <= r.deliver[1]
            seqs.add(r.seq)
        assert len(seqs) == len(sl.batches)

    def test_snapshots_hold_cpu_heartbeat_and_groups(self, tmp_path):
        one_session(tmp_path)
        sl = tracing.last_slice()
        groups = sl.cpu_ns_by_group()
        assert set(groups) == {"loop", "match", "other"}
        assert min(groups.values()) >= 0
        whole = sl.b["process_cpu_ns"] - sl.a["process_cpu_ns"]
        assert sum(groups.values()) >= whole  # "other" takes the unnamed rest
        assert any(n.startswith("mqtt-tpu-h2d") for n in sl.b["thread_cpu_ns"])
        assert sl.b["loop_beats"] > sl.a["loop_beats"]
        assert sl.b["loop_stall_max_ns"] >= 0
        assert thread_group("MainThread") == thread_group("mqtt-tpu-shard-3") == "loop"
        # the match path off the loop: with matcher_resilience on, its
        # work runs on the guard pool, whichever thread handed it over
        for name in ("mqtt-tpu-h2d_0", "mqtt-tpu-resolve_1", "mqtt-tpu-guard-2"):
            assert thread_group(name) == "match"
        assert thread_group("mqtt-tpu-csr-rebuild") == "other"

    def test_host_plane_carries_the_spans_with_their_batch(self, tmp_path):
        one_session(tmp_path)
        sl = tracing.last_slice()
        found = glob.glob(
            os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
        )
        assert found
        profile = jax.profiler.ProfileData.from_file(found[-1])
        seen: dict = {}
        for plane in profile.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("mqtt/"):
                        stats = dict(ev.stats)
                        # the loop's own annotations (mqtt/loop.*,
                        # mqtt/clock, mqtt/gc) belong to no batch
                        if "batch" in stats:
                            seen.setdefault(ev.name, set()).add(int(stats["batch"]))
        kept = {r.seq for r in sl.batches}
        for name in BUSY_SPANS.values():
            assert seen.get(name), name
            assert seen[name] <= kept
        assert seen["mqtt/tokenize"] == kept

    def test_no_session_keeps_nothing(self):
        index, topic_gen = build_index(5)
        prof = DeviceProfiler()
        tel = Telemetry(sample=0)
        _m, stage = staged(index, prof, telemetry=tel)

        async def scenario():
            stage.start()
            await bursts(stage, index, [topic_gen() for _ in range(90)], 30)
            await stage.stop()

        run(scenario())
        assert tracing.last_slice() is None
        assert not prof.armed and prof._kept == [] and prof.batches >= 3
        assert prof.fanout_n == prof.ingest_n == 0
        # the records are still stamped, unkept, and numbered in order
        recent = list(prof._recent)
        assert [r.seq for r in recent] == sorted(r.seq for r in recent)
        assert all(not r.kept and r.deliver is not None for r in recent)
        # and the leg-wait histograms read the same stamps
        assert tel.leg_wait["h2d"].count == tel.leg_wait["d2h"].count == prof.batches

    def test_leg_waits_without_a_profiler(self):
        """A stage with telemetry and no profiler still stamps a record
        of its own per batch: the hand-off waits come from it."""
        index, topic_gen = build_index(9)
        tel = Telemetry(sample=0)
        _m, stage = staged(index, None, telemetry=tel)

        async def scenario():
            stage.start()
            await bursts(stage, index, [topic_gen() for _ in range(60)], 20)
            await stage.stop()

        run(scenario())
        assert tel.leg_wait["h2d"].count >= 3
        assert tel.leg_wait["h2d"].sum >= 0 and tel.leg_wait["d2h"].sum >= 0

    def test_a_session_that_ends_with_no_traffic_closes_on_poll(self, tmp_path):
        """The sampler thread's poll, not a batch, notices both edges."""
        prof = DeviceProfiler()
        assert prof.poll() is False
        start_session(tmp_path)
        try:
            assert prof.poll() is True and prof.armed
            rec = prof.open_batch()
            assert rec.kept
        finally:
            jax.profiler.stop_trace()
        assert tracing.last_slice() is None  # not noticed yet
        assert prof.poll() is False
        sl = tracing.last_slice()
        assert sl is not None and sl.batches == [rec]
        assert not prof.open_batch().kept

    def test_batches_in_flight_when_the_session_opens_are_kept(self, tmp_path):
        prof = DeviceProfiler()
        done, flying = prof.open_batch(), prof.open_batch()
        done.deliver = (1, 2)
        start_session(tmp_path)
        try:
            prof.poll()
        finally:
            jax.profiler.stop_trace()
        prof.poll()
        assert tracing.last_slice().batches == [flying] and flying.kept
        assert not done.kept

    def test_kept_records_are_bounded(self, tmp_path, monkeypatch):
        monkeypatch.setattr(tracing, "MAX_SLICE_BATCHES", 3)
        prof = DeviceProfiler()
        start_session(tmp_path)
        try:
            prof.poll()
            for _ in range(5):
                prof.open_batch()
        finally:
            jax.profiler.stop_trace()
        prof.poll()
        sl = tracing.last_slice()
        assert len(sl.batches) == 3 and all(r.kept for r in sl.batches)


    def test_thread_cpu_is_the_kernels_own_accounting(self):
        """Per thread, by name, at the kernel's tick: this thread's
        reading rises by about what it burns."""
        before = tracing.thread_cpu_ns()["MainThread"]
        t0 = time.thread_time_ns()
        while time.thread_time_ns() - t0 < 80_000_000:
            pass
        grew = tracing.thread_cpu_ns()["MainThread"] - before
        assert 50_000_000 <= grew <= 150_000_000
        assert grew % tracing._TICK_NS == 0

    def test_traces_serves_the_slices_batch_trees(self, tmp_path):
        """``GET /traces`` is ``Tracer.export()``: after the publish
        spans come the newest slice's batches, one track each, under the
        number a sampled publish's root span names."""
        one_session(tmp_path)
        sl = tracing.last_slice()
        doc = tracing.Tracer(seed=1).export()
        assert tracing.check_trace_events(doc) == len(doc["traceEvents"])
        by_batch: dict = {}
        others = []
        for ev in doc["traceEvents"]:
            if ev["cat"] == "batch":
                by_batch.setdefault(ev["args"]["batch"], []).append(ev)
            else:
                others.append(ev)
        # beside the batches: the loop's longest iteration with its
        # parts, and the collections that ended inside the slice
        assert [e["name"] for e in others if e["cat"] == "loop"] == ["loop/stall"]
        assert {e["cat"] for e in others} <= {"loop", "gc"}
        stall = next(e for e in others if e["cat"] == "loop")
        assert stall["dur"] == pytest.approx(sl.b["stall"]["busy_ns"] / 1e3, abs=0.01)
        assert stall["args"]["busy_ns"] >= stall["args"]["ingest_ns"] >= 0
        assert set(by_batch) == {r.seq for r in sl.batches}
        for rec in sl.batches:
            events = by_batch[rec.seq]
            assert len({e["tid"] for e in events}) == 1
            assert [e["name"] for e in events] == [s[0] for s in rec.spans()]
            root = events[0]
            assert root["name"] == "mqtt/batch"
            assert root["args"]["topics"] == rec.topics
            first = rec.submit_first_ns or rec.formed_ns
            assert root["dur"] == pytest.approx(
                (rec.deliver[1] - first) / 1e3, abs=0.01
            )
            for e in events[1:]:
                assert root["ts"] - 1.0 <= e["ts"]  # epoch us: a float holds 0.25
                assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1.0

    def test_traces_serves_only_the_newest_batches(self, tmp_path, monkeypatch):
        monkeypatch.setattr(tracing, "TRACES_BATCHES", 2)
        one_session(tmp_path)
        sl = tracing.last_slice()
        doc = tracing.Tracer(seed=1).export()
        assert {
            e["args"]["batch"] for e in doc["traceEvents"] if e["cat"] == "batch"
        } == {r.seq for r in sl.batches[-2:]}


class TestUndispatchedPaths:
    def test_exact_map_leaves_the_matcher_spans_none(self, tmp_path):
        """A wildcard-free filter set is served from the exact map: no
        device dispatch, so tokenize / h2d / d2h / resolve stay None as
        ``dispatch`` / ``d2h`` do, while staging's own stamps are there."""
        index = TopicsIndex()
        for i in range(40):
            index.subscribe(f"c{i}", Subscription(filter=f"a/b/{i}", qos=0))
        prof = DeviceProfiler()
        m, stage = staged(index, prof)
        assert m.csr.exact_map is not None

        async def scenario():
            stage.start()
            start_session(tmp_path)
            try:
                await bursts(stage, index, [f"a/b/{i}" for i in range(40)], 20)
            finally:
                jax.profiler.stop_trace()
            prof.poll()
            await stage.stop()

        run(scenario())
        sl = tracing.last_slice()
        assert sl.batches
        for r in sl.batches:
            assert r.dispatch is None and r.d2h is None
            for slot in ("tokenize", "h2d_dispatch", "d2h_sync", "resolve"):
                assert getattr(r, slot) is None, slot
            assert r.formed_ns and r.issue_end_ns and r.deliver is not None
            names = {s[0] for s in r.spans()}
            assert "mqtt/tokenize" not in names and "mqtt/batch" in names

    def test_host_fallback_leaves_the_matcher_spans_none(self):
        """An issue that fails falls back to the host walk: the record
        never sees a dispatch. The batch still takes its turn through
        the drain queue, as held members under ``mqtt/order.hold``."""
        index, topic_gen = build_index(13)

        class Broken:
            def match_topics_async(self, topics, profile=None):
                raise RuntimeError("no device")

        prof = DeviceProfiler()
        stage = MatchStage(Broken(), index.subscribers, window_s=0.001, profiler=prof)

        async def scenario():
            stage.start()
            topics = [topic_gen() for _ in range(10)]
            got = await asyncio.gather(*[stage.submit(t) for t in topics])
            for t, subs in zip(topics, got):
                assert canon(subs) == canon(index.subscribers(t))
            await stage.stop()

        run(scenario())
        (rec,) = list(prof._recent)
        assert rec.formed_ns is not None and rec.issue_start_ns is not None
        for slot in ("dispatch", "d2h", "tokenize", "h2d_dispatch", "d2h_sync",
                     "resolve"):
            assert getattr(rec, slot) is None, slot
        assert rec.deliver is not None and stage.order_held == 10
        assert rec.hold_n == 10 and rec.hold[0] <= rec.deliver[0] <= rec.hold[1]
        hold = [s for s in rec.spans() if s[0] == "mqtt/order.hold"]
        assert hold[0][3] == {
            "batch": rec.seq, "n": 10, "sum_ns": rec.hold_sum_ns,
        }


class TestGen2Pauses:
    def test_counts_full_collections_only(self):
        g = Gen2Pauses()
        g.install()
        g.install()  # idempotent
        try:
            assert gc.callbacks.count(g._on_gc) == 1
            gc.collect(0)
            gc.collect(1)
            assert g.hist.count == 0 and not g.recent
            t0 = time.perf_counter_ns()
            gc.collect(2)
            t1 = time.perf_counter_ns()
            (end_ns, dur_ns), = g.recent
            assert t0 < end_ns <= t1 and 0 < dur_ns <= t1 - t0
            assert g.hist.count == 1 and g.hist.sum == dur_ns / 1e9
            gc.collect()  # the default is a full collection
            assert g.hist.count == len(g.recent) == 2
        finally:
            gc.callbacks.remove(g._on_gc)

    def test_a_slice_names_the_pauses_inside_it(self, tmp_path):
        prof = DeviceProfiler()  # hooks the process-wide counter in
        assert tracing.GC2._on_gc in gc.callbacks
        gc.collect(2)  # before the session: not the slice's
        start_session(tmp_path)
        try:
            prof.poll()
            gc.collect(2)
        finally:
            jax.profiler.stop_trace()
        prof.poll()
        sl = tracing.last_slice()
        pauses = sl.gen2_pauses()
        assert pauses and all(sl.a["t_ns"] < end <= sl.b["t_ns"] for end, _ in pauses)
        # /traces serves them, beside the slice's batches
        doc = tracing.Tracer(seed=1).export()
        assert [e["name"] for e in doc["traceEvents"]] == ["gc/gen2"] * len(pauses)

    def test_exported_on_metrics(self):
        tel = Telemetry(sample=0)
        DeviceProfiler(registry=tel.registry)
        gc.collect(2)
        text = tel.registry.exposition()
        assert "mqtt_tpu_gc_gen2_pause_seconds_count" in text
        assert "not device busy time" in text  # the duty-cycle gauge says what it is


class TestLoopCounters:
    def test_served_publishes_count_ingest_and_fanout_while_armed(self, tmp_path):
        """Through a real broker: armed, every staged publish adds to the
        ingest and fan-out counters and its sampled trace names its
        batch; the counters do not move outside a session."""
        from mqtt_tpu import Options
        from mqtt_tpu.packets import PUBLISH, SUBACK
        from tests.test_server import Harness, pub_packet, read_wire_packet, sub_packet

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True, device_matcher=True,
                    matcher_stage_window_ms=2.0,
                    matcher_opts={"max_levels": 4, "background": False},
                    telemetry_sample=1, trace_sample=1,
                )
            )
            await h.server.serve()
            prof = h.server.profiler
            assert prof is not None and prof.matcher_stats is h.server.matcher.stats
            assert h.server.host_profiler.on_sweep == prof.poll
            sub_r, sub_w, _ = await h.connect("sub")
            sub_w.write(sub_packet(1, [Subscription(filter="t/#", qos=0)]))
            await sub_w.drain()
            assert (await read_wire_packet(sub_r)).fixed_header.type == SUBACK
            h.server.matcher.flush()
            pub_r, pub_w, _ = await h.connect("pub")

            async def publish(lo, hi):
                for i in range(lo, hi):
                    pub_w.write(pub_packet(f"t/{i}", b"m"))
                await pub_w.drain()
                for _ in range(lo, hi):
                    assert (await read_wire_packet(sub_r)).fixed_header.type == PUBLISH

            await publish(0, 6)
            assert prof.ingest_n == prof.fanout_n == 0
            start_session(tmp_path)
            try:
                # no batch forms: the sampler thread's sweep notices
                await asyncio.sleep(0.15)
                assert prof.armed
                await publish(6, 18)
            finally:
                jax.profiler.stop_trace()
            await asyncio.sleep(0.15)
            assert not prof.armed and tracing.last_slice() is not None
            await publish(18, 24)
            doc = h.server.tracer.export()
            await h.server.close()
            await h.shutdown()
            return prof, doc

        prof, doc = run(scenario())
        sl = tracing.last_slice()
        assert sl is not None
        n = sl.b["fanout_n"] - sl.a["fanout_n"]
        assert n == sl.b["ingest_n"] - sl.a["ingest_n"] == 12
        assert prof.fanout_n == 12  # nothing counted after the session
        for key in ("ingest_busy_ns", "fanout_busy_ns", "fanout_wait_ns"):
            assert sl.b[key] - sl.a[key] > 0, key
        # the broker's own counts ride the snapshots: one delivery a
        # publish here, no fallback held; one socket send a completion
        # slice (the subscriber's frames of a slice leave as one write),
        # so at most one a publish and as a rule one for all twelve
        assert sl.b["deliveries"] - sl.a["deliveries"] == 12
        assert 1 <= sl.b["socket_sends"] - sl.a["socket_sends"] <= 12
        assert sl.b["order_held"] == sl.a["order_held"] == 0
        # every publish came in by the run (a v4 client, QoS0, no hook
        # that takes the packet): a run a socket read, and one read as a
        # rule; the six before the session are in both snapshots
        assert sl.a["ingest_run_publishes"] == 6
        assert sl.b["ingest_run_publishes"] - sl.a["ingest_run_publishes"] == 12
        assert 1 <= sl.b["ingest_runs"] - sl.a["ingest_runs"] <= 12
        roots = [e for e in doc["traceEvents"] if e["name"] == "publish"]
        assert len(roots) == 24
        kept = {r.seq for r in sl.batches}
        named = {e["args"]["batch"] for e in roots}
        assert None not in named and kept <= named
