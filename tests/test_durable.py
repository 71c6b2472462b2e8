"""Durable session plane (ISSUE 16): crash-safe log-structured storage
with snapshot + tail replay, seeded crash-point fault injection proving
recovery converges bit-identically from any kill point, batched restart
re-registration, the device-resident retained-match kernel with its
host-walk differential oracle and breaker degradation, and per-tenant
retained/subscription COUNT quotas refusing with v5 0x97.

The crash matrix drives the SAME seeded workload into every named crash
point (mid-append clean + torn, rotation, each snapshot and compaction
step) and asserts the recovered map equals the durable shadow — twice,
because recovery itself must be idempotent."""

import asyncio
import random
import time
import types

import pytest

import mqtt_tpu.packets as pkts
from mqtt_tpu.faults import (
    STORAGE_CRASH_POINTS,
    StorageCrashPlan,
    dup_last_segment,
    lose_unsynced,
    tear_tail,
)
from mqtt_tpu.hooks.storage.logkv import (
    LogKVOptions,
    LogKVStore,
    SimulatedCrash,
)
from mqtt_tpu.packets import FixedHeader, Packet, Subscription
from mqtt_tpu.server import Options, Server
from mqtt_tpu.topics import TopicsIndex, ns_scope_filter, ns_scope_topic

from tests.test_server import (
    Harness,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)

PUBACK = 4
PUBLISH = 3
SUBACK = 9


# -- workload -------------------------------------------------------------


def _ops(seed, n):
    """A seeded set/del mix over a small hot key space (forces dead
    records, overwrites, and deletes into every segment)."""
    rng = random.Random(seed)
    ops = []
    for _ in range(n):
        k = f"CL_{rng.randrange(40)}"
        if rng.random() < 0.2:
            ops.append(("del", k, b""))
        else:
            ops.append(("set", k, bytes([rng.randrange(256)]) * rng.randrange(1, 24)))
    return ops


def _shadow_apply(shadow, kind, k, v):
    if kind == "set":
        shadow[k] = v
    else:
        shadow.pop(k, None)


def _reopen(path):
    s = LogKVStore()
    s.init(LogKVOptions(path=path, gc_interval=0))
    return s


class TestCrashPointMatrix:
    @pytest.mark.parametrize("point", STORAGE_CRASH_POINTS)
    def test_crash_point_converges(self, tmp_path, point):
        path = str(tmp_path / "kv")
        s = LogKVStore()
        s.init(
            LogKVOptions(
                path=path,
                gc_interval=0,
                durability_fsync="always",
                max_segment_bytes=512 if point == "rotate" else 1 << 20,
            )
        )
        shadow = {}
        crashed = False
        if point == "rotate":
            s.crash_plan = StorageCrashPlan(crash_point="rotate")
        for kind, k, v in _ops(1234, 300):
            try:
                if kind == "set":
                    s._set(k, v)
                else:
                    s._del(k)
            except SimulatedCrash:
                # the record that triggered rotation was written AND
                # fsynced before the crash point fired: it is durable
                crashed = True
                _shadow_apply(shadow, kind, k, v)
                break
            _shadow_apply(shadow, kind, k, v)
        if point.startswith("snapshot"):
            s.crash_plan = StorageCrashPlan(crash_point=point)
            with pytest.raises(SimulatedCrash):
                s.snapshot()
            crashed = True
        elif point.startswith("compact"):
            s.crash_plan = StorageCrashPlan(crash_point=point)
            with pytest.raises(SimulatedCrash):
                s.compact(0.0)
            crashed = True
        assert crashed, f"crash point {point} never fired"
        if s._file is not None:
            s._file.close()  # abandon: no clean stop() flush path

        s2 = _reopen(path)
        assert s2._map == shadow
        assert s2.replay_corruptions == 0
        s2.stop()
        # recovery must be idempotent: replaying the same files again
        # (including any overlap the crash left) reconverges
        s3 = _reopen(path)
        assert s3._map == shadow
        s3.stop()

    @pytest.mark.parametrize("torn", [False, True])
    @pytest.mark.parametrize("kill_at", [5, 57, 123])
    def test_crash_mid_append(self, tmp_path, torn, kill_at):
        """A kill mid-append (clean, or torn partial write) loses exactly
        the in-flight record; everything before it recovers."""
        path = str(tmp_path / "kv")
        s = LogKVStore()
        s.init(LogKVOptions(path=path, gc_interval=0, durability_fsync="always"))
        s.crash_plan = StorageCrashPlan(seed=kill_at, crash_at_op=kill_at, torn=torn)
        shadow = {}
        crashed = False
        for kind, k, v in _ops(99, 200):
            try:
                if kind == "set":
                    s._set(k, v)
                else:
                    s._del(k)
            except SimulatedCrash:
                crashed = True
                break  # the in-flight record never became durable
            _shadow_apply(shadow, kind, k, v)
        assert crashed
        if s._file is not None:
            s._file.close()
        s2 = _reopen(path)
        assert s2._map == shadow
        # a torn TAIL is a normal crash artifact, not corruption
        assert s2.replay_corruptions == 0
        s2.stop()

    def test_dup_segment_converges(self, tmp_path):
        """Replaying a duplicated newest segment is a no-op: records are
        absolute values, so recovery converges bit-identically."""
        path = str(tmp_path / "kv")
        s = LogKVStore()
        s.init(LogKVOptions(path=path, gc_interval=0))
        shadow = {}
        for kind, k, v in _ops(7, 150):
            if kind == "set":
                s._set(k, v)
            else:
                s._del(k)
            _shadow_apply(shadow, kind, k, v)
        s.stop()
        assert dup_last_segment(path)
        s2 = _reopen(path)
        assert s2._map == shadow
        assert s2.replay_corruptions == 0
        s2.stop()

    def test_tear_tail_recovers_a_prefix(self, tmp_path):
        """Tearing bytes off the newest segment recovers SOME prefix of
        the applied ops — never garbage, never a corruption count."""
        path = str(tmp_path / "kv")
        s = LogKVStore()
        s.init(LogKVOptions(path=path, gc_interval=0, durability_fsync="always"))
        states = [{}]
        for kind, k, v in _ops(41, 60):
            if kind == "set":
                s._set(k, v)
            else:
                s._del(k)
            nxt = dict(states[-1])
            _shadow_apply(nxt, kind, k, v)
            states.append(nxt)
        s.stop()
        assert tear_tail(path, seed=3)  # returns the torn segment's name
        s2 = _reopen(path)
        assert s2._map in states
        s2.stop()

    def test_lose_unsynced_rolls_back_to_watermark(self, tmp_path):
        """With fsync off, a power cut loses everything after the last
        explicit durability barrier — and nothing before it."""
        path = str(tmp_path / "kv")
        s = LogKVStore()
        s.init(LogKVOptions(path=path, gc_interval=0, durability_fsync="off"))
        for i in range(10):
            s._set(f"CL_a{i}", b"durable")
        s.sync()  # the barrier
        for i in range(10):
            s._set(f"CL_b{i}", b"volatile")
        lost = lose_unsynced(s)
        assert lost > 0
        s2 = _reopen(path)
        assert sorted(s2._map) == [f"CL_a{i}" for i in range(10)]
        s2.stop()


class TestSnapshotRecovery:
    def test_snapshot_plus_tail_replay(self, tmp_path):
        path = str(tmp_path / "kv")
        s = LogKVStore()
        s.init(LogKVOptions(path=path, gc_interval=0, max_segment_bytes=2048))
        shadow = {}
        for kind, k, v in _ops(11, 400):
            if kind == "set":
                s._set(k, v)
            else:
                s._del(k)
            _shadow_apply(shadow, kind, k, v)
        assert s.snapshot()
        tail_ops = 0
        for kind, k, v in _ops(12, 80):
            if kind == "set":
                s._set(k, v)
            else:
                s._del(k)
            _shadow_apply(shadow, kind, k, v)
            tail_ops += 1
        s.stop()

        s2 = _reopen(path)
        assert s2._map == shadow
        assert s2.snapshot_seq >= 0  # recovery used the snapshot
        # snapshot keys + tail records, NOT the full 400-op history —
        # that is the whole point of checkpointing
        assert s2.replayed_keys < 400 + tail_ops
        assert s2.durable_stats()["snapshot_age_seconds"] >= 0.0
        s2.stop()

    def test_fsync_policy_resolution(self):
        assert LogKVOptions(sync=True).fsync_policy() == "always"
        assert LogKVOptions(sync=False).fsync_policy() == "off"
        assert LogKVOptions(durability_fsync="batch").fsync_policy() == "batch"
        with pytest.raises(ValueError):
            LogKVOptions(durability_fsync="bogus").fsync_policy()

    def test_group_commit_batches_fsyncs(self, tmp_path):
        """The batch policy group-commits: one fsync covers many appends
        (vs. always = one fsync PER append)."""
        import time as _time

        path = str(tmp_path / "kv")
        s = LogKVStore()
        s.init(
            LogKVOptions(
                path=path,
                gc_interval=0,
                durability_fsync="batch",
                fsync_interval_ms=5.0,
            )
        )
        for i in range(200):
            s._set(f"CL_{i}", b"x" * 16)
        deadline = _time.monotonic() + 2.0
        while s._dirty and _time.monotonic() < deadline:
            _time.sleep(0.01)
        assert not s._dirty  # the flusher picked the batch up
        assert 0 < s.fsyncs < s.appends / 2
        s.stop()
        s2 = _reopen(path)
        assert len(s2._map) == 200
        s2.stop()

    @pytest.mark.slow
    def test_100k_key_recovery_bit_identical(self, tmp_path):
        """Fleet-shape leg: 100k+ keys recover bit-identically through a
        snapshot + tail, inside a sane time budget."""
        path = str(tmp_path / "kv")
        s = LogKVStore()
        s.init(
            LogKVOptions(path=path, gc_interval=0, max_segment_bytes=8 << 20)
        )
        shadow = {}
        for i in range(100_000):
            k, v = f"CL_{i}", b"v%d" % i
            s._set(k, v)
            shadow[k] = v
        assert s.snapshot()
        for i in range(0, 5000):  # tail updates after the checkpoint
            k, v = f"CL_{i}", b"w%d" % i
            s._set(k, v)
            shadow[k] = v
        s.stop()
        s2 = _reopen(path)
        assert len(s2._map) == 100_000
        assert s2._map == shadow
        assert s2.recovery_seconds < 30.0
        s2.stop()


# -- device-resident retained matching ------------------------------------


def _retain(idx, topic, payload=b"x"):
    pk = Packet(
        fixed_header=FixedHeader(type=PUBLISH, retain=True),
        topic_name=topic,
        payload=payload,
    )
    idx.retain_message(pk)


def _seed_retained_index():
    idx = TopicsIndex()
    topics = [
        "a",
        "a/b",
        "a/b/c",
        "x/y",
        "$SYS/broker/uptime",
        "$other/visible",
        ns_scope_topic("acme", "a/b"),
        ns_scope_topic("acme", "jobs/1"),
        ns_scope_topic("bulkco", "a/b"),
    ]
    for t in topics:
        _retain(idx, t)
    return idx, topics


FILTERS = [
    "a",
    "a/b",
    "#",
    "+",
    "a/#",
    "a/+",
    "+/b",
    "+/+",
    "$SYS/#",
    "$SYS/broker/+",
    "$other/#",
    "nope/+",
    ns_scope_filter("acme", "#"),
    ns_scope_filter("acme", "a/+"),
    ns_scope_filter("acme", "jobs/#"),
    ns_scope_filter("bulkco", "+/b"),
]


class TestRetainedMatchEngine:
    def test_bit_identical_vs_host_walk(self):
        from mqtt_tpu.ops.retained import RetainedMatchEngine

        idx, _ = _seed_retained_index()
        eng = RetainedMatchEngine(idx, oracle_sample=1)  # oracle EVERY call
        eng.reseed()
        for f in FILTERS:
            names = eng.match(f)
            host = sorted(p.topic_name for p in idx.messages(f))
            if names is not None:
                assert sorted(names) == host, f
        assert eng.oracle_mismatches == 0
        assert eng.device_matches > 0

    def test_deletion_tracked(self):
        from mqtt_tpu.ops.retained import RetainedMatchEngine

        idx, _ = _seed_retained_index()
        eng = RetainedMatchEngine(idx, oracle_sample=1)
        eng.reseed()
        assert "a/b" in (eng.match("a/+") or [])
        _retain(idx, "a/b", b"")  # clear
        eng.note_retained("a/b", False)
        names = eng.match("a/+")
        assert names is not None and "a/b" not in names
        assert eng.oracle_mismatches == 0

    def test_fault_storm_degrades_to_host(self, monkeypatch):
        """A failing kernel must degrade to the host walk through the
        breaker — never raise, never return wrong results."""
        import mqtt_tpu.ops.retained as retained_mod
        from mqtt_tpu.ops.retained import RetainedMatchEngine

        idx, _ = _seed_retained_index()
        eng = RetainedMatchEngine(idx, oracle_sample=1_000_000)
        eng.reseed()

        def boom(*a, **k):
            raise RuntimeError("device storm")

        monkeypatch.setattr(retained_mod, "flat_match_packed", boom)
        for _ in range(10):
            assert eng.match("a/+") is None  # host walk serves
        assert eng.breaker.state != "closed"
        assert eng.fallbacks["error"] >= 3
        assert eng.fallbacks["breaker"] >= 1

    def test_server_retained_delivery_with_engine(self):
        """Wire-level zero-missed-deliveries: retained messages reach a
        wildcard subscriber with the engine healthy AND mid-fault-storm
        (host degradation)."""

        async def scenario():
            h = Harness(Options(inline_client=False, retained_matcher=True))
            pr, pw, _ = await h.connect("rpub")
            pw.write(pub_packet("job/1", b"r1", retain=True))
            pw.write(pub_packet("job/2", b"r2", retain=True))
            await pw.drain()
            await asyncio.sleep(0.05)

            async def expect_retained(cid):
                sr, sw, _ = await h.connect(cid)
                sw.write(sub_packet(1, [Subscription(filter="job/+", qos=0)]))
                await sw.drain()
                got = set()
                for _ in range(3):
                    pk = await read_wire_packet(sr)
                    if pk.fixed_header.type == SUBACK:
                        continue
                    got.add((pk.topic_name, bytes(pk.payload)))
                assert got == {("job/1", b"r1"), ("job/2", b"r2")}

            await expect_retained("rsub-healthy")
            assert h.server._retained_engine.device_matches > 0

            # storm: every device call fails; delivery must not change
            def boom(*a, **k):
                raise RuntimeError("device storm")

            h.server._retained_engine._device_names = boom
            await expect_retained("rsub-storm")
            await h.shutdown()

        run(scenario())


# -- tenant count quotas ---------------------------------------------------


def quota_options(**kw):
    tenants = kw.pop("tenants", {"acme": {}})
    return Options(
        inline_client=False,
        tenancy=True,
        tenants=tenants,
        tenant_users={"cidA": "acme", "cidB": "acme"},
        **kw,
    )


class TestTenantCountQuotas:
    def test_subscription_cap_refuses_0x97(self):
        async def scenario():
            h = Harness(quota_options(tenant_max_subscriptions=2))
            r, w, _ = await h.connect("cidA", version=5)
            w.write(
                sub_packet(
                    1,
                    [
                        Subscription(filter="f/1", qos=0),
                        Subscription(filter="f/2", qos=0),
                        Subscription(filter="f/3", qos=0),
                    ],
                    version=5,
                )
            )
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert list(ack.reason_codes) == [0, 0, 0x97]
            t = h.server._tenancy.get("acme")
            assert t.subscriptions_count == 2
            assert t.subscriptions_refused == 1
            # replacing an existing filter is NOT growth
            w.write(sub_packet(2, [Subscription(filter="f/1", qos=0)], version=5))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert list(ack.reason_codes) == [0]
            # unsubscribing frees the slot
            from mqtt_tpu.packets import encode_packet

            w.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=pkts.UNSUBSCRIBE, qos=1),
                        protocol_version=5,
                        packet_id=3,
                        filters=[Subscription(filter="f/2")],
                    )
                )
            )
            await w.drain()
            await read_wire_packet(r, 5)  # UNSUBACK
            assert t.subscriptions_count == 1
            w.write(sub_packet(4, [Subscription(filter="f/3", qos=0)], version=5))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert list(ack.reason_codes) == [0]
            await h.shutdown()

        run(scenario())

    def test_subscription_cap_clamps_for_v3(self):
        async def scenario():
            h = Harness(quota_options(tenant_max_subscriptions=1))
            r, w, _ = await h.connect("cidA", version=4)
            w.write(
                sub_packet(
                    1,
                    [
                        Subscription(filter="f/1", qos=0),
                        Subscription(filter="f/2", qos=0),
                    ],
                )
            )
            await w.drain()
            ack = await read_wire_packet(r)
            assert list(ack.reason_codes) == [0, 0x80]  # v3: no 0x97
            await h.shutdown()

        run(scenario())

    def test_retained_cap_refuses_0x97(self):
        async def scenario():
            h = Harness(quota_options(tenant_max_retained=2))
            r, w, _ = await h.connect("cidA", version=5)
            for pid, topic in ((1, "r/1"), (2, "r/2")):
                w.write(pub_packet(topic, b"x", qos=1, pid=pid, version=5, retain=True))
                await w.drain()
                ack = await read_wire_packet(r, 5)
                assert ack.fixed_header.type == PUBACK and ack.reason_code == 0
            t = h.server._tenancy.get("acme")
            assert t.retained_count == 2
            # the third NEW retained topic refuses 0x97
            w.write(pub_packet("r/3", b"x", qos=1, pid=3, version=5, retain=True))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.reason_code == 0x97
            assert t.retained_refused == 1
            assert t.retained_count == 2  # memory did not grow past cap
            # overwriting an existing retained topic always passes
            w.write(pub_packet("r/1", b"y", qos=1, pid=4, version=5, retain=True))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.reason_code == 0
            # clearing frees a slot; the refused topic then fits
            w.write(pub_packet("r/1", b"", qos=1, pid=5, version=5, retain=True))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.reason_code == 0
            assert t.retained_count == 1
            w.write(pub_packet("r/3", b"x", qos=1, pid=6, version=5, retain=True))
            await w.drain()
            ack = await read_wire_packet(r, 5)
            assert ack.reason_code == 0
            assert t.retained_count == 2
            await h.shutdown()

        run(scenario())

    def test_retained_cap_qos0_drops_counted(self):
        async def scenario():
            h = Harness(quota_options(tenant_max_retained=1))
            r, w, _ = await h.connect("cidA", version=5)
            w.write(pub_packet("r/1", b"x", qos=1, pid=1, version=5, retain=True))
            await w.drain()
            await read_wire_packet(r, 5)
            dropped = h.server.info.messages_dropped
            w.write(pub_packet("r/2", b"x", version=5, retain=True))  # qos0
            await w.drain()
            await asyncio.sleep(0.05)
            t = h.server._tenancy.get("acme")
            assert t.retained_refused == 1
            assert h.server.info.messages_dropped == dropped + 1
            assert t.retained_count == 1
            await h.shutdown()

        run(scenario())

    def test_per_tenant_override_beats_default(self):
        async def scenario():
            h = Harness(
                quota_options(
                    tenants={"acme": {"max_retained": 1}},
                    tenant_max_retained=5,
                )
            )
            r, w, _ = await h.connect("cidA", version=5)
            w.write(pub_packet("r/1", b"x", qos=1, pid=1, version=5, retain=True))
            await w.drain()
            assert (await read_wire_packet(r, 5)).reason_code == 0
            w.write(pub_packet("r/2", b"x", qos=1, pid=2, version=5, retain=True))
            await w.drain()
            assert (await read_wire_packet(r, 5)).reason_code == 0x97
            await h.shutdown()

        run(scenario())


# -- batched restart re-registration / recovery plumbing -------------------


class TestBatchedRestore:
    def test_load_subscriptions_flows_in_bulk(self):
        srv = Server(Options(inline_client=False, durable_restore_batch=8))
        subs = [
            types.SimpleNamespace(
                client=f"c{i}",
                filter=f"t/{i}",
                qos=1,
                retain_handling=0,
                retain_as_published=False,
                no_local=False,
                identifier=0,
                predicates=(),
            )
            for i in range(20)
        ]
        batches = []
        orig = srv.topics.subscribe_bulk
        srv.topics.subscribe_bulk = lambda entries: (
            batches.append(len(entries)),
            orig(entries),
        )[1]
        srv.load_subscriptions(subs)
        assert batches == [8, 8, 4]  # chunked, NOT one-at-a-time
        assert srv._durable["restored_subscriptions"] == 20
        assert srv._durable["restore_batches"] == 3
        # the trie actually holds them
        assert not srv.topics.subscribe("c3", Subscription(filter="t/3", qos=1))

    def test_load_retained_bulk_and_engine_seed(self):
        srv = Server(Options(inline_client=False, retained_matcher=True))

        def stored(topic):
            return types.SimpleNamespace(
                to_packet=lambda t=topic: Packet(
                    fixed_header=FixedHeader(type=PUBLISH, retain=True),
                    topic_name=t,
                    payload=b"x",
                )
            )

        srv.load_retained([stored(f"r/{i}") for i in range(10)])
        assert srv._durable["restored_retained"] == 10
        assert len(srv.topics.retained) == 10
        names = srv._retained_engine.match("r/+")
        assert names is not None and len(names) == 10

    @pytest.mark.parametrize("resilient", [True, False], ids=["breaker", "bare"])
    def test_restored_server_leaves_recovering_after_the_one_build(self, resilient):
        """The restore is one bulk load of the trie: the device matcher
        builds nothing while it runs, once when it ends, and `recovering`
        is left only after that build (healthz 503 until then)."""
        from mqtt_tpu.hooks import STORED_SUBSCRIPTIONS, Hook
        from mqtt_tpu.listeners import Config as LConfig
        from mqtt_tpu.listeners.tcp import TCP

        stored = [
            types.SimpleNamespace(
                client=f"c{i}",
                filter=f"t/{i % 50}/+" if i % 10 == 0 else f"t/{i % 50}/{i}",
                qos=1,
                retain_handling=0,
                retain_as_published=False,
                no_local=False,
                identifier=0,
                predicates=(),
            )
            for i in range(3000)
        ]

        class Store(Hook):
            def id(self):
                return "stub-store"

            def provides(self, b):
                return b == STORED_SUBSCRIPTIONS

            def stored_subscriptions(self):
                return stored

        async def scenario():
            srv = Server(
                Options(
                    inline_client=False,
                    device_matcher=True,
                    matcher_resilience=resilient,
                    matcher_opts={"rebuild_after": 8, "rebuild_interval": 0.01},
                    durable_restore_batch=256,
                )
            )
            srv.add_hook(Store(), None)
            srv.add_listener(TCP(LConfig(type="tcp", id="t", address="127.0.0.1:0")))
            stats = srv.matcher.stats
            during, at_build = [], []
            chunk = srv.topics.subscribe_bulk
            srv.topics.subscribe_bulk = lambda entries: (
                time.sleep(0.02),  # interval ticks fall inside the load
                during.append((stats.rebuilds, srv.topics.bulk_depth)),
                chunk(entries),
            )[2]
            snap = srv.matcher._snap
            build = snap.rebuild

            def rebuild():
                build()
                ok, detail = srv.health_report()
                at_build.append((ok, list(detail["not_ready"])))

            snap.rebuild = rebuild
            try:
                await srv.serve()  # read_store(), then the wait for the build
                assert len(during) == 12 and all(d == (1, 1) for d in during), during
                assert srv._durable["restored_subscriptions"] == 3000
                # exactly the one build, made while healthz still said 503
                assert at_build == [(False, ["recovering"])]
                assert not srv._durable["recovering"]
                assert srv.health_report()[0]
                assert (stats.rebuilds, stats.folds, stats.bulk_loads) == (2, 0, 1)
                assert stats.rebuilds_held >= 2
                assert srv.matcher.pending_deltas == 0
                assert srv._durable["restore_build_seconds"] > 0.0
                for topic in ("t/0/0", "t/7/7", "t/40/x"):
                    got = srv.matcher.subscribers(topic)
                    assert sorted(got.subscriptions) == sorted(
                        srv.topics.subscribers(topic).subscriptions
                    )
                    assert got.subscriptions
                text = srv.telemetry.registry.exposition()
                rows = dict(
                    line.rsplit(" ", 1)
                    for line in text.splitlines()
                    if line and not line.startswith("#")
                )
                assert float(rows["mqtt_tpu_matcher_bulk_loads_total"]) == 1
                assert float(rows["mqtt_tpu_matcher_rebuilds_held_total"]) >= 2
                assert float(rows["mqtt_tpu_matcher_rebuilds_total"]) == 2
                assert float(rows["mqtt_tpu_durable_restore_build_seconds"]) > 0
                row = srv.topics.retained.get(
                    "$SYS/broker/durable/restore_build_seconds"
                )
                assert row is not None and float(row.payload) > 0
                assert int(
                    srv.topics.retained.get("$SYS/broker/matcher/bulk_loads").payload
                ) == 1
            finally:
                await srv.close()

        run(scenario())

    def test_healthz_holds_503_while_recovering(self):
        srv = Server(Options(inline_client=False))
        srv._durable["recovering"] = True
        ok, detail = srv.health_report()
        assert not ok and "recovering" in detail["not_ready"]
        srv._durable["recovering"] = False
        ok, detail = srv.health_report()
        assert ok and "recovering" not in detail["not_ready"]

    def test_unacked_inflight_survives_kill9(self, tmp_path):
        """The QoS1 unacked window rides the batched restore path: a
        subscriber that never PUBACKs is killed along with the broker
        (the store directory is frozen mid-flight, exactly what a
        kill -9 leaves on disk), and the next life re-inflates the
        window through ``staging.bulk_inflight`` — counted, batched,
        and live in the session's inflight map."""
        import shutil

        path = str(tmp_path / "kv")
        crash = str(tmp_path / "kv-crash-image")

        async def first_life():
            h = Harness(Options(inline_client=False))
            store = LogKVStore()
            h.server.add_hook(store, LogKVOptions(path=path, gc_interval=0))
            r, w, _ = await h.connect("keeper", version=4, clean=False)
            w.write(sub_packet(1, [Subscription(filter="dur/+", qos=1)]))
            await w.drain()
            await read_wire_packet(r)
            rp, wp, _ = await h.connect("pusher", version=4)
            wp.write(pub_packet("dur/q", b"unacked", qos=1, pid=9))
            await wp.drain()
            assert (await read_wire_packet(rp)).fixed_header.type == PUBACK
            # the delivery reaches the wire (on_qos_publish persisted
            # the window entry)... and is never acknowledged
            fwd = await read_wire_packet(r)
            assert fwd.fixed_header.type == PUBLISH
            assert bytes(fwd.payload) == b"unacked"
            store.sync()  # the fsync the group-commit loop would do
            # kill -9: freeze the on-disk state at this instant; the
            # clean teardown below never touches the crash image
            shutil.copytree(path, crash)
            await h.shutdown()
            store.stop()

        run(first_life())

        async def second_life():
            h = Harness(Options(inline_client=False))
            h.server.add_hook(
                LogKVStore(), LogKVOptions(path=crash, gc_interval=0)
            )
            h.server.read_store()
            srv = h.server
            assert srv._durable["restored_inflight"] == 1
            assert srv._durable["restore_batches"] >= 1
            cl = srv.clients.get("keeper")
            assert cl is not None
            # the window is LIVE: the restored packet is queued for
            # resend under its original packet id
            assert len(cl.state.inflight) == 1
            pk = cl.state.inflight.get_all(False)[0]
            assert bytes(pk.payload) == b"unacked"
            srv.publish_durable_sys()
            row = srv.topics.retained.get(
                "$SYS/broker/durable/restored_inflight"
            )
            assert row is not None and int(row.payload) == 1
            await h.shutdown()

        run(second_life())

    def test_restart_restores_through_logkv(self, tmp_path):
        """End-to-end in-process restart: sessions + retained topics
        persisted through the LogKV store come back bit-identical, the
        recovery counters populate, and $SYS/broker/durable rows exist."""
        path = str(tmp_path / "kv")

        async def first_life():
            h = Harness(Options(inline_client=False))
            store = LogKVStore()
            h.server.add_hook(store, LogKVOptions(path=path, gc_interval=0))
            # v4 clean=False: the session persists across disconnects
            r, w, _ = await h.connect("keeper", version=4, clean=False)
            w.write(
                sub_packet(
                    1,
                    [
                        Subscription(filter="dur/+", qos=1),
                        Subscription(filter="other/#", qos=0),
                    ],
                )
            )
            await w.drain()
            await read_wire_packet(r)
            w.write(pub_packet("dur/ret", b"keepme", retain=True))
            await w.drain()
            await asyncio.sleep(0.05)
            await h.shutdown()
            store.stop()  # the clean-shutdown flush the broker would do

        run(first_life())

        async def second_life():
            h = Harness(Options(inline_client=False))
            h.server.add_hook(
                LogKVStore(), LogKVOptions(path=path, gc_interval=0)
            )
            h.server.read_store()
            srv = h.server
            assert srv._durable["recovering"]  # serve() clears it
            assert srv._durable["replayed_keys"] > 0
            assert srv._durable["restored_subscriptions"] == 2
            assert srv._durable["restored_retained"] == 1
            assert srv._durable["recovery_seconds"] > 0.0
            # the restored subscription is live in the trie
            assert not srv.topics.subscribe(
                "keeper", Subscription(filter="dur/+", qos=1)
            )
            ret = srv.topics.retained.get("dur/ret")
            assert ret is not None and bytes(ret.payload) == b"keepme"
            ok, detail = srv.health_report()
            assert not ok and "recovering" in detail["not_ready"]
            assert detail["durable"]["replayed_keys"] > 0
            # what serve() does once listeners are up
            srv._durable["recovering"] = False
            srv.publish_durable_sys()
            row = srv.topics.retained.get("$SYS/broker/durable/replayed_keys")
            assert row is not None and int(row.payload) > 0
            await h.shutdown()

        run(second_life())
