"""Zero-materialization fan-out (ISSUE 13): lazy SubscribersView
semantics, the Subscription freelist pool's lifetime rules, the
encode-once variant-grouped write path, and the lazy-vs-eager delivery
differential across exact/+/#/$SHARE/predicated/tenant-scoped mixes.

The eager resolvers (accelmod.resolve_compact / resolve_batch) are the
differential oracle throughout — every lazy behavior is pinned against
them, unit-level (views) and wire-level (delivered frames).
"""

from __future__ import annotations

import asyncio
import gc

import numpy as np
import pytest

from mqtt_tpu import Capabilities, Options
from mqtt_tpu.hooks import ON_PACKET_ENCODE, ON_PACKET_SENT
from mqtt_tpu.packets import PUBLISH, SUBACK, Subscription
from mqtt_tpu.topics import Subscribers

from tests.test_server import (
    Harness,
    ObservingHook,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)

acc = pytest.importorskip("mqtt_tpu.native").accel()
if acc is None:
    pytest.skip("no C toolchain: lazy views cannot exist", allow_module_level=True)

import importlib.util
import os

needs_jax = pytest.mark.skipif(
    importlib.util.find_spec("jax") is None
    or os.environ.get("MQTT_TPU_SAN") == "1",
    reason="jax not importable (the sanitizer leg also skips these: "
    "jaxlib is uninstrumented and its XLA compiler aborts under ASAN — "
    "the leg exists to verify OUR C, the view/pool/flush machinery)",
)


def wire_equiv(a: Subscription, b: Subscription) -> bool:
    """Delivery equivalence: every field publish_to_client consults.
    (A borrowed single-sighting target keeps identifiers=None where the
    eager copy materializes {filter: 0} — wire-identical, since only
    identifier values > 0 ever reach the encoder [MQTT-3.3.4-3].)"""
    ids_a = {k: v for k, v in (a.identifiers or {}).items() if v > 0}
    ids_b = {k: v for k, v in (b.identifiers or {}).items() if v > 0}
    return (
        a.qos, a.no_local, a.retain_as_published, a.fwd_retained_flag,
        a.predicates, ids_a,
    ) == (
        b.qos, b.no_local, b.retain_as_published, b.fwd_retained_flag,
        b.predicates, ids_b,
    )


def snap_fixture():
    """A 3-entry snapshot table (window 4) covering client, shared and
    inline sections plus a duplicate-client overlap."""
    sub_plus = Subscription(filter="a/+", qos=1)
    sub_exact = Subscription(filter="a/b", qos=0, no_local=True)
    sub_hash = Subscription(filter="a/#", qos=2)
    sub_ident = Subscription(filter="i/#", identifier=9, qos=1)
    shared = Subscription(filter="$share/g/a/+", qos=1)

    class _Inline:
        def __init__(self, ident):
            self.identifier = ident
            self.filter = "a/#"
            self.predicates = ()

    inline = _Inline(41)
    snaps = [
        # entry 0: two clients, one shared member, one inline
        ((("c1", sub_plus), ("c2", sub_exact)), (("s1", shared),), (inline,)),
        # entry 1: c1 again (duplicate-client merge) via a/#
        ((("c1", sub_hash),), (), ()),
        # entry 2: identifier-carrying subscription (copy-on-sight)
        ((("c3", sub_ident),), (), ()),
    ]
    return snaps, dict(
        sub_plus=sub_plus, sub_exact=sub_exact, sub_hash=sub_hash,
        sub_ident=sub_ident, shared=shared, inline=inline,
    )


def make_views(sids, totals, route, n_topics, snaps, window=4):
    sids = np.asarray(sids, dtype=np.int32)
    totals = np.asarray(totals, dtype=np.int32)
    route = np.asarray(route, dtype=np.int32)
    return acc.resolve_compact_views(
        sids, None, totals, route, int(totals.sum()), n_topics, snaps,
        window, Subscribers,
    )


def make_eager(sids, totals, route, n_topics, snaps, window=4):
    sids = np.asarray(sids, dtype=np.int32)
    totals = np.asarray(totals, dtype=np.int32)
    route = np.asarray(route, dtype=np.int32)
    return acc.resolve_compact(
        sids, None, totals, route, int(totals.sum()), n_topics, snaps,
        window, Subscribers,
    )


class TestViewSemantics:
    def test_targets_wire_equivalent_to_eager(self):
        snaps, _ = snap_fixture()
        # topic hits: c1 (a/+), c2 (a/b), c1 again (a/# -> merge)
        views, ovf = make_views([0, 1, 4], [3], [0], 1, snaps)
        eager, eovf = make_eager([0, 1, 4], [3], [0], 1, snaps)
        assert ovf == [] and eovf == []
        t = dict(views[0].targets())
        e = eager[0].subscriptions
        assert set(t) == set(e)
        for cid in e:
            assert wire_equiv(t[cid], e[cid]), cid
        # the duplicate-client entry is a true merge (value-equal)
        assert t["c1"] == e["c1"]
        assert t["c1"].qos == 2  # max of a/+ (1) and a/# (2)

    def test_single_sighting_is_zero_copy(self):
        snaps, fix = snap_fixture()
        views, _ = make_views([1], [1], [0], 1, snaps)
        ((cid, sub),) = views[0].targets()
        assert cid == "c2"
        assert sub is fix["sub_exact"]  # the STORED object, no copy

    def test_identifier_carrier_is_copied_and_materialized(self):
        """identifier > 0 must take the eager first-sighting copy
        ([MQTT-3.3.4-3]: the identifiers map materializes), never the
        borrowed stored object."""
        snaps, fix = snap_fixture()
        views, _ = make_views([8], [1], [0], 1, snaps)
        ((cid, sub),) = views[0].targets()
        assert cid == "c3"
        assert sub is not fix["sub_ident"]
        assert sub.identifiers == {"i/#": 9}
        # the stored subscription was NOT mutated (identifiers was None)
        assert fix["sub_ident"].identifiers is None

    def test_classification_flags(self):
        snaps, _ = snap_fixture()
        views, _ = make_views([0, 2, 3], [3], [0], 1, snaps)
        v = views[0]
        assert v.has_shared and v.has_inline
        views2, _ = make_views([0, 1], [2], [0], 1, snaps)
        assert not views2[0].has_shared and not views2[0].has_inline

    def test_materialize_matches_eager_exactly(self):
        snaps, _ = snap_fixture()
        sids, totals, route = [0, 1, 2, 3, 4], [5], [0]
        views, _ = make_views(sids, totals, route, 1, snaps)
        eager, _ = make_eager(sids, totals, route, 1, snaps)
        m = views[0].materialize()
        assert m.subscriptions == eager[0].subscriptions
        assert m.shared == eager[0].shared
        assert m.inline_subscriptions == eager[0].inline_subscriptions

    def test_attribute_delegation_and_len(self):
        snaps, _ = snap_fixture()
        views, _ = make_views([0, 2], [2], [0], 1, snaps)
        v = views[0]
        assert v.is_lazy
        assert len(v) == 2
        # dict-semantics access transparently materializes
        assert set(v.subscriptions) == {"c1"}
        assert set(v.shared) == {"$share/g/a/+"}
        assert not v.is_lazy
        # Subscribers methods reach through too (select_shared mutates
        # the materialized result via setattr delegation)
        v.select_shared()
        assert v.shared_selected

    def test_routed_rows_and_geometry_tripwire(self):
        snaps, _ = snap_fixture()
        views, ovf = make_views([0, 1], [1, 1], [0, 1], 2, snaps)
        assert ovf == [1] and views[1] is None and views[0] is not None
        with pytest.raises(ValueError):
            # totals claim more pairs than the stream carries
            acc.resolve_compact_views(
                np.array([0], dtype=np.int32), None,
                np.array([3], dtype=np.int32),
                np.array([0], dtype=np.int32),
                3, 1, snaps, 4, Subscribers,
            )

    def test_ranges_views_match_eager(self):
        snaps, _ = snap_fixture()
        P = 2
        packed = np.array(
            [
                [0, 4, 2, 1, 3, 0],  # sids 0,1 + 4 (c1 dup-merge)
                [8, 0, 1, 0, 1, 0],  # sid 8 (identifier carrier)
                [0, 0, 0, 0, 0, 1],  # overflow row
            ],
            dtype=np.int32,
        )
        lazy, lovf = acc.resolve_batch_views(
            packed, 3, P, snaps, 4, Subscribers
        )
        eager, eovf = acc.resolve_batch(packed, 3, P, snaps, 4, Subscribers)
        assert lovf == eovf == [2]
        assert lazy[2] is None
        for i in range(2):
            t = dict(lazy[i].targets())
            e = eager[i].subscriptions
            assert set(t) == set(e)
            for cid in e:
                assert wire_equiv(t[cid], e[cid])
        assert len(lazy[0]) == 3

    def test_empty_view(self):
        snaps, _ = snap_fixture()
        views, _ = make_views([], [0], [0], 1, snaps)
        v = views[0]
        assert len(v) == 0 and v.targets() == []
        assert not v.has_shared and not v.has_inline
        assert v.materialize().subscriptions == {}


class TestFreelistPool:
    def test_pool_cycles_and_reuses(self):
        snaps, _ = snap_fixture()
        acc.pool_clear()
        base = acc.view_stats()
        for _ in range(3):
            views, _ = make_views([8], [1], [0], 1, snaps)
            views[0].targets()
            del views
            gc.collect()
        st = acc.view_stats()
        assert st["pool_returns"] - base["pool_returns"] >= 3
        assert st["pool_hits"] - base["pool_hits"] >= 2

    def test_consumer_held_copy_is_never_recycled(self):
        """UAF-safety: a pool copy the consumer still references must
        NOT be parked when its view dies — recycling it would alias a
        live Subscription."""
        snaps, fix = snap_fixture()
        acc.pool_clear()
        views, _ = make_views([8], [1], [0], 1, snaps)
        ((_cid, held),) = views[0].targets()
        snapshot = (held.filter, held.identifier, dict(held.identifiers))
        base = acc.view_stats()["pool_returns"]
        del views
        gc.collect()
        assert acc.view_stats()["pool_returns"] == base  # not parked
        # another round may allocate fresh copies; the held object must
        # stay untouched throughout
        views2, _ = make_views([8], [1], [0], 1, snaps)
        views2[0].targets()
        del views2
        gc.collect()
        assert (held.filter, held.identifier, dict(held.identifiers)) == snapshot
        assert wire_equiv(held, fix["sub_ident"].self_merged_copy())

    def test_snapshot_pins_subscriptions_across_mutation(self):
        """The view's batch owns the snapshot list: dropping every
        other reference to the stored subscriptions (the unsubscribe
        analog) must leave consumption intact — lifetime safety is by
        ownership, not by luck."""
        snaps, fix = snap_fixture()
        views, _ = make_views([0, 1, 4], [3], [0], 1, snaps)
        del snaps, fix
        gc.collect()
        t = dict(views[0].targets())
        assert t["c1"].qos == 2 and t["c2"].filter == "a/b"


def _collect(r, n, version=4):
    async def inner():
        out = []
        for _ in range(n):
            pk = await read_wire_packet(r, version)
            assert pk.fixed_header.type == PUBLISH
            out.append(
                (
                    pk.topic_name,
                    bytes(pk.payload),
                    pk.fixed_header.qos,
                    pk.fixed_header.retain,
                    pk.packet_id,
                    tuple(pk.properties.subscription_identifier or ()),
                )
            )
        return out

    return inner()


@needs_jax
class TestDeliveryDifferential:
    """Delivered wire frames must be bit-identical between the lazy
    batched path and the eager per-subscriber path across subscription
    shapes. The eager side is selected the way a deployment selects it:
    ``matcher_opts={"lazy": False}`` for the eager resolver, a hook that
    observes sends for the per-subscriber loop."""

    SCENARIO = [
        # (client id, version, filters [(filter, qos)])
        ("exact", 4, [("d/t/1", 0)]),
        ("plus", 4, [("d/+/1", 1)]),
        ("hash", 5, [("d/#", 1)]),
        ("multi", 4, [("d/+/1", 0), ("d/t/+", 1)]),  # dup-merge target
        ("shared", 4, [("$share/g/d/t/1", 1)]),
        ("pred", 5, [("d/t/2$GT{5}", 0)]),
    ]
    PUBLISHES = [
        ("d/t/1", b"alpha", 0),
        ("d/t/1", b"beta", 1),
        ("d/t/2", b"9.5", 0),   # passes $GT{5}
        ("d/t/2", b"1.0", 0),   # filtered for pred, delivered to hash
        ("d/x/9", b"gamma", 1),  # only d/#
    ]
    EXPECTED = {
        "exact": 2, "plus": 2, "hash": 5, "multi": 4, "shared": 2,
        "pred": 1,
    }

    def _run_scenario(self, lazy: bool):
        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    device_matcher=True,
                    matcher_opts={
                        "max_levels": 4, "background": False, "lazy": lazy,
                    },
                )
            )
            if not lazy:
                h.server.add_hook(ObservingHook())
            await h.server.serve()
            conns = {}
            for cid, ver, filters in self.SCENARIO:
                r, w, _ = await h.connect(cid, version=ver)
                w.write(
                    sub_packet(
                        1,
                        [Subscription(filter=f, qos=q) for f, q in filters],
                        version=ver,
                    )
                )
                await w.drain()
                assert (await read_wire_packet(r, ver)).fixed_header.type == SUBACK
                # the writer is HELD: Python 3.12's StreamWriter.__del__
                # closes a dropped writer and disconnects the subscriber
                conns[cid] = (r, ver, w)
            h.server.matcher.flush()
            pr, pw, _ = await h.connect("src")
            pid = 1
            for topic, payload, qos in self.PUBLISHES:
                pw.write(
                    pub_packet(topic, payload, qos=qos, pid=pid if qos else 0)
                )
                pid += 1
            await pw.drain()
            got = {}
            for cid, (r, ver, _w) in conns.items():
                got[cid] = await asyncio.wait_for(
                    # generous: the first staged batch pays the XLA
                    # compile of the match kernel inside this wait
                    _collect(r, self.EXPECTED[cid], ver), 60
                )
            await h.server.close()
            await h.shutdown()
            return got

        return run(scenario())

    def test_lazy_matches_eager_bit_identically(self):
        lazy = self._run_scenario(True)
        eager = self._run_scenario(False)
        assert lazy == eager
        # and the lazy run actually delivered everything it promised
        assert {k: len(v) for k, v in lazy.items()} == self.EXPECTED


async def _read_publish_frames(reader, n):
    """``n`` raw PUBLISH frames off a subscriber's stream, each with
    its packet id (QoS > 0) zeroed: ids are per-client counters, every
    other byte of the frame is the broker's choice."""
    frames = []
    while len(frames) < n:
        head = bytearray(await asyncio.wait_for(reader.readexactly(1), 5))
        remaining, shift = 0, 0
        while True:
            b = (await asyncio.wait_for(reader.readexactly(1), 5))[0]
            head.append(b)
            remaining |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        body = bytearray(await asyncio.wait_for(reader.readexactly(remaining), 5))
        if head[0] >> 4 != PUBLISH:
            continue
        if (head[0] >> 1) & 3:
            pid_at = 2 + ((body[0] << 8) | body[1])
            body[pid_at : pid_at + 2] = b"\x00\x00"
        frames.append(bytes(head + body))
    return frames


class TestObservedHookDifferential:
    """The per-subscriber loop a hook on ON_PACKET_ENCODE / ON_PACKET_SENT
    selects must put the same frames on the wire as the encode-once
    batched flush it replaces. Nothing else selects that loop, so the
    hook is the way in."""

    PUBLISHES = [("f/t/1", b"plain", False), ("f/t/1", b"kept", True)]

    def _frames(self, event, qos, version):
        async def scenario():
            # no expiry interval to count down: the send-time rewrite
            # reads the wall clock, so two runs could differ by a second
            h = Harness(
                Options(
                    inline_client=True,
                    capabilities=Capabilities(
                        maximum_message_expiry_interval=0
                    ),
                )
            )
            hook = None
            if event is not None:
                hook = ObservingHook(event)
                h.server.add_hook(hook)
            await h.server.serve()
            conns = {}
            for cid, ver, sub in (
                ("exact", version, Subscription(filter="f/t/1", qos=2)),
                ("plus", version, Subscription(filter="f/+/1", qos=1)),
                (
                    "rap", 5,
                    Subscription(
                        filter="f/t/1", qos=2, retain_as_published=True
                    ),
                ),
            ):
                r, w, _ = await h.connect(cid, version=ver)
                w.write(sub_packet(1, [sub], version=ver))
                await w.drain()
                assert (await read_wire_packet(r, ver)).fixed_header.type == SUBACK
                conns[cid] = (r, w)
            # a v5 publisher: a v4 QoS0 frame would take the passthrough
            # (try_fast_publish) on this host-only broker, not _fan_out
            _pr, pw, _ = await h.connect("src", version=5)
            for pid, (topic, payload, retain) in enumerate(self.PUBLISHES, 1):
                pw.write(
                    pub_packet(
                        topic, payload, qos=qos, pid=pid if qos else 0,
                        version=5, retain=retain,
                    )
                )
            await pw.drain()
            got = {
                cid: await _read_publish_frames(r, len(self.PUBLISHES))
                for cid, (r, _w) in conns.items()
            }
            variants = h.server.telemetry.fanout_variants.value
            await h.server.close()
            await h.shutdown()
            return got, variants, hook.seen if hook is not None else 0

        return run(scenario())

    @pytest.mark.parametrize("version", [4, 5])
    @pytest.mark.parametrize("qos", [0, 1, 2])
    @pytest.mark.parametrize(
        "event", [ON_PACKET_ENCODE, ON_PACKET_SENT], ids=["encode", "sent"]
    )
    def test_observed_loop_matches_batched_frame_for_frame(
        self, event, qos, version
    ):
        batched, variants, _ = self._frames(None, qos, version)
        observed, variants_observed, seen = self._frames(event, qos, version)
        assert observed == batched
        assert all(len(f) == len(self.PUBLISHES) for f in batched.values())
        # each side really took its own path: the batched flush groups
        # variants, the observed loop never does and feeds the hook
        assert variants > 0 and variants_observed == 0
        assert seen >= 3 * len(self.PUBLISHES)


@needs_jax
class TestTenantScopedDifferential:
    """Tenant-scoped delivery through the lazy path: namespace-scoped
    topics resolve to views too, deliveries strip the scope prefix, and
    cross-tenant isolation + wire bytes match the eager path exactly."""

    def _run(self, lazy: bool):
        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    device_matcher=True,
                    matcher_opts={
                        "max_levels": 4, "background": False, "lazy": lazy,
                    },
                    tenancy=True,
                    tenants={"acme": {}, "globex": {}},
                    tenant_users={
                        "a-sub": "acme", "a-pub": "acme", "g-sub": "globex",
                    },
                )
            )
            if not lazy:
                h.server.add_hook(ObservingHook())
            await h.server.serve()
            a_r, a_w, _ = await h.connect("a-sub")
            a_w.write(sub_packet(1, [Subscription(filter="t/+", qos=1)]))
            await a_w.drain()
            assert (await read_wire_packet(a_r)).fixed_header.type == SUBACK
            g_r, g_w, _ = await h.connect("g-sub")
            g_w.write(sub_packet(1, [Subscription(filter="t/+", qos=1)]))
            await g_w.drain()
            assert (await read_wire_packet(g_r)).fixed_header.type == SUBACK
            h.server.matcher.flush()
            p_r, p_w, _ = await h.connect("a-pub")
            p_w.write(pub_packet("t/1", b"scoped", qos=1, pid=5))
            p_w.write(pub_packet("t/2", b"zero"))
            await p_w.drain()
            got = await asyncio.wait_for(_collect(a_r, 2), 60)
            # cross-tenant isolation: globex must receive NOTHING
            with pytest.raises(asyncio.TimeoutError):
                await asyncio.wait_for(read_wire_packet(g_r), 0.4)
            await h.server.close()
            await h.shutdown()
            return got

        return run(scenario())

    def test_tenant_lazy_matches_eager(self):
        lazy = self._run(True)
        eager = self._run(False)
        assert lazy == eager
        assert [(t, p) for t, p, *_r in lazy] == [
            ("t/1", b"scoped"), ("t/2", b"zero")
        ]


@needs_jax
class TestLazyLifetimeE2E:
    def test_unsubscribe_and_disconnect_between_resolve_and_consume(self):
        """A subscriber that unsubscribes or disconnects BETWEEN device
        resolve and fan-out consumption must neither UAF nor receive
        the publish once dead — the view snapshot pins objects, the
        live client registry gates delivery."""

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    device_matcher=True,
                    matcher_opts={"max_levels": 4, "background": False},
                )
            )
            await h.server.serve()
            r1, w1, _ = await h.connect("stay")
            w1.write(sub_packet(1, [Subscription(filter="l/+", qos=0)]))
            await w1.drain()
            assert (await read_wire_packet(r1)).fixed_header.type == SUBACK
            r2, w2, _ = await h.connect("leave")
            w2.write(sub_packet(1, [Subscription(filter="l/+", qos=0)]))
            await w2.drain()
            assert (await read_wire_packet(r2)).fixed_header.type == SUBACK
            h.server.matcher.flush()

            # resolve views OUT OF BAND (the exact state fan-out sees),
            # then kill the subscriber before consumption
            views = h.server.matcher.match_topics(["l/1"])
            leaver = h.server.clients.get("leave")
            leaver.stop()
            h.server.clients.delete("leave")
            gc.collect()
            targets = dict(views[0].targets())
            assert set(targets) == {"stay", "leave"}  # snapshot-time truth
            # now the real fan-out: only the live client receives
            pr, pw, _ = await h.connect("src")
            pw.write(pub_packet("l/1", b"z"))
            await pw.drain()
            pk = await read_wire_packet(r1)
            assert pk.topic_name == "l/1"
            assert h.server.clients.get("leave") is None
            await h.server.close()
            await h.shutdown()

        run(scenario())


@needs_jax
class TestScanGate:
    def test_coalesced_scans_deliver_identically(self):
        async def scenario():
            h = Harness(Options(inline_client=True, scan_coalesce=True))
            await h.server.serve()
            gate = h.server._ops.scan_gate
            assert gate is not None
            r, w, _ = await h.connect("sub")
            w.write(sub_packet(1, [Subscription(filter="s/#", qos=0)]))
            await w.drain()
            assert (await read_wire_packet(r)).fixed_header.type == SUBACK
            pr, pw, _ = await h.connect("pub")
            n = 16
            for i in range(n):
                pw.write(pub_packet(f"s/{i}", f"m{i}".encode()))
            await pw.drain()
            for i in range(n):
                pk = await read_wire_packet(r)
                assert pk.fixed_header.type == PUBLISH
            assert gate.batches > 0 and gate.scans >= gate.batches
            await h.server.close()
            await h.shutdown()

        run(scenario())


class TestRecryptAssembly:
    def test_c_frame_assembly_matches_numpy(self):
        from mqtt_tpu import native

        head = b"\x30\x20\x00\x03a/b"
        n, pt = 5, b"secret payload bytes"
        rng = np.random.default_rng(7)
        nonces = rng.integers(0, 256, (n, 12), dtype=np.uint8)
        ks = rng.integers(0, 256, (n, 32), dtype=np.uint8)
        out = native.assemble_frames(head, nonces, ks, pt)
        if out is None:
            pytest.skip("native library unavailable")
        pt_arr = np.frombuffer(pt, dtype=np.uint8)
        for i in range(n):
            expect = (
                head + nonces[i].tobytes()
                + (ks[i][: len(pt)] ^ pt_arr).tobytes()
            )
            assert out[i].tobytes() == expect

    def test_assembly_refuses_short_keystream(self):
        from mqtt_tpu import native

        nonces = np.zeros((1, 12), dtype=np.uint8)
        ks = np.zeros((1, 4), dtype=np.uint8)
        assert native.assemble_frames(b"h", nonces, ks, b"longer-than-4") is None
