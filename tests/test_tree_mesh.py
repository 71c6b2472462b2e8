"""Spanning-tree mesh suite (ISSUE 9): the in-process end of the
interest-scoped tree fabric — deterministic election and O(degree)
links, multi-hop summary-gated routing, the per-edge health machine
(sever -> scoped re-election -> exactly-once heal under the new epoch),
duplicate suppression, and the per-signal pressure-gossip fold.

The 32-worker subprocess drill lives in tests/test_mesh_drill.py (slow,
nightly); this file is the tier-1 correctness net over the same
machinery at 5 workers, where every worker is a full in-process Server.
"""

import asyncio
import json
import struct
import time

import pytest

from mqtt_tpu.cluster import (
    _T_RFRAME,
    PEER_SUSPECT,
    PEER_UP,
    Cluster,
)
from mqtt_tpu.faults import asymmetric_partition, sever_peer_link
from mqtt_tpu.mesh_topology import compute_parents, tree_neighbors
from mqtt_tpu.overload import PeerPressureSignal
from mqtt_tpu.packets import PUBACK, PUBLISH, Subscription
from mqtt_tpu.server import Options

from tests.test_server import (
    Harness,
    pub_packet,
    read_wire_packet,
    sub_packet,
)


def run(coro, timeout=60):
    """Local runner with headroom for partition/backoff legs (the
    test_server default of 15s is tuned for single-broker scenarios)."""
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


async def wait_for(cond, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        await asyncio.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


DEGREE = 2


class TreeMesh:
    """N in-process workers in tree mode, with the drill-grade fast
    clocks: 0.1s ping/gossip cadence and millisecond dial backoff."""

    def __init__(self, n, tmp_path, degree=DEGREE, partition_pings=0, **opt_kw):
        self.n = n
        self.harnesses = [
            Harness(
                Options(
                    inline_client=True,
                    cluster_topology="tree",
                    cluster_tree_degree=degree,
                    **opt_kw,
                )
            )
            for _ in range(n)
        ]
        self.clusters = [
            Cluster(h.server, i, n, str(tmp_path))
            for i, h in enumerate(self.harnesses)
        ]
        for c in self.clusters:
            c.PING_INTERVAL_S = 0.1
            c.DIAL_BACKOFF_S = 0.02
            c.DIAL_BACKOFF_MAX_S = 0.2
            c.PROBE_BACKOFF_S = 0.1
            if partition_pings:
                # tests that must OBSERVE the SUSPECT park before the
                # partition verdict widen the window: at the 0.1s drill
                # cadence the default 5-ping threshold is only 0.5s and
                # a loaded CI host can blow through it
                c.partition_pings = partition_pings

    async def start(self):
        for h in self.harnesses:
            await h.server.serve()
        for c in self.clusters:
            await c.start()
        await wait_for(
            lambda: all(
                all(p in c._writers for p in c.topo.neighbors())
                for c in self.clusters
            ),
            msg="tree links up",
        )

    async def stop(self, skip=()):
        for c in self.clusters:
            if c.worker_id not in skip:
                await c.stop()
        for h in self.harnesses:
            await h.server.close()
            await h.shutdown()

    async def subscribe(self, worker, client_id, filter, qos=1):
        r, w, _ = await self.harnesses[worker].connect(client_id, version=4)
        w.write(sub_packet(1, [Subscription(filter=filter, qos=qos)], version=4))
        ack = await read_wire_packet(r, 4)
        assert ack.fixed_header.type != PUBLISH or True
        return r, w

    async def settle_summaries(self):
        """Wait until every edge's interest summary is stamped with the
        receiver's CURRENT epoch (the summary gate is live, not in
        conservative pass-through) AND holds what its sender would
        advertise on that edge now: a subscription made just before the
        call has then reached every bloom it feeds, however many hops
        (one gossip tick each) away."""
        def _epoch_key(c):
            ep = c.topo.epoch
            return (ep.num, ep.boot, ep.proposer)

        def _settled(c, p):
            es = c._edge_summaries.get(p)
            if es is None or es.ep_key != _epoch_key(c):
                return False
            want = self.clusters[p]._edge_summary_for(c.worker_id)
            return (es.bits.data, es.bits.match_all) == (
                want.data, want.match_all
            )

        await wait_for(
            lambda: all(
                all(_settled(c, p) for p in c.topo.neighbors())
                for c in self.clusters
            ),
            msg="summaries settled",
        )


async def read_until_payload(reader, payload, version=4, timeout=10):
    """Read PUBLISHes until ``payload`` arrives; returns all payloads
    seen (duplicate accounting reads the full list)."""
    seen = []

    async def inner():
        while True:
            pk = await read_wire_packet(reader, version)
            if pk.fixed_header.type != PUBLISH:
                continue
            seen.append(bytes(pk.payload))
            if pk.payload == payload:
                return

    await asyncio.wait_for(inner(), timeout)
    return seen


# -- election + links ---------------------------------------------------------


class TestTreeBoot:
    def test_links_stay_o_degree_and_match_the_computed_tree(self, tmp_path):
        async def scenario():
            mesh = TreeMesh(5, tmp_path)
            await mesh.start()
            parents = compute_parents(range(5), DEGREE)
            for c in mesh.clusters:
                assert set(c.topo.neighbors()) == set(
                    tree_neighbors(parents, c.worker_id)
                )
                # the O(degree) bound: parent + children, nothing else
                assert len(c._writers) <= DEGREE + 1
                assert set(c._writers) <= set(c.topo.neighbors())
            await mesh.stop()

        run(scenario())

    def test_knob_normalization(self):
        o = Options(
            cluster_topology="RING",
            cluster_tree_degree=0,
            cluster_summary_bits=7,
            cluster_dup_window=-1,
        )
        o.ensure_defaults()
        assert o.cluster_topology == "mesh"  # unknown mode: safe fallback
        assert o.cluster_tree_degree == 4
        assert o.cluster_summary_bits == 4096
        assert o.cluster_dup_window == 8192
        o2 = Options(cluster_topology="Tree")
        o2.ensure_defaults()
        assert o2.cluster_topology == "tree"

    def test_config_file_passthrough(self):
        from mqtt_tpu.config import from_bytes

        opts = from_bytes(
            b"""
options:
  cluster_topology: tree
  cluster_tree_degree: 3
  cluster_summary_bits: 8192
  cluster_dup_window: 1024
"""
        )
        assert opts.cluster_topology == "tree"
        assert opts.cluster_tree_degree == 3
        assert opts.cluster_summary_bits == 8192
        assert opts.cluster_dup_window == 1024

    def test_epoch_digest_reconciles_divergence(self, tmp_path):
        """The anti-entropy heartbeat is a 3-int digest: agreement costs
        nothing, disagreement is answered with the full member map, and
        a digest alone can never move the tree (adoption needs the map).
        End to end, a divergent pair reconciles off one digest."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            c0, c1 = mesh.clusters[0], mesh.clusters[1]
            ep = c0.topo.epoch
            calls = []
            real = c0._announce_epoch
            c0._announce_epoch = lambda only=None, digest=False: calls.append(
                (tuple(only or ()), digest)
            )
            try:
                agree = json.dumps({"e": [ep.num, ep.boot, ep.proposer]})
                c0._on_epoch(1, agree.encode())
                assert not calls  # agreement is free
                ahead = json.dumps({"e": [ep.num + 5, ep.boot, ep.proposer]})
                c0._on_epoch(1, ahead.encode())
                assert calls == [((1,), False)]  # answered with the map
                assert c0.topo.epoch == ep  # the digest moved nothing
            finally:
                c0._announce_epoch = real
            # e2e: worker 1 re-elects without worker 2; its next digest
            # heartbeat makes 0 answer back, 1 answers with its map, 0
            # adopts — full convergence off a 3-int frame
            assert c1.topo.propose_remove(2) is not None
            assert c1.topo.epoch > c0.topo.epoch
            await wait_for(
                lambda: c0.topo.epoch == c1.topo.epoch, msg="digest heal"
            )
            await mesh.stop()

        run(scenario())

    def test_worker_env_round_trip(self, tmp_path):
        from mqtt_tpu.cluster import worker_env

        env = worker_env(3, 8, str(tmp_path), topology="tree", degree=3)
        assert env["MQTT_TPU_CLUSTER_TOPOLOGY"] == "tree"
        assert env["MQTT_TPU_CLUSTER_DEGREE"] == "3"
        # mesh mode (the default) sets neither: every worker must agree
        assert "MQTT_TPU_CLUSTER_TOPOLOGY" not in worker_env(0, 2, "x")


# -- routing ------------------------------------------------------------------


class TestTreeRouting:
    def test_multi_hop_qos0_and_qos1(self, tmp_path):
        """Leaf-to-leaf delivery crosses two interior hops (2 -> 0 -> 1
        -> 4 at degree 2): the passthrough frame is re-forwarded at each
        hop under the frame's epoch, and QoS1 rides the packet path."""

        async def scenario():
            mesh = TreeMesh(5, tmp_path)
            await mesh.start()
            r4, _w4 = await mesh.subscribe(4, "sub4", "t/x")
            await mesh.settle_summaries()
            _rp, wp, _ = await mesh.harnesses[2].connect("pub2", version=4)
            wp.write(pub_packet("t/x", b"hop0", qos=0, version=4))
            wp.write(pub_packet("t/x", b"hop1", qos=1, pid=3, version=4))
            await wp.drain()
            seen = await read_until_payload(r4, b"hop1")
            assert seen == [b"hop0", b"hop1"]  # both, once, in order
            await mesh.stop()

        run(scenario())

    def test_summary_gates_uninterested_edges(self, tmp_path):
        """With summaries settled, a publish matching NO remote interest
        is filtered at the origin (counted) instead of flooding the
        tree; interested publishes still forward (no false negatives)."""

        async def scenario():
            mesh = TreeMesh(5, tmp_path)
            await mesh.start()
            r4, _w4 = await mesh.subscribe(4, "sub4", "wanted/#")
            await mesh.settle_summaries()
            origin = mesh.clusters[2]
            filtered0 = origin.summary_filtered_forwards
            _rp, wp, _ = await mesh.harnesses[2].connect("pub2", version=4)
            wp.write(pub_packet("nobody/cares", b"drop me", qos=0, version=4))
            wp.write(pub_packet("wanted/t", b"keep me", qos=0, version=4))
            await wp.drain()
            seen = await read_until_payload(r4, b"keep me")
            assert seen == [b"keep me"]
            assert origin.summary_filtered_forwards > filtered0
            await mesh.stop()

        run(scenario())

    def test_retained_replicates_to_every_worker(self, tmp_path):
        """Retained state floods every edge regardless of summaries: a
        subscriber landing on ANY worker later must see it."""

        async def scenario():
            mesh = TreeMesh(5, tmp_path)
            await mesh.start()
            await mesh.settle_summaries()
            _rp, wp, _ = await mesh.harnesses[3].connect("pub3", version=4)
            wp.write(
                pub_packet("cfg/x", b"retained", qos=0, version=4, retain=True)
            )
            await wp.drain()
            await wait_for(
                lambda: all(
                    h.server.topics.retained.get("cfg/x") is not None
                    for h in mesh.harnesses
                ),
                msg="retained replication",
            )
            # a late subscriber on a different leaf gets the retained copy
            r2, _w2 = await mesh.subscribe(2, "late2", "cfg/#")
            seen = await read_until_payload(r2, b"retained")
            assert seen == [b"retained"]
            await mesh.stop()

        run(scenario())

    def test_predicate_subscriber_receives_cross_worker(self, tmp_path):
        """The ISSUE 9 seam test: a ``sensors/+/temp$GT{25}`` subscriber
        contributes its BASE filter to the edge summaries, so remote
        publishes still forward — and the predicate then gates delivery
        at the subscriber's worker (30.0 passes, 20.0 is filtered)."""

        async def scenario():
            mesh = TreeMesh(
                5, tmp_path, predicate_filters=True
            )
            await mesh.start()
            r4, _w4 = await mesh.subscribe(
                4, "pred4", "sensors/+/temp$GT{25}"
            )
            await mesh.settle_summaries()
            # the base filter (not the suffixed form) reached the blooms
            origin = mesh.clusters[2]
            assert any(
                es.bits.might_match("sensors/a/temp")
                for es in origin._edge_summaries.values()
            )
            _rp, wp, _ = await mesh.harnesses[2].connect("pub2", version=4)
            wp.write(pub_packet("sensors/a/temp", b"20.0", qos=0, version=4))
            wp.write(pub_packet("sensors/a/temp", b"30.0", qos=0, version=4))
            await wp.drain()
            seen = await read_until_payload(r4, b"30.0")
            assert seen == [b"30.0"]  # 20.0 forwarded but predicate-gated
            await mesh.stop()

        run(scenario())

    def test_shared_group_subscriber_receives_cross_worker(self, tmp_path):
        """$SHARE summarizes as its inner filter: publishes arrive on
        the inner topic space and must forward to the member's worker."""

        async def scenario():
            mesh = TreeMesh(5, tmp_path)
            await mesh.start()
            r3, _w3 = await mesh.subscribe(3, "share3", "$SHARE/g/jobs/#")
            await mesh.settle_summaries()
            _rp, wp, _ = await mesh.harnesses[1].connect("pub1", version=4)
            wp.write(pub_packet("jobs/t", b"job", qos=0, version=4))
            await wp.drain()
            seen = await read_until_payload(r3, b"job")
            assert seen == [b"job"]
            await mesh.stop()

        run(scenario())

    def test_unsubscribe_is_a_counted_delete(self, tmp_path):
        """UNSUBSCRIBE removes the filter from the local bloom (counted
        delete, not rebuild-the-world): once summaries refresh, the
        publish is filtered again at the origin."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            sub = mesh.clusters[2]
            assert not sub._local_interest.bits().might_match("u/t")
            r2, w2 = await mesh.subscribe(2, "sub2", "u/t")
            await wait_for(
                lambda: sub._local_interest.bits().might_match("u/t"),
                msg="bloom add",
            )
            from mqtt_tpu.packets import (
                UNSUBSCRIBE,
                FixedHeader,
                Packet,
                encode_packet,
            )

            w2.write(
                encode_packet(
                    Packet(
                        fixed_header=FixedHeader(type=UNSUBSCRIBE, qos=1),
                        protocol_version=4,
                        packet_id=2,
                        filters=[Subscription(filter="u/t")],
                    )
                )
            )
            await w2.drain()
            await wait_for(
                lambda: not sub._local_interest.bits().might_match("u/t"),
                msg="bloom delete",
            )
            await mesh.stop()

        run(scenario())


# -- duplicate suppression + loop guards --------------------------------------


def _rframe_payload(origin: str, rt: dict, frame: bytes) -> bytes:
    ob = origin.encode()
    rj = json.dumps(rt).encode()
    return (
        struct.pack(">H", len(ob)) + ob + struct.pack(">H", len(rj)) + rj + frame
    )


class TestDuplicateSuppression:
    def test_replayed_rframe_is_suppressed_and_counted(self, tmp_path):
        """The same (origin, boot, seq) arriving twice — the
        re-parenting replay shape — delivers once; the second arrival is
        a counted no-op (no delivery, no re-forward)."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            r2, _w2 = await mesh.subscribe(2, "sub2", "d/x")
            await mesh.settle_summaries()
            target = mesh.clusters[2]
            ep = target.topo.epoch
            rt = {
                "e": ep.num, "eb": ep.boot, "ep": ep.proposer,
                "o": 0, "b": 424242, "s": 1,
            }
            frame = pub_packet("d/x", b"dup?", qos=0, version=4)
            payload = _rframe_payload("pub-far", rt, frame)
            suppressed0 = target.duplicates_suppressed
            target._on_rframe(0, payload)
            target._on_rframe(0, payload)  # the replay
            assert target.duplicates_suppressed == suppressed0 + 1
            seen = await read_until_payload(r2, b"dup?")
            assert seen == [b"dup?"]
            await mesh.stop()

        run(scenario())

    def test_origin_echo_is_suppressed(self, tmp_path):
        """A routed frame whose origin is THIS incarnation arriving
        back (mixed-epoch trees can route a frame to its source) must
        never re-deliver to the origin's local subscribers: the origin
        delivered at publish time and records no window entry for its
        own sends."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            origin = mesh.clusters[0]
            r0, _w0 = await mesh.subscribe(0, "sub0", "echo/#")
            await mesh.settle_summaries()
            ep = origin.topo.epoch
            echo = {
                "e": ep.num, "eb": ep.boot, "ep": ep.proposer,
                "o": 0, "b": origin.boot_id, "s": 12345,
            }
            frame = pub_packet("echo/t", b"boomerang", qos=0, version=4)
            suppressed0 = origin.duplicates_suppressed
            origin._on_rframe(1, _rframe_payload("self", echo, frame))
            assert origin.duplicates_suppressed == suppressed0 + 1
            # a CANARY publish proves nothing from the echo arrived
            _rp, wp, _ = await mesh.harnesses[1].connect("pub1", version=4)
            wp.write(pub_packet("echo/t", b"canary", qos=0, version=4))
            await wp.drain()
            seen = await read_until_payload(r0, b"canary")
            assert seen == [b"canary"]
            await mesh.stop()

        run(scenario())

    def test_park_replay_restamps_full_epoch_identity(self, tmp_path):
        """_park_payload must restamp num AND boot AND proposer: the
        receiver re-forwards only on an exact triple match, so a
        replayed park carrying the dead proposal's identity would stop
        at the first hop instead of fanning down the healed subtree."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            c0 = mesh.clusters[0]
            stale_rt = {"e": 1, "eb": 999, "ep": 9, "o": 0, "b": 7, "s": 3}
            head = {"origin": "x", "qos": 1, "retain": False, "rt": stale_rt}
            entry = ("P", "p/t", head, b"\x30\x05\x00\x03p/t")
            payload = c0._park_payload(entry)
            restamped = json.loads(payload.split(b"\x00", 1)[0])["rt"]
            ep = c0.topo.epoch
            assert restamped["e"] == ep.num
            assert restamped["eb"] == ep.boot
            assert restamped["ep"] == ep.proposer
            # the exactly-once key survives the restamp untouched
            assert (restamped["o"], restamped["b"], restamped["s"]) == (0, 7, 3)
            await mesh.stop()

        run(scenario())

    def test_stale_epoch_frame_delivers_and_reforwards_live_tree(
        self, tmp_path
    ):
        """A frame stamped under a dead tree reaches local subscribers
        AND re-forwards down the LIVE tree's edges — dropping it would
        starve the downstream subtree every time a re-election races an
        in-flight frame (the 32-worker drill's loss mode before this
        was fixed). The (origin, boot, seq) window, not epoch
        agreement, is the loop guard: a second arrival anywhere is a
        counted no-op."""

        async def scenario():
            mesh = TreeMesh(5, tmp_path)
            await mesh.start()
            # worker 1 is interior: its children (3, 4) receive
            # re-forwards of anything arriving from the root side
            interior = mesh.clusters[1]
            r1, _w1 = await mesh.subscribe(1, "sub1", "s/x")
            r3, _w3 = await mesh.subscribe(3, "sub3", "s/x")
            await mesh.settle_summaries()
            stale = {
                "e": 999, "eb": 1, "ep": 0,  # no tree this worker runs
                "o": 0, "b": 99, "s": 50,
            }
            frame = pub_packet("s/x", b"stale", qos=0, version=4)
            stale0 = interior.stale_epoch_frames
            interior._on_rframe(0, _rframe_payload("pub-x", stale, frame))
            assert interior.stale_epoch_frames == stale0 + 1
            seen = await read_until_payload(r1, b"stale")
            assert seen == [b"stale"]  # delivered locally...
            seen3 = await read_until_payload(r3, b"stale")
            assert seen3 == [b"stale"]  # ...AND routed down the live tree
            # replaying the same (origin, boot, seq) is suppressed:
            # conservative re-forwarding cannot loop or double-deliver
            suppressed0 = interior.duplicates_suppressed
            interior._on_rframe(0, _rframe_payload("pub-x", stale, frame))
            assert interior.duplicates_suppressed == suppressed0 + 1
            await mesh.stop()

        run(scenario())


# -- per-edge health: sever -> re-election -> exactly-once heal ---------------


class TestTreePartition:
    def test_suspect_edge_parks_then_heal_replays_exactly_once(self, tmp_path):
        """An asymmetric partition (pongs lost) walks the edge to
        SUSPECT; QoS1 forwards park in the byte-budget buffer; the heal
        replays them exactly once — the subscriber sees each payload
        once, and the replay counter matches the park depth."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path, partition_pings=600)
            # 0 -- 1 and 0 -- 2 at degree 2: sever the 0->2 return path
            await mesh.start()
            r2, _w2 = await mesh.subscribe(2, "sub2", "p/#")
            await mesh.settle_summaries()
            origin = mesh.clusters[0]
            release = asymmetric_partition(origin, 2)
            await wait_for(
                lambda: origin._health_for(2).state == PEER_SUSPECT,
                msg="suspect",
            )
            _rp, wp, _ = await mesh.harnesses[0].connect("pub0", version=4)
            for i in range(5):
                wp.write(
                    pub_packet("p/t", f"m{i}".encode(), qos=1, pid=10 + i,
                               version=4)
                )
            await wp.drain()
            await wait_for(
                lambda: len(origin._health_for(2).park) == 5, msg="parked"
            )
            replayed0 = origin.replayed_forwards
            release()
            await wait_for(
                lambda: origin._health_for(2).state == PEER_UP, msg="heal"
            )
            seen = await read_until_payload(r2, b"m4")
            assert seen == [b"m0", b"m1", b"m2", b"m3", b"m4"]
            assert origin.replayed_forwards == replayed0 + 5
            assert not origin._health_for(2).park
            await mesh.stop()

        run(scenario())

    def test_interior_death_scoped_re_election_and_reroute(self, tmp_path):
        """Killing the interior worker orphans its subtree: survivors
        re-elect WITHOUT it (strictly-greater epoch), the orphans
        re-parent, and leaf-to-leaf delivery works under the new tree —
        with zero duplicate deliveries across the transition."""

        async def scenario():
            mesh = TreeMesh(5, tmp_path)
            await mesh.start()
            r4, _w4 = await mesh.subscribe(4, "sub4", "e/#")
            await mesh.settle_summaries()
            survivors = [c for c in mesh.clusters if c.worker_id != 1]
            ep0 = {c.worker_id: c.topo.epoch_num() for c in survivors}
            await mesh.clusters[1].stop()
            await wait_for(
                lambda: all(
                    c.topo.epoch_num() > ep0[c.worker_id]
                    and 1 not in c.topo.members()
                    for c in survivors
                ),
                timeout=30,
                msg="scoped re-election",
            )
            # concurrent proposals (several survivors detect the death
            # independently) must CONVERGE on one winner: the strict
            # total order picks it, adoption re-floods carry it
            await wait_for(
                lambda: len({c.topo.epoch for c in survivors}) == 1,
                timeout=30,
                msg="epoch convergence",
            )
            await wait_for(
                lambda: all(
                    all(p in c._writers for p in c.topo.neighbors())
                    for c in survivors
                ),
                timeout=30,
                msg="post-election links",
            )
            _rp, wp, _ = await mesh.harnesses[2].connect("pub2", version=4)
            wp.write(pub_packet("e/t", b"post-heal", qos=0, version=4))
            await wp.drain()
            seen = await read_until_payload(r4, b"post-heal")
            assert seen == [b"post-heal"]
            await mesh.stop(skip=(1,))

        run(scenario())

    def test_flapped_link_heals_without_duplicates(self, tmp_path):
        """A hard-severed live edge (connection reset) re-dials and
        heals; traffic published after the heal arrives exactly once."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            r2, _w2 = await mesh.subscribe(2, "sub2", "f/#")
            await mesh.settle_summaries()
            origin = mesh.clusters[0]
            assert sever_peer_link(origin, 2)
            await wait_for(
                lambda: 2 in origin._writers
                and origin._health_for(2).state == PEER_UP,
                msg="re-dial heal",
            )
            _rp, wp, _ = await mesh.harnesses[0].connect("pub0", version=4)
            wp.write(pub_packet("f/t", b"after-flap", qos=1, pid=7, version=4))
            await wp.drain()
            seen = await read_until_payload(r2, b"after-flap")
            assert seen == [b"after-flap"]
            await mesh.stop()

        run(scenario())

    def test_restarted_incarnation_forces_new_epoch(self, tmp_path):
        """A peer HELLO with a MOVED boot nonce (restarted incarnation)
        must advance the epoch — its dead tree can never be resurrected
        by stale announcements."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            c0 = mesh.clusters[0]
            ep0 = c0.topo.epoch_num()
            boot1 = c0.topo.members()[1]
            assert boot1  # learned from the live HELLO/SYNC
            c0._member_contact(1, boot1 + 1)  # same id, new incarnation
            assert c0.topo.epoch_num() > ep0
            assert c0.topo.members()[1] == boot1 + 1
            await mesh.stop()

        run(scenario())


# -- per-signal pressure gossip (ISSUE 9 satellite) ---------------------------


class TestPerSignalGossip:
    def test_signal_breakdown_folds_and_decays(self):
        clock = [100.0]
        sig = PeerPressureSignal(
            weight=0.9, ttl_s=10.0, clock=lambda: clock[0]
        )
        sig.observe(1, 0, 0.4, signals={"staging": 0.4, "rss": 0.1})
        sig.observe(2, 0, 0.8, signals={"staging": 0.2, "backlog": 0.8})
        assert sig.signal_names() == {"staging", "rss", "backlog"}
        assert sig.signal_value("staging") == pytest.approx(0.4)
        vals = sig.signal_values()
        assert vals["backlog"] == pytest.approx(0.8)
        clock[0] += 5.0  # half the TTL: linear decay to half weight
        assert sig.signal_value("staging") == pytest.approx(0.2)
        clock[0] += 6.0  # past the TTL: stale adverts contribute zero
        assert sig.signal_values() == {}
        sig.observe(3, 0, 0.5, signals={"staging": 0.5})
        sig.forget(3)
        assert sig.signal_value("staging") == 0.0

    def test_gossip_carries_breakdown_to_peer_gauges(self, tmp_path):
        """_on_gossip feeds the advert's per-signal map into the
        governor's PeerPressureSignal and registers one labeled gauge
        per signal name — the operator's WHY view."""
        from tests.test_federation import _bare_cluster

        c, gov = _bare_cluster(tmp_path)
        payload = json.dumps(
            {"s": 1, "p": 0.7, "sig": {"staging": 0.7, "rss": 0.3}}
        ).encode()
        c._on_gossip(2, payload)
        sig = gov.peer_signal
        assert sig.signal_value("staging") == pytest.approx(0.7)
        assert c._peer_advert_sigs[2] == {"staging": 0.7, "rss": 0.3}
        # the governor's $SYS gauge map exposes the breakdown
        assert gov.gauges()["peers_signal/staging"] == pytest.approx(0.7)

    def test_malformed_breakdown_is_ignored(self, tmp_path):
        from tests.test_federation import _bare_cluster

        c, _gov = _bare_cluster(tmp_path)
        c._on_gossip(2, json.dumps({"s": 0, "p": 0.1, "sig": "junk"}).encode())
        assert 2 not in c._peer_advert_sigs  # scalar advert still applied
        assert c._peer_adverts[2][1] == pytest.approx(0.1)

    def test_tree_advert_folds_subtree_excluding_target_edge(self, tmp_path):
        """The advert sent on edge E is the elementwise max of the local
        posture and every OTHER edge's advert — E's own contribution is
        excluded (re-advertising a peer's pressure back to it would
        echo), and stale adverts age out of the fold."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            c0 = mesh.clusters[0]  # root, edges to 1 and 2
            c0._peer_adverts[1] = (1, 0.9, time.monotonic())
            c0._peer_advert_sigs[1] = {"staging": 0.9}
            c0._peer_adverts[2] = (0, 0.2, time.monotonic())
            c0._peer_advert_sigs[2] = {"rss": 0.2}
            toward_2 = json.loads(c0._advert_payload(exclude=2))
            assert toward_2["s"] == 1  # worker 1's THROTTLE folds through
            assert toward_2["p"] == pytest.approx(0.9)
            assert toward_2["sig"]["staging"] == pytest.approx(0.9)
            assert "rss" not in toward_2["sig"]  # 2's own echo excluded
            toward_1 = json.loads(c0._advert_payload(exclude=1))
            assert toward_1["sig"].get("rss", 0.0) == pytest.approx(0.2)
            assert "staging" not in toward_1["sig"]
            # a stale advert ages out of the fold entirely
            c0._peer_adverts[1] = (
                1, 0.9, time.monotonic() - c0.advert_ttl_s - 1
            )
            toward_2b = json.loads(c0._advert_payload(exclude=2))
            assert toward_2b["p"] < 0.9
            await mesh.stop()

        run(scenario())

    def test_sys_topics_carry_tree_gauges(self, tmp_path):
        """$SYS publishes the tree epoch/links/duplicate counters (the
        drill scrapes these from the outside)."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            srv = mesh.harnesses[0].server
            srv.publish_sys_topics()
            ret = srv.topics.retained
            pfx = "$SYS/broker/cluster/tree/"
            for suffix in (
                "epoch", "neighbors", "links", "re_elections",
                "duplicates_suppressed", "stale_epoch_frames",
                "summary_filtered", "summary_passthrough",
            ):
                assert ret.get(pfx + suffix) is not None, suffix
            assert ret.get("$SYS/broker/cluster/control_bytes") is not None
            await mesh.stop()

        run(scenario())


# -- ISSUE 17: root-failure fast path ----------------------------------------


class TestRootFailover:
    def test_successor_promotes_without_full_re_election(self, tmp_path):
        """Killing the ROOT takes the fast path: the pre-agreed
        successor (second-lowest live id, announced with every epoch)
        promotes at its own SUSPECT transition and floods the new epoch
        — no PARTITIONED wait, no scoped-re-election blackout. With
        partition_pings cranked out of reach, the fast path is the ONLY
        way the mesh can converge, so convergence proves it fired."""

        async def scenario():
            mesh = TreeMesh(5, tmp_path, partition_pings=600)
            await mesh.start()
            c1 = mesh.clusters[1]
            assert mesh.clusters[0].topo.root() == 0
            assert c1.topo.successor() == 1  # the pre-agreed successor
            r4, _w4 = await mesh.subscribe(4, "sub4", "ft/#")
            await mesh.settle_summaries()

            await mesh.clusters[0].stop()  # SIGKILL-shaped: root gone
            survivors = mesh.clusters[1:]
            await wait_for(
                lambda: c1.root_failovers == 1,
                timeout=30,
                msg="successor promotion",
            )
            # the promotion window (propose -> epoch flooded) is bounded
            # well inside the acceptance budget of 2 ping intervals
            assert 0.0 < c1.root_failover_last_s < 2 * c1.PING_INTERVAL_S
            await wait_for(
                lambda: all(
                    c.topo.root() == 1 and 0 not in c.topo.members()
                    for c in survivors
                )
                and len({c.topo.epoch for c in survivors}) == 1,
                timeout=30,
                msg="one epoch under the promoted root",
            )
            # the NEXT successor is re-agreed from the shrunken view
            assert c1.topo.successor() == 2
            await wait_for(
                lambda: all(
                    all(p in c._writers for p in c.topo.neighbors())
                    for c in survivors
                ),
                timeout=30,
                msg="post-failover links",
            )
            # routing works under the promoted root's tree
            _rp, wp, _ = await mesh.harnesses[2].connect("pub2", version=4)
            wp.write(pub_packet("ft/x", b"post-failover", qos=0, version=4))
            await wp.drain()
            seen = await read_until_payload(r4, b"post-failover")
            assert seen == [b"post-failover"]
            await mesh.stop(skip=(0,))

        run(scenario())

    def test_non_successor_never_takes_the_fast_path(self, tmp_path):
        """Only the agreed successor may promote: any other worker
        observing the root SUSPECT must wait for the ordinary
        re-election machinery (never two competing fast promotions)."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path, partition_pings=600)
            await mesh.start()
            c2 = mesh.clusters[2]
            before = c2.topo.epoch
            c2._maybe_promote_root(0)  # root suspect, but 2 != successor
            assert c2.root_failovers == 0
            assert c2.topo.epoch == before
            # and the successor ignores a non-root suspect the same way
            c1 = mesh.clusters[1]
            c1._maybe_promote_root(2)
            assert c1.root_failovers == 0
            await mesh.stop()

        run(scenario())

    def test_epoch_announcement_carries_the_successor(self, tmp_path):
        """The non-digest epoch announcement advertises the pre-agreed
        successor — observability for operators and the drill scrape;
        receivers recompute it from the member view."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            c0 = mesh.clusters[0]
            sent = []
            orig = c0._send_nowait
            c0._send_nowait = (
                lambda p, w, t, b: sent.append((p, t, b)) or orig(p, w, t, b)
            )
            try:
                c0._announce_epoch()
                from mqtt_tpu.cluster import _T_EPOCH

                bodies = [
                    json.loads(b.decode())
                    for _p, t, b in sent
                    if t == _T_EPOCH
                ]
                assert bodies and all(b.get("sc") == 1 for b in bodies)
            finally:
                c0._send_nowait = orig
            await mesh.stop()

        run(scenario())


# -- ISSUE 17: predicate push-down over edge summaries ------------------------


class TestPredicatePushdown:
    def test_edge_filters_failing_payloads_and_passes_matching(self, tmp_path):
        """A remote ``pp/#$GT{v:50}`` subscriber interns its predicate
        digest into the edge summaries: a publish whose payload PROVABLY
        fails the predicate is filtered at the ORIGIN edge (counted),
        while a passing payload still forwards and delivers — false
        negatives impossible, same contract as the blooms."""

        async def scenario():
            mesh = TreeMesh(5, tmp_path)
            await mesh.start()
            r4, _w4 = await mesh.subscribe(4, "pred4", "pp/#$GT{v:50}")
            await mesh.settle_summaries()
            origin = mesh.clusters[2]
            before = origin.summary_predicate_filtered_forwards
            _rp, wp, _ = await mesh.harnesses[2].connect("pub2", version=4)

            # digest folds propagate transitively (4 -> 1 -> 0 -> 2), one
            # presence round per hop: keep publishing provably-failing
            # payloads until the origin's edge gate starts cutting them.
            # every one of these either dies at the origin (counted) or
            # is predicate-gated at worker 4 — NEVER delivered.
            async def _edge_filtering():
                wp.write(
                    pub_packet("pp/x", b'{"v": 10}', qos=0, version=4)
                )
                await wp.drain()
                return origin.summary_predicate_filtered_forwards > before

            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if await _edge_filtering():
                    break
                await asyncio.sleep(0.05)
            assert origin.summary_predicate_filtered_forwards > before

            wp.write(pub_packet("pp/x", b'{"v": 90}', qos=0, version=4))
            await wp.drain()
            seen = await read_until_payload(r4, b'{"v": 90}')
            assert seen == [b'{"v": 90}']  # zero failing payloads leaked
            await mesh.stop()

        run(scenario())

    def test_unknown_digest_is_conservative_passthrough(self, tmp_path):
        """An edge whose summary advertises predicate interest but whose
        digest plane is unknown (old sender / cap overflow) must forward
        everything — stale knowledge can only cost bytes, never a
        delivery."""

        async def scenario():
            mesh = TreeMesh(3, tmp_path)
            await mesh.start()
            r2, _w2 = await mesh.subscribe(2, "pd2", "pq/#$GT{v:50}")
            await mesh.settle_summaries()
            origin = mesh.clusters[1]
            # poison the digest plane on every edge: unknown, not empty
            for es in origin._edge_summaries.values():
                es.digests = None
            _rp, wp, _ = await mesh.harnesses[1].connect("pub1", version=4)
            wp.write(pub_packet("pq/x", b'{"v": 90}', qos=0, version=4))
            await wp.drain()
            seen = await read_until_payload(r2, b'{"v": 90}')
            assert seen == [b'{"v": 90}']
            await mesh.stop()

        run(scenario())


# -- ISSUE 17: shaped links + rapid-flap exactly-once -------------------------


class TestShapedLinks:
    def test_rapid_flap_replays_each_parked_frame_once(self, tmp_path):
        """A peer flapping UP -> SUSPECT -> UP repeatedly within one
        park window replays each parked frame AT MOST ONCE across all
        heals: frames parked before the first heal must not ride the
        second heal's replay. Run over seeded shaped links (delay +
        jitter) so the WAN-ish reordering pressure is part of the
        regression, reproducibly."""

        async def scenario():
            from mqtt_tpu.faults import LinkShape, shape_cluster_links

            mesh = TreeMesh(3, tmp_path, partition_pings=600)
            await mesh.start()
            shape = LinkShape(seed=7, delay_s=0.004, jitter_s=0.002)
            releases = [
                shape_cluster_links(c, shape) for c in mesh.clusters
            ]
            r2, _w2 = await mesh.subscribe(2, "sub2", "rf/#")
            await mesh.settle_summaries()
            origin = mesh.clusters[0]
            # the shaper delays the post-subscribe summary push: wait for
            # the INTEREST (not just a fresh epoch stamp) before cutting
            # the link, or the partition swallows it and nothing parks
            await wait_for(
                lambda: 2 in origin._edge_summaries
                and origin._edge_summaries[2].bits.might_match("rf/t"),
                msg="interest propagated",
            )
            replayed0 = origin.replayed_forwards
            _rp, wp, _ = await mesh.harnesses[0].connect("pub0", version=4)

            # flap 1: park 5 under SUSPECT, heal, each replays once
            cut = asymmetric_partition(origin, 2)
            await wait_for(
                lambda: origin._health_for(2).state == PEER_SUSPECT,
                msg="suspect #1",
            )
            for i in range(5):
                wp.write(
                    pub_packet("rf/t", f"m{i}".encode(), qos=1, pid=20 + i,
                               version=4)
                )
            await wp.drain()
            await wait_for(
                lambda: len(origin._health_for(2).park) == 5, msg="park #1"
            )
            cut()
            await wait_for(
                lambda: origin._health_for(2).state == PEER_UP,
                msg="heal #1",
            )
            seen1 = await read_until_payload(r2, b"m4")
            assert seen1 == [b"m0", b"m1", b"m2", b"m3", b"m4"]

            # flap 2, same park window: ONLY the newly parked frames
            # may replay — m0..m4 are spent
            cut = asymmetric_partition(origin, 2)
            await wait_for(
                lambda: origin._health_for(2).state == PEER_SUSPECT,
                msg="suspect #2",
            )
            for i in range(5, 8):
                wp.write(
                    pub_packet("rf/t", f"m{i}".encode(), qos=1, pid=20 + i,
                               version=4)
                )
            await wp.drain()
            await wait_for(
                lambda: len(origin._health_for(2).park) == 3, msg="park #2"
            )
            cut()
            await wait_for(
                lambda: origin._health_for(2).state == PEER_UP,
                msg="heal #2",
            )
            seen2 = await read_until_payload(r2, b"m7")
            assert seen2 == [b"m5", b"m6", b"m7"]  # no m0..m4 re-replay
            assert origin.replayed_forwards == replayed0 + 8
            assert not origin._health_for(2).park
            for rel in releases:
                rel()
            await mesh.stop()

        run(scenario())

    def test_link_shape_is_deterministic_per_seed(self):
        """Two shapers built from the same LinkShape drop/delay the same
        frames — the WAN schedule is part of the test's identity."""
        import random

        from mqtt_tpu.faults import LinkShape

        shape = LinkShape(seed=11, loss=0.3)
        rng_a = random.Random((shape.seed << 24) ^ (0 << 12) ^ 2)
        rng_b = random.Random((shape.seed << 24) ^ (0 << 12) ^ 2)
        assert [rng_a.random() for _ in range(64)] == [
            rng_b.random() for _ in range(64)
        ]
        # distinct edges draw from distinct streams
        rng_c = random.Random((shape.seed << 24) ^ (1 << 12) ^ 2)
        assert [rng_a.random() for _ in range(8)] != [
            rng_c.random() for _ in range(8)
        ]


# -- ISSUE 17: TCP / TLS peer transport ---------------------------------------


def _free_ports(n):
    import socket as _socket

    socks = [_socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


class TestTcpTransport:
    def test_tcp_mesh_routes_cross_worker(self, tmp_path):
        """The same mesh over TCP peer links (pinned per-worker
        addresses, keepalive armed): multi-hop publish/subscribe
        delivers exactly once — bit-identical semantics to unix."""

        async def scenario():
            ports = _free_ports(3)
            addrs = {i: f"127.0.0.1:{p}" for i, p in enumerate(ports)}
            mesh = TreeMesh(
                3, tmp_path,
                cluster_transport="tcp",
                cluster_peer_addrs=addrs,
                cluster_keepalive_s=30.0,
                cluster_connect_timeout_s=2.0,
            )
            await mesh.start()
            for c in mesh.clusters:
                assert c.transport == "tcp"
            r2, _w2 = await mesh.subscribe(2, "sub2", "tcp/#")
            await mesh.settle_summaries()
            _rp, wp, _ = await mesh.harnesses[1].connect("pub1", version=4)
            wp.write(pub_packet("tcp/t", b"over-tcp", qos=1, pid=5, version=4))
            await wp.drain()
            seen = await read_until_payload(r2, b"over-tcp")
            assert seen == [b"over-tcp"]
            await mesh.stop()

        run(scenario())

    @pytest.mark.skipif(
        __import__("shutil").which("openssl") is None,
        reason="openssl binary unavailable: cannot mint a test cert",
    )
    def test_tls_mesh_routes_cross_worker(self, tmp_path):
        """TLS peer links with CA verification BOTH directions: a
        self-signed cert doubles as the CA, every worker presents it,
        and routed delivery still works — the handshake is in the path,
        not mocked."""
        import subprocess

        cert = tmp_path / "mesh-cert.pem"
        key = tmp_path / "mesh-key.pem"
        # no -addext: -x509 already stamps basicConstraints=CA:TRUE, and
        # a DUPLICATE extension makes OpenSSL reject the chain
        subprocess.run(
            [
                "openssl", "req", "-x509", "-newkey", "rsa:2048",
                "-nodes", "-keyout", str(key), "-out", str(cert),
                "-days", "1", "-subj", "/CN=mqtt-tpu-mesh",
            ],
            check=True, capture_output=True,
        )

        async def scenario():
            ports = _free_ports(3)
            addrs = {i: f"127.0.0.1:{p}" for i, p in enumerate(ports)}
            mesh = TreeMesh(
                3, tmp_path,
                cluster_transport="tcp",
                cluster_peer_addrs=addrs,
                cluster_tls_cert=str(cert),
                cluster_tls_key=str(key),
                cluster_tls_ca=str(cert),
            )
            await mesh.start()
            r2, _w2 = await mesh.subscribe(2, "sub2", "tls/#")
            await mesh.settle_summaries()
            _rp, wp, _ = await mesh.harnesses[1].connect("pub1", version=4)
            wp.write(pub_packet("tls/t", b"over-tls", qos=0, version=4))
            await wp.drain()
            seen = await read_until_payload(r2, b"over-tls")
            assert seen == [b"over-tls"]
            await mesh.stop()

        run(scenario())

    def test_transport_env_round_trip(self, tmp_path):
        from mqtt_tpu.cluster import worker_env

        env = worker_env(
            2, 4, str(tmp_path), topology="tree", degree=2,
            transport="tcp", base_port=39000,
        )
        assert env["MQTT_TPU_CLUSTER_TRANSPORT"] == "tcp"
        assert env["MQTT_TPU_CLUSTER_BASE_PORT"] == "39000"
        # unix mode (the default) sets neither
        env_u = worker_env(0, 2, str(tmp_path))
        assert "MQTT_TPU_CLUSTER_TRANSPORT" not in env_u
        assert "MQTT_TPU_CLUSTER_BASE_PORT" not in env_u
