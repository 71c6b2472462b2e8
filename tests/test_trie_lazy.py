"""A trie particle allocates only what it holds (mqtt_tpu.topics): no
subscription, shared or inline map and no lock until the first entry of
the kind arrives at the particle, none left behind when the last leaves.
The answers are those of the eager layout, which every particle entered
with all its maps made: ``EagerIndex`` below is that layout, kept as the
oracle. Counts and equalities, never a rate."""

import gc
import random
from types import SimpleNamespace

import pytest

from mqtt_tpu.packets import PUBLISH, FixedHeader, Packet, Subscription
from mqtt_tpu.staging import bulk_register
from mqtt_tpu.topics import (
    SHARE_PREFIX,
    InlineSubscription,
    Subscribers,
    TopicsIndex,
)

from tests.test_batch_completion import load_benchmark_module

# -- the eager layout, as the oracle ------------------------------------------


class EagerParticle:
    """A particle born with every map, as before this layout."""

    def __init__(self, key, parent):
        self.key, self.parent = key, parent
        self.particles = {}
        self.subscriptions = {}
        self.shared = {}  # group -> client -> sub
        self.inline = {}
        self.retain_path = ""


class EagerIndex:
    """The parent commit's trie, maps pre-made, dicts for containers: the
    walk, the gathers, the quirk at ``a/#`` against ``a`` and the trim
    are its, line for line."""

    def __init__(self):
        self.root = EagerParticle("", None)
        self.retained = {}

    def _set(self, topic, d):
        parts = topic.split("/")
        n = self.root
        for key in parts[d:] if d < len(parts) else [parts[-1]]:
            p = n.particles.get(key)
            if p is None:
                p = n.particles[key] = EagerParticle(key, n)
            n = p
        return n

    def _seek(self, flt, d):
        parts = flt.split("/")
        n = self.root
        for key in parts[d:] if d < len(parts) else [parts[-1]]:
            n = n.particles.get(key)
            if n is None:
                return None
        return n

    def _trim(self, n):
        while (
            n.parent is not None and n.retain_path == ""
            and not (n.particles or n.subscriptions or n.shared or n.inline)
        ):
            key, n = n.key, n.parent
            n.particles.pop(key, None)

    def subscribe(self, client, sub):
        parts = sub.filter.split("/")
        if parts[0].upper() == SHARE_PREFIX:
            group = parts[1] if len(parts) > 1 else parts[-1]
            held = self._set(sub.filter, 2).shared.setdefault(group, {})
        else:
            held = self._set(sub.filter, 0).subscriptions
        new = client not in held
        held[client] = sub
        return new

    def unsubscribe(self, flt, client):
        parts = flt.split("/")
        shared = parts[0].upper() == SHARE_PREFIX
        n = self._seek(flt, 2 if shared else 0)
        if n is None:
            return False
        if shared:
            group = parts[1] if len(parts) > 1 else parts[-1]
            n.shared.get(group, {}).pop(client, None)
            if not n.shared.get(group, True):
                del n.shared[group]
        else:
            n.subscriptions.pop(client, None)
        self._trim(n)
        return True

    def inline_subscribe(self, sub):
        held = self._set(sub.filter, 0).inline
        new = sub.identifier not in held
        held[sub.identifier] = sub
        return new

    def inline_unsubscribe(self, id_, flt):
        n = self._seek(flt, 0)
        if n is None:
            return False
        n.inline.pop(id_, None)
        if not n.inline:
            self._trim(n)
        return True

    def retain_message(self, pk):
        n = self._set(pk.topic_name, 0)
        if pk.payload:
            n.retain_path = pk.topic_name
            self.retained[pk.topic_name] = pk
            return 1
        out = -1 if pk.topic_name in self.retained else 0
        n.retain_path = ""
        self.retained.pop(pk.topic_name, None)
        self._trim(n)
        return out

    def subscribers(self, topic):
        subs = Subscribers()
        if not topic:
            return subs
        parts = topic.split("/")
        last = len(parts) - 1

        def gather(p, inline_of=None):
            for client, sub in p.subscriptions.items():
                if sub.filter and topic[0] == "$" and sub.filter[0] in "+#":
                    continue
                cls = subs.subscriptions.get(client, sub)
                subs.subscriptions[client] = cls.merge(sub)
            for shares in p.shared.values():
                for client, sub in shares.items():
                    subs.shared.setdefault(sub.filter, {})[client] = sub
            subs.inline_subscriptions.update((inline_of or p).inline)

        stack = [(self.root, 0)]
        while stack:
            n, d = stack.pop()
            key = parts[d] if d < len(parts) else parts[-1]
            for part_key in (key, "+"):
                p = n.particles.get(part_key)
                if p is None:
                    continue
                if d < last:
                    stack.append((p, d + 1))
                    continue
                gather(p)
                wild = p.particles.get("#")
                if wild is not None and part_key != "+":
                    gather(wild, inline_of=p)  # topics.go:615's quirk
            p = n.particles.get("#")
            if p is not None:
                gather(p)
        return subs

    def paths(self):
        out, stack = set(), [(self.root, ())]
        while stack:
            p, path = stack.pop()
            out.add(path)
            stack += [(c, path + (k,)) for k, c in p.particles.items()]
        return out


# -- seeded mutation streams ---------------------------------------------------

LEVELS = ("a", "b", "c", "+", "$SYS", "")


def some_filter(rng):
    parts = [rng.choice(LEVELS) for _ in range(rng.randint(1, 4))]
    if rng.random() < 0.3:
        parts.append("#")
    flt = "/".join(parts) or "a"
    if rng.random() < 0.2:
        flt = f"{SHARE_PREFIX}/g{rng.randrange(2)}/{flt}"
    return flt


def some_topic(rng):
    return "/".join(
        rng.choice(("a", "b", "c", "$SYS", "")) for _ in range(rng.randint(1, 5))
    ) or "a"


def retained(topic, payload):
    return Packet(
        fixed_header=FixedHeader(type=PUBLISH, retain=True),
        topic_name=topic, payload=payload,
    )


def step(rng, lazy, eager, live):
    """One random mutation applied to both tries; ``live`` keeps what
    was added so that removals mostly hit. Returns both answers."""
    op = rng.random()
    if op < 0.35:
        client, flt = f"c{rng.randrange(6)}", some_filter(rng)
        sub = Subscription(filter=flt, qos=rng.randrange(3))
        live["sub"].append((flt, client))
        return lazy.subscribe(client, sub), eager.subscribe(client, sub)
    if op < 0.60:
        flt, client = (
            rng.choice(live["sub"]) if live["sub"] and rng.random() < 0.8
            else (some_filter(rng), "c0")
        )
        return lazy.unsubscribe(flt, client), eager.unsubscribe(flt, client)
    if op < 0.72:
        sub = InlineSubscription(
            filter=some_filter(rng).replace(SHARE_PREFIX + "/", "x/"),
            identifier=rng.randrange(4), handler=None,
        )
        live["inline"].append((sub.identifier, sub.filter))
        return lazy.inline_subscribe(sub), eager.inline_subscribe(sub)
    if op < 0.84:
        id_, flt = (
            rng.choice(live["inline"]) if live["inline"] and rng.random() < 0.8
            else (0, some_filter(rng))
        )
        return lazy.inline_unsubscribe(id_, flt), eager.inline_unsubscribe(id_, flt)
    pk = retained(some_topic(rng), b"x" if rng.random() < 0.6 else b"")
    return lazy.retain_message(pk), eager.retain_message(pk)


def lazy_paths_and_maps(idx):
    """Every live path, and the containers alive across the particles;
    asserts on the way that a map that is there holds something."""
    paths, maps, held, stack = set(), 0, 0, [(idx.root, ())]
    while stack:
        p, path = stack.pop()
        paths.add(path)
        maps += 1  # the children dict
        for m in (p.subscriptions, p.shared, p.inline_subscriptions):
            if m is not None:
                assert len(m) > 0, f"an empty map left behind at {path}"
                maps += 1
                held += len(m)
        stack += [(c, path + (k,)) for k, c in p.particles.items()]
    return paths, maps, held


def answer(subs):
    return (
        {c: (s.filter, s.qos) for c, s in subs.subscriptions.items()},
        {f: sorted(by) for f, by in subs.shared.items()},
        sorted(subs.inline_subscriptions),
    )


class TestSameAnswersAsTheEagerLayout:
    @pytest.mark.parametrize("seed", [31, 2003, 77777, 2**31 + 7, 123456789, 5])
    def test_random_mutation_streams(self, seed):
        """Subscribe, shared, inline, retained, unsubscribe and trim in a
        seeded stream: each call returns what the eager layout returns,
        the same particles are alive after each, ``subscribers()`` agrees
        on every probe topic, observers see one Mutation a subscription
        call, and the three counts equal a walk of the trie."""
        rng = random.Random(seed)
        lazy, eager = TopicsIndex(), EagerIndex()
        seen = []
        lazy.add_observer(seen.append)
        live = {"sub": [], "inline": []}
        probes = [some_topic(rng) for _ in range(40)]
        for n in range(1, 601):
            before = len(seen)
            got, want = step(rng, lazy, eager, live)
            assert got == want
            assert len(seen) - before <= 1
            paths, maps, held = lazy_paths_and_maps(lazy)
            assert paths == eager.paths()
            assert (lazy.particles, lazy.particle_maps, lazy.held) == (
                len(paths), maps, held,
            )
            if n % 20 == 0:
                for topic in probes:
                    assert answer(lazy.subscribers(topic)) == answer(
                        eager.subscribers(topic)
                    ), topic
                assert sorted(lazy.retained.internal) == sorted(eager.retained)

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_back_to_empty(self, seed):
        """Everything added is taken away again: the root is alone, with
        its children dict and nothing else, and holds nothing."""
        rng = random.Random(seed)
        idx = TopicsIndex()
        subs = [(f"c{i % 5}", some_filter(rng)) for i in range(200)]
        inl = [(i % 7, some_filter(rng).replace(SHARE_PREFIX + "/", "x/")) for i in range(60)]
        topics = [some_topic(rng) for _ in range(60)]
        for client, flt in subs:
            idx.subscribe(client, Subscription(filter=flt, qos=1))
        for id_, flt in inl:
            idx.inline_subscribe(InlineSubscription(filter=flt, identifier=id_))
        for t in topics:
            idx.retain_message(retained(t, b"x"))
        assert idx.held > 0 and idx.particles > 1
        for work in (subs, inl, topics):
            rng.shuffle(work)
        for client, flt in subs:
            idx.unsubscribe(flt, client)
        for id_, flt in inl:
            idx.inline_unsubscribe(id_, flt)
        for t in topics:
            idx.retain_message(retained(t, b""))
        assert idx.root.particles == {}
        assert (idx.particles, idx.particle_maps, idx.held) == (1, 1, 0)

    def test_messages_walk_particles_without_maps(self):
        idx = TopicsIndex()
        for t in ("a/b/c", "a/b/d", "a/x", "$SYS/up"):
            idx.retain_message(retained(t, b"v"))
        assert sorted(p.topic_name for p in idx.messages("a/#")) == [
            "a/b/c", "a/b/d", "a/x",
        ]
        assert [p.topic_name for p in idx.messages("a/+/c")] == ["a/b/c"]
        assert idx.messages("#") and all(
            p.topic_name[0] != "$" for p in idx.messages("#")
        )
        assert idx.held == 0 and idx.particle_maps == idx.particles


# -- what a particle costs -------------------------------------------------------


def deep_hash_fleet(n=20_000):
    """``deep-hash-1m``'s shape at a fiftieth: 20 x 30^7 paths for 20,000
    rows is 1,000 x 30^7 for a million, so levels 1-3 are shared as
    there and levels 4-8 of nearly every path are its own."""
    deephash = load_benchmark_module("deployments/deephash")
    params = {
        "subscriptions": n, "levels": 8, "top_vocabulary": 20,
        "level_vocabulary": 30, "hash_share": 0.05, "live_hash": 8,
        "live_exact": 2, "topics_per_publisher": 64, "subscribed_share": 0.25,
    }
    return deephash.plan(params, 31, 1)["subscriptions"]


class TestWhatAParticleCosts:
    def test_objects_a_subscription_at_deep_hashs_shape(self):
        """At most 20 collector-tracked objects a loaded subscription
        (46 in the eager layout), loaded by the restore's route."""
        fleet = deep_hash_fleet()
        idx = TopicsIndex()
        gc.collect()
        gc.disable()
        try:
            before = len(gc.get_objects())
            bulk_register(
                idx, ((c, Subscription(filter=f, qos=q)) for c, f, q in fleet)
            )
            tracked = len(gc.get_objects()) - before
        finally:
            gc.enable()
        assert idx.held == len(fleet)
        assert 5.0 < idx.particles / idx.held < 6.0  # deep-hash's 5.6
        assert tracked / len(fleet) <= 20, tracked / len(fleet)
        assert idx.particle_maps / idx.particles < 1.25

    def test_a_particle_that_holds_nothing_has_no_map_and_no_lock(self):
        idx = TopicsIndex()
        idx.subscribe("c", Subscription(filter="a/b/c/d", qos=0))
        p = idx.root
        for key in ("a", "b", "c"):
            p = p.particles[key]
            assert (p.subscriptions, p.shared, p.inline_subscriptions) == (
                None, None, None,
            )
            assert not any("lock" in name for name in type(p).__slots__)
        leaf = p.particles["d"]
        assert leaf.subscriptions.get("c").filter == "a/b/c/d"
        assert leaf.shared is None and leaf.inline_subscriptions is None
        assert (idx.particles, idx.particle_maps, idx.held) == (5, 6, 1)

    @pytest.mark.parametrize("kind", ["sub", "shared", "inline"])
    def test_the_last_entry_takes_its_map_with_it(self, kind):
        idx = TopicsIndex()
        idx.subscribe("keep", Subscription(filter="a/b/c", qos=0))  # anchors a/b
        flt = "a/b" if kind != "shared" else f"{SHARE_PREFIX}/g/a/b"
        if kind == "inline":
            idx.inline_subscribe(InlineSubscription(filter=flt, identifier=7))
        else:
            idx.subscribe("c1", Subscription(filter=flt, qos=1))
            idx.subscribe("c2", Subscription(filter=flt, qos=1))
        node = idx.root.particles["a"].particles["b"]
        attr = {"sub": "subscriptions", "shared": "shared",
                "inline": "inline_subscriptions"}[kind]
        assert len(getattr(node, attr)) == (1 if kind == "inline" else 2)
        maps = idx.particle_maps
        if kind == "inline":
            assert idx.inline_unsubscribe(7, flt)
        else:
            assert idx.unsubscribe(flt, "c1")
            assert getattr(node, attr) is not None  # c2 is still there
            assert idx.unsubscribe(flt, "c2")
            assert idx.unsubscribe(flt, "c2")  # gone already: no count moves
        assert getattr(node, attr) is None
        assert idx.particle_maps == maps - 1 and idx.held == 1
        assert idx.root.particles["a"].particles["b"] is node  # anchored


# -- the walks outside topics.py read a particle that has no maps ------------------


def mixed_index():
    idx = TopicsIndex()
    idx.subscribe("c1", Subscription(filter="a/b/c/d", qos=1))
    idx.subscribe("c2", Subscription(filter="a/b/#", qos=0))
    idx.subscribe("c3", Subscription(filter=f"{SHARE_PREFIX}/g/a/x", qos=1))
    idx.inline_subscribe(InlineSubscription(filter="a/i", identifier=3))
    idx.retain_message(retained("r/only/retained", b"v"))
    return idx


class TestWalksOverParticlesWithoutMaps:
    def test_walk_terminals_and_the_flat_build(self):
        from mqtt_tpu.ops.flat import _node_snap, _walk_terminals, build_flat_index

        idx = mixed_index()
        found = {"/".join(path): _node_snap(p) for path, p in _walk_terminals(idx)}
        assert sorted(found) == ["a/b/#", "a/b/c/d", "a/i", "a/x"]
        cli, shr, inl = found["a/x"]
        assert (cli, inl) == ((), ()) and shr[0][0] == "c3"
        assert _node_snap(idx.root.particles["a"]) == ((), (), ())
        flat = build_flat_index(idx)
        assert flat.n_entries == 4

    def test_clusters_probes(self):
        from mqtt_tpu.cluster import Cluster

        me = SimpleNamespace(server=SimpleNamespace(topics=mixed_index()))
        assert sorted(Cluster._populated_filters(me)) == [
            f"{SHARE_PREFIX}/g/a/x", "a/b/#", "a/b/c/d", "a/i",
        ]
        probe = lambda f: Cluster._probe_populated(me, f)  # noqa: E731
        assert probe("a/b/c/d") == (True, False)
        assert probe("a/i") == (True, True)
        assert probe(f"{SHARE_PREFIX}/g/a/x") == (True, False)
        assert probe("a/b") == (False, False)  # interior: no map at all
        assert probe("r/only/retained") == (False, False)
        assert probe("no/such") == (False, False)
        assert Cluster._probe_interest(me, "a/b") == (False, False, frozenset())
        assert Cluster._probe_interest(me, "a/b/#") == (True, True, frozenset())
        assert Cluster._probe_interest(me, "a/i")[:2] == (True, True)

    def test_the_sharded_partition(self):
        from mqtt_tpu.parallel.sharded import ShardedTpuMatcher

        idx = mixed_index()
        me = SimpleNamespace(n_shards=2, topics=idx)
        replicas = ShardedTpuMatcher._partition_live(me)
        assert sum(r.held for r in replicas) == idx.held == 4
        merged = Subscribers()
        for r in replicas:
            got = r.subscribers("a/b/c/d")
            merged.subscriptions.update(got.subscriptions)
        assert sorted(merged.subscriptions) == ["c1", "c2"]


class TestTheCountsAreExported:
    def test_slice_counters_metrics_and_sys(self):
        """The three counts ride the profiler slice's snapshots, /metrics
        and $SYS, and follow the trie (the $SYS tree itself is retained
        in it: its particles count too)."""
        import asyncio

        from tests.test_server import Harness

        async def scenario():
            h = Harness()
            srv = h.server
            assert (srv.topics.particles, srv.topics.particle_maps, srv.topics.held) == (1, 1, 0)
            srv.topics.subscribe("c", Subscription(filter="a/b/c/#", qos=1))
            counts = srv._slice_counters()
            assert (counts["particles"], counts["particle_maps"], counts["held"]) == (5, 6, 1)
            text = srv.telemetry.registry.exposition()
            for line in (
                "mqtt_tpu_topics_particles 5", "mqtt_tpu_topics_particle_maps 6",
                "mqtt_tpu_topics_held 1",
            ):
                assert line in text, line
            srv.publish_sys_topics()
            got = {
                p.topic_name: bytes(p.payload)
                for p in srv.topics.messages("$SYS/broker/topics/#")
            }
            assert got["$SYS/broker/topics/held"] == b"1"
            assert int(got["$SYS/broker/topics/particles"]) >= 5
            srv.topics.unsubscribe("a/b/c/#", "c")
            assert srv._slice_counters()["held"] == 0
            await h.shutdown()

        asyncio.run(asyncio.wait_for(scenario(), timeout=30))
