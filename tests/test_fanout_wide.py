"""Wide entries (``ops/flat.py``: a filter more subscribers hold than the
table's window, laid over consecutive ordinals and answered from the
device) and ``fanout-5-1000``, the broadcast deployment that needs them,
at the sandbox's size on the CPU backend: the device matcher against the
host trie AND the plain reference (``benchmark/reference.py``) at every
width and shape, folds across the window boundary, the served path
through a listener, and the deployment's generator
(``benchmark/deployments/fanout.py``). Answers and counts, never a rate."""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from mqtt_tpu import Options, staging
from mqtt_tpu.ops.delta import DeltaMatcher
from mqtt_tpu.ops.flat import (
    BUCKET_ENTRIES,
    _WIDE_SHIFT,
    _bucket_entries,
    _chunk_snaps,
    build_flat_index,
)
from mqtt_tpu.ops.matcher import TpuMatcher, subscribers_equal
from mqtt_tpu.packets import CONNACK, PUBLISH, SUBACK, Subscription
from mqtt_tpu.topics import InlineSubscription, TopicsIndex

from tests.test_batch_completion import load_benchmark_module
from tests.test_server import (
    Harness,
    connect_packet,
    pub_packet,
    read_wire_packet,
    sub_packet,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_benchmark_module("reference")
fanout = load_benchmark_module("deployments/fanout")

with open(os.path.join(ROOT, "benchmark/configs/fanout-5-1000.json"), encoding="utf-8") as f:
    CONFIG = json.load(f)
REHEARSE = CONFIG["rehearse_params"]
WINDOW = 16


def noop(*_a):
    pass


def materialized(result):
    return result.materialize() if hasattr(result, "materialize") else result


# -- (a) every width, kind and shape against the host trie and the reference -----

# the filter under test, and the topics asked of it: a hit, a miss, and
# the corner its shape has (a ``$``-topic under a top-level wildcard,
# ``filter/#`` at its own depth, mochi's ``+/#`` parent rule)
SHAPES = {
    "exact": ("e/a/b", ["e/a/b", "e/a/c"]),
    "plus": ("p/+/b", ["p/a/b", "p/zz/b", "p/a/c"]),
    "hash": ("h/a/#", ["h/a", "h/a/b", "h/a/b/c/d", "h/b"]),
    "top_plus": ("+/tp", ["d/tp", "$d/tp", "d/x"]),
    "top_hash": ("#", ["any/thing", "$SYS/x", "one"]),
    "plus_hash": ("l/+/#", ["l/a", "l/a/b", "l"]),
}
# where mochi's walk (the program, host and device) and the spec's rule
# (the reference) differ: tests/test_deep_hash.py holds that corner
MOCHI_DIFFERS = {"l/a"}
WIDTHS = [1, 16, 17, 63, 64, 1000, 5000]
KINDS = ["clients", "mixed"]


def hold(index, flt, n, kind, tag):
    """``n`` subscribers on ``flt``: clients only, or half clients, a
    quarter ``$share`` members of three groups, the rest inline. Returns
    the client subscriptions as the reference takes them."""
    n_cli = n if kind == "clients" else n // 2
    n_shr = 0 if kind == "clients" else n // 4
    rows = []
    for i in range(n_cli):
        index.subscribe(f"{tag}-c{i}", Subscription(filter=flt, qos=i % 3))
        rows.append((f"{tag}-c{i}", flt, i % 3))
    for i in range(n_shr):
        index.subscribe(
            f"{tag}-s{i}", Subscription(filter=f"$share/g{i % 3}/{flt}", qos=1)
        )
    for i in range(n - n_cli - n_shr):
        index.inline_subscribe(
            InlineSubscription(filter=flt, identifier=i + 1, handler=noop)
        )
    return rows


def an_index(shape, width, kind):
    """Every shape's filter held by one client, so that P (and with it
    the compiled program) is the same in every case, and the filter
    under test held by ``width`` subscribers more."""
    index, alone = TopicsIndex(), TopicsIndex()
    rows = []
    for name, (flt, _topics) in SHAPES.items():
        rows += hold(index, flt, 1, "clients", "base-" + name)
    flt = SHAPES[shape][0]
    rows += hold(index, flt, width, kind, "w")
    hold(alone, flt, width, kind, "w")
    return index, alone, rows


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_every_width_is_answered_from_the_device(shape, width, kind):
    """The device matcher's sets equal ``TopicsIndex.subscribers()``
    (clients, shared and inline, value for value) and the reference's
    ``FilterSet`` (the clients), on the ranges program and on the
    compacted one, with no topic walked on the host; the topics whose
    answer held the wide entry's hit are counted, and only those."""
    index, alone, rows = an_index(shape, width, kind)
    full = reference.FilterSet(rows)
    topics = [t for _f, ts in SHAPES.values() for t in ts]
    # the filter under test is wide with the base client's one more id
    wide = width + 1 > WINDOW
    expect_wide = 0
    for t in topics:
        s = alone.subscribers(t)
        if wide and (s.subscriptions or s.shared or s.inline_subscriptions):
            expect_wide += 1
    for name, opts in (
        ("ranges", {"compact": False}),
        ("compact", {"compact": True, "compact_capacity": 131072}),
    ):
        m = TpuMatcher(index, window=WINDOW, **opts)
        results = m.match_topics(topics)
        assert m.stats.host_fallbacks == 0 and m.stats.overflows == 0, name
        assert m.stats.wide_topics == expect_wide, name
        assert m.stats.wide_entries == int(wide), name
        if name == "compact":
            # '#' at 5,000 outgrows the pair buffer (the bucket's padding
            # rows match it too): that batch re-runs as ranges, and says so
            over = shape == "top_hash" and width == 5000
            assert (m.stats.compact_batches, m.stats.compact_overflows) == (
                (0, 1) if over else (1, 0)
            )
        for topic, result in zip(topics, results):
            got = materialized(result)
            assert subscribers_equal(got, index.subscribers(topic)), (name, topic)
            if topic not in MOCHI_DIFFERS:
                assert {c: s.qos for c, s in got.subscriptions.items()} == dict(
                    full.matches(topic)
                ), (name, topic)


def test_a_wide_entry_takes_two_slots_and_consecutive_ordinals():
    """The layout, in the words of ``ops/flat.py``: the meta word has the
    wide flag and zero counts, the slot after it holds
    ``[ncli, nreg, 0, ninl]``, the ids are cut into window-sized
    snapshots on consecutive ordinals (clients, then shared, then
    inline), and a narrow entry beside it is built as before."""
    index = TopicsIndex()
    hold(index, "w/+", 50, "mixed", "w")  # 25 clients, 12 shared, 13 inline
    hold(index, "n/+", 16, "clients", "n")
    flat = build_flat_index(index, window=WINDOW)
    assert (flat.n_entries, flat.n_wide, flat.max_width, flat.n_subs) == (2, 1, 50, 66)
    rows = flat.table.reshape(-1, BUCKET_ENTRIES, 4)
    entries = [e for row in rows if row.any() for e in _bucket_entries(row)]
    (wide,) = [e for e in entries if e[4] is not None]
    (narrow,) = [e for e in entries if e[4] is None]
    assert (wide[2] >> _WIDE_SHIFT) & 1 and wide[2] & 0x3FFFF == 0
    assert wide[4] == (25, 37, 13)
    assert narrow[2] & 0x3FFFF == 16 | (16 << 6) and narrow[3] == 0
    first = wide[3] // WINDOW
    assert first == 1 and len(flat.subs) == (1 + 4) * WINDOW
    chunks = flat.subs.snaps[first : first + 4]
    assert [tuple(len(part) for part in c) for c in chunks] == [
        (16, 0, 0), (9, 7, 0), (0, 5, 11), (0, 0, 2),
    ]
    whole = tuple(sum((c[k] for c in chunks), ()) for k in range(3))
    assert chunks == _chunk_snaps(whole, WINDOW)
    kinds = [flat.subs[wide[3] + i].kind for i in range(50)]
    assert kinds == [0] * 25 + [1] * 12 + [2] * 13


def test_a_bucket_that_cannot_hold_its_slots_is_saturated_not_wrong():
    """Three wide entries need six slots of a four-slot bucket: the build
    marks the bucket saturated and its topics take the host route, which
    is what ``overflow`` is left for."""
    # find three '+' filters of one shape that share a bucket
    for salt in range(40):
        index = TopicsIndex()
        names = [f"k{salt}x{i}/+" for i in range(3000)]
        for f in names:
            index.subscribe("solo", Subscription(filter=f))
        flat = build_flat_index(index, window=WINDOW, min_buckets=1024)
        S = flat.table.shape[0]
        rows = flat.table.reshape(S, BUCKET_ENTRIES, 4)
        full = [s for s in range(S) if rows[s, 2].any() and not rows[s, 3].any()]
        if full:
            break
    assert full, "no bucket of exactly three entries in 40 tries"
    keys = {(int(r[0]), int(r[1])) for r in rows[full[0], :3]}
    mine = []
    for f in names:
        probe = build_flat_index(_one(f), window=WINDOW, salt=flat.salt)
        e = [x for row in probe.table.reshape(-1, BUCKET_ENTRIES, 4) if row.any()
             for x in _bucket_entries(row)]
        if (e[0][0], e[0][1]) in keys:
            mine.append(f)
    assert len(mine) == 3
    for f in mine:
        for i in range(20):
            index.subscribe(f"c{i}", Subscription(filter=f, qos=1))
    m = TpuMatcher(index, window=WINDOW)
    topics = [f.replace("+", "t") for f in mine]
    results = m.match_topics(topics)
    assert m.index.n_sat >= 1
    assert m.stats.overflows == 3 and m.stats.wide_topics == 0
    for topic, result in zip(topics, results):
        assert subscribers_equal(materialized(result), index.subscribers(topic))


def _one(flt):
    index = TopicsIndex()
    index.subscribe("solo", Subscription(filter=flt))
    return index


# -- (b) folds across the window boundary ---------------------------------------------


def equal_everywhere(m, index, topics):
    before = m.stats.host_fallbacks
    for topic, result in zip(topics, m.match_topics(topics)):
        assert subscribers_equal(materialized(result), index.subscribers(topic)), topic
    return m.stats.host_fallbacks - before


def adder(index, flt, kind):
    """``add(i)`` / ``drop(i)`` of subscriber ``i`` on ``flt``: clients,
    or clients, ``$share`` members and inline ones by turns."""
    def which(i):
        return 0 if kind == "clients" else i % 3

    def add(i):
        if which(i) == 0:
            index.subscribe(f"c{i}", Subscription(filter=flt, qos=i % 3))
        elif which(i) == 1:
            index.subscribe(f"s{i}", Subscription(filter=f"$share/g/{flt}", qos=1))
        else:
            index.inline_subscribe(
                InlineSubscription(filter=flt, identifier=i + 1, handler=noop)
            )

    def drop(i):
        if which(i) == 0:
            index.unsubscribe(flt, f"c{i}")
        elif which(i) == 1:
            index.unsubscribe(f"$share/g/{flt}", f"s{i}")
        else:
            index.inline_unsubscribe(i + 1, flt)

    return add, drop


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("flt, topics", [
    ("f/+", ["f/a", "$f/a", "f/a/b"]),
    ("+/f/#", ["x/f", "x/f/a", "$x/f/a"]),
], ids=["plus", "top_plus_hash"])
def test_an_entry_grown_and_shrunk_across_16_and_63(flt, topics, kind):
    """One subscriber at a time from 1 to 70 and back to 0, a fold after
    each: equal to the host trie after every fold, no topic on the host
    route once the fold is in, and the entry is narrow or wide as its
    width says. A fold may ask for a rebuild (orphaned ordinals); it
    never answers wrong and never leaves a host route standing."""
    index = TopicsIndex()
    index.subscribe("other", Subscription(filter="o/+", qos=0))
    index.subscribe("deep", Subscription(filter="+/+/#", qos=0))
    m = DeltaMatcher(index, background=False, window=WINDOW)
    add, drop = adder(index, flt, kind)
    topics = topics + ["o/a"]
    steps = [(add, i) for i in range(70)] + [(drop, i) for i in reversed(range(70))]
    width = 0
    for op, i in steps:
        op(i)
        width += 1 if op is add else -1
        # the overlay routes the mutated filter's topics to the host ...
        assert equal_everywhere(m, index, topics) > 0
        m.flush()
        # ... and the fold takes them back
        assert equal_everywhere(m, index, topics) == 0, (op.__name__, i)
        assert m.stats.wide_entries == int(width > WINDOW), width
    assert m.stats.folds > 100  # the folds did the work, not 140 rebuilds
    flat = m._snap.index
    assert flat.n_wide == 0 and flat.n_subs == 2


def test_the_harness_sequence_bulk_load_then_clean_session_connects():
    """``benchmark/run.py``'s set-up at the deployment's full width: one
    bulk load of 1,000 subscribers on ``<root>/+``, the build it ends
    in, then every client's clean-session connect (its inherited
    subscription goes, its own SUBSCRIBE puts it back), flushed in
    batches as the background thread would: equal after every flush,
    nothing left on the host route, one wide entry of 1,000 at the end."""
    params = CONFIG["params"]
    plan = fanout.plan(params, 2**31 + 5, None)
    subs = plan["subscriptions"]
    flt = subs[0][1]
    topics = [next(fanout.topics(params, 2**31 + 5, k)) for k in range(5)]
    index = TopicsIndex()
    m = DeltaMatcher(index, background=False, window=WINDOW)
    staging.bulk_register(
        index, ((c, Subscription(filter=f, qos=q)) for c, f, q in subs)
    )
    m.flush()
    assert m.stats.bulk_loads == 1 and m.stats.wide_entries == 1
    assert equal_everywhere(m, index, topics) == 0
    for n, (client, f, qos) in enumerate(subs, 1):
        index.unsubscribe(f, client)
        if n % 7:  # some connects overlap: a few are gone at once
            index.subscribe(client, Subscription(filter=f, qos=qos))
        if n % 50 == 0:
            m.flush()
            assert equal_everywhere(m, index, topics) == 0, n
    for n, (client, f, qos) in enumerate(subs, 1):
        if n % 7 == 0:
            index.subscribe(client, Subscription(filter=f, qos=qos))
    m.flush()
    before = m.stats.wide_topics
    assert equal_everywhere(m, index, topics) == 0
    assert m.stats.wide_topics == before + 5
    flat = m._snap.index
    assert (flat.n_wide, flat.max_width) == (1, 1000)
    assert len(index.subscribers(topics[0]).subscriptions) == 1000
    assert flt.endswith("/+")


# -- (c) the served path at the rehearse size, through a listener ---------------------


def serve_fanout(seed, publishes=12):
    """``fanout.plan`` at the rehearse size (50 subscribers, wider than
    the window) into a broker with the device matcher, loaded as
    ``benchmark/run.py`` loads it; every row connects over loopback TCP,
    subscribes, and acknowledges each QoS1 delivery; the five publishers
    each write ``publishes`` QoS1 frames to their own topic."""
    plan = fanout.plan(REHEARSE, seed, None)
    subs = plan["subscriptions"]
    n_pub = len(plan["publishers"])
    sent = [
        (k, seq, topic, 1)
        for k in range(n_pub)
        for seq, topic in zip(range(publishes), fanout.topics(REHEARSE, seed, k))
    ]

    async def subscriber(port, row, received, acked):
        cid, flt, qos = subs[row]
        r, w = await asyncio.open_connection("127.0.0.1", port)
        w.write(connect_packet(cid))
        assert (await read_wire_packet(r)).fixed_header.type == CONNACK
        w.write(sub_packet(1, [Subscription(filter=flt, qos=qos)]))
        assert (await read_wire_packet(r)).fixed_header.type == SUBACK
        got = received[cid] = []

        async def read():
            while True:
                pk = await read_wire_packet(r)
                if pk.fixed_header.type != PUBLISH:
                    continue
                pub, seq = bytes(pk.payload).split(b":")
                got.append(reference.pack_delivery(
                    int(pub), int(seq), pk.fixed_header.qos,
                    int(pk.fixed_header.dup),
                    reference.topic_tag(pk.topic_name.encode()),
                ))
                if pk.fixed_header.qos:
                    w.write(b"\x40\x02" + pk.packet_id.to_bytes(2, "big"))
                    acked[0] += 1

        return w, asyncio.ensure_future(read())

    async def scenario():
        from mqtt_tpu.listeners import Config as LConfig
        from mqtt_tpu.listeners.tcp import TCP

        h = Harness(Options(
            inline_client=True, device_matcher=True,
            matcher_opts={"background": False},
            matcher_stage_latency_budget_ms=0,
        ))
        srv = h.server
        srv.add_listener(TCP(LConfig(type="tcp", id="t", address="127.0.0.1:0")))
        await srv.serve()
        port = int(srv.listeners.get("t").address().rsplit(":", 1)[1])
        staging.bulk_register(
            srv.topics, ((c, Subscription(filter=f, qos=q)) for c, f, q in subs)
        )
        srv.matcher.flush()
        received: dict = {}
        acked = [0]
        conns = [
            await subscriber(port, row, received, acked) for row in plan["live"]
        ]
        srv.matcher.flush()
        live = reference.FilterSet(subs[row] for row in plan["live"])
        expected = reference.expected_deliveries(live, iter(sent))
        due = sum(len(v) for by in expected.values() for v in by.values())
        pubs = []
        for k in range(n_pub):
            r, w = await asyncio.open_connection("127.0.0.1", port)
            w.write(connect_packet(plan["publishers"][k]))
            assert (await read_wire_packet(r)).fixed_header.type == CONNACK
            pubs.append((r, w))
        stats = srv.matcher.stats
        c0 = dict(srv._slice_counters(), topics=stats.topics,
                  host_fallbacks=stats.host_fallbacks)
        for k, (_r, w) in enumerate(pubs):
            w.write(b"".join(
                pub_packet(topic, b"%d:%d" % (k, seq), qos=qos, pid=seq + 1)
                for kk, seq, topic, qos in sent if kk == k
            ))
        for _ in range(600):
            stage = srv._stage
            if (
                sum(len(v) for v in received.values()) >= due
                and stage.pending_depth == 0 and stage.inflight_batches == 0
            ):
                break
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.3)  # a surplus delivery would arrive now
        c1 = dict(srv._slice_counters(), topics=stats.topics,
                  host_fallbacks=stats.host_fallbacks)
        pubacks = 0
        for r, _w in pubs:
            for _ in range(publishes):
                pk = await asyncio.wait_for(read_wire_packet(r), 5)
                pubacks += pk.fixed_header.type == 4
        inflight_left = sum(
            len(cl.state.inflight) for cl in srv.clients.get_all().values()
        )
        srv.publish_sys_topics()
        sys_topics = {
            p.topic_name: bytes(p.payload).decode()
            for p in srv.topics.messages("$SYS/#")
        }
        metrics = srv.telemetry.registry.exposition()
        for w, task in conns:
            task.cancel()
            w.close()
        for _r, w in pubs:
            w.close()
        await srv.close()
        await h.shutdown()
        return {
            "received": received, "expected": expected, "due": due,
            "delta": {k: c1[k] - c0[k] for k in c0}, "after": c1,
            "pubacks": pubacks, "acked": acked[0], "inflight_left": inflight_left,
            "info_inflight": srv.info.inflight,
            "metrics": metrics, "sys_topics": sys_topics, "sent": len(sent),
        }

    return asyncio.run(asyncio.wait_for(scenario(), timeout=120))


@pytest.fixture(scope="module", params=[33, 2**31 + 33])
def served(request):
    return serve_fanout(request.param)


class TestServedPathAtTheRehearseSize:
    def test_the_sockets_saw_what_the_reference_says(self, served):
        """Every subscriber got every publish of every publisher, each
        once, in each publisher's order, at QoS 1, on its topic; every
        publisher got its PUBACKs and every delivery was acknowledged."""
        verdict = reference.compare_deliveries(served["expected"], served["received"])
        assert verdict["errors"] == 0, verdict
        assert served["due"] == served["sent"] * REHEARSE["subscribers"]
        assert served["pubacks"] == served["sent"]
        assert served["acked"] == served["due"]

    def test_the_device_answered_and_the_counters_say_so(self, served):
        """What ``device_resolved_share``, ``deliveries_per_pub``,
        ``wide_resolved_share`` and ``slice_targets_max`` read, from the
        same counters, over the publishes: no topic on the host route,
        50 deliveries a publish, every answer a wide entry's."""
        d = served["delta"]
        assert d["topics"] == served["sent"] and d["host_fallbacks"] == 0
        assert d["deliveries"] / d["topics"] == REHEARSE["subscribers"]
        assert d["wide_topics"] == d["topics"]
        assert served["after"]["wide_entries"] == 1
        assert served["after"]["slice_targets_max"] >= REHEARSE["subscribers"]
        assert served["after"]["slice_targets_max"] % REHEARSE["subscribers"] == 0

    def test_metrics_and_sys_carry_the_three_counters(self, served):
        after, text, tree = served["after"], served["metrics"], served["sys_topics"]
        assert f"mqtt_tpu_matcher_wide_entries {after['wide_entries']}" in text
        assert f"mqtt_tpu_matcher_wide_topics_total {after['wide_topics']}" in text
        assert (
            f"mqtt_tpu_stage_slice_targets_max {after['slice_targets_max']}" in text
        )
        assert tree["$SYS/broker/matcher/wide_entries"] == "1"
        assert tree["$SYS/broker/matcher/wide_topics"] == str(after["wide_topics"])
        assert tree["$SYS/broker/overload/stage_slice_targets_max"] == str(
            after["slice_targets_max"]
        )


# -- (d) the deployment ------------------------------------------------------------------


class TestTheDeployment:
    def test_plan_and_topics_replay_from_the_seed_in_another_process(self):
        seed = 2**31 + 11
        code = (
            "import json, sys, itertools; sys.path.insert(0, 'benchmark');"
            "from deployments import fanout;"
            "p = json.load(open('benchmark/configs/fanout-5-1000.json'))['params'];"
            f"plan = fanout.plan(p, {seed}, 5);"
            f"t = [list(itertools.islice(fanout.topics(p, {seed}, k), 3)) for k in range(5)];"
            "bad = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', 'mqtt_tpu'))];"
            "print(json.dumps([plan, t, bad]))"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
            env={**os.environ, "PYTHONHASHSEED": "77"}, check=True,
        ).stdout
        plan, topics, bad = json.loads(out)
        assert bad == []  # pure Python: neither jax nor the program
        mine = fanout.plan(CONFIG["params"], seed, None)
        assert [tuple(s) for s in plan["subscriptions"]] == mine["subscriptions"]
        assert plan["live"] == mine["live"] and plan["publishers"] == mine["publishers"]
        for k in range(5):
            stream = fanout.topics(CONFIG["params"], seed, k)
            assert topics[k] == [next(stream)] * 3

    @pytest.mark.parametrize("seed", [0, 33, 2**31 + 7])
    def test_the_deployments_shape(self, seed):
        """1,000 rows, all live, one ``<root>/+`` filter at QoS 1; five
        publishers that subscribe to nothing, one topic each under the
        root; another seed gives other names."""
        params = CONFIG["params"]
        plan = fanout.plan(params, seed, 5)
        subs = plan["subscriptions"]
        assert len(subs) == 1000 and plan["live"] == list(range(1000))
        assert len({c for c, _f, _q in subs}) == 1000
        (flt,) = {f for _c, f, _q in subs}
        root = flt[: -len("/+")]
        assert flt == root + "/+" and "/" not in root
        assert {q for _c, _f, q in subs} == {1}
        assert len(plan["publishers"]) == 5
        assert not set(plan["publishers"]) & {c for c, _f, _q in subs}
        topics = [next(fanout.topics(params, seed, k)) for k in range(5)]
        assert topics == [f"{root}/t{k}" for k in range(5)]
        full = reference.FilterSet(subs)
        assert all(len(full.matches(t)) == 1000 for t in topics)
        other = fanout.plan(params, seed + 1, None)
        assert other["subscriptions"][0][1] != flt
        assert other["publishers"][0] != plan["publishers"][0]

    def test_five_publishers_or_none(self):
        with pytest.raises(ValueError):
            fanout.plan(CONFIG["params"], 1, 32)
        assert len(fanout.plan(REHEARSE, 1, 5)["subscriptions"]) == 50

    def test_the_configuration_file(self):
        with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
            manifest = json.load(f)
        (entry,) = [c for c in manifest["configs"] if c["name"] == "fanout-5-1000"]
        assert CONFIG["source"] == entry["source"] == (
            "EMQ Open MQTT Benchmark Suite (github.com/emqx/mqttbs), enterprise "
            "scenario fanout-5-1000-5-250K: 5 publishers, 5 topics, 1,000 "
            "subscribers each on all 5 topics, QoS 1, 16 B payload, 250 msg/s in"
        )
        assert len(CONFIG["source"]) <= 200
        assert entry["reduced"] == ["publish_rate"] == list(CONFIG["reduced"])
        assert CONFIG["params"] == {"subscribers": 1000, "publishers": 5, "topics": 5}
        assert REHEARSE == {"subscribers": 50, "publishers": 5, "topics": 5}
        assert REHEARSE["subscribers"] > WINDOW  # still wider than the window
        assert CONFIG["broker_options"] == {"device_matcher": True}
        assert CONFIG["match_plane_sample"] == 40
        assert CONFIG["control"]["fanout_cap"] == 4
        with open(os.path.join(ROOT, "benchmark/traffic/broadcast.json"), encoding="utf-8") as f:
            mix = json.load(f)
        assert (mix["loop"], mix["connections"], mix["chunk"], mix["qos1_every"]) == (
            "closed", 5, 8, 1,
        )
        assert mix["payload_bytes"] == 24 and mix["generator_procs"] == 4
        (cell,) = [w for w in manifest["workloads"] if w["config"] == "fanout-5-1000"]
        assert cell["name"] == "fanout-5-1000.broadcast" and cell["chips"] == 1
