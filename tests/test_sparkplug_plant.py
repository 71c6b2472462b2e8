"""``sparkplug-plant`` (Eclipse Sparkplug Specification 3.0.0: the
namespace ``spBv1.0/group/type/node[/device]``, edge nodes on their NCMD
and DCMD topics, host applications on ``spBv1.0/#``) at the sandbox's
size on the CPU backend: the served path over live sockets and the
device matcher against the plain reference (``benchmark/reference.py``),
the corners of a ``+`` and a ``#`` in one filter one by one, the
counters of the way out (``deliveries_flush`` / ``_cork`` / ``_queue``,
the corks), and the deployment's generator
(``benchmark/deployments/sparkplug.py``). Answers and counts, never a
rate."""

import asyncio
import itertools
import json
import os
import subprocess
import sys

import pytest

from mqtt_tpu import Options, staging
from mqtt_tpu.ops.delta import DeltaMatcher
from mqtt_tpu.ops.flat import KIND_EXACT, KIND_HASH, build_flat_index
from mqtt_tpu.packets import CONNACK, PUBACK, PUBLISH, SUBACK, Subscription
from mqtt_tpu.topics import TopicsIndex

from tests.test_batch_completion import load_benchmark_module
from tests.test_loop_ledger import arm_at_serve  # noqa: F401  (a fixture)
from tests.test_server import (
    Harness,
    connect_packet,
    pub_packet,
    read_wire_packet,
    sub_packet,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_benchmark_module("reference")
sparkplug = load_benchmark_module("deployments/sparkplug")

with open(os.path.join(ROOT, "benchmark/configs/sparkplug-plant.json"), encoding="utf-8") as f:
    CONFIG = json.load(f)
PARAMS, REHEARSE = CONFIG["params"], CONFIG["rehearse_params"]
ROUTES = ("deliveries_flush", "deliveries_cork", "deliveries_queue")
EGRESS = ROUTES + (
    "deliveries_dropped_full", "cork_writes", "cork_frames", "cork_early_writes",
    "socket_checks",
)


def levels(text):
    return tuple(text.split("/"))


def answer_of(result):
    if hasattr(result, "materialize"):
        result = result.materialize()
    return {c: s.qos for c, s in result.subscriptions.items()}


# -- the served path against the reference ----------------------------------------


def serve_plant(seed, publishes=48):
    """``sparkplug.plan`` at the rehearse size into a broker with the
    device matcher, loaded by the restore's route as ``benchmark/run.py``
    loads it; every live row connects over loopback TCP, subscribes and
    acknowledges each QoS1 delivery; the publishers (the live edge nodes
    and the primary host, each on the connection it subscribes on) write
    ``publishes`` frames of their stream, every 16th QoS1. Returns what
    the sockets saw as the reference's packed records, the served
    matcher's whole answer for every distinct topic, and the broker's
    counters over the publishes."""
    plan = sparkplug.plan(REHEARSE, seed, None)
    subs = plan["subscriptions"]
    n_pub = len(plan["publishers"])
    sent = [
        (k, seq, topic, int(seq % 16 == 15))
        for k in range(n_pub)
        for seq, topic in zip(range(publishes), sparkplug.topics(REHEARSE, seed, k))
    ]

    async def connect(port, row, received, counts):
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
                if pk.fixed_header.type == PUBACK:
                    counts["pubacks"] += 1
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

        return cid, w, asyncio.ensure_future(read())

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
        assert srv.topics.held == len(subs)
        received: dict = {}
        counts = {"pubacks": 0}
        conns = [await connect(port, row, received, counts) for row in plan["live"]]
        writer = {cid: w for cid, w, _t in conns}
        srv.matcher.flush()
        # a clean-session connect drops what the load gave the client id
        # and its one SUBSCRIBE puts one row back: the table is the plan
        assert srv.topics.held == len(subs)
        live = reference.FilterSet(subs[row] for row in plan["live"])
        expected = reference.expected_deliveries(live, iter(sent))
        due = sum(len(v) for by in expected.values() for v in by.values())
        stats = srv.matcher.stats
        c0 = dict(srv._slice_counters(), topics=stats.topics,
                  host_fallbacks=stats.host_fallbacks,
                  dropped=srv.info.messages_dropped)
        for k, cid in enumerate(plan["publishers"]):
            writer[cid].write(b"".join(
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
                  host_fallbacks=stats.host_fallbacks,
                  dropped=srv.info.messages_dropped)
        topics = sorted({topic for _k, _seq, topic, _q in sent})
        results = await asyncio.get_running_loop().run_in_executor(
            None, srv.matcher.match_topics, topics
        )
        answers = [answer_of(r) for r in results]
        index = srv.matcher.inner._snap.index
        shapes = sorted(
            (int(k), int(d), int(m))
            for k, d, m in zip(index.pat_kind, index.pat_depth, index.pat_mask)
            if d >= 0
        )
        srv.publish_sys_topics()
        sys_topics = {
            p.topic_name: bytes(p.payload).decode()
            for p in srv.topics.messages("$SYS/#")
        }
        metrics = srv.telemetry.registry.exposition()
        for _cid, w, task in conns:
            task.cancel()
            w.close()
        await srv.close()
        await h.shutdown()
        return {
            "plan": plan, "received": received, "expected": expected, "due": due,
            "topics": topics, "answers": answers, "shapes": shapes,
            "delta": {k: c1[k] - c0[k] for k in c0 if isinstance(c0[k], int)},
            "after": c1, "pubacks": counts["pubacks"], "sent": sent,
            "metrics": metrics, "sys_topics": sys_topics,
        }

    return asyncio.run(asyncio.wait_for(scenario(), timeout=120))


@pytest.fixture(scope="module", params=[37, 2**31 + 37])
def served(request):
    return serve_plant(request.param)


class TestServedPathAgainstTheReference:
    def test_the_sockets_saw_what_the_reference_says(self, served):
        """Delivered records equal ``reference.expected_deliveries`` on
        all 22 sockets: same deliveries, each once, in each publisher's
        order, at min(publish, subscription) QoS, on the topic they were
        sent on; every QoS1 publish was acknowledged; half of all
        deliveries landed on the two host applications."""
        verdict = reference.compare_deliveries(served["expected"], served["received"])
        assert verdict["errors"] == 0, verdict
        sent = served["sent"]
        assert served["pubacks"] == sum(q for _k, _s, _t, q in sent)
        hosts = [c for c, f, _q in served["plan"]["subscriptions"] if f == "spBv1.0/#"]
        assert len(hosts) == 2
        for host in hosts:
            assert len(served["received"][host]) == len(sent)
        assert 3.9 < served["due"] / len(sent) < 4.3
        assert 2 * len(sent) / served["due"] > 0.45

    def test_the_served_matcher_gives_whole_subscriber_sets(self, served):
        """``matcher.match_topics`` equals ``reference.FilterSet.matches``
        over all 601 subscriptions, offline sessions and the catalog
        among them, on every distinct topic of the streams: 4 matches a
        data topic, 5 a birth (the catalog) and an NCMD (its node)."""
        subs = served["plan"]["subscriptions"]
        full = reference.FilterSet(subs)
        verdict = reference.compare_match_sets(full, served["topics"], served["answers"])
        assert verdict["errors"] == 0 and verdict["sampled"] > 80, verdict
        sizes = {
            kind: {len(a) for t, a in zip(served["topics"], served["answers"])
                   if t.split("/")[2] == kind}
            for kind in ("NBIRTH", "DBIRTH", "NDATA", "DDATA", "NCMD", "DCMD")
        }
        assert sizes == {
            "NBIRTH": {5}, "DBIRTH": {5}, "NDATA": {4}, "DDATA": {4},
            "NCMD": {5}, "DCMD": {4},
        }
        catalog = subs[-1][0]
        assert any(catalog in a for a in served["answers"])

    def test_six_probe_shapes_and_the_device_answered(self, served):
        """One EXACT shape (the STATE topic) and five HASH ones, two of
        them with a ``+`` before the ``#`` (level 2 of ``g/+/n/#``,
        level 1 of ``+/NBIRTH/#``); no topic walked on the host."""
        assert served["shapes"] == sorted([
            (KIND_EXACT, 3, 0),
            (KIND_HASH, 1, 0), (KIND_HASH, 2, 0), (KIND_HASH, 4, 0),
            (KIND_HASH, 4, 1 << 2), (KIND_HASH, 3, 1 << 1),
        ])
        d = served["delta"]
        assert d["topics"] == len(served["sent"]) and d["host_fallbacks"] == 0


class TestTheWayOutIsCounted:
    def test_the_three_routes_come_to_the_deliveries_the_sockets_counted(self, served):
        """Every delivery left by the native flush, an open cork or the
        outbound queue, and the three counts come to what the sockets
        received and to ``deliveries``; nothing was dropped."""
        d = served["delta"]
        got = sum(len(v) for v in served["received"].values())
        assert got == served["due"] == d["deliveries"]
        assert sum(d[k] for k in ROUTES) == got
        assert d["deliveries_dropped_full"] == 0 and d["dropped"] == 0
        # the hosts are hit by every publish of a slice: their share
        # leaves by the cork
        assert d["deliveries_cork"] >= got // 2

    def test_a_cork_holds_at_least_a_frame(self, served):
        d = served["delta"]
        assert d["cork_writes"] > 0
        assert d["cork_frames"] >= d["cork_writes"] >= d["cork_early_writes"]
        # the deliveries that took a cork are among its packets (the
        # rest are the acks a read's handlers wrote)
        assert d["cork_frames"] >= d["deliveries_cork"]

    def test_metrics_and_sys_carry_the_counters(self, served):
        after, text, tree = served["after"], served["metrics"], served["sys_topics"]
        for key in EGRESS:
            # the scrape and the $SYS pass ran after the snapshot, with
            # the sockets quiet
            assert f"mqtt_tpu_{key}_total {after[key]}" in text, key
            assert tree["$SYS/broker/egress/" + key] == str(after[key]), key

    def test_armed_and_disarmed_give_equal_answers(self, arm_at_serve):
        """With a profiler session live (the ``mqtt/loop.flush`` span
        and its ``frames`` argument, the timed sends) the sockets see
        what they see without, and the counters count the same."""
        arm, made = arm_at_serve
        runs = {}
        for on in (False, True):
            arm(on)
            runs[on] = serve_plant(41, publishes=32)
        assert made and made[0].armed
        for key in ("received", "answers", "pubacks"):
            assert runs[True][key] == runs[False][key], key
        for key in ("deliveries", "deliveries_dropped_full"):
            assert runs[True]["delta"][key] == runs[False]["delta"][key], key
        for on in (False, True):
            d = runs[on]["delta"]
            assert sum(d[k] for k in ROUTES) == d["deliveries"] == runs[on]["due"]


# -- a '+' and a '#' in one filter, corner by corner ---------------------------------

G, N = "spBv1.0/G1", "N7"
CORNER_FILTERS = [
    "spBv1.0/#", f"{G}/#", f"{G}/+/{N}/#", f"{G}/NCMD/{N}/#", f"{G}/DCMD/{N}/#",
    "spBv1.0/STATE/scada", "spBv1.0/+/NBIRTH/#", "spBv1.0/+/DBIRTH/#",
]
# topic -> the filters that must answer, by the spec's rule
CORNERS = {
    "ncmd_parent_level": (f"{G}/NCMD/{N}", {
        "spBv1.0/#", f"{G}/#", f"{G}/+/{N}/#", f"{G}/NCMD/{N}/#"}),
    "dcmd_below_the_hash": (f"{G}/DCMD/{N}/D3", {
        "spBv1.0/#", f"{G}/#", f"{G}/+/{N}/#", f"{G}/DCMD/{N}/#"}),
    "plus_hash_at_four_levels": (f"{G}/NDATA/{N}", {
        "spBv1.0/#", f"{G}/#", f"{G}/+/{N}/#"}),
    "plus_hash_at_five_levels": (f"{G}/DDATA/{N}/D0", {
        "spBv1.0/#", f"{G}/#", f"{G}/+/{N}/#"}),
    "plus_hash_too_short": (f"{G}/x", {"spBv1.0/#", f"{G}/#"}),
    "plus_hash_another_node": (f"{G}/NDATA/N8", {"spBv1.0/#", f"{G}/#"}),
    "top_plus_nbirth": ("spBv1.0/G9/NBIRTH/N1", {"spBv1.0/#", "spBv1.0/+/NBIRTH/#"}),
    "top_plus_nbirth_parent": ("spBv1.0/G9/NBIRTH", {"spBv1.0/#", "spBv1.0/+/NBIRTH/#"}),
    "top_plus_dbirth_device": ("spBv1.0/G9/DBIRTH/N1/D2", {
        "spBv1.0/#", "spBv1.0/+/DBIRTH/#"}),
    "namespace_alone": ("spBv1.0", {"spBv1.0/#"}),
    "group_alone": (G, {"spBv1.0/#", f"{G}/#"}),
    "state_exact": ("spBv1.0/STATE/scada", {"spBv1.0/#", "spBv1.0/STATE/scada"}),
    "another_namespace": ("spAv1.0/G1/NDATA/N7", set()),
    "dollar_topic": ("$SYS/G1/NBIRTH/N7", set()),
}


@pytest.fixture(scope="module")
def corner_matcher():
    """One index over the plant's filter shapes, each held by a client
    named after it, and two with a ``+`` as the last level before the
    ``#``; the device matcher built over it."""
    index = TopicsIndex()
    for flt in CORNER_FILTERS + [f"{G}/+/#", "+/+/#"]:
        index.subscribe(flt, Subscription(filter=flt, qos=1))
    return index, DeltaMatcher(index, background=False)


class TestPlusAndHashCornersOnTheDevice:
    @pytest.mark.parametrize("corner", sorted(CORNERS))
    def test_against_the_reference_rule(self, corner_matcher, corner):
        """The device matcher's answer over the plant's shapes is the set
        ``reference.filter_matches`` gives, and the host trie's, with no
        topic walked on the host."""
        index, m = corner_matcher
        topic, wanted = CORNERS[corner]
        before = m.stats.host_fallbacks
        (result,) = m.match_topics([topic])
        assert m.stats.host_fallbacks == before
        got = set(answer_of(result)) & set(CORNER_FILTERS)
        by_rule = {
            f for f in CORNER_FILTERS
            if reference.filter_matches(levels(f), levels(topic))
        }
        assert got == by_rule == wanted
        assert got == set(index.subscribers(topic).subscriptions) & set(CORNER_FILTERS)

    @pytest.mark.parametrize("flt, topic, below", [
        (f"{G}/+/#", f"{G}/NDATA", f"{G}/NDATA/{N}"),
        ("+/+/#", "spBv1.0/G1", "spBv1.0/G1/NCMD"),
    ], ids=["group_plus_hash", "plus_plus_hash"])
    def test_a_plus_as_the_last_level_before_the_hash(
        self, corner_matcher, flt, topic, below
    ):
        """``g/+/#`` against ``g/NDATA``: the spec's rule says the parent
        level matches; mochi's walk (topics.go:612, ``ops/flat.py``'s
        ``last_plus``) does not gather a ``#`` child below a ``+``, on
        the device as on the host. None of the deployment's filters ends
        in ``+/#`` (a node's own id is the last level before each), so
        no cell sees the difference; below the parent level they agree."""
        index, m = corner_matcher
        assert reference.filter_matches(levels(flt), levels(topic))
        (result,) = m.match_topics([topic])
        assert flt not in answer_of(result)
        assert flt not in index.subscribers(topic).subscriptions
        (result,) = m.match_topics([below])
        assert flt in answer_of(result)
        assert not any(f.endswith("/+/#") for _c, f, _q in
                       sparkplug.plan(REHEARSE, 3, None)["subscriptions"])


# -- the deployment's generator -------------------------------------------------------


class TestTheDeployment:
    def test_plan_and_topics_replay_from_the_seed_in_another_process(self):
        seed = 2**31 + 11
        code = (
            "import json, sys, itertools; sys.path.insert(0, 'benchmark');"
            "from deployments import sparkplug;"
            "p = json.load(open('benchmark/configs/sparkplug-plant.json'))['rehearse_params'];"
            f"plan = sparkplug.plan(p, {seed}, 9);"
            f"t = [list(itertools.islice(sparkplug.topics(p, {seed}, k), 40)) for k in range(9)];"
            "bad = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', 'mqtt_tpu'))];"
            "print(json.dumps([plan, t, bad]))"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
            env={**os.environ, "PYTHONHASHSEED": "77"}, check=True,
        ).stdout
        plan, topics, bad = json.loads(out)
        assert bad == []  # pure Python: neither jax nor the program
        mine = sparkplug.plan(REHEARSE, seed, None)
        assert [tuple(s) for s in plan["subscriptions"]] == mine["subscriptions"]
        assert plan["live"] == mine["live"] and plan["publishers"] == mine["publishers"]
        for k in range(9):
            stream = sparkplug.topics(REHEARSE, seed, k)
            assert topics[k] == list(itertools.islice(stream, 40))

    @pytest.mark.parametrize("seed", [0, 37, 2**31 + 7])
    def test_the_plants_shape(self, seed):
        """29,897 rows in the issue's load order: the two hosts first,
        20 dashboards, 128 node applications, the 128 live nodes' NCMD
        row, three rows for each of the 9,872 offline nodes, the
        catalog's three; 278 live rows, one a client; 129 publishers,
        each one of the live clients; another seed gives other names."""
        plan = sparkplug.plan(PARAMS, seed, None)
        subs, live = plan["subscriptions"], plan["live"]
        assert len(subs) == 29_897 == 2 + 20 + 128 + 128 + 3 * 9_872 + 3
        assert live == list(range(278))
        assert [f for _c, f, _q in subs[:2]] == ["spBv1.0/#"] * 2
        assert [q for _c, _f, q in subs[:2]] == [1, 1]
        assert all(f.count("/") == 2 and f.endswith("/#") and q == 0
                   for _c, f, q in subs[2:22])
        assert all(f.split("/")[2] == "+" and f.endswith("/#") and q == 0
                   for _c, f, q in subs[22:150])
        assert all(f.split("/")[2] == "NCMD" and f.endswith("/#") and q == 1
                   for _c, f, q in subs[150:278])
        live_clients = [subs[row][0] for row in live]
        assert len(set(live_clients)) == 278  # one row a live client
        offline = subs[278:-3]
        assert len(offline) == 3 * 9_872
        assert not {c for c, _f, _q in offline} & set(live_clients)
        for i in range(0, len(offline), 3 * 1234):
            (c1, ncmd, _), (c2, dcmd, _), (c3, state, _) = offline[i : i + 3]
            assert c1 == c2 == c3
            assert ncmd.split("/")[2] == "NCMD" and dcmd == ncmd.replace("NCMD", "DCMD")
            assert state == "spBv1.0/STATE/" + subs[0][0]
        assert {q for _c, _f, q in offline} == {1}
        assert [f for _c, f, _q in subs[-3:]] == [
            "spBv1.0/+/NBIRTH/#", "spBv1.0/+/DBIRTH/#", "spBv1.0/+/NDEATH/#",
        ]
        assert len({c for c, _f, _q in subs[-3:]}) == 1
        # the live nodes are spread evenly over the groups: 6 or 7 each
        per_group: dict = {}
        for _c, f, _q in subs[150:278]:
            per_group[f.split("/")[1]] = per_group.get(f.split("/")[1], 0) + 1
        assert len(per_group) == 20 and set(per_group.values()) == {6, 7}
        pubs = plan["publishers"]
        assert len(pubs) == 129 and pubs[:128] == [c for c, _f, _q in subs[150:278]]
        assert pubs[128] == subs[0][0]  # the primary host publishes too
        other = sparkplug.plan(PARAMS, seed + 1, None)
        assert other["subscriptions"][2][1] != subs[2][1]
        assert other["publishers"][0] != pubs[0]

    @pytest.mark.parametrize("seed", [5, 2**31 + 7])
    def test_the_streams(self, seed):
        """A node's first publish is its NBIRTH, then one DBIRTH a
        device, then scans of 9; the host walks every live node with an
        NCMD, every fourth publish a DCMD; a data publish is due 4
        deliveries, a command to a node 5, and the control's cap of 2
        cuts both hosts from every one."""
        plan = sparkplug.plan(PARAMS, seed, 129)
        subs = plan["subscriptions"]
        full = reference.FilterSet(subs)
        live = reference.FilterSet(subs[row] for row in plan["live"])
        flt = subs[150 + 5][1]  # live node 5's own NCMD filter
        _ns, group, _ncmd, node, _hash = flt.split("/")
        head = list(itertools.islice(sparkplug.topics(PARAMS, seed, 5), 1 + 8 + 18))
        assert head[0] == f"spBv1.0/{group}/NBIRTH/{node}"
        assert head[1:9] == [f"spBv1.0/{group}/DBIRTH/{node}/D{d}" for d in range(8)]
        scan = [f"spBv1.0/{group}/NDATA/{node}"] + [
            f"spBv1.0/{group}/DDATA/{node}/D{d}" for d in range(8)
        ]
        assert head[9:] == scan + scan
        for topic in scan:
            assert len(live.matches(topic)) == 4 and len(full.matches(topic)) == 4
        hosts = {subs[0][0], subs[1][0]}
        assert not hosts & {c for c, _q in full.matches(scan[0])[-2:]}
        walk = list(itertools.islice(sparkplug.topics(PARAMS, seed, 128), 4 * 128))
        kinds = [t.split("/")[2] for t in walk]
        assert kinds[:8] == ["NCMD", "NCMD", "NCMD", "DCMD"] * 2
        assert {t.count("/") for t, k in zip(walk, kinds) if k == "DCMD"} == {4}
        named = {tuple(t.split("/")[1:4:2]) for t in walk}
        assert len(named) == 128  # every live node, and no other
        assert {len(live.matches(t)) for t, k in zip(walk, kinds) if k == "NCMD"} == {5}
        assert {len(live.matches(t)) for t, k in zip(walk, kinds) if k == "DCMD"} == {4}
        assert walk != list(
            itertools.islice(sparkplug.topics(PARAMS, seed + 1, 128), 4 * 128)
        )

    def test_129_publishers_or_none(self):
        with pytest.raises(ValueError):
            sparkplug.plan(PARAMS, 1, 32)
        assert len(sparkplug.plan(PARAMS, 1, 129)["publishers"]) == 129
        plan = sparkplug.plan(REHEARSE, 1, 9)
        assert len(plan["subscriptions"]) == 2 + 4 + 8 + 8 + 3 * 192 + 3
        with pytest.raises(ValueError):
            sparkplug.plan({**REHEARSE, "live_nodes": 4 * 50 + 1}, 1, None)

    def test_six_patterns_two_of_them_hash_with_a_plus(self):
        """The built index over the full plan at the rehearse size."""
        index = TopicsIndex()
        staging.bulk_register(index, (
            (c, Subscription(filter=f, qos=q))
            for c, f, q in sparkplug.plan(REHEARSE, 37, None)["subscriptions"]
        ))
        flat = build_flat_index(index)
        real = [
            (int(k), int(d), int(m))
            for k, d, m in zip(flat.pat_kind, flat.pat_depth, flat.pat_mask) if d >= 0
        ]
        assert len(real) == 6 and flat.num_patterns == 8  # padded to a power of two
        assert sorted(s for s in real if s[2]) == [
            (KIND_HASH, 3, 1 << 1), (KIND_HASH, 4, 1 << 2),
        ]
        # the primary host's STATE topic is held by every offline node:
        # one wide entry, which no publish of the streams names
        assert (flat.n_wide, flat.max_width) == (1, 192)

    def test_the_configuration_file(self):
        with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
            manifest = json.load(f)
        (entry,) = [c for c in manifest["configs"] if c["name"] == "sparkplug-plant"]
        assert CONFIG["source"] == entry["source"] == (
            "Eclipse Sparkplug Specification 3.0.0 (ISO/IEC 20237:2023), Topics "
            "and Messages: spBv1.0/group_id/message_type/edge_node_id/[device_id]; "
            "Edge Node NCMD/DCMD and Host Application spBv1.0/# filters"
        )
        assert len(CONFIG["source"]) <= 200
        assert entry["reduced"] == list(CONFIG["reduced"]) == [
            "live_clients", "publish_rate", "session_events",
        ]
        assert all(len(why) > 80 for why in CONFIG["reduced"].values())
        assert "ISSUE 37" in CONFIG["source_says"]
        assert CONFIG["architecture"] is None and CONFIG["deployment"] == "sparkplug"
        assert PARAMS == {
            "groups": 20, "nodes_per_group": 500, "devices_per_node": 8,
            "live_nodes": 128,
        }
        assert REHEARSE == {
            "groups": 4, "nodes_per_group": 50, "devices_per_node": 4,
            "live_nodes": 8,
        }
        assert CONFIG["broker_options"] == {"device_matcher": True}
        assert CONFIG["match_plane_sample"] == 2048
        assert CONFIG["control"]["fanout_cap"] == 2
        assert len(CONFIG["guarantees"]) == 6
        assert {"plant_size", "payload_bytes", "one_row_a_live_client"} <= set(
            CONFIG["assumed"]
        )
        with open(os.path.join(ROOT, "benchmark/traffic/fan-in.json"), encoding="utf-8") as f:
            mix = json.load(f)
        assert (mix["loop"], mix["connections"], mix["chunk"], mix["qos1_every"]) == (
            "closed", None, 16, 0,
        )
        assert mix["payload_bytes"] == 128 and mix["generator_procs"] == 4
        assert mix["warm_ladder"] == [16, 32, 64, 128, 256, 512, 1024, 2048, 4096]
        (cell,) = [w for w in manifest["workloads"] if w["config"] == "sparkplug-plant"]
        assert cell["name"] == "sparkplug-plant.fan-in" and cell["chips"] == 1
