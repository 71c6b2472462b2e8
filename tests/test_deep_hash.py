"""``deep-hash-1m`` (BASELINE.json configs[2]: 8-level filters, 5% cut at
depth 1-7 and ended in ``#``) at the sandbox's size on the CPU backend:
the served path and the device matcher against the plain reference
(``benchmark/reference.py``), the ``#`` corners one by one, and the
deployment's generator (``benchmark/deployments/deephash.py``). Answers
and counts, never a rate."""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from mqtt_tpu import Options, staging
from mqtt_tpu.ops.delta import DeltaMatcher
from mqtt_tpu.ops.flat import KIND_EXACT, KIND_HASH, build_flat_index
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
deephash = load_benchmark_module("deployments/deephash")

with open(os.path.join(ROOT, "benchmark/configs/deep-hash-1m.json"), encoding="utf-8") as f:
    CONFIG = json.load(f)
REHEARSE = CONFIG["rehearse_params"]


def levels(text):
    return tuple(text.split("/"))


def answer_of(result):
    if hasattr(result, "materialize"):
        result = result.materialize()
    return {c: s.qos for c, s in result.subscriptions.items()}


# -- the served path against the reference ----------------------------------------


def serve_deep_hash(seed, n_publishers=4, publishes=96):
    """``deephash.plan`` at the rehearse size into a broker with the
    device matcher, loaded by the restore's route as ``benchmark/run.py``
    loads it; the live rows connect over loopback TCP and subscribe;
    ``n_publishers`` connections each write ``publishes`` frames of
    ``deephash.topics``, one in eight QoS1. Returns what the sockets saw
    as the reference's packed records, the served matcher's whole answer
    for every published topic, and the plan."""
    plan = deephash.plan(REHEARSE, seed, n_publishers)
    subs = plan["subscriptions"]
    sent = [
        (k, seq, topic, int(seq % 8 == 0))
        for k in range(n_publishers)
        for seq, topic in zip(range(publishes), deephash.topics(REHEARSE, seed, k))
    ]

    async def subscriber(port, row, received):
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
        assert srv.topics.held == len(subs)
        received: dict = {}
        conns = [await subscriber(port, row, received) for row in plan["live"]]
        srv.matcher.flush()
        live = reference.FilterSet(subs[row] for row in plan["live"])
        expected = reference.expected_deliveries(live, iter(sent))
        due = sum(len(v) for by in expected.values() for v in by.values())
        pubs = []
        for k in range(n_publishers):
            r, w = await asyncio.open_connection("127.0.0.1", port)
            w.write(connect_packet(plan["publishers"][k]))
            assert (await read_wire_packet(r)).fixed_header.type == CONNACK
            pubs.append((r, w))
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
        await asyncio.sleep(0.2)  # a surplus delivery would arrive now
        topics = sorted({topic for _k, _seq, topic, _q in sent})
        results = await asyncio.get_running_loop().run_in_executor(
            None, srv.matcher.match_topics, topics
        )
        answers = [answer_of(r) for r in results]
        index = srv.matcher.inner._snap.index
        shape = (
            int(index.pat_depth.shape[0]), int(index.max_levels),
            srv.matcher.stats.host_fallbacks, srv.matcher.stats.topics,
        )
        for w, task in conns:
            task.cancel()
            w.close()
        for _r, w in pubs:
            w.close()
        await srv.close()
        await h.shutdown()
        return received, expected, due, topics, answers, shape

    received, expected, due, topics, answers, shape = asyncio.run(
        asyncio.wait_for(scenario(), timeout=120)
    )
    return {
        "plan": plan, "received": received, "expected": expected, "due": due,
        "topics": topics, "answers": answers, "shape": shape,
    }


@pytest.fixture(scope="module", params=[31, 2**31 + 7])
def served(request):
    return serve_deep_hash(request.param)


class TestServedPathAgainstTheReference:
    def test_the_sockets_saw_what_the_reference_says(self, served):
        """Delivered records equal ``reference.expected_deliveries``:
        same deliveries, each once, in each publisher's order, at
        min(publish, subscription) QoS, with the topic they were sent on."""
        verdict = reference.compare_deliveries(served["expected"], served["received"])
        assert verdict["errors"] == 0, verdict
        assert served["due"] > 50  # the live '#' holders do hear the pools

    def test_the_served_matcher_gives_whole_subscriber_sets(self, served):
        """``matcher.match_topics`` equals ``reference.FilterSet.matches``
        over all 20,000 subscriptions, on every published topic; most
        topics match something and some match an exact filter."""
        subs = served["plan"]["subscriptions"]
        full = reference.FilterSet(subs)
        verdict = reference.compare_match_sets(
            full, served["topics"], served["answers"]
        )
        assert verdict["errors"] == 0 and verdict["sampled"] > 200, verdict
        exact = {c for c, f, _q in subs if f[-1] != "#"}
        assert any(exact & set(a) for a in served["answers"])
        assert sum(1 for a in served["answers"] if a) > len(served["answers"]) // 2

    def test_eight_probe_shapes_and_the_device_answered(self, served):
        patterns, max_levels, host_fallbacks, topics = served["shape"]
        assert (patterns, max_levels) == (8, 8)
        assert topics > 0 and host_fallbacks < topics // 4


# -- the '#' corners through the device matcher --------------------------------------

DEEP = "p/l2/l3/l4/l5/l6/l7/l8"
CORNERS = {
    "parent_and_below": (
        ["a/#"],
        {"a": {"a/#"}, "a/b/c": {"a/#"}, "ab": set(), "a/": {"a/#"}},
    ),
    "lone_hash_and_dollar": (
        ["#", "$SYS/#", "+/up"],
        {"x/y": {"#"}, "$SYS/up": {"$SYS/#"}, "$SYS": {"$SYS/#"}, "x/up": {"#", "+/up"}},
    ),
    "hash_at_every_depth": (
        ["/".join(DEEP.split("/")[:d]) + "/#" for d in range(1, 8)] + [DEEP],
        {
            DEEP: {"/".join(DEEP.split("/")[:d]) + "/#" for d in range(1, 8)} | {DEEP},
            "p/l2/l3/zz/l5/l6/l7/l8": {"p/#", "p/l2/#", "p/l2/l3/#"},
        },
    ),
    "nine_levels": (
        ["n/2/3/4/5/6/7/8/9", "n/2/3/4/5/6/7/8/#", "n/2/#"],
        {
            "n/2/3/4/5/6/7/8/9": {"n/2/3/4/5/6/7/8/9", "n/2/3/4/5/6/7/8/#", "n/2/#"},
            "n/2/3/4/5/6/7/8": {"n/2/3/4/5/6/7/8/#", "n/2/#"},
        },
    ),
}
CORNER_FILTERS = sorted({f for flts, _w in CORNERS.values() for f in flts})


@pytest.fixture(scope="module")
def corner_matcher():
    """One index over every corner's filters, each held by a client
    named after it, and the device matcher built over it."""
    index = TopicsIndex()
    for flt in CORNER_FILTERS:
        index.subscribe(flt, Subscription(filter=flt, qos=1))
    index.subscribe("regs", Subscription(filter="q/#", qos=0))
    index.inline_subscribe(InlineSubscription(filter="q/#", identifier=9))
    index.subscribe("plus", Subscription(filter="m/+/#", qos=0))
    return index, DeltaMatcher(index, background=False)


class TestHashCornersOnTheDevice:
    @pytest.mark.parametrize("corner", sorted(CORNERS))
    def test_against_the_reference_rule(self, corner_matcher, corner):
        """The device matcher's answer is the set ``reference.filter_matches``
        gives over ALL the index's filters, and the host trie's."""
        index, m = corner_matcher
        _filters, wanted = CORNERS[corner]
        topics = sorted(wanted)
        for topic, result in zip(topics, m.match_topics(topics)):
            got = set(answer_of(result))
            by_rule = {
                f for f in CORNER_FILTERS if reference.filter_matches(levels(f), levels(topic))
            }
            assert got == by_rule, topic
            assert got == set(index.subscribers(topic).subscriptions), topic
            assert wanted[topic] <= got, topic

    def test_a_nine_level_topic_takes_the_host_route(self, corner_matcher):
        _index, m = corner_matcher
        before = m.stats.host_fallbacks
        m.match_topics(["n/2/3/4/5/6/7/8/9"])
        assert m.stats.host_fallbacks == before + 1
        m.match_topics(["n/2/3/4/5/6/7/8"])
        assert m.stats.host_fallbacks == before + 1

    def test_regs_before_inline_on_a_parent_match(self, corner_matcher):
        """``q/#`` against ``q``: the client subscription answers, the
        inline one at the same particle does not (topics.go:615's quirk,
        kept: reg ids sit before inline ids in the entry's window);
        against ``q/x`` both answer."""
        index, m = corner_matcher
        parent, below = m.match_topics(["q", "q/x"])
        for r in (parent, below):
            assert "regs" in answer_of(r)
        for got, topic in ((parent, "q"), (below, "q/x")):
            if hasattr(got, "materialize"):
                got = got.materialize()
            assert sorted(got.inline_subscriptions) == sorted(
                index.subscribers(topic).inline_subscriptions
            )
        assert sorted(index.subscribers("q").inline_subscriptions) == []
        assert sorted(index.subscribers("q/x").inline_subscriptions) == [9]

    def test_where_mochis_last_plus_rule_and_the_reference_differ(self, corner_matcher):
        """``m/+/#`` against ``m/b``: the spec's rule (and
        ``reference.filter_matches``) says the parent level matches;
        mochi's walk (topics.go:612, ``ops/flat.py``'s ``last_plus``)
        does not gather a ``#`` child below a ``+``. The program holds
        mochi's rule, on the device as on the host; ``deep-hash-1m`` has
        no ``+``, so no cell sees the difference. Left as it is."""
        index, m = corner_matcher
        assert reference.filter_matches(levels("m/+/#"), levels("m/b"))
        (result,) = m.match_topics(["m/b"])
        assert "plus" not in answer_of(result)
        assert "plus" not in index.subscribers("m/b").subscriptions
        (result,) = m.match_topics(["m/b/c"])
        assert "plus" in answer_of(result)  # below the parent level they agree


# -- the deployment's generator -------------------------------------------------------


class TestTheDeployment:
    def test_plan_and_topics_replay_from_the_seed_in_another_process(self):
        seed = 2**31 + 11
        code = (
            "import json, sys, itertools; sys.path.insert(0, 'benchmark');"
            "from deployments import deephash;"
            f"p = json.load(open('benchmark/configs/deep-hash-1m.json'))['rehearse_params'];"
            f"plan = deephash.plan(p, {seed}, 3);"
            f"t = list(itertools.islice(deephash.topics(p, {seed}, 2), 300));"
            "bad = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', 'mqtt_tpu'))];"
            "print(json.dumps([plan, t, bad]))"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
            env={**os.environ, "PYTHONHASHSEED": "77"}, check=True,
        ).stdout
        plan, topics, bad = json.loads(out)
        assert bad == []  # pure Python: neither jax nor the program
        mine = deephash.plan(REHEARSE, seed, 3)
        assert [tuple(s) for s in plan["subscriptions"]] == mine["subscriptions"]
        assert plan["live"] == mine["live"] and plan["publishers"] == mine["publishers"]
        stream = deephash.topics(REHEARSE, seed, 2)
        assert topics == [next(stream) for _ in range(300)]

    @pytest.mark.parametrize("seed", [0, 31, 2**31 + 7])
    def test_row_i_is_a_function_of_seed_and_i(self, seed):
        plan = deephash.plan(REHEARSE, seed, 1)
        subs = plan["subscriptions"]
        for i in (0, 1, 999, len(subs) - 1):
            assert deephash.row_filter(REHEARSE, seed, i) == subs[i][1]
            assert subs[i][0] == f"cl{i}" and subs[i][2] == i % 3
        shorter = deephash.plan({**REHEARSE, "subscriptions": 500}, seed, 1)
        assert shorter["subscriptions"] == subs[:500]
        other = deephash.plan(REHEARSE, seed + 1, 1)["subscriptions"]
        assert sum(a[1] == b[1] for a, b in zip(subs, other)) < len(subs) // 100

    def test_the_fleets_shape(self):
        """8 levels; 5% end in ``#``, cut at depths 1-7 evenly; the live
        rows are the first 80 that do and the first 20 that do not."""
        plan = deephash.plan(REHEARSE, 7, 32)
        subs = plan["subscriptions"]
        assert len(subs) == REHEARSE["subscriptions"] and len(plan["publishers"]) == 32
        hashed = [f for _c, f, _q in subs if f.endswith("/#")]
        assert all(f.count("/") == 7 for _c, f, _q in subs if not f.endswith("#"))
        assert 0.04 < len(hashed) / len(subs) < 0.06
        depths = [f.count("/") for f in hashed]
        assert set(depths) == set(range(1, 8))
        assert all(90 < depths.count(d) < 200 for d in range(1, 8))
        live = [subs[row][1] for row in plan["live"]]
        assert sum(f.endswith("#") for f in live) == REHEARSE["live_hash"]
        assert sum(not f.endswith("#") for f in live) == REHEARSE["live_exact"]
        first_hash = [i for i, s in enumerate(subs) if s[1].endswith("#")][:80]
        assert set(first_hash) <= set(plan["live"])

    def test_the_pools_share_of_subscribed_paths(self):
        """Every fourth pool topic is a subscribed path: all 8 levels, and
        matched by the row it was drawn from; the stream draws from the
        pool alone."""
        seed = 2**31 + 7
        full = reference.FilterSet(deephash.plan(REHEARSE, seed, 1)["subscriptions"])
        pool = deephash.pool(REHEARSE, seed, 5)
        assert len(pool) == REHEARSE["topics_per_publisher"]
        assert all(t.count("/") == 7 and "#" not in t and "+" not in t for t in pool)
        exact = {f for f in full.by_filter if not f.endswith("#")}
        subscribed = [t for j, t in enumerate(pool) if j % 4 == 3]
        assert all(full.matches(t) for t in subscribed)
        assert sum(t in exact for t in subscribed) > len(subscribed) * 0.8
        # a uniform topic names an exact filter by chance alone: 19,000
        # of them over 20 x 4^7 paths here (6%), one in 23 million at 1M
        assert sum(t in exact for j, t in enumerate(pool) if j % 4 != 3) < 30
        stream = deephash.topics(REHEARSE, seed, 5)
        drawn = {next(stream) for _ in range(4000)}
        assert drawn <= set(pool) and len(drawn) > len(pool) * 0.9
        assert deephash.pool(REHEARSE, seed, 6) != pool

    def test_eight_patterns_with_the_seven_hash_depths(self):
        """The built index at the rehearse size probes P = 8 shapes: one
        EXACT at depth 8 and HASH at depths 1-7, none with a ``+``."""
        index = TopicsIndex()
        staging.bulk_register(index, (
            (c, Subscription(filter=f, qos=q))
            for c, f, q in deephash.plan(REHEARSE, 31, 1)["subscriptions"]
        ))
        flat = build_flat_index(index)
        shapes = sorted(zip(
            (int(k) for k in flat.pat_kind), (int(d) for d in flat.pat_depth),
            (int(m) for m in flat.pat_mask),
        ))
        assert shapes == sorted(
            [(KIND_EXACT, 8, 0)] + [(KIND_HASH, d, 0) for d in range(1, 8)]
        )
        assert flat.max_levels == 8

    def test_the_configuration_file(self):
        assert CONFIG["source"] == (
            "BASELINE.json configs[2] (xyzj/mqtt-server graft): 1M subs, deep "
            "8-level topics, 5% '#' multi-level wildcards; vocabulary and cut as "
            "bench.build_cfg3 (git 12c7918:bench.py:212-235), data from --seed"
        )
        assert len(CONFIG["source"]) == 198
        assert list(CONFIG["reduced"]) == ["live_clients"]
        p = CONFIG["params"]
        assert (p["subscriptions"], p["levels"], p["hash_share"]) == (1_000_000, 8, 0.05)
        assert (p["top_vocabulary"], p["level_vocabulary"]) == (1000, 30)
        assert CONFIG["control"]["fanout_cap"] == 4 and len(CONFIG["guarantees"]) == 5
        with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
            manifest = json.load(f)
        entry = next(c for c in manifest["configs"] if c["name"] == "deep-hash-1m")
        assert entry["source"] == CONFIG["source"] and entry["reduced"] == ["live_clients"]
