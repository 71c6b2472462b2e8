"""``shared-100k`` (BASELINE.json configs[3]: 100,000 ``$share`` groups x
16 members, QoS 1; MQTT 5.0 section 4.8.2) at the rehearse size on the
CPU backend: the deployment's plan and streams
(``benchmark/deployments/shared.py``), the device matcher's shared groups
against the plain reference (``benchmark/reference.py``), two groups on one
filter as one wide entry, the served path over live sockets held to "one
member a group", and the counters of group selection
(``share_groups``, ``share_picks_offline``, ``share_select_ns``,
``share_groups_early``, ``view_materializations``). Answers and counts,
never a rate."""

import asyncio
import collections
import itertools
import json
import os
import subprocess
import sys

import pytest

from mqtt_tpu import Options, staging
from mqtt_tpu.ops.delta import DeltaMatcher
from mqtt_tpu.ops.flat import build_flat_index
from mqtt_tpu.ops.matcher import TpuMatcher
from mqtt_tpu.packets import CONNACK, PUBACK, PUBLISH, SUBACK, Subscription
from mqtt_tpu.server import _view_class
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
shared = load_benchmark_module("deployments/shared")

with open(os.path.join(ROOT, "benchmark/configs/shared-100k.json"), encoding="utf-8") as f:
    CONFIG = json.load(f)
PARAMS, REHEARSE = CONFIG["params"], CONFIG["rehearse_params"]
# the served path's size: 8 inner filters for 24 groups, so every live
# group shares its filter with offline ones (a wide entry each)
SERVED = {**REHEARSE, "groups": 24, "vocabulary": 2, "live_groups": 2, "publishers": 2}
COUNTERS = ("share_groups", "share_picks_offline", "share_select_ns", "share_groups_early")


def answer_of(result) -> tuple:
    """As ``benchmark/run.py``'s ``match_plane_answers`` reads a result."""
    if hasattr(result, "materialize"):
        result = result.materialize()
    return (
        {c: s.qos for c, s in result.subscriptions.items()},
        {g: {c: s.qos for c, s in m.items()} for g, m in result.shared.items()},
    )


def loaded(rows) -> TopicsIndex:
    index = TopicsIndex()
    staging.bulk_register(index, ((c, Subscription(filter=f, qos=q)) for c, f, q in rows))
    return index


# -- the deployment ------------------------------------------------------------------


class TestThePlan:
    @pytest.mark.parametrize("seed", [0, 41, 2**31 + 41])
    def test_sixteen_rows_a_group_in_load_order(self, seed):
        """2,000 groups x 16 rows at the rehearse size, group by group,
        every row ``$share/grp<g>-<tag>/<region>/<device>/<metric>`` at
        QoS 1 (the spec's spelling, which the reference reads as a
        group), then the offline dashboard's ``$SYS/#`` at QoS 0."""
        plan = shared.plan(REHEARSE, seed, None)
        subs = plan["subscriptions"]
        assert len(subs) == 2000 * 16 + 1
        tag = shared._tag(seed)
        for g in (0, 1, 999, 1999):
            rows = subs[16 * g : 16 * g + 16]
            assert [c for c, _f, _q in rows] == [f"g{g}m{m}-{tag}" for m in range(16)]
            assert len({f for _c, f, _q in rows}) == 1 and {q for *_, q in rows} == {1}
            name, inner = reference.share_group(rows[0][1])
            assert name == f"grp{g}-{tag}" and rows[0][1].startswith("$share/")
            region, device, metric = inner.split("/")
            assert (region[:6], device[:6], metric[:6]) == ("region", "device", "metric")
        assert subs[-1] == ("ops-dashboard", "$SYS/#", 0)
        assert reference.FilterSet(subs).shared().keys() == set(
            shared._inner(seed, 2000, REHEARSE["vocabulary"])
        )

    @pytest.mark.parametrize("seed", [3, 2**31 + 3])
    def test_fully_live_groups_one_row_a_client(self, seed):
        """The live rows are all 16 members of each of the 8 live groups,
        one row a client; the publishers are connections of their own."""
        plan = shared.plan(REHEARSE, seed, 32)
        subs, live = plan["subscriptions"], plan["live"]
        assert len(live) == 8 * 16 == len({subs[row][0] for row in live})
        groups = collections.Counter(subs[row][1] for row in live)
        assert len(groups) == 8 and set(groups.values()) == {16}
        for flt in groups:  # every member of a live group is live
            assert all(row in live for row, s in enumerate(subs) if s[1] == flt)
        pubs = plan["publishers"]
        assert pubs == [f"pub-{shared._tag(seed)}-{k}" for k in range(8)]
        assert not set(pubs) & {c for c, _f, _q in subs}

    @pytest.mark.parametrize("seed", [5, 2**31 + 5])
    def test_three_of_four_publishes_name_a_live_group(self, seed):
        """Every 4th publish names a group drawn from all of them, the
        others a live group's filter, uniformly; a stream is a function of
        the seed and the publisher."""
        plan = shared.plan(REHEARSE, seed, None)
        full = reference.FilterSet(plan["subscriptions"])
        hot = {plan["subscriptions"][row][1].split("/", 2)[2] for row in plan["live"]}
        stream = list(itertools.islice(shared.topics(REHEARSE, seed, 2), 4000))
        assert all(t in hot for i, t in enumerate(stream) if i % 4 != 3)
        cold = [t for i, t in enumerate(stream) if i % 4 == 3]
        assert sum(t in hot for t in cold) < len(cold) // 10
        assert len(set(cold)) > 600  # drawn from all 2,000 groups
        assert collections.Counter(t for t in stream if t in hot).most_common(1)[0][1] < 500
        assert all(full.groups(t) for t in stream)  # every publish is due a group
        assert stream == list(itertools.islice(shared.topics(REHEARSE, seed, 2), 4000))
        assert stream != list(itertools.islice(shared.topics(REHEARSE, seed, 3), 4000))
        assert stream != list(itertools.islice(shared.topics(REHEARSE, seed + 1, 2), 4000))

    def test_plan_and_topics_replay_from_the_seed_in_another_process(self):
        seed = 2**31 + 11
        code = (
            "import json, sys, itertools; sys.path.insert(0, 'benchmark');"
            "from deployments import shared;"
            "p = json.load(open('benchmark/configs/shared-100k.json'))['rehearse_params'];"
            f"plan = shared.plan(p, {seed}, None);"
            f"t = [list(itertools.islice(shared.topics(p, {seed}, k), 40)) for k in range(8)];"
            "bad = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', 'mqtt_tpu'))];"
            "print(json.dumps([plan, t, bad]))"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
            env={**os.environ, "PYTHONHASHSEED": "77"}, check=True,
        ).stdout
        plan, topics, bad = json.loads(out)
        assert bad == []  # pure Python: neither jax nor the program
        mine = shared.plan(REHEARSE, seed, None)
        assert [tuple(s) for s in plan["subscriptions"]] == mine["subscriptions"]
        assert plan["live"] == mine["live"] and plan["publishers"] == mine["publishers"]
        for k in range(8):
            assert topics[k] == list(itertools.islice(shared.topics(REHEARSE, seed, k), 40))

    def test_the_dollar_sys_row_keeps_the_device_path(self):
        """Every inner filter is exact: without the dashboard's ``$SYS/#``
        the build answers every topic from ``exact_map`` on the host."""
        subs = shared.plan(REHEARSE, 7, None)["subscriptions"]
        assert build_flat_index(loaded(subs)).exact_map is None
        assert build_flat_index(loaded(subs[:-1])).exact_map is not None

    @pytest.mark.parametrize("seed", [1, 2**31 + 1])
    def test_the_full_size_draws_two_groups_on_a_filter_about_4700_times(self, seed):
        """100,000 inner filters over 10^6: by the birthday count about
        4,700 filters hold two groups or more (a wide entry of 32 ids or
        more) and about a tenth of the publishes name one."""
        inner = shared._inner(seed, PARAMS["groups"], PARAMS["vocabulary"])
        per = collections.Counter(inner)
        assert 94_000 < len(per) < 96_500
        assert 4_300 < sum(n >= 2 for n in per.values()) < 5_100
        stream = list(itertools.islice(shared.topics(PARAMS, seed, 0), 20_000))
        assert 0.05 < sum(per[t] >= 2 for t in stream) / len(stream) < 0.16
        assert len(shared.live_groups(PARAMS, seed)) == 64


# -- the match plane against the reference --------------------------------------------


@pytest.fixture(scope="module")
def rehearsed():
    plan = shared.plan(REHEARSE, 40, None)
    # the streams' topics, every group's filter, and topics no group holds
    topics = sorted(
        {t for k in range(8) for t in itertools.islice(shared.topics(REHEARSE, 40, k), 300)}
        | set(shared._inner(40, REHEARSE["groups"], REHEARSE["vocabulary"]))
        | {f"region{i}/device{i}/metric{i}/x" for i in range(20)}
        | {f"region{i}/device{i}" for i in range(20)}
    )
    return plan, loaded(plan["subscriptions"]), topics


class TestTheMatchPlane:
    @pytest.mark.parametrize("matcher", ["delta", "tpu", "tpu_compact"])
    def test_groups_equal_the_reference(self, rehearsed, matcher):
        """The served matchers' whole answer (no plain subscription, the
        groups with all 16 members) equals ``FilterSet.groups`` over all
        32,001 rows on every distinct topic of the streams, wide entries
        of two groups or more among them."""
        plan, index, topics = rehearsed
        if matcher == "delta":
            m = DeltaMatcher(index, background=False)
        else:
            m = TpuMatcher(index, window=16, compact=matcher == "tpu_compact")
        answers = [answer_of(r) for r in m.match_topics(topics)]
        full = reference.FilterSet(plan["subscriptions"])
        plain = [a for a, _g in answers]
        groups = [g for _a, g in answers]
        verdict = reference.compare_match_sets(full, topics, plain, groups)
        assert verdict["errors"] == 0 and verdict["sampled"] == len(topics) > 1000, verdict
        assert all(not a for a in plain)
        assert all(len(members) == 16 for g in groups for members in g.values())
        assert sum(len(g) >= 2 for g in groups) > 50  # topics that hit a wide entry
        groups[next(i for i, g in enumerate(groups) if g)] = {}
        assert reference.compare_match_sets(full, topics, plain, groups)["errors"] == 1

    def test_two_groups_on_one_filter_are_one_wide_entry(self):
        """Two groups of 16 on one filter: 32 ids, past the 16-id window,
        answered from the device as one wide entry (``FLAG_WIDE``), both
        groups whole."""
        rows = [(f"a{m}", "$share/ga/region1/device2/metric3", 1) for m in range(16)]
        rows += [(f"b{m}", "$share/gb/region1/device2/metric3", 1) for m in range(16)]
        rows += [("c", "$share/gc/region1/device2/metric4", 1), ("ops", "$SYS/#", 0)]
        index = loaded(rows)
        flat = build_flat_index(index)
        assert (flat.n_wide, flat.max_width) == (1, 32)
        m = TpuMatcher(index, window=16)
        topics = ["region1/device2/metric3", "region1/device2/metric4", "region1/device2/x"]
        answers = [answer_of(r) for r in m.match_topics(topics)]
        assert m.stats.host_fallbacks == 0 and m.stats.wide_topics == 1
        full = reference.FilterSet(rows)
        verdict = reference.compare_match_sets(
            full, topics, [a for a, _g in answers], [g for _a, g in answers]
        )
        assert verdict["errors"] == 0, verdict
        assert {g: len(ms) for g, ms in answers[0][1].items()} == {
            "$share/ga/region1/device2/metric3": 16, "$share/gb/region1/device2/metric3": 16,
        }
        assert answers[1][1] == {"$share/gc/region1/device2/metric4": {"c": 1}}
        assert answers[2] == ({}, {})


# -- the served path over live sockets ------------------------------------------------


def serve(seed, publishes=40):
    """``shared.plan`` at ``SERVED``'s size in a broker with the device
    matcher, loaded by the restore's route as ``benchmark/run.py`` loads
    it; every live member connects over loopback TCP (v4, clean),
    subscribes to its own row and acknowledges each QoS1 delivery; each
    publisher writes ``publishes`` QoS1 frames of its stream. Returns
    what the sockets saw as the reference's packed records and the
    broker's counters over the publishes."""
    plan = shared.plan(SERVED, seed, None)
    subs = plan["subscriptions"]
    sent = [
        (k, seq, topic, 1)
        for k in range(len(plan["publishers"]))
        for seq, topic in zip(range(publishes), shared.topics(SERVED, seed, k))
    ]

    async def connect(port, cid, received, counts, row=None):
        r, w = await asyncio.open_connection("127.0.0.1", port)
        w.write(connect_packet(cid))
        assert (await read_wire_packet(r)).fixed_header.type == CONNACK
        if row is not None:
            _c, flt, qos = subs[row]
            w.write(sub_packet(1, [Subscription(filter=flt, qos=qos)]))
            ack = await read_wire_packet(r)
            assert ack.fixed_header.type == SUBACK and ack.reason_codes == bytes([qos])
        got = received.setdefault(cid, [])

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

        return w, asyncio.ensure_future(read())

    def counters(srv):
        stats = srv.matcher.stats
        return dict(srv._slice_counters(), topics=stats.topics,
                    host_fallbacks=stats.host_fallbacks,
                    dropped=srv.info.messages_dropped)

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
        counts = {"pubacks": 0}
        conns = [
            await connect(port, subs[row][0], received, counts, row)
            for row in plan["live"]
        ]
        pubs = [await connect(port, cid, {}, counts) for cid in plan["publishers"]]
        srv.matcher.flush()
        # a live member's SUBSCRIBE replaced its row: the table is the plan
        assert srv.topics.held == len(subs)
        full = reference.FilterSet(subs)
        due = reference.expected_groups(full, iter(sent))
        c0 = counters(srv)
        for k, (w, _task) in enumerate(pubs):
            w.write(b"".join(
                pub_packet(topic, b"%d:%d" % (k, seq), qos=qos, pid=seq + 1)
                for kk, seq, topic, qos in sent if kk == k
            ))
        stage = srv._stage
        for _ in range(600):
            if (
                counts["pubacks"] >= len(sent)
                and stage.pending_depth == 0 and stage.inflight_batches == 0
            ):
                break
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.3)  # a surplus delivery would arrive now
        c1 = counters(srv)
        srv.publish_sys_topics()
        sys_topics = {
            p.topic_name: bytes(p.payload).decode()
            for p in srv.topics.messages("$SYS/#")
        }
        metrics = srv.telemetry.registry.exposition()
        for w, task in conns + pubs:
            task.cancel()
            w.close()
        await srv.close()
        await h.shutdown()
        return {
            "plan": plan, "sent": sent, "due": due, "received": received,
            "pubacks": counts["pubacks"], "after": c1, "metrics": metrics,
            "sys_topics": sys_topics,
            "delta": {k: c1[k] - c0[k] for k in c0 if isinstance(c0[k], int)},
        }

    return asyncio.run(asyncio.wait_for(scenario(), timeout=120))


@pytest.fixture(scope="module", params=[11, 2**31 + 11])
def served(request):
    return serve(request.param)


def live_groups_of(served) -> set:
    subs = served["plan"]["subscriptions"]
    return {subs[row][1] for row in served["plan"]["live"]}


class TestTheServedPath:
    def test_each_due_live_group_reaches_exactly_one_member(self, served):
        """Every publish reached exactly one member of each fully live
        group it was due, at QoS 1, once, in each publisher's order; the
        offline groups sharing its filter (a wide entry) reached no one;
        every QoS1 publish was acknowledged; the reference agrees."""
        subs = served["plan"]["subscriptions"]
        live = reference.FilterSet(subs[row] for row in served["plan"]["live"])
        verdict = reference.compare_deliveries(
            {}, served["received"], served["due"], live.order
        )
        assert verdict["errors"] == 0, verdict
        assert served["pubacks"] == len(served["sent"])
        got: dict = collections.defaultdict(list)
        for key, recs in served["received"].items():
            for rec in recs:
                d = reference.unpack_delivery(rec)
                assert d["qos"] == 1 and not d["dup"]
                got[(d["publisher"], d["seq"])].append(key)
        hot = live_groups_of(served)
        n_live_due = 0
        for ps, (_q, _t, groups) in served["due"].items():
            for flt, members in groups:
                if flt in hot:
                    n_live_due += 1
                    assert len(set(got[ps]) & set(members)) == 1, (ps, flt)
        assert n_live_due == sum(len(v) for v in got.values()) > len(served["sent"]) // 2
        # a live group shares its filter with an offline group
        assert any(
            len(groups) >= 2 and {f for f, _m in groups} & hot
            for _q, _t, groups in served["due"].values()
        )

    def test_the_share_to_control_comes_out_not_correct(self, served):
        subs = served["plan"]["subscriptions"]
        live = reference.FilterSet(subs[row] for row in served["plan"]["live"])
        full = reference.FilterSet(subs)
        received, _plain, _groups = reference.control_answers(
            CONFIG["control"], live, full, {}, iter(served["sent"]), [], served["due"]
        )
        verdict = reference.compare_deliveries({}, received, served["due"], live.order)
        assert verdict["surplus"] > 0 and verdict["missing"] == 0


class TestTheCounters:
    def test_groups_and_offline_picks_move_as_counted(self, served):
        """``share_groups`` moves by the groups each publish was due;
        ``share_picks_offline`` by those with no live member (the first
        member in insertion order is then an offline session); the
        timing stays 0 with no profiler session live; a result that holds
        groups is forced into dicts once (the C view module's count).
        With no selecting hook and no predicate rule every group is
        picked in its completion slice's prologue."""
        d = served["delta"]
        hot = live_groups_of(served)
        groups = [f for _q, _t, gs in served["due"].values() for f, _m in gs]
        assert d["share_groups"] == len(groups) > len(served["sent"])
        assert d["share_groups_early"] == d["share_groups"]
        assert d["share_picks_offline"] == sum(f not in hot for f in groups) > 0
        assert d["share_select_ns"] == 0
        assert d["topics"] == len(served["sent"]) and d["dropped"] == 0
        device = d["topics"] - d["host_fallbacks"]
        assert d["view_materializations"] == (device if _view_class() is not None else 0)

    def test_metrics_and_sys_carry_the_counters(self, served):
        after, text, tree = served["after"], served["metrics"], served["sys_topics"]
        for key in ("share_groups", "share_picks_offline", "share_groups_early"):
            assert f"mqtt_tpu_{key}_total {after[key]}" in text, key
        assert "mqtt_tpu_share_select_seconds_total 0" in text
        assert (
            f"mqtt_tpu_fanout_view_materializations_total {after['view_materializations']}"
            in text
        )
        for key in COUNTERS + ("view_materializations",):
            assert tree["$SYS/broker/fanout/" + key] == str(after[key]), key

    def test_the_timing_moves_only_while_armed(self, arm_at_serve):
        """With a profiler session live the selection leg is timed and the
        sockets see what they see without; the counts are the same."""
        arm, made = arm_at_serve
        runs = {}
        for on in (False, True):
            arm(on)
            runs[on] = serve(29, publishes=24)
        assert made and made[0].armed
        assert runs[False]["delta"]["share_select_ns"] == 0
        assert runs[True]["delta"]["share_select_ns"] > 0
        for key in ("received", "pubacks"):
            assert runs[True][key] == runs[False][key], key
        for key in ("share_groups", "share_picks_offline"):
            assert runs[True]["delta"][key] == runs[False]["delta"][key], key


# -- the configuration --------------------------------------------------------------


def test_the_configuration_file():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    (entry,) = [c for c in manifest["configs"] if c["name"] == "shared-100k"]
    assert CONFIG["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "BASELINE.json configs[3]" in entry["source"] and "4.8.2" in entry["source"]
    assert entry["reduced"] == list(CONFIG["reduced"]) == ["live_clients", "publish_rate"]
    assert CONFIG["architecture"] is None and CONFIG["deployment"] == "shared"
    assert {
        k: PARAMS[k] for k in ("groups", "members", "vocabulary", "live_groups", "publishers")
    } == {"groups": 100_000, "members": 16, "vocabulary": 100, "live_groups": 64,
          "publishers": 32}
    assert PARAMS["offline_subscriptions"] == [["ops-dashboard", "$SYS/#"]]
    assert (REHEARSE["groups"], REHEARSE["live_groups"], REHEARSE["publishers"]) == (
        2000, 8, 8,
    )
    assert CONFIG["control"]["share_to"] == 2 and CONFIG["match_plane_sample"] == 2048
    assert CONFIG["broker_options"] == {"device_matcher": True}
    assert len(CONFIG["guarantees"]) == 6
    assert {"share_spelling", "ops_dashboard", "fully_live_groups", "topic_mix",
            "load_route", "one_row_a_live_client"} <= set(CONFIG["assumed"])
    with open(os.path.join(ROOT, "benchmark/traffic/qos1.json"), encoding="utf-8") as f:
        mix = json.load(f)
    assert (mix["loop"], mix["connections"], mix["chunk"], mix["qos1_every"]) == (
        "closed", 32, 64, 1,
    )
    assert mix["payload_bytes"] == 64 and mix["generator_procs"] == 4
    assert mix["warm_ladder"] == [16, 32, 64, 128, 256, 512, 1024, 2048, 4096]
    (cell,) = [w for w in manifest["workloads"] if w["config"] == "shared-100k"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == ("shared-100k.qos1", "qos1", 1)
    mine = {m["name"] for m in manifest["per_layer"] if cell["name"] in m["workloads"]}
    assert {"share_select_us_per_group", "share_offline_pick_share",
            "view_materialize_share", "wide_resolved_share", "ack_run_share"} <= mine
