"""The plain reference against the spec's own examples ([MQTT-4.7.1-2],
[MQTT-4.7.1-3], [MQTT-4.7.2-1]), and its fast form against its plain form."""

import random

import pytest

import reference
from reference import FilterSet, filter_matches


def m(flt, topic):
    return filter_matches(tuple(flt.split("/")), tuple(topic.split("/")))


@pytest.mark.parametrize(
    "flt,topic,want",
    [
        # 4.7.1.2: sport/tennis/player1/#
        ("sport/tennis/player1/#", "sport/tennis/player1", True),
        ("sport/tennis/player1/#", "sport/tennis/player1/ranking", True),
        ("sport/tennis/player1/#", "sport/tennis/player1/score/wimbledon", True),
        ("sport/#", "sport", True),
        ("#", "sport/tennis", True),
        # 4.7.1.3: sport/tennis/+
        ("sport/tennis/+", "sport/tennis/player1", True),
        ("sport/tennis/+", "sport/tennis/player2", True),
        ("sport/tennis/+", "sport/tennis/player1/ranking", False),
        ("sport/+", "sport", False),
        ("sport/+", "sport/", True),
        ("+/+", "/finance", True),
        ("/+", "/finance", True),
        ("+", "/finance", False),
        # 4.7.2: topics beginning with $
        ("#", "$SYS/broker/load", False),
        ("+/monitor/Clients", "$SYS/monitor/Clients", False),
        ("$SYS/#", "$SYS/broker/load", True),
        ("$SYS/monitor/+", "$SYS/monitor/Clients", True),
        # plain
        ("a/b/c", "a/b/c", True),
        ("a/b/c", "a/b", False),
        ("a/b", "a/b/c", False),
    ],
)
def test_spec_examples(flt, topic, want):
    assert m(flt, topic) is want
    got = FilterSet([("c", flt, 1)]).matches(topic)
    assert got == ([("c", 1)] if want else [])


def test_filter_set_equals_the_loop_over_levels():
    rng = random.Random(24)
    words = ["a", "b", "c", "", "$s"]

    def levels(n):
        return [rng.choice(words) for _ in range(n)]

    subs = []
    for i in range(600):
        lv = levels(rng.randint(1, 4))
        for j in range(len(lv)):
            if rng.random() < 0.3:
                lv[j] = "+"
        if rng.random() < 0.25:
            lv.append("#")
        subs.append((f"c{i}", "/".join(lv), i % 3))
    fs = FilterSet(subs)
    for _ in range(400):
        topic = "/".join(levels(rng.randint(1, 4)))
        brute = [(c, q) for c, f, q in subs if m(f, topic)]
        assert fs.matches(topic) == brute, topic


def test_expected_deliveries_and_compare():
    live = FilterSet([("s1", "a/+", 1), ("s2", "a/b", 0)])
    sent = [(0, 0, "a/b", 1), (0, 1, "a/c", 0), (1, 0, "a/b", 0)]
    exp = reference.expected_deliveries(live, sent)
    tag_ab, tag_ac = (reference.topic_tag(t) for t in (b"a/b", b"a/c"))
    p = reference.pack_delivery
    assert exp == {
        "s1": {0: [p(0, 0, 1, 0, tag_ab), p(0, 1, 0, 0, tag_ac)],
               1: [p(1, 0, 0, 0, tag_ab)]},
        "s2": {0: [p(0, 0, 0, 0, tag_ab)], 1: [p(1, 0, 0, 0, tag_ab)]},
    }
    good = {k: [r for recs in by.values() for r in recs] for k, by in exp.items()}
    assert reference.compare_deliveries(exp, good)["errors"] == 0
    # publishers may interleave at a subscriber; one publisher may not reorder
    good["s1"] = [good["s1"][2], good["s1"][0], good["s1"][1]]
    assert reference.compare_deliveries(exp, good)["errors"] == 0
    swapped = dict(good, s1=[good["s1"][0], good["s1"][2], good["s1"][1]])
    assert reference.compare_deliveries(exp, swapped)["misordered"] == 1
    dropped = dict(good, s2=good["s2"][:1])
    assert reference.compare_deliveries(exp, dropped)["missing"] == 1
    twice = dict(good, s2=good["s2"] + good["s2"][:1])
    assert reference.compare_deliveries(exp, twice)["surplus"] == 1
    wrong_qos = dict(good, s2=[good["s2"][0] | 2, good["s2"][1]])
    out = reference.compare_deliveries(exp, wrong_qos)
    assert out["missing"] == 1 and out["surplus"] == 1
    stranger = dict(good, s3=[p(0, 0, 0, 0, tag_ab)])
    assert reference.compare_deliveries(exp, stranger)["surplus"] == 1


def test_match_set_compare_and_the_control_cap():
    subs = [(f"c{i}", "t/+", i % 3) for i in range(12)] + [("x", "t/u", 2)]
    full = FilterSet(subs)
    right = [dict(full.matches("t/u"))]
    assert reference.compare_match_sets(full, ["t/u"], right)["errors"] == 0
    capped = [dict(full.matches("t/u")[-8:])]
    assert reference.compare_match_sets(full, ["t/u"], capped)["errors"] == 1
    live = FilterSet(subs[:2])
    sent = [(0, 0, "t/u", 1)]
    assert len(reference.expected_deliveries(live, sent)) == 2
    assert reference.expected_deliveries(live, sent, cap=8, full=full) == {}
