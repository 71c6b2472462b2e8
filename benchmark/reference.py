"""The plain reference: who must receive what, from the filter list and
the spec's matching rule alone.

Imports nothing of the program and takes nothing the program made. The
authority is ``filter_matches`` ([MQTT-4.7.1], [MQTT-4.7.2-1]) — a loop
over levels. ``FilterSet`` answers the same question for a million
filters by asking, for one topic, every filter text that COULD match it
(each level literal or ``+``, or a ``#`` after any prefix) in a dict of
the filters that exist; ``tests/test_reference.py`` holds the two equal.
"""

from __future__ import annotations

import zlib
from itertools import product


def filter_matches(flt: tuple, topic: tuple) -> bool:
    """MQTT filter semantics on pre-split levels. ``#`` matches the
    parent level too (``a/#`` matches ``a``); a filter that starts with
    a wildcard matches no topic that starts with ``$``."""
    if topic and topic[0][:1] == "$" and flt and flt[0] in ("+", "#"):
        return False
    for i, f in enumerate(flt):
        if f == "#":
            return True
        if i >= len(topic) or (f != "+" and f != topic[i]):
            return False
    return len(flt) == len(topic)


def candidate_filters(topic: str):
    """Every filter text that matches ``topic`` under the rule above."""
    levels = topic.split("/")
    dollar = levels[0][:1] == "$"
    for k in range(len(levels) + 1):
        head = levels[:k]
        first = [(head[0],)] if dollar and head else None
        choices = [(lvl, "+") for lvl in head]
        if first:
            choices[0] = first[0]
        for combo in product(*choices):
            if k == len(levels):
                yield "/".join(combo)
            if not (dollar and k == 0):
                yield "/".join(combo + ("#",))


class FilterSet:
    """``(client, filter, qos)`` subscriptions, answering ``matches(topic)``
    with every ``(key, qos)`` whose filter matches, in the order the
    subscriptions were given. ``key`` is whatever the caller keyed a
    subscription by (a client id, a row number). A client holds one
    subscription per filter: a later one for the same pair replaces the
    earlier ([MQTT-3.8.4-3])."""

    def __init__(self, subscriptions) -> None:
        self.by_filter: dict = {}
        self.order: dict = {}
        for n, (key, flt, qos) in enumerate(subscriptions):
            self.by_filter.setdefault(flt, {})[key] = qos
            self.order.setdefault(key, n)
        self._memo: dict = {}

    def matches(self, topic: str) -> list:
        hit = self._memo.get(topic)
        if hit is None:
            best: dict = {}
            for flt in candidate_filters(topic):
                for key, qos in self.by_filter.get(flt, {}).items():
                    # one client matched by several of its filters gets
                    # ONE copy at the highest of their QoS ([MQTT-3.3.5-1]
                    # allows either; the program, like mochi, merges)
                    if qos > best.get(key, -1):
                        best[key] = qos
            order = self.order
            hit = sorted(best.items(), key=lambda kv: order[kv[0]])
            if len(self._memo) < 2_000_000:
                self._memo[topic] = hit
        return hit


def topic_tag(topic: bytes) -> int:
    """16 bits of the topic a delivery carried: an altered topic shows in
    the record without the record carrying the text."""
    return zlib.crc32(topic) & 0xFFFF


def pack_delivery(publisher: int, seq: int, qos: int, dup: int, tag: int) -> int:
    """One delivery as one integer, the unit the comparison works in."""
    return (publisher << 51) | (seq << 19) | (tag << 3) | (qos << 1) | dup


def unpack_delivery(rec: int) -> dict:
    return {
        "publisher": rec >> 51, "seq": (rec >> 19) & 0xFFFFFFFF,
        "tag": (rec >> 3) & 0xFFFF, "qos": (rec >> 1) & 3, "dup": rec & 1,
    }


def expected_deliveries(live: FilterSet, sent, cap=None, full=None) -> dict:
    """What each live subscriber must have received, in arrival order per
    publisher: ``{key: {publisher: [packed, ...]}}``. ``sent`` yields
    ``(publisher, seq, topic, qos)`` in each publisher's send order.
    Delivery QoS is ``min(publish, subscription)``; nothing is a
    duplicate. ``cap`` is the CONTROL's broken guarantee: a fan-out cut
    to the last ``cap`` matched subscriptions (in load order) of ``full``, the FilterSet
    over every subscription of the deployment, keyed as ``live`` is.
    None is the reference."""
    out: dict = {}
    tags: dict = {}
    for publisher, seq, topic, qos in sent:
        targets = live.matches(topic)
        if not targets:
            continue
        if cap is not None:
            kept = {k for k, _q in full.matches(topic)[-cap:]}
            targets = [t for t in targets if t[0] in kept]
        tag = tags.get(topic)
        if tag is None:
            tag = tags[topic] = topic_tag(topic.encode())
        for key, sub_qos in targets:
            rec = pack_delivery(publisher, seq, min(qos, sub_qos), 0, tag)
            out.setdefault(key, {}).setdefault(publisher, []).append(rec)
    return out


def compare_deliveries(expected: dict, received: dict) -> dict:
    """Per subscriber and publisher the two sequences must be EQUAL:
    same deliveries, same order, each once, at the right QoS with the
    right topic. ``received`` is ``{key: [packed, ...]}`` in arrival
    order. Returns counts; ``errors`` is their sum."""
    missing = surplus = misordered = 0
    first: list = []
    for key in set(expected) | set(received):
        got: dict = {}
        for rec in received.get(key, ()):
            got.setdefault(rec >> 51, []).append(rec)
        want = expected.get(key, {})
        for publisher in set(want) | set(got):
            w, g = want.get(publisher, []), got.get(publisher, [])
            if w == g:
                continue
            ws, gs = set(w), set(g)
            m, s = len(ws - gs), len(gs - ws) + (len(g) - len(gs))
            missing += m
            surplus += s
            if not m and not s:
                misordered += 1
            if len(first) < 5:
                odd = (
                    sorted(ws ^ gs) or [b for a, b in zip(w, g) if a != b] or g[-1:]
                )
                first.append({"subscriber": key, **unpack_delivery(odd[0])})
    return {
        "errors": missing + surplus + misordered, "missing": missing,
        "surplus": surplus, "misordered": misordered, "first": first,
    }


def compare_match_sets(full: FilterSet, topics, answers) -> dict:
    """The match plane's whole answer for sampled topics — every matched
    subscription of the deployment, socket or none — against the
    reference: ``answers[i]`` is ``{client: qos}`` for ``topics[i]``."""
    errors = 0
    first: list = []
    for topic, answer in zip(topics, answers):
        want = full.matches(topic)
        if dict(want) != answer:
            errors += 1
            if len(first) < 3:
                first.append({
                    "topic": topic, "want": len(want), "got": len(answer),
                })
    return {"errors": errors, "sampled": len(topics), "first": first}


def control_answers(control: dict, live, full, expected, sent, sample) -> tuple:
    """The control: the reference's own answers with ONE guarantee of the
    configuration broken, put where the program's answers go. It has to
    come out as not correct. ``control`` is the configuration's block:
    ``fanout_cap`` cuts every fan-out to that many matched subscriptions
    (breaks "exact subscriber sets"); ``drop_every`` loses every n-th
    delivery (breaks "every message comes back"). Returns ``(received,
    match-plane answers)`` in the shapes ``compare_deliveries`` and
    ``compare_match_sets`` take."""
    if control.get("fanout_cap") is not None:
        cap = int(control["fanout_cap"])
        broken = expected_deliveries(live, sent, cap=cap, full=full)
        answers = [dict(full.matches(t)[-cap:]) for t in sample]
    else:
        every = int(control["drop_every"])
        broken, answers, n = {}, [], 0
        for key, by in expected.items():
            for publisher, recs in by.items():
                kept = []
                for rec in recs:
                    n += 1
                    if n % every:
                        kept.append(rec)
                broken.setdefault(key, {})[publisher] = kept
    received = {
        key: [r for _p, recs in sorted(by.items()) for r in recs]
        for key, by in broken.items()
    }
    return received, answers
