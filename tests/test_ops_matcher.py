"""Device matcher conformance: the TPU CSR/NFA matcher must be bit-identical
to the host trie (the oracle) on the same corpora that validate the trie —
the wildcard matrix, shared groups, $-exclusions, and a randomized
differential fuzz with live churn (SURVEY.md §7 stages 4-5)."""

import random

import pytest

from mqtt_tpu import Options, Server
from mqtt_tpu.ops import TpuMatcher
from mqtt_tpu.ops.delta import DeltaMatcher
from mqtt_tpu.packets import Subscription
from mqtt_tpu.topics import SHARE_PREFIX, InlineSubscription, TopicsIndex

from tests.test_topics import FIND_MATRIX


def canon(subs):
    """Canonicalize a Subscribers result for set comparison: client -> (qos,
    no_local, sorted positive identifiers); shared -> group filters ->
    client sets; inline -> identifier set. Zero-valued identifier entries
    are excluded (Go-map zero-value semantics make them unobservable)."""
    return (
        {
            c: (s.qos, s.no_local, tuple(sorted(v for v in (s.identifiers or {c: s.identifier}).values() if v > 0)))
            for c, s in subs.subscriptions.items()
        },
        {g: frozenset(m) for g, m in subs.shared.items()},
        frozenset(subs.inline_subscriptions),
    )


@pytest.mark.parametrize("filter_,topic,matched", FIND_MATRIX, ids=[f"{f}~{t}" for f, t, _ in FIND_MATRIX])
def test_find_matrix_on_device(filter_, topic, matched):
    index = TopicsIndex()
    index.subscribe("cl1", Subscription(filter=filter_))
    matcher = TpuMatcher(index)
    subs = matcher.subscribers(topic)
    assert (len(subs.subscriptions) == 1) == matched
    assert canon(subs) == canon(index.subscribers(topic))


def test_scan_subscribers_table_on_device():
    index = TopicsIndex()
    index.subscribe("cl1", Subscription(qos=1, filter="a/b/c", identifier=22))
    index.subscribe("cl1", Subscription(qos=1, filter="a/b/c/d/e/f"))
    index.subscribe("cl1", Subscription(qos=2, filter="a/b/c/d/+/f"))
    index.subscribe("cl2", Subscription(qos=0, filter="a/#"))
    index.subscribe("cl2", Subscription(qos=1, filter="a/b/c"))
    index.subscribe("cl2", Subscription(qos=2, filter="a/b/+", identifier=77))
    index.subscribe("cl2", Subscription(qos=2, filter="d/e/f", identifier=7237))
    index.subscribe("cl2", Subscription(qos=2, filter="$SYS/uptime", identifier=3))
    index.subscribe("cl3", Subscription(qos=1, filter="+/b", identifier=234))
    index.subscribe("cl4", Subscription(qos=0, filter="#", identifier=5))
    index.subscribe("cl2", Subscription(qos=0, filter="$SYS/test", identifier=2))
    matcher = TpuMatcher(index)
    for topic in ["a/b/c", "d/e/f/g", "a/b", "$SYS/uptime", "$SYS/test", "x"]:
        assert canon(matcher.subscribers(topic)) == canon(index.subscribers(topic)), topic


def test_shared_and_inline_on_device():
    index = TopicsIndex()
    index.subscribe("cl1", Subscription(qos=1, filter=SHARE_PREFIX + "/tmp/a/b/c", identifier=111))
    index.subscribe("cl2", Subscription(qos=0, filter=SHARE_PREFIX + "/tmp/a/b/c", identifier=112))
    index.subscribe("cl3", Subscription(qos=0, filter=SHARE_PREFIX + "/tmp2/a/b/+", identifier=113))
    index.subscribe("cl4", Subscription(qos=0, filter="a/b/c"))
    index.inline_subscribe(InlineSubscription(filter="a/+/c", identifier=9, handler=lambda *a: None))
    index.inline_subscribe(InlineSubscription(filter="a/#", identifier=8, handler=lambda *a: None))
    matcher = TpuMatcher(index)
    for topic in ["a/b/c", "a/x/c", "a", "a/b"]:
        assert canon(matcher.subscribers(topic)) == canon(index.subscribers(topic)), topic


def test_inline_parent_hash_quirk_on_device():
    # an inline sub on a/# must NOT match topic "a" (topics.go:615 quirk)
    index = TopicsIndex()
    index.inline_subscribe(InlineSubscription(filter="a/#", identifier=1, handler=lambda *a: None))
    matcher = TpuMatcher(index)
    assert len(matcher.subscribers("a").inline_subscriptions) == 0
    assert len(matcher.subscribers("a/b").inline_subscriptions) == 1


def test_differential_fuzz_with_churn():
    rng = random.Random(99)
    segs = ["a", "b", "c", "dd", "", "x", "$SYS", "long-segment-name"]

    def rand_topic():
        return "/".join(rng.choice(segs) for _ in range(rng.randint(1, 6)))

    def rand_filter():
        parts = [rng.choice(segs + ["+"]) for _ in range(rng.randint(1, 6))]
        if rng.random() < 0.25:
            parts[-1] = "#"
        return "/".join(parts)

    index = TopicsIndex()
    filters = {}
    for i in range(500):
        flt = rand_filter()
        filters[f"cl{i}"] = flt
        index.subscribe(f"cl{i}", Subscription(filter=flt, qos=rng.randint(0, 2), identifier=rng.choice([0, 0, i])))
    matcher = TpuMatcher(index)

    topics = [rand_topic() for _ in range(600)]
    device = matcher.match_topics(topics)
    for topic, dev in zip(topics, device):
        host = index.subscribers(topic)
        assert canon(dev) == canon(host), topic

    # churn: unsubscribe a third, add some, then verify staleness triggers
    # rebuild and results stay identical
    for i in range(0, 500, 3):
        index.unsubscribe(filters[f"cl{i}"], f"cl{i}")
    for i in range(500, 550):
        flt = rand_filter()
        filters[f"cl{i}"] = flt
        index.subscribe(f"cl{i}", Subscription(filter=flt, qos=1))
    assert matcher.stale
    topics = [rand_topic() for _ in range(300)]
    for topic, dev in zip(topics, matcher.match_topics(topics)):
        assert canon(dev) == canon(index.subscribers(topic)), topic


def test_overflow_falls_back_to_host():
    index = TopicsIndex()
    # >out_slots matching subs on one topic forces output overflow
    for i in range(40):
        index.subscribe(f"cl{i}", Subscription(filter="hot/topic", qos=0))
    matcher = TpuMatcher(index, out_slots=16)
    subs = matcher.subscribers("hot/topic")
    assert len(subs.subscriptions) == 40

    # deep topic beyond max_levels falls back too
    deep = "/".join(["d"] * 20)
    index.subscribe("deep", Subscription(filter=deep))
    matcher2 = TpuMatcher(index, max_levels=8)
    assert "deep" in matcher2.subscribers(deep).subscriptions


def test_many_plus_forks_resolve_completely():
    index = TopicsIndex()
    # many '+' forks at each level: five wildcard shapes, one topic
    for i, flt in enumerate(["+/+/+/a", "+/+/a/+", "+/a/+/+", "a/+/+/+", "a/a/a/a"]):
        index.subscribe(f"w{i}", Subscription(filter=flt))
    matcher = TpuMatcher(index)
    subs = matcher.subscribers("a/a/a/a")
    assert len(subs.subscriptions) == 5


def test_ranges_transfer_carries_large_fanouts_without_fallback():
    """The packed ranges output carries the COMPLETE result (2P ints per
    topic), so a fan-out that would have exceeded any slot prefix still
    resolves entirely from the device — no host fallback class for it."""
    index = TopicsIndex()
    # 12 subs all matching 'hot/x'; 1 sub matching 'cold/y'
    for i in range(6):
        index.subscribe(f"e{i}", Subscription(filter="hot/x", qos=1))
        index.subscribe(f"w{i}", Subscription(filter="hot/+", qos=2))
    index.subscribe("solo", Subscription(filter="cold/y"))
    matcher = TpuMatcher(index, max_levels=4, out_slots=4)
    hot = matcher.subscribers("hot/x")
    cold = matcher.subscribers("cold/y")
    assert canon(hot) == canon(index.subscribers("hot/x"))
    assert canon(cold) == canon(index.subscribers("cold/y"))
    assert len(hot.subscriptions) == 12
    assert matcher.stats.host_fallbacks == 0
    assert matcher.stats.overflows == 0
    assert matcher.stats.topics == 2


@pytest.mark.parametrize(
    "keyword,build",
    [
        pytest.param(
            "frontier",
            lambda: TpuMatcher(TopicsIndex(), frontier=2),
            id="TpuMatcher-frontier",
        ),
        pytest.param(
            "transfer_slots",
            lambda: DeltaMatcher(
                TopicsIndex(), background=False, transfer_slots=4
            ),
            id="DeltaMatcher-transfer_slots",
        ),
        pytest.param(
            "frontier",
            lambda: Server(
                Options(device_matcher=True, matcher_opts={"frontier": 2})
            ),
            id="Server-matcher_opts-frontier",
        ),
    ],
)
def test_retired_matcher_keyword_is_refused_by_name(keyword, build):
    """The parameters of the retired NFA kernel were accepted and
    ignored for many rounds; a config that still names one must fail at
    construction, as any unknown keyword does, not be swallowed."""
    with pytest.raises(TypeError, match=keyword):
        build()


def test_saturated_bucket_routes_to_host():
    """Entries dropped from a build-saturated bucket must never produce
    false negatives: the kernel flags any probe touching the bucket and the
    topic re-walks the host trie (ops/flat.py SAT marker)."""
    import numpy as np

    from mqtt_tpu.ops.flat import _M2, KIND_EXACT, _mix_np, hash_token

    S = 1024  # build_flat_index's minimum bucket count

    def slot_of(token: str) -> int:
        a, _ = hash_token(token, 0)
        with np.errstate(over="ignore"):
            h1 = np.uint32(np.uint64(1) * np.uint64(_M2) & np.uint64(0xFFFFFFFF)) ^ np.uint32(KIND_EXACT)
            h1 = _mix_np(h1, np.uint32(a))
        return int(h1) & (S - 1)

    by_slot = {}
    colliding = None
    for i in range(200_000):
        tok = f"sat{i}"
        s = slot_of(tok)
        by_slot.setdefault(s, []).append(tok)
        if len(by_slot[s]) == 6:
            colliding = by_slot[s]
            break
    assert colliding, "no 6-way bucket collision found in 200k tokens"

    index = TopicsIndex()
    for i, tok in enumerate(colliding):
        index.subscribe(f"cl{i}", Subscription(filter=tok, qos=1))
    index.subscribe("solo", Subscription(filter="plain/topic", qos=0))
    # one wildcard filter keeps the index off the exact-map host fast path
    # (this test exercises the DEVICE path's saturation routing); it
    # matches neither the colliding tokens nor plain/topic
    index.subscribe("wild", Subscription(filter="wild/only/+", qos=0))
    matcher = TpuMatcher(index, max_levels=4)
    matcher.rebuild()
    assert matcher.csr.n_sat >= 1  # the build really saturated a bucket
    # every dropped filter still matches, via the host route
    for i, tok in enumerate(colliding):
        subs = matcher.subscribers(tok)
        assert list(subs.subscriptions) == [f"cl{i}"], tok
    assert matcher.stats.overflows >= len(colliding)
    # untouched buckets still serve from the device
    before = matcher.stats.host_fallbacks
    assert list(matcher.subscribers("plain/topic").subscriptions) == ["solo"]
    assert matcher.stats.host_fallbacks == before


def test_window_above_meta_capacity_raises():
    from mqtt_tpu.ops.flat import MAX_WINDOW, build_flat_index

    index = TopicsIndex()
    index.subscribe("c", Subscription(filter="a/b"))
    with pytest.raises(ValueError):
        build_flat_index(index, window=MAX_WINDOW + 1)


def test_duplicate_client_merge_matches_host_exactly_and_does_not_accumulate():
    """One client matching a topic through several filters must merge
    exactly like the host gather (max QoS, identifiers union, sticky
    no_local) — and repeated matching must NOT accumulate state across
    results (the expand_sids fast path copies per result; a shared
    identifiers map would leak merge products between batches)."""
    index = TopicsIndex()
    index.subscribe("dup", Subscription(filter="m/x", qos=0, identifier=7))
    index.subscribe("dup", Subscription(filter="m/+", qos=2, identifier=9, no_local=True))
    index.subscribe("dup", Subscription(filter="m/#", qos=1))
    index.subscribe("other", Subscription(filter="m/x", qos=1))
    matcher = TpuMatcher(index, max_levels=4)
    matcher.rebuild()

    host = index.subscribers("m/x")
    for attempt in range(3):  # identical every time: no accumulation
        dev = matcher.subscribers("m/x")
        assert set(dev.subscriptions) == {"dup", "other"}
        d, h = dev.subscriptions["dup"], host.subscriptions["dup"]
        assert (d.qos, d.no_local) == (h.qos, h.no_local) == (2, True)
        assert {k: v for k, v in d.identifiers.items() if v > 0} == {
            k: v for k, v in h.identifiers.items() if v > 0
        } == {"m/x": 7, "m/+": 9}, attempt
        o = dev.subscriptions["other"]
        assert (o.qos, {k: v for k, v in o.identifiers.items() if v > 0}) == (1, {})
        # result objects are fresh per match: mutating one must not bleed
        d.qos = 99
        d.identifiers["poison"] = 1
        # (the stored trie copy keeps its own map only when it had one; the
        # device result's map must at minimum not feed back into results)
        nxt = matcher.subscribers("m/x").subscriptions["dup"]
        assert nxt.qos == 2 and "poison" not in {
            k for k, v in nxt.identifiers.items() if v > 0
        }


class TestExactMapFastPath:
    """Wildcard-free filter sets answer from the host exact-map — one dict
    probe per topic, no device dispatch, no fallback classes (SURVEY §7
    hard part 4)."""

    def _index(self):
        index = TopicsIndex()
        index.subscribe("c1", Subscription(filter="a/b/c", qos=1, identifier=9))
        index.subscribe("c2", Subscription(filter="a/b/c", qos=2))
        index.subscribe("c3", Subscription(filter="x/y", qos=0))
        index.subscribe("sys", Subscription(filter="$SYS/broker/load", qos=0))
        index.subscribe(
            "m1", Subscription(filter=f"{SHARE_PREFIX}/g1/a/b/c", qos=1)
        )
        index.inline_subscribe(
            InlineSubscription(filter="x/y", identifier=5, handler=lambda *a: None)
        )
        # deeper than max_levels: the device table would drop it; the map
        # still serves it
        index.subscribe("deep", Subscription(filter="d/e/f/g/h/i", qos=1))
        return index

    def test_serves_without_device_and_matches_host(self):
        index = self._index()
        matcher = TpuMatcher(index, max_levels=4)
        matcher.rebuild()
        assert matcher.csr.exact_map is not None
        topics = ["a/b/c", "x/y", "$SYS/broker/load", "d/e/f/g/h/i", "no/match", ""]
        results = matcher.match_topics(topics)
        for topic, got in zip(topics, results):
            assert canon(got) == canon(index.subscribers(topic)), topic
        assert matcher.stats.host_fast == 5  # all but the empty topic
        assert matcher.stats.host_fallbacks == 0

    def test_wide_entry_served_from_map(self):
        index = TopicsIndex()
        for i in range(40):  # >> window: a wide entry on the device table
            index.subscribe(f"c{i}", Subscription(filter="hot/topic", qos=1))
        matcher = TpuMatcher(index, max_levels=4, window=8)
        matcher.rebuild()
        assert matcher.csr.exact_map is not None
        subs = matcher.subscribers("hot/topic")
        assert len(subs.subscriptions) == 40
        assert canon(subs) == canon(index.subscribers("hot/topic"))
        assert matcher.stats.host_fallbacks == 0

    def test_any_wildcard_disables_map(self):
        index = self._index()
        index.subscribe("w", Subscription(filter="a/+/c", qos=0))
        matcher = TpuMatcher(index, max_levels=4)
        matcher.rebuild()
        assert matcher.csr.exact_map is None
        # deep-wildcard-only sets must not sneak back onto the fast path
        index2 = TopicsIndex()
        index2.subscribe("c", Subscription(filter="a/b/c/d/e/f/+", qos=0))
        m2 = TpuMatcher(index2, max_levels=4)
        m2.rebuild()
        assert m2.csr.exact_map is None

    def test_fold_maintains_map(self):
        index = self._index()
        m = DeltaMatcher(index, max_levels=4, background=False)
        assert m._snap.csr.exact_map is not None
        index.subscribe("new", Subscription(filter="fresh/topic", qos=2))
        index.unsubscribe("x/y", "c3")
        m.flush()
        for topic in ["fresh/topic", "x/y", "a/b/c"]:
            assert canon(m.subscribers(topic)) == canon(index.subscribers(topic))
        # a folded-in wildcard drops the map and stays correct
        index.subscribe("w", Subscription(filter="fresh/+", qos=1))
        m.flush()
        assert canon(m.subscribers("fresh/topic")) == canon(
            index.subscribers("fresh/topic")
        )
        m.close()

    def test_identifier_merge_parity_on_fast_path(self):
        index = TopicsIndex()
        index.subscribe("c1", Subscription(filter="t/1", qos=1, identifier=3))
        matcher = TpuMatcher(index)
        got = matcher.subscribers("t/1").subscriptions["c1"]
        want = index.subscribers("t/1").subscriptions["c1"]
        assert got.identifiers == want.identifiers == {"t/1": 3}
