"""Delta-staged matcher conformance: under arbitrary churn the DeltaMatcher
must stay bit-identical to the live host trie at every instant, without
recompiling the CSR on the match path (SURVEY.md §7 stage 5, hard part #2)."""

import random
import threading
import time

import pytest

from mqtt_tpu.ops.delta import DeltaMatcher
from mqtt_tpu.packets import Subscription
from mqtt_tpu.staging import bulk_register
from mqtt_tpu.topics import SHARE_PREFIX, InlineSubscription, TopicsIndex

from tests.test_ops_matcher import canon


def test_parity_without_churn():
    index = TopicsIndex()
    index.subscribe("cl1", Subscription(filter="a/b/c", qos=1))
    index.subscribe("cl2", Subscription(filter="a/+/c", qos=2, identifier=7))
    index.subscribe("cl3", Subscription(filter="#"))
    m = DeltaMatcher(index, background=False)
    for topic in ["a/b/c", "a/x/c", "x", "$SYS/x"]:
        assert canon(m.subscribers(topic)) == canon(index.subscribers(topic)), topic
    assert m.pending_deltas == 0


def test_churn_routes_affected_topics_to_host():
    index = TopicsIndex()
    index.subscribe("old", Subscription(filter="a/b", qos=1))
    m = DeltaMatcher(index, background=False)
    assert canon(m.subscribers("a/b")) == canon(index.subscribers("a/b"))

    # mutations after the snapshot: results must reflect them immediately
    index.subscribe("new", Subscription(filter="a/+", qos=2))
    index.unsubscribe("a/b", "old")
    assert m.pending_deltas == 2
    subs = m.subscribers("a/b")
    assert canon(subs) == canon(index.subscribers("a/b"))
    assert "new" in subs.subscriptions and "old" not in subs.subscriptions

    # unaffected topics still serve from the stale snapshot
    index.subscribe("z", Subscription(filter="zzz/zzz"))
    assert canon(m.subscribers("a/b")) == canon(index.subscribers("a/b"))


def test_flush_folds_deltas_into_new_snapshot():
    index = TopicsIndex()
    index.subscribe("cl1", Subscription(filter="a/b"))
    m = DeltaMatcher(index, background=False)
    index.subscribe("cl2", Subscription(filter="a/#"))
    index.subscribe("cl3", Subscription(filter=SHARE_PREFIX + "/g/a/b"))
    index.inline_subscribe(InlineSubscription(filter="a/+", identifier=4, handler=lambda *a: None))
    assert m.pending_deltas == 3
    m.flush()
    assert m.pending_deltas == 0
    assert canon(m.subscribers("a/b")) == canon(index.subscribers("a/b"))


def test_shared_and_inline_deltas_flag_topics():
    index = TopicsIndex()
    index.subscribe("cl1", Subscription(filter="t/1"))
    m = DeltaMatcher(index, background=False)
    index.subscribe("s1", Subscription(filter=SHARE_PREFIX + "/grp/t/1"))
    subs = m.subscribers("t/1")
    assert canon(subs) == canon(index.subscribers("t/1"))
    assert SHARE_PREFIX + "/grp/t/1" in subs.shared
    index.inline_subscribe(InlineSubscription(filter="t/#", identifier=1, handler=lambda *a: None))
    assert canon(m.subscribers("t/1")) == canon(index.subscribers("t/1"))


def test_background_rebuild_drains_overlay():
    index = TopicsIndex()
    index.subscribe("cl0", Subscription(filter="seed"))
    m = DeltaMatcher(index, background=True, rebuild_after=8)
    try:
        for i in range(32):
            index.subscribe(f"cl{i}", Subscription(filter=f"t/{i}"))
        deadline = time.time() + 20
        while m.pending_deltas >= 8 and time.time() < deadline:
            time.sleep(0.05)
        assert m.pending_deltas < 8
        for i in range(32):
            assert canon(m.subscribers(f"t/{i}")) == canon(index.subscribers(f"t/{i}"))
    finally:
        m.close()


def test_concurrent_churn_differential_fuzz():
    """Mutator thread churns the trie while the main thread matches; every
    result must equal a host walk taken after the device result (mutations
    between the two walks can only make the host MORE recent, so we only
    compare topics untouched by the racing window — tracked exactly)."""
    rng = random.Random(41)
    segs = ["a", "b", "c", "", "x", "$SYS", "node"]

    def rand_topic(r):
        return "/".join(r.choice(segs) for _ in range(r.randint(1, 4)))

    def rand_filter(r):
        parts = [r.choice(segs + ["+"]) for _ in range(r.randint(1, 4))]
        if r.random() < 0.2:
            parts[-1] = "#"
        return "/".join(parts)

    index = TopicsIndex()
    for i in range(300):
        index.subscribe(f"cl{i}", Subscription(filter=rand_filter(rng), qos=rng.randint(0, 2)))
    m = DeltaMatcher(index, background=True, rebuild_after=64)
    stop = threading.Event()

    def mutate():
        r = random.Random(97)
        i = 300
        while not stop.is_set():
            if r.random() < 0.5:
                index.subscribe(f"m{i}", Subscription(filter=rand_filter(r), qos=1))
                i += 1
            else:
                index.unsubscribe(rand_filter(r), f"m{r.randint(300, max(301, i))}")
            time.sleep(0.001)

    t = threading.Thread(target=mutate, daemon=True)
    t.start()
    try:
        for _ in range(150):
            topic = rand_topic(rng)
            v0 = index.version
            dev = m.subscribers(topic)
            host = index.subscribers(topic)
            if index.version != v0:
                continue  # a mutation raced the two walks; not comparable
            assert canon(dev) == canon(host), topic
    finally:
        stop.set()
        t.join(timeout=5)
    try:
        # churn stopped: every remaining overlay delta must still route
        # correctly — these comparisons are race-free and always run
        for _ in range(100):
            topic = rand_topic(rng)
            assert canon(m.subscribers(topic)) == canon(index.subscribers(topic)), topic
    finally:
        m.close()


def test_inline_wildcard_delta_flags_dollar_topics():
    """An inline delta on '#' must flag $-topics: inline gathers are exempt
    from the MQTT-4.7.1 $-exclusion, so recording it as a client sub in the
    overlay would silently serve stale results (code-review regression)."""
    index = TopicsIndex()
    index.subscribe("cl1", Subscription(filter="seed"))
    m = DeltaMatcher(index, background=False)
    index.inline_subscribe(InlineSubscription(filter="#", identifier=5, handler=lambda *a: None))
    subs = m.subscribers("$SYS/broker/uptime")
    assert canon(subs) == canon(index.subscribers("$SYS/broker/uptime"))
    assert 5 in subs.inline_subscriptions
    # ...while a CLIENT delta on '#' must NOT flag $-topics (exclusion holds)
    index2 = TopicsIndex()
    index2.subscribe("cl1", Subscription(filter="seed"))
    m2 = DeltaMatcher(index2, background=False)
    index2.subscribe("cl2", Subscription(filter="#"))
    gen = m2._gen
    assert not gen.affected("$SYS/broker/uptime")
    assert canon(m2.subscribers("$SYS/broker/uptime")) == canon(
        index2.subscribers("$SYS/broker/uptime")
    )


def test_close_unregisters_observer():
    index = TopicsIndex()
    index.subscribe("cl1", Subscription(filter="a"))
    m = DeltaMatcher(index, background=False)
    m.close()
    index.subscribe("cl2", Subscription(filter="b"))
    assert m.pending_deltas == 0
    assert index._observers == []


def test_server_option_wires_delta_matcher():
    import asyncio

    from mqtt_tpu.server import Options, Server

    async def run():
        s = Server(Options(inline_client=True, device_matcher=True))
        got = []
        s.subscribe("d/+", 9, lambda cl, sub, pk: got.append(pk.payload))
        s.publish("d/1", b"hello", False, 0)
        await s.close()
        return got

    got = asyncio.run(run())
    assert got == [b"hello"]


def test_incremental_fold_parity_over_many_rounds():
    """Folds (in-place bucket edits + device scatter) must keep the
    snapshot bit-identical to a from-scratch rebuild across adds,
    removals, narrow/wide transitions, and brand-new wildcard shapes."""
    rng = random.Random(11)
    v = [f"t{i}" for i in range(12)]
    index = TopicsIndex()
    for i in range(400):
        parts = [rng.choice(v), rng.choice(v), rng.choice(v)]
        if rng.random() < 0.2:
            parts[rng.randrange(3)] = "+"
        index.subscribe(f"c{i}", Subscription(filter="/".join(parts), qos=i % 3))
    m = DeltaMatcher(index, background=False, max_levels=4)
    base_rebuilds = m.stats.rebuilds
    live = 400

    def check(tag):
        topics = ["/".join([rng.choice(v)] * 3) for _ in range(48)] + [
            f"{rng.choice(v)}/{rng.choice(v)}/{rng.choice(v)}" for _ in range(48)
        ]
        for t in topics:
            assert canon(m.subscribers(t)) == canon(index.subscribers(t)), (tag, t)

    for round_ in range(6):
        # adds (some to existing paths, some new paths)
        for i in range(40):
            parts = [rng.choice(v), rng.choice(v), rng.choice(v)]
            if rng.random() < 0.3:
                parts[rng.randrange(3)] = "+"
            index.subscribe(f"n{live}", Subscription(filter="/".join(parts), qos=1))
            live += 1
        # removals
        for i in range(20):
            index.unsubscribe(
                "/".join([rng.choice(v), rng.choice(v), rng.choice(v)]),
                f"c{rng.randrange(400)}",
            )
        m.flush()
        assert m.pending_deltas == 0
        check(round_)
    # folds actually ran (the whole point): no full rebuild after the first
    assert m.stats.folds >= 5, m.stats.as_dict()
    assert m.stats.rebuilds == base_rebuilds, m.stats.as_dict()


def test_fold_new_wildcard_shape_claims_pad_slot():
    index = TopicsIndex()
    index.subscribe("a", Subscription(filter="x/y", qos=0))
    m = DeltaMatcher(index, background=False, max_levels=4)
    r0 = m.stats.rebuilds
    # a shape that did not exist at build time: depth-3 with '+' at level 1
    index.subscribe("b", Subscription(filter="x/+/z", qos=1))
    m.flush()
    assert canon(m.subscribers("x/q/z")) == canon(index.subscribers("x/q/z"))
    assert m.stats.folds >= 1
    assert m.stats.rebuilds == r0  # pad slot claimed, no recompile-rebuild


def test_fold_wide_and_narrow_transitions():
    index = TopicsIndex()
    index.subscribe("seed", Subscription(filter="s/t", qos=0))
    m = DeltaMatcher(index, background=False, max_levels=4, window=16)
    # wide: push one path over the window
    for i in range(40):
        index.subscribe(f"sp{i}", Subscription(filter="s/t", qos=0))
    m.flush()
    assert canon(m.subscribers("s/t")) == canon(index.subscribers("s/t"))
    # narrow again: back under the window
    for i in range(40):
        index.unsubscribe("s/t", f"sp{i}")
    m.flush()
    assert canon(m.subscribers("s/t")) == canon(index.subscribers("s/t"))
    assert m.stats.folds >= 2, m.stats.as_dict()


def test_fold_empty_then_resubscribe_path():
    index = TopicsIndex()
    index.subscribe("a", Subscription(filter="e/1", qos=0))
    index.subscribe("b", Subscription(filter="e/2", qos=0))
    m = DeltaMatcher(index, background=False, max_levels=4)
    index.unsubscribe("e/1", "a")
    m.flush()
    assert canon(m.subscribers("e/1")) == canon(index.subscribers("e/1"))
    index.subscribe("c", Subscription(filter="e/1", qos=2))
    m.flush()
    assert canon(m.subscribers("e/1")) == canon(index.subscribers("e/1"))
    assert list(m.subscribers("e/1").subscriptions) == ["c"]


# -- a bulk load is one generation of the table ------------------------------


def _bulk_entries(n, on_entry=None, prefix="c"):
    for i in range(n):
        if on_entry is not None:
            on_entry(i)
        flt = f"bulk/{i % 97}/+" if i % 10 == 0 else f"bulk/{i % 97}/{i}"
        yield f"{prefix}{i}", Subscription(filter=flt, qos=i % 3)


_BULK_TOPICS = ["bulk/0/0", "bulk/3/100", "bulk/10/x", "bulk/96/96", "none/at/all"]


def _wait_for(cond, timeout=30.0):
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.01)
    return cond()


def _assert_parity(m, index, topics=_BULK_TOPICS):
    for topic in topics:
        assert canon(m.subscribers(topic)) == canon(index.subscribers(topic)), topic


def test_bulk_load_holds_rebuilds_and_builds_once_on_close():
    index = TopicsIndex()
    m = DeltaMatcher(index, background=True, rebuild_after=8, rebuild_interval=0.01)
    seen = []

    def on_entry(i):
        if i % 500 == 0:
            time.sleep(0.05)  # several interval ticks fall inside the load
            seen.append((m.stats.rebuilds, index.bulk_depth))

    try:
        assert m.stats.rebuilds == 1  # the empty table's build at start
        added, batches = bulk_register(index, _bulk_entries(3000, on_entry), batch=256)
        assert (added, batches) == (3000, 12)
        # not one rebuild while the load ran, threshold and ticks long past
        assert seen and all(s == (1, 1) for s in seen), seen
        assert index.bulk_depth == 0
        # woken by the close itself: nobody calls flush()
        assert _wait_for(lambda: m.stats.rebuilds == 2 and m.pending_deltas == 0)
        time.sleep(0.1)  # a clean overlay: further ticks build nothing
        st = m.stats
        assert (st.rebuilds, st.folds, st.bulk_loads) == (2, 0, 1)
        assert st.rebuilds_held >= 2  # the threshold once, and the ticks
        assert m.bulk_build_seconds > 0.0
        _assert_parity(m, index)
        # served from the table now, not from the host walk
        before = st.host_fallbacks
        m.match_topics(_BULK_TOPICS)
        assert st.host_fallbacks == before
    finally:
        m.close()


def test_publishes_during_a_bulk_load_resolve_to_the_exact_sets():
    """The load runs on its own thread and is paused mid-way; publishes
    from this thread, before and after a live SUBSCRIBE that is no part of
    the load, must equal the host trie's sets."""
    index = TopicsIndex()
    index.subscribe("old", Subscription(filter="bulk/0/+", qos=1))
    m = DeltaMatcher(index, background=True, rebuild_after=8, rebuild_interval=0.01)
    paused, resume = threading.Event(), threading.Event()

    def on_entry(i):
        if i == 1500:
            paused.set()
            assert resume.wait(30)

    loader = threading.Thread(
        target=bulk_register, args=(index, _bulk_entries(3000, on_entry)), kwargs={"batch": 128}
    )
    try:
        loader.start()
        assert paused.wait(30)
        assert index.bulk_depth == 1 and m.stats.rebuilds == 1
        _assert_parity(m, index)
        assert "c0" in m.subscribers("bulk/0/0").subscriptions  # loaded, unbuilt, served
        # a client subscribing mid-restore is in the overlay too
        index.subscribe("live", Subscription(filter="bulk/+/x", qos=2))
        index.unsubscribe("bulk/0/+", "old")
        got = m.subscribers("bulk/10/x")
        assert "live" in got.subscriptions
        assert canon(got) == canon(index.subscribers("bulk/10/x"))
        _assert_parity(m, index)
        assert m.stats.rebuilds == 1  # still held
        resume.set()
        loader.join(30)
        assert _wait_for(lambda: m.stats.rebuilds == 2 and m.pending_deltas == 0)
        _assert_parity(m, index)
        assert "live" in m.subscribers("bulk/10/x").subscriptions
    finally:
        resume.set()
        loader.join(30)
        m.close()


def _load_nested(index, register):
    with index.bulk_load():
        with index.bulk_load():
            register(index, _bulk_entries(600))
        yield "inner closed"
        register(index, _bulk_entries(600, prefix="d"))
        yield "second load closed"


def _load_repeated(index, register):
    with index.bulk_load():  # what a restore fed a stored batch at a time holds
        for k in range(3):
            register(index, _bulk_entries(400, prefix=f"b{k}-"))
            yield f"batch {k} closed"


def _parametrize(**cases):
    return pytest.mark.parametrize("case", list(cases.values()), ids=list(cases))


@_parametrize(nested=_load_nested, repeated=_load_repeated)
def test_bulk_loads_hold_until_the_outermost_closes(case):
    index = TopicsIndex()
    m = DeltaMatcher(index, background=True, rebuild_after=8, rebuild_interval=0.01)
    try:
        for step in case(index, bulk_register):
            time.sleep(0.05)  # ticks pass; an inner close is no close
            assert index.bulk_depth == 1, step
            assert (m.stats.rebuilds, m.stats.bulk_loads) == (1, 0), step
            _assert_parity(m, index)
        assert index.bulk_depth == 0
        assert _wait_for(lambda: m.stats.rebuilds == 2 and m.pending_deltas == 0)
        assert m.stats.bulk_loads == 1
        _assert_parity(m, index)
    finally:
        m.close()


def test_exception_inside_a_bulk_load_still_closes_it():
    index = TopicsIndex()
    m = DeltaMatcher(index, background=True, rebuild_after=8, rebuild_interval=0.01)

    def on_entry(i):
        if i == 700:
            raise OSError("the store went away")

    try:
        with pytest.raises(OSError):
            bulk_register(index, _bulk_entries(3000, on_entry), batch=256)
        assert index.bulk_depth == 0  # the hold cannot leak
        # what did load (two whole chunks) is built and served, unprompted
        assert _wait_for(lambda: m.stats.rebuilds == 2 and m.pending_deltas == 0)
        assert m.stats.bulk_loads == 1
        assert "c0" in m.subscribers("bulk/0/0").subscriptions
        _assert_parity(m, index)
        # and the threshold wakes as ever afterwards
        for i in range(8):
            index.subscribe(f"after{i}", Subscription(filter=f"after/{i}"))
        assert _wait_for(lambda: m.stats.rebuilds + m.stats.folds == 3)
    finally:
        m.close()


@_parametrize(
    threshold=dict(rebuild_after=8, rebuild_interval=3600.0, mutations=8),
    interval=dict(rebuild_after=10**9, rebuild_interval=0.01, mutations=1),
)
def test_outside_a_load_threshold_and_interval_wake_as_before(case):
    index = TopicsIndex()
    index.subscribe("cl0", Subscription(filter="seed"))
    m = DeltaMatcher(
        index,
        background=True,
        rebuild_after=case["rebuild_after"],
        rebuild_interval=case["rebuild_interval"],
    )
    try:
        for i in range(case["mutations"] - 1):
            index.subscribe(f"cl{i}", Subscription(filter=f"t/{i}"))
        if case["mutations"] > 1:
            time.sleep(0.1)
            assert m.pending_deltas == case["mutations"] - 1  # one short: asleep
        index.subscribe("last", Subscription(filter="t/last"))
        assert _wait_for(lambda: m.pending_deltas == 0)
        st = m.stats
        assert st.rebuilds + st.folds == 2
        assert (st.rebuilds_held, st.bulk_loads) == (0, 0)
        _assert_parity(m, index, ["t/0", "t/last", "seed"])
    finally:
        m.close()


@_parametrize(live=False, bulk=True)
def test_generation_after_a_whole_dirty_one_carries_what_raced_its_walk(case):
    """The hazard of keeping no list while a load is open: a mutation that
    lands after the walk began is in the trie, not in the new table, and
    must be in the new generation's overlay, per entry or whole."""
    index = TopicsIndex()
    m = DeltaMatcher(index, background=False)
    bulk_register(index, _bulk_entries(500))
    gen = m._gen
    assert (gen.held, gen.deltas, len(gen.delta_trie.root.particles)) == (500, [], 0)
    assert m.pending_deltas == 500
    offered = []
    walk = m._rebuild_snapshot

    def walk_then_race(filters=None):
        offered.append(filters)
        walk(filters)  # the table is built: what follows is not in it
        if case:
            with index.bulk_load():
                index.subscribe("late", Subscription(filter="bulk/+/late", qos=1))
        else:
            index.subscribe("late", Subscription(filter="bulk/+/late", qos=1))

    m._rebuild_snapshot = walk_then_race
    m.flush()
    m._rebuild_snapshot = walk
    assert offered == [None]  # a full rebuild: no set of filters for fold()
    gen = m._gen
    if case:
        assert (gen.held, gen.deltas) == (1, [])  # whole-dirty again
    else:
        assert (gen.held, gen.deltas) == (0, [("bulk/+/late", "sub")])
        assert gen.affected("bulk/5/late") and not gen.affected("bulk/5/5")
    got = m.subscribers("bulk/5/late")
    assert "late" in got.subscriptions
    assert canon(got) == canon(index.subscribers("bulk/5/late"))
    _assert_parity(m, index)
    m.flush()
    assert m.pending_deltas == 0 and m._gen.held == 0
    assert "late" in m.subscribers("bulk/5/late").subscriptions
    assert m.stats.rebuilds + m.stats.folds == 3
    m.close()


def test_per_entry_recording_resumes_the_instant_the_load_closes():
    index = TopicsIndex()
    m = DeltaMatcher(index, background=False)
    closed = []
    index.add_observer(lambda mu: None, lambda: closed.append(m._gen.held))
    bulk_register(index, _bulk_entries(300))
    assert closed == [300] and m.stats.bulk_loads == 1
    # before any build: the next mutation is recorded, not held
    index.subscribe("live", Subscription(filter="live/+"))
    assert (m._gen.held, m._gen.deltas) == (300, [("live/+", "sub")])
    m.flush()
    assert m.pending_deltas == 0
    index.subscribe("live2", Subscription(filter="live2/+"))
    assert m.pending_deltas == 1 and m._gen.held == 0
    before = m.stats.host_fallbacks
    _assert_parity(m, index, ["live2/x"])
    assert m.stats.host_fallbacks == before + 1  # the affected topic alone...
    _assert_parity(m, index)
    assert m.stats.host_fallbacks == before + 1  # ...and no other
    m.close()
