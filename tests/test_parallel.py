"""Multi-chip sharded matcher: runs on the virtual 8-device CPU mesh
(conftest sets xla_force_host_platform_device_count=8) and must be
bit-identical to the host trie."""

import random

import jax
import pytest

from mqtt_tpu.packets import Subscription
from mqtt_tpu.topics import SHARE_PREFIX, TopicsIndex
from mqtt_tpu.parallel import ShardedTpuMatcher, dryrun_multichip, make_mesh

from tests.test_ops_matcher import canon


def test_eight_virtual_devices_present():
    assert len(jax.devices()) >= 8


def test_dryrun_multichip():
    dryrun_multichip(8)


def test_sharded_matches_host_oracle():
    rng = random.Random(31337)
    segs = ["a", "b", "c", "d", "", "x"]

    def rand_topic():
        return "/".join(rng.choice(segs) for _ in range(rng.randint(1, 5)))

    def rand_filter():
        parts = [rng.choice(segs + ["+"]) for _ in range(rng.randint(1, 5))]
        if rng.random() < 0.25:
            parts[-1] = "#"
        return "/".join(parts)

    index = TopicsIndex()
    for i in range(200):
        index.subscribe(f"cl{i}", Subscription(filter=rand_filter(), qos=rng.randint(0, 2)))
    for i in range(20):
        index.subscribe(
            f"sh{i}", Subscription(filter=f"{SHARE_PREFIX}/g{i % 3}/{rand_filter()}")
        )
    matcher = ShardedTpuMatcher(index, mesh=make_mesh(jax.devices()[:8]), max_levels=6)
    topics = [rand_topic() for _ in range(64)]
    for topic, dev in zip(topics, matcher.match_topics(topics)):
        assert canon(dev) == canon(index.subscribers(topic)), topic


def test_sharded_churn_rebuild():
    index = TopicsIndex()
    for i in range(50):
        index.subscribe(f"cl{i}", Subscription(filter=f"t/{i}"))
    matcher = ShardedTpuMatcher(index, mesh=make_mesh(jax.devices()[:4]))
    try:
        assert set(matcher.subscribers("t/7").subscriptions) == {"cl7"}
        index.unsubscribe("t/7", "cl7")
        index.subscribe("new", Subscription(filter="t/7"))
        assert matcher.stale
        assert set(matcher.subscribers("t/7").subscriptions) == {"new"}
    finally:
        matcher.close()


def test_incremental_rebuild_touches_one_shard():
    """A single subscription mutation must dirty exactly the stable-hash
    shard that owns it, and the incremental rebuild must recompile only
    that shard's replica (round-robin resharding made every mutation a
    full rebuild)."""
    from mqtt_tpu.parallel.sharded import shard_of

    index = TopicsIndex()
    for i in range(100):
        index.subscribe(f"cl{i}", Subscription(filter=f"t/{i % 10}/{i}"))
    matcher = ShardedTpuMatcher(index, mesh=make_mesh(jax.devices()[:4]))
    try:
        matcher.rebuild()
        assert matcher._dirty == [False] * matcher.n_shards
        sizes_before = [c.num_subs for c in matcher._flats]

        sub = Subscription(filter="t/3/fresh", qos=1)
        index.subscribe("fresh", sub)
        owner = shard_of("sub", "fresh", "t/3/fresh", 0, matcher.n_shards)
        dirty = [s for s in range(matcher.n_shards) if matcher._dirty[s]]
        assert dirty == [owner]

        matcher.rebuild()
        sizes_after = [c.num_subs for c in matcher._flats]
        for s in range(matcher.n_shards):
            expected = sizes_before[s] + (1 if s == owner else 0)
            assert sizes_after[s] == expected
        assert set(matcher.subscribers("t/3/fresh").subscriptions) >= {"fresh"}

        # unsubscribe dirties the same shard and shrinks it back
        index.unsubscribe("t/3/fresh", "fresh")
        assert [s for s in range(matcher.n_shards) if matcher._dirty[s]] == [owner]
        matcher.rebuild()
        assert [c.num_subs for c in matcher._flats] == sizes_before
    finally:
        matcher.close()


def test_per_shard_compile_histograms_merge_at_scrape():
    """ISSUE 5 satellite: every shard compile records into its own
    shard-local Histogram (no cross-thread write sharing) and
    ``merged_shard_compile`` folds them into one scrape-time snapshot
    whose count equals the sum of the shards'."""
    index = TopicsIndex()
    for i in range(40):
        index.subscribe(f"cl{i}", Subscription(filter=f"a/{i}/+", qos=0))
    matcher = ShardedTpuMatcher(index, mesh=make_mesh(jax.devices()[:4]))
    try:
        matcher.rebuild()  # full build: every shard compiles at least once
        per_shard = [h.count for h in matcher.shard_compile_hists]
        assert sum(per_shard) >= matcher.n_shards
        assert all(n >= 1 for n in per_shard)
        merged = matcher.merged_shard_compile()
        assert merged.count == sum(per_shard)
        assert merged.sum == pytest.approx(
            sum(h.sum for h in matcher.shard_compile_hists)
        )
        # an incremental rebuild only touches the dirty shard's histogram
        index.subscribe("late", Subscription(filter="z/z", qos=0))
        matcher.rebuild()
        after = [h.count for h in matcher.shard_compile_hists]
        assert sum(after) == sum(per_shard) + 1, (per_shard, after)
    finally:
        matcher.close()


def test_stable_hash_assignment_is_churn_invariant():
    """The shard owning a subscription must not depend on what else is in
    the index (round-robin regression guard)."""
    from mqtt_tpu.parallel.sharded import shard_of

    before = shard_of("sub", "clX", "a/b/c", 0, 4)
    # identity-only inputs: any index contents are irrelevant by construction
    assert shard_of("sub", "clX", "a/b/c", 0, 4) == before
    assert shard_of("inline", "", "a/b/c", 7, 4) == shard_of("inline", "", "a/b/c", 7, 4)


def test_sharded_incremental_matches_oracle_under_churn():
    """Randomized subscribe/unsubscribe churn with incremental rebuilds
    after every mutation batch: results must stay bit-identical."""
    rng = random.Random(4242)
    segs = ["a", "b", "c", "", "x"]

    def rand_filter():
        parts = [rng.choice(segs + ["+"]) for _ in range(rng.randint(1, 4))]
        if rng.random() < 0.2:
            parts[-1] = "#"
        return "/".join(parts)

    def rand_topic():
        return "/".join(rng.choice(segs) for _ in range(rng.randint(1, 4)))

    index = TopicsIndex()
    live: list[tuple[str, str]] = []
    for i in range(150):
        f = rand_filter()
        index.subscribe(f"cl{i}", Subscription(filter=f, qos=rng.randint(0, 2)))
        live.append((f, f"cl{i}"))
    matcher = ShardedTpuMatcher(index, mesh=make_mesh(jax.devices()[:8]), max_levels=5)
    try:
        for round_ in range(6):
            for _ in range(10):
                if live and rng.random() < 0.4:
                    f, cl = live.pop(rng.randrange(len(live)))
                    index.unsubscribe(f, cl)
                else:
                    f = rand_filter()
                    cl = f"m{round_}x{rng.randint(0, 10**6)}"
                    index.subscribe(cl, Subscription(filter=f, qos=1))
                    live.append((f, cl))
            topics = [rand_topic() for _ in range(16)]
            for topic, dev in zip(topics, matcher.match_topics(topics)):
                assert canon(dev) == canon(index.subscribers(topic)), topic
    finally:
        matcher.close()


def test_delta_matcher_over_mesh():
    """DeltaMatcher(mesh=...) serves from the sharded snapshot, routes
    affected topics to host, and folds deltas per-shard on flush."""
    from mqtt_tpu.ops.delta import DeltaMatcher
    from tests.test_ops_matcher import canon as _canon

    index = TopicsIndex()
    for i in range(60):
        index.subscribe(f"cl{i}", Subscription(filter=f"room/{i % 6}/+"))
    m = DeltaMatcher(index, background=False, mesh=make_mesh(jax.devices()[:4]))
    try:
        assert _canon(m.subscribers("room/3/x")) == _canon(index.subscribers("room/3/x"))
        # post-snapshot mutations are visible immediately (overlay -> host)
        index.subscribe("newbie", Subscription(filter="room/3/#"))
        assert "newbie" in m.subscribers("room/3/x").subscriptions
        assert m.pending_deltas == 1
        m.flush()
        assert m.pending_deltas == 0
        # folded into the device snapshot now; still identical
        assert _canon(m.subscribers("room/3/x")) == _canon(index.subscribers("room/3/x"))
        assert m.stats.rebuilds >= 2
    finally:
        m.close()
    assert index._observers == []
