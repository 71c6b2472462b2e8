"""Device-resident hit compaction (ISSUE 11): seeded parity of the
compacted (topic_idx, sid) pair path against the host trie oracle across
exact/`+`/`#`/`$SHARE`/predicated subscriptions, capacity edge cases
(hits == capacity, hits > capacity per-batch fallback), empty batches,
the C-vs-Python materializer differential, the sharded gathered-result
compaction, the 3-deep pipelined staging's per-leg accounting, the
buffered-window device aggregation reductions, and a chaos leg where the
breaker degrades mid-pipeline with batches in flight."""

import asyncio
import json
import random

import numpy as np
import pytest

import jax.numpy as jnp

from mqtt_tpu.ops.flat import _bucket, build_flat_index, flat_match_compact, pack_tokens
from mqtt_tpu.ops.hashing import tokenize_topics
from mqtt_tpu.ops.matcher import TpuMatcher, resolve_compact_py
from mqtt_tpu.ops.delta import DeltaMatcher
from mqtt_tpu.packets import Subscription
from mqtt_tpu.topics import SHARE_PREFIX, InlineSubscription, Subscribers, TopicsIndex
from mqtt_tpu import native

from tests.test_ops_matcher import canon
from tests.test_server import run


def _noop(*_a) -> None:
    pass


def build_index(seed: int, n: int = 400) -> tuple[TopicsIndex, list]:
    """A seeded subscription mix over every gather class: exact, `+`,
    `#`, `$SHARE` groups, inline, sub identifiers."""
    r = random.Random(seed)
    segs = [f"s{i}" for i in range(10)]
    index = TopicsIndex()
    for i in range(n):
        parts = [r.choice(segs) for _ in range(r.randint(1, 4))]
        roll = r.random()
        if roll < 0.2:
            parts[r.randrange(len(parts))] = "+"
        elif roll < 0.3:
            parts = parts[: r.randint(1, len(parts))] + ["#"]
        flt = "/".join(parts)
        if r.random() < 0.1:
            flt = f"{SHARE_PREFIX}/grp{r.randrange(4)}/{flt}"
        index.subscribe(
            f"cl{i}",
            Subscription(filter=flt, qos=i % 3, identifier=i % 5),
        )
    index.inline_subscribe(
        InlineSubscription(filter="s1/#", identifier=777, handler=_noop)
    )

    def topic_gen():
        parts = [r.choice(segs) for _ in range(r.randint(1, 5))]
        if r.random() < 0.05:
            parts[0] = "$SYS"
        return "/".join(parts)

    return index, topic_gen


def assert_parity(matcher, index, topics):
    for t, dev in zip(topics, matcher.match_topics(topics)):
        if t:
            assert canon(dev) == canon(index.subscribers(t)), t
        else:
            assert canon(dev) == canon(Subscribers())


class TestCompactParity:
    @pytest.mark.parametrize("seed", [3, 17, 90125])
    def test_seeded_mix_matches_host_oracle(self, seed):
        index, topic_gen = build_index(seed)
        m = TpuMatcher(index, max_levels=4)
        m.rebuild()
        topics = [topic_gen() for _ in range(150)] + ["", "a/b/c/d/e/f"]
        # first batch may overflow the seed capacity (high fan-in
        # seeds): the per-batch fallback serves it bit-identically and
        # teaches the EWMA, so the SECOND batch always compacts
        assert_parity(m, index, topics)
        assert_parity(m, index, topics)
        assert m.stats.compact_batches >= 1
        assert m.stats.compact_overflows <= 1
        assert m.stats.d2h_bytes > 0

    def test_empty_batch_and_empty_index(self):
        index, _ = build_index(1, n=0)
        m = TpuMatcher(index, max_levels=4)
        m.rebuild()
        assert m.match_topics([]) == []
        assert canon(m.match_topics(["a/b"])[0]) == canon(Subscribers())
        index2, topic_gen = build_index(5)
        m2 = TpuMatcher(index2, max_levels=4)
        m2.rebuild()
        assert m2.match_topics([]) == []

    def test_compact_off_still_bit_identical(self):
        index, topic_gen = build_index(23)
        m = TpuMatcher(index, max_levels=4, compact=False)
        m.rebuild()
        topics = [topic_gen() for _ in range(80)]
        assert_parity(m, index, topics)
        assert m.stats.compact_batches == 0

    def test_delta_churn_keeps_parity(self):
        """Compaction under the delta overlay: mutated filters host-route
        until folded, compacted results stay bit-identical throughout."""
        index, topic_gen = build_index(41)
        dm = DeltaMatcher(index, max_levels=4, background=False)
        try:
            topics = [topic_gen() for _ in range(60)]
            assert_parity(dm, index, topics)
            index.subscribe("late", Subscription(filter="s1/+", qos=1))
            index.unsubscribe("s1/s2", "cl3")
            assert_parity(dm, index, topics)  # overlay host-routes
            dm.flush()
            assert_parity(dm, index, topics)  # folded snapshot
        finally:
            dm.close()


class TestCapacityEdges:
    def _kernel_out(self, capacity):
        index, topic_gen = build_index(7)
        flat = build_flat_index(index, max_levels=4)
        arrays = tuple(
            jnp.asarray(a)
            for a in (flat.table, flat.pat_kind, flat.pat_depth, flat.pat_mask)
        )
        r = random.Random(7)
        topics = [topic_gen() for _ in range(40)]
        padded = topics + [""] * (_bucket(len(topics), minimum=16) - len(topics))
        tok = tokenize_topics(padded, flat.max_levels, flat.salt)
        out = np.asarray(
            flat_match_compact(
                *arrays,
                jnp.asarray(pack_tokens(*tok[:4])),
                max_levels=flat.max_levels,
                capacity=capacity,
            )
        )
        return out, len(padded)

    def test_hits_equal_capacity_is_not_overflow(self):
        out, bp = self._kernel_out(4096)
        n_hits = int(out[0])
        assert n_hits > 0
        exact, _ = self._kernel_out(n_hits)
        assert int(exact[0]) == n_hits
        assert int(exact[1]) == 0  # hits == capacity fits exactly
        # every pair slot is real (no -1 padding left)
        assert (exact[2 + 2 * bp :] >= 0).all()

    def test_hits_past_capacity_sets_the_flag(self):
        out, _bp = self._kernel_out(4096)
        n_hits = int(out[0])
        over, _ = self._kernel_out(max(1, n_hits - 1))
        assert int(over[1]) == 1
        # the TRUE hit count still reports (the capacity EWMA feeds on it)
        assert int(over[0]) == n_hits

    def test_matcher_overflow_falls_back_per_batch_and_recovers(self):
        index, topic_gen = build_index(7)
        m = TpuMatcher(index, max_levels=4, compact_capacity=8)
        m.rebuild()
        topics = [topic_gen() for _ in range(60)]
        assert_parity(m, index, topics)
        assert m.stats.compact_overflows == 1
        assert m.stats.compact_batches == 0
        # the overflow taught the EWMA the true rate: an ADAPTIVE matcher
        # seeded by it compacts the very next batch
        m.compact_capacity = 0
        m._hits_ewma = max(m._hits_ewma, 1.0)
        assert_parity(m, index, topics)
        assert m.stats.compact_batches >= 1


class TestMaterializerDifferential:
    def test_c_and_python_pair_expansion_identical(self):
        acc = native.accel()
        if acc is None or not hasattr(acc, "resolve_compact"):
            pytest.skip("C materializer unavailable")
        index, topic_gen = build_index(13)
        m = TpuMatcher(index, max_levels=4)
        m.rebuild()
        flat = m.csr
        topics = [topic_gen() for _ in range(50)] + [""]
        padded = topics + [""] * (_bucket(len(topics), minimum=16) - len(topics))
        tok = tokenize_topics(padded, flat.max_levels, flat.salt)
        cap = 4096
        out = np.asarray(
            flat_match_compact(
                *m.device_arrays,
                jnp.asarray(pack_tokens(*tok[:4])),
                max_levels=flat.max_levels,
                capacity=cap,
            )
        )
        bp = len(padded)
        n_hits = int(out[0])
        totals = out[2 : 2 + bp]
        route = out[2 + bp : 2 + 2 * bp].astype(np.int32)
        sids = out[2 + 2 * bp : 2 + 2 * bp + cap]
        res_c, ovf_c = acc.resolve_compact(
            np.ascontiguousarray(sids), None, np.ascontiguousarray(totals),
            np.ascontiguousarray(route), n_hits, len(topics),
            flat.subs.snaps, flat.window, Subscribers,
        )
        res_p, ovf_p = resolve_compact_py(
            sids, None, totals, route.astype(bool), topics, flat.subs
        )
        assert ovf_c == ovf_p
        for a, b in zip(res_c, res_p):
            if a is None or b is None:
                assert a is None and b is None
            else:
                assert canon(a) == canon(b)

    def test_python_rejects_mismatched_geometry(self):
        """The Python expansion enforces the same tripwire as the C
        path: totals that disagree with the pair stream raise instead
        of silently truncating the slices."""
        sids = np.zeros(8, dtype=np.int32)
        totals = np.full(4, 2, dtype=np.int32)
        route = np.zeros(4, dtype=bool)
        with pytest.raises(ValueError):
            resolve_compact_py(
                sids, None, totals, route, ["a"] * 4, None, n_hits=3
            )

    def test_c_rejects_mismatched_geometry(self):
        acc = native.accel()
        if acc is None or not hasattr(acc, "resolve_compact"):
            pytest.skip("C materializer unavailable")
        sids = np.zeros(8, dtype=np.int32)
        totals = np.full(4, 2, dtype=np.int32)
        route = np.zeros(4, dtype=np.int32)
        with pytest.raises(ValueError):
            # totals claim 8 pairs but n_hits says 3: never mis-expand
            acc.resolve_compact(
                sids, None, totals, route, 3, 4, [], 16, Subscribers
            )


class TestShardedCompact:
    def _mesh_matcher(self, index, **kw):
        from mqtt_tpu.parallel import ShardedTpuMatcher, make_mesh

        return ShardedTpuMatcher(index, mesh=make_mesh(), max_levels=4, **kw)

    def test_gathered_compaction_matches_host_oracle(self):
        index, topic_gen = build_index(29)
        m = self._mesh_matcher(index)
        try:
            topics = [topic_gen() for _ in range(60)] + [""]
            assert_parity(m, index, topics)  # may overflow: EWMA learns
            before = m.stats.d2h_bytes
            assert_parity(m, index, topics)  # compacts
            assert m.stats.compact_batches >= 1
            # the compacted transfer beats the padded [S, B, K] buffer
            bp = _bucket(61, minimum=max(2, m.n_batch))
            bp += (-bp) % m.n_batch
            padded_bytes = m.n_shards * bp * m.out_slots * 4
            assert m.stats.d2h_bytes - before < padded_bytes
        finally:
            m.close()

    def test_sharded_overflow_falls_back_per_batch(self):
        index, topic_gen = build_index(29)
        m = self._mesh_matcher(index, compact_capacity=8)
        try:
            topics = [topic_gen() for _ in range(60)]
            assert_parity(m, index, topics)
            assert m.stats.compact_overflows >= 1
        finally:
            m.close()


class TestPipelinedStaging:
    def test_leg_waits_and_inflight_accounting(self):
        """A few batches through the 3-deep pipeline: both leg-wait
        histograms populate, the in-flight gauge returns to zero, and
        every result is host-parity."""
        from mqtt_tpu.staging import MatchStage
        from mqtt_tpu.telemetry import Telemetry

        index, topic_gen = build_index(53)
        m = TpuMatcher(index, max_levels=4)
        m.rebuild()
        tel = Telemetry(sample=0)

        async def scenario():
            stage = MatchStage(
                m, index.subscribers, window_s=0.001, telemetry=tel,
                pipeline_depth=3,
            )
            assert stage.pipeline_depth == 3
            stage.start()
            topics = [topic_gen() for _ in range(120)]
            for burst in range(0, 120, 40):
                futs = [stage.submit(t) for t in topics[burst : burst + 40]]
                got = await asyncio.gather(*futs)
                for t, subs in zip(topics[burst : burst + 40], got):
                    assert canon(subs) == canon(index.subscribers(t))
                await asyncio.sleep(0.01)
            await stage.stop()
            assert stage.inflight_batches == 0

        run(scenario())
        assert tel.leg_wait["h2d"].count >= 3
        assert tel.leg_wait["d2h"].count >= 3

    def test_pipeline_depth_zero_falls_back_to_max_inflight(self):
        from mqtt_tpu.staging import MatchStage

        stage = MatchStage(
            object(), lambda t: Subscribers(), max_inflight=5,
            pipeline_depth=0,
        )
        assert stage.pipeline_depth == 5


class TestPredicatedStagedBroker:
    def test_predicated_delivery_through_compacted_pipeline(self):
        """MQTT+ predicate filtering rides the compacted staged batch:
        a `$GT` subscriber sees only passing payloads, a plain wildcard
        subscriber sees everything, and the matcher compacted."""
        from mqtt_tpu import Options
        from mqtt_tpu.packets import PUBLISH, SUBACK
        from tests.test_server import (
            Harness, pub_packet, read_wire_packet, sub_packet,
        )

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    device_matcher=True,
                    matcher_stage_window_ms=5.0,
                    matcher_opts={"max_levels": 4, "background": False},
                    matcher_stage_pipeline_depth=3,
                )
            )
            await h.server.serve()
            pred_r, pred_w, _ = await h.connect("sub-pred")
            pred_w.write(
                sub_packet(1, [Subscription(filter="t/+/v$GT{n:5.0}", qos=0)])
            )
            await pred_w.drain()
            assert (await read_wire_packet(pred_r)).fixed_header.type == SUBACK
            wild_r, wild_w, _ = await h.connect("sub-wild")
            wild_w.write(sub_packet(1, [Subscription(filter="t/#", qos=0)]))
            await wild_w.drain()
            assert (await read_wire_packet(wild_r)).fixed_header.type == SUBACK

            # fold the subscribe mutations into a fresh snapshot so the
            # publishes take the compacted device path instead of the
            # delta overlay's host route
            h.server.matcher.flush()
            pub_r, pub_w, _ = await h.connect("pub")
            payloads = [
                json.dumps({"n": n}).encode() for n in (1.0, 9.0, 3.0, 7.5)
            ]
            for i, p in enumerate(payloads):
                pub_w.write(pub_packet(f"t/d{i}/v", p, qos=0))
            await pub_w.drain()

            async def read_n(reader, n):
                got = []
                for _ in range(n):
                    pk = await asyncio.wait_for(read_wire_packet(reader), 5)
                    assert pk.fixed_header.type == PUBLISH
                    got.append(pk.payload)
                return got

            wild_got = await read_n(wild_r, 4)
            pred_got = await read_n(pred_r, 2)
            assert sorted(wild_got) == sorted(payloads)
            assert sorted(pred_got) == sorted(
                [json.dumps({"n": n}).encode() for n in (9.0, 7.5)]
            )
            stats = h.server.matcher.stats
            assert stats.compact_batches >= 1
            await h.server.close()
            await h.shutdown()

        run(scenario())


class TestChaosMidPipeline:
    def test_breaker_degrades_with_batches_in_flight(self):
        """The chaos leg: seeded device faults under the full stack
        (FaultyMatcher -> ResilientMatcher -> 3-deep MatchStage). The
        breaker trips mid-pipeline with compacted batches in flight;
        every future still resolves bit-identical to the host trie."""
        from mqtt_tpu.faults import FaultPlan, FaultyMatcher
        from mqtt_tpu.resilience import BreakerConfig, ResilientMatcher
        from mqtt_tpu.staging import MatchStage

        index, topic_gen = build_index(67)
        inner = TpuMatcher(index, max_levels=4)
        inner.rebuild()
        plan = FaultPlan(
            seed=9, error_rate=0.3, issue_error_rate=0.1,
            at={2: "error", 3: "error"},
        )
        faulty = FaultyMatcher(inner, plan)
        resilient = ResilientMatcher(
            faulty,
            index,
            BreakerConfig(
                failure_threshold=2, probe_backoff_s=30.0, seed=4,
                verify_sample=1, watchdog_s=5.0,
            ),
        )

        async def scenario():
            stage = MatchStage(
                resilient, index.subscribers, window_s=0.001,
                pipeline_depth=3,
            )
            stage.start()
            try:
                for _ in range(12):
                    topics = [topic_gen() for _ in range(25)]
                    futs = [stage.submit(t) for t in topics]
                    got = await asyncio.gather(*futs)
                    for t, subs in zip(topics, got):
                        assert canon(subs) == canon(index.subscribers(t))
            finally:
                await stage.stop()

        try:
            run(scenario())
            # the seeded plan guarantees consecutive failures: the
            # breaker tripped and host fallbacks served traffic
            assert resilient.breaker.trips >= 1
            assert resilient.fallback_batches >= 1
        finally:
            resilient.close()


class TestDeviceAggReduction:
    def _engine(self, min_batch=1, **kw):
        from mqtt_tpu.predicates import PredicateEngine

        eng = PredicateEngine(oracle_sample=0, **kw)
        # most tests complete one or two windows per tick; production
        # gates the dispatch on a real batch (device_agg_min_batch=4)
        eng.device_agg_min_batch = min_batch
        return eng

    def _subs(self, *entries):
        s = Subscribers()
        for cid, sub in entries:
            s.subscriptions[cid] = sub
        return s

    def test_large_windows_reduce_on_device(self):
        eng = self._engine(device_agg_min_window=4)
        eng.register("$MEAN{v:5}")
        eng.register("$MAX{v:4}")
        eng.register("$MIN{v:4}")
        sub_mean = Subscription(filter="t", predicates=("$MEAN{v:5}",))
        sub_max = Subscription(filter="t", predicates=("$MAX{v:4}",))
        sub_min = Subscription(filter="t", predicates=("$MIN{v:4}",))
        emitted = []
        vals = [3.0, 9.0, 1.5, 6.0, 0.5]
        for v in vals:
            subs = self._subs(("m", sub_mean), ("x", sub_max), ("n", sub_min))
            _out, emissions = eng.apply(subs, json.dumps({"v": v}).encode())
            emitted.extend(emissions)
        assert eng.agg_device_reductions >= 2  # max + min windows (4 wide)
        got = {(k, t): p for k, t, _s, p in emitted}
        assert got[("client", "x")] == b"9"  # max(3, 9, 1.5, 6) exact
        assert got[("client", "n")] == b"1.5"  # min exact
        mean = float(got[("client", "m")])
        assert abs(mean - sum(vals) / 5) < 1e-4  # float32 device mean

    def test_small_windows_keep_the_host_accumulator(self):
        eng = self._engine(device_agg_min_window=32)
        eng.register("$MEAN{v:3}")
        sub = Subscription(filter="t", predicates=("$MEAN{v:3}",))
        emitted = []
        for v in (1.0, 2.0, 6.0):
            _out, emissions = eng.apply(
                self._subs(("m", sub)), json.dumps({"v": v}).encode()
            )
            emitted.extend(emissions)
        assert eng.agg_device_reductions == 0
        assert emitted[0][3] == b"3"

    def test_device_fault_degrades_to_host_reduction(self, monkeypatch):
        import mqtt_tpu.ops.predicates as opspred

        def boom(_pending):
            raise RuntimeError("injected device fault")

        monkeypatch.setattr(opspred, "agg_reduce_batch", boom)
        eng = self._engine(device_agg_min_window=2)
        eng.register("$MAX{v:3}")
        sub = Subscription(filter="t", predicates=("$MAX{v:3}",))
        emitted = []
        for v in (1.0, 7.0, 2.0):
            _out, emissions = eng.apply(
                self._subs(("m", sub)), json.dumps({"v": v}).encode()
            )
            emitted.extend(emissions)
        assert emitted[0][3] == b"7"  # host fallback, value intact
        assert eng.agg_device_reductions == 0
        assert eng.device_errors >= 1

    def test_oracle_samples_device_reductions(self):
        eng = self._engine(device_agg_min_window=2)
        eng.oracle_sample = 1  # every apply checks
        eng.register("$MAX{v:2}")
        sub = Subscription(filter="t", predicates=("$MAX{v:2}",))
        # non-float32-representable samples: the oracle must still agree
        # exactly (both sides reduce float32-coerced values)
        for v in (0.1, 0.30000000000004):
            eng.apply(self._subs(("m", sub)), json.dumps({"v": v}).encode())
        assert eng.agg_device_reductions >= 1
        assert eng.oracle_checks >= 1
        assert eng.oracle_mismatches == 0

    def test_single_window_ticks_stay_on_host(self):
        """Below device_agg_min_batch the samples (already host-resident)
        reduce on host — no device round trip for one window."""
        eng = self._engine(min_batch=4, device_agg_min_window=2)
        eng.register("$MAX{v:2}")
        sub = Subscription(filter="t", predicates=("$MAX{v:2}",))
        emitted = []
        for v in (1.0, 7.0):
            _out, emissions = eng.apply(
                self._subs(("m", sub)), json.dumps({"v": v}).encode()
            )
            emitted.extend(emissions)
        assert eng.agg_device_reductions == 0
        assert emitted[0][3] == b"7"

    def test_open_breaker_serves_windows_from_host_silently(self):
        """An open breaker must not pay a failing dispatch per tick:
        windows reduce on host with no device attempt at all."""
        eng = self._engine(device_agg_min_window=2)
        eng.breaker.record_failure("agg")
        eng.breaker.record_failure("agg")
        eng.breaker.record_failure("agg")
        assert not eng.breaker.allow()
        eng.register("$MIN{v:2}")
        sub = Subscription(filter="t", predicates=("$MIN{v:2}",))
        emitted = []
        for v in (5.0, 2.0):
            _out, emissions = eng.apply(
                self._subs(("m", sub)), json.dumps({"v": v}).encode()
            )
            emitted.extend(emissions)
        assert eng.agg_device_reductions == 0
        assert eng.device_errors == 0  # no failing dispatch was attempted
        assert emitted[0][3] == b"2"
