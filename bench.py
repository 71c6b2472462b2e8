#!/usr/bin/env python
"""Benchmark: batched publish-topic matching against large subscription
indexes on the real device — the five BASELINE.md device configs plus the
broker and host-materializer configs, timed end to end.

Per config the timed loop covers the full seam: host tokenization, H2D
transfer, the device flat-hash match, D2H transfer, and host expansion into
bit-identical ``Subscribers`` sets (including host-fallback re-walks for
overflowed topics) — i.e. exactly what ``publish_to_subscribers`` pays when
the device matcher is enabled. A separate pipeline rate isolates the device
path (tokenize -> H2D -> match -> D2H as numpy sub-id sets) to show where
the remaining host cost sits.

Configs (BASELINE.md "Our target"):
  1. 10k exact subs — host-trie parity baseline (reference topics.go:583)
  2. 1M subs, 3-level topics, 10% ``+`` — the north-star config
  3. 1M subs, 8-level topics, 5% ``#`` — deep/fan-in stress (out_slots=256)
  4. 100k ``$share`` groups x 16 members — shared selection included
  5. 200k subs w/ v5 subscription-identifiers + retained scans under live
     subscribe/unsubscribe churn (DeltaMatcher, background rebuilds)
  6. broker: the mqtt-stresser analog over real TCP (README.md:474-508
     scenarios), one SO_REUSEPORT worker per core on multi-core hosts
  7. host materializer in isolation (no device needed): the C extension
     vs the pure-Python oracle on cfg2-shaped synthetic result rows
  8. publish storm (no device needed): offered load >> sustainable against
     an in-process broker with the overload governor (mqtt_tpu.overload)
     active — records shed rate, eviction count, peak staging pending
     depth, and admitted-traffic delivery p99

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "configs"}.
The headline value is config #2's end-to-end matches/sec vs the 10M north
star. The document and every config's result object name the device they
ran on (``platform``, ``device_kind``, ``n_devices`` as JAX reports them):
a CPU-jax number is a count or a correctness check, never a chip rate. A
config that needs the device and cannot initialise its backend RAISES —
nothing here probes, retries, skips or falls back, and the exit status
says so. Environment overrides: BENCH_SUBS, BENCH_BATCH, BENCH_ITERS,
BENCH_FAST=1 (small sizes, smoke), BENCH_CONFIGS=2,4 (subset),
BENCH_P99_BUDGET_MS.
"""

import json
import os
import random
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

TARGET_MATCHES_PER_SEC = 10_000_000  # the BASELINE.json north star


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def canon(s):
    """Order-free digest of a Subscribers set for parity checks."""
    return (
        {c: (sub.qos, tuple(sorted(sub.identifiers.items()))) for c, sub in s.subscriptions.items()},
        {f: set(m) for f, m in s.shared.items()},
        set(s.inline_subscriptions),
    )


def pctl(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, max(0, int(len(xs) * q) - 1))]


def telemetry_block(stage_lat, stage_name, fallbacks=None, fill=None):
    """The per-config BENCH telemetry block (ISSUE 3): stage latencies
    folded through the broker's own log-scale histogram so the p50/p99
    here and the live /metrics percentiles share bucket math — future
    PRs diff stage-level regressions, not just the end-to-end rate."""
    from mqtt_tpu.telemetry import Histogram

    h = Histogram()
    for v in stage_lat:
        h.observe(v)
    block = {
        "stages": {
            stage_name: {
                "count": h.count,
                "p50_ms": round(h.percentile(0.5) * 1e3, 3),
                "p99_ms": round(h.percentile(0.99) * 1e3, 3),
            }
        }
    }
    if fill is not None:
        block["batch_fill"] = fill
    if fallbacks:
        block["fallbacks"] = fallbacks
    return block


def probe_link():
    """Measure the host<->device link: round-trip latency and H2D/D2H
    bandwidth. Reported alongside the results so a transfer-bound e2e
    number can be told from a kernel-bound one."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda v, i: v + i)
    tiny = jnp.zeros((8,), jnp.int32)
    big = jnp.zeros((2 * 1024 * 1024,), jnp.int32)  # 8MB
    jax.block_until_ready([f(tiny, 0), f(big, 0)])
    rtts = []
    for i in range(1, 4):
        y = f(tiny, i)
        y.block_until_ready()
        t0 = time.perf_counter()
        np.asarray(y)
        rtts.append(time.perf_counter() - t0)
    y = f(big, 9)
    y.block_until_ready()
    t0 = time.perf_counter()
    np.asarray(y)
    d2h_s = time.perf_counter() - t0
    a = np.zeros((2 * 1024 * 1024,), dtype=np.int32)
    t0 = time.perf_counter()
    jnp.asarray(a).block_until_ready()
    h2d_s = time.perf_counter() - t0
    rtt = min(rtts)
    return {
        "d2h_rtt_ms": round(rtt * 1e3, 2),
        "d2h_mb_per_s": round(8 / max(1e-9, d2h_s - rtt), 1),
        "h2d_mb_per_s": round(8 / max(1e-9, h2d_s - rtt), 1),
    }


def bench_lazy() -> bool:
    """BENCH_LAZY=0 disables the zero-materialization fan-out A/B-wide:
    matchers return eager Subscribers dicts (no lazy views) and the
    in-process + serve-side brokers take the legacy per-subscriber
    encode path instead of the batched variant flush (ISSUE 13)."""
    return os.environ.get("BENCH_LAZY", "1") != "0"


def bench_compact() -> bool:
    """BENCH_COMPACT=0 disables device-resident hit compaction for an
    A/B against the padded-ranges transfer (default: on, the production
    posture)."""
    return os.environ.get("BENCH_COMPACT", "1") != "0"


# -- index builders ---------------------------------------------------------


def build_cfg1(rng):
    """10k exact-match subs over 3-level topics (examples/benchmark parity)."""
    from mqtt_tpu.packets import Subscription
    from mqtt_tpu.topics import TopicsIndex

    v = [f"seg{i}" for i in range(40)]
    index = TopicsIndex()
    filters = set()
    while len(filters) < 10_000:
        filters.add("/".join(rng.choice(v) for _ in range(3)))
    for i, f in enumerate(sorted(filters)):
        index.subscribe(f"cl{i}", Subscription(filter=f, qos=0))
    pool = sorted(filters)

    def topic_gen():
        return rng.choice(pool)

    return index, topic_gen


_CFG2_VOCAB = tuple(
    [f"{name}{i}" for i in range(100)] for name in ("region", "device", "metric")
)


def cfg2_subscriptions(n_subs, rng):
    """BASELINE.json config 2's subscription stream as ``(client, filter,
    qos)``: 3-level filters over a 100^3 vocabulary, 10% with one level
    replaced by ``+``. chip_smoke.py loads the same stream into a served
    broker, so the draw order is part of the deployment."""
    v0, v1, v2 = _CFG2_VOCAB
    for i in range(n_subs):
        parts = [rng.choice(v0), rng.choice(v1), rng.choice(v2)]
        if rng.random() < 0.10:
            parts[rng.randrange(3)] = "+"
        yield f"cl{i}", "/".join(parts), i % 3


def cfg2_topic(rng) -> str:
    """One publish topic on config 2's (uniform) topic distribution."""
    v0, v1, v2 = _CFG2_VOCAB
    return f"{rng.choice(v0)}/{rng.choice(v1)}/{rng.choice(v2)}"


def build_cfg2(n_subs, rng):
    """3-level topics, 10% single-level + wildcards (north star)."""
    from mqtt_tpu.packets import Subscription
    from mqtt_tpu.topics import TopicsIndex

    index = TopicsIndex()
    for client, flt, qos in cfg2_subscriptions(n_subs, rng):
        index.subscribe(client, Subscription(filter=flt, qos=qos))

    def topic_gen():
        return cfg2_topic(rng)

    return index, topic_gen


def build_cfg3(n_subs, rng):
    """Deep 8-level topics, 5% multi-level # wildcards."""
    from mqtt_tpu.packets import Subscription
    from mqtt_tpu.topics import TopicsIndex

    v_top = [f"t{i}" for i in range(1000)]
    v = [f"s{i}" for i in range(30)]

    def rand_parts():
        return [rng.choice(v_top)] + [rng.choice(v) for _ in range(7)]

    index = TopicsIndex()
    for i in range(n_subs):
        parts = rand_parts()
        if rng.random() < 0.05:
            depth = rng.randint(1, 7)
            parts = parts[:depth] + ["#"]
        index.subscribe(f"cl{i}", Subscription(filter="/".join(parts), qos=i % 3))

    def topic_gen():
        return "/".join(rand_parts())

    return index, topic_gen


def build_cfg4(n_groups, members, rng):
    """100k $share groups x 16 members, QoS1 (shared selection included)."""
    from mqtt_tpu.packets import Subscription
    from mqtt_tpu.topics import SHARE_PREFIX, TopicsIndex

    v0 = [f"region{i}" for i in range(100)]
    v1 = [f"device{i}" for i in range(100)]
    v2 = [f"metric{i}" for i in range(100)]
    index = TopicsIndex()
    for g in range(n_groups):
        flt = f"{rng.choice(v0)}/{rng.choice(v1)}/{rng.choice(v2)}"
        for m in range(members):
            index.subscribe(
                f"g{g}m{m}",
                Subscription(filter=f"{SHARE_PREFIX}/grp{g}/{flt}", qos=1),
            )

    def topic_gen():
        return f"{rng.choice(v0)}/{rng.choice(v1)}/{rng.choice(v2)}"

    return index, topic_gen


# -- timing harness ---------------------------------------------------------


def parity_check(matcher, index, topic_gen, n=32):
    topics = [topic_gen() for _ in range(n)]
    for topic, dev in zip(topics, matcher.match_topics(topics)):
        host = index.subscribers(topic)
        assert canon(dev) == canon(host), f"parity mismatch on {topic!r}"


def time_host(index, topic_gen, iters):
    """The host trie walk rate — the CPU-reference path (topics.go:583)."""
    topics = [topic_gen() for _ in range(iters)]
    t0 = time.perf_counter()
    for t in topics:
        index.subscribers(t)
    dt = time.perf_counter() - t0
    return iters / dt


def time_matcher(matcher, index, topic_gen, batch, iters, select_shared=False):
    """Full-path timing through matcher.match_topics (tokenize + H2D +
    device match + D2H + expand + host fallback), plus an isolated device
    pipeline rate. Returns a metrics dict."""
    import jax
    import jax.numpy as jnp

    from mqtt_tpu.ops.hashing import tokenize_topics

    batches = [[topic_gen() for _ in range(batch)] for _ in range(4)]

    # threshold tuning matches the broker's runtime posture (Server.serve
    # applies the same); the freeze is bench-only — here the just-built
    # index is the entire object graph, while a live broker must not
    # freeze transient asyncio state (see gctune.freeze_index)
    from mqtt_tpu.utils.gctune import freeze_index, tune_for_throughput

    tune_for_throughput()
    freeze_index()

    # warmup / compile both paths
    matcher.match_topics(batches[0])

    # end-to-end THROUGHPUT: depth-2 software pipeline (issue batch i+1,
    # resolve batch i) — exactly the broker staging-loop shape; hides the
    # host<->device round trip but pays every byte and every expand
    s0_fall, s0_ovf, s0_topics = (
        matcher.stats.host_fallbacks,
        matcher.stats.overflows,
        matcher.stats.topics,
    )
    # device pipeline profiler (mqtt_tpu.tracing): duty cycle / overlap /
    # idle-gap over the pipelined loop — the exact numbers ROADMAP item
    # 1's overlapped-staging work must move, baselined per round.
    # Attached AFTER warmup so the cold compile doesn't skew the windows.
    from mqtt_tpu.tracing import DeviceProfiler

    profiler = DeviceProfiler()
    if hasattr(matcher, "profiler"):
        matcher.profiler = profiler
    # compile-ledger watermark (ISSUE 18, the PR 11 regression guard):
    # the warmup above compiled every executable this loop needs, so any
    # ledger growth across the steady-state window below IS a recompile
    # — the silent one-recompile-per-step failure mode, now a scalar the
    # bench-history ledger diffs round over round
    from mqtt_tpu.ops.devicestats import LEDGER

    ledger_t0 = LEDGER.total()
    hits = 0
    t_start = time.perf_counter()
    pending = matcher.match_topics_async(batches[0])
    for i in range(1, iters + 1):
        nxt = (
            matcher.match_topics_async(batches[i % len(batches)])
            if i < iters
            else None
        )
        results = pending()
        if select_shared:
            for r in results:
                for members in r.shared.values():
                    next(iter(members), None)  # SelectShared analog
        else:
            # consume every result the way _fan_out does (ISSUE 13): a
            # lazy SubscribersView yields its (client, sub) plan, an
            # eager dict is already built — either way the e2e number
            # includes the cost fan-out actually pays
            for r in results:
                consume = getattr(r, "targets", None)
                if consume is not None:
                    consume()
        if i == 1:
            hits = sum(
                len(r.subscriptions) + sum(len(m) for m in r.shared.values())
                for r in results
            )
        pending = nxt
    e2e_dt = time.perf_counter() - t_start
    steady_recompiles = LEDGER.total() - ledger_t0
    device_pipeline = profiler.bench_block()
    if hasattr(matcher, "profiler"):
        matcher.profiler = None  # the latency loops below stay unprofiled
    n_topics = matcher.stats.topics - s0_topics
    fallbacks = matcher.stats.host_fallbacks - s0_fall
    overflows = matcher.stats.overflows - s0_ovf

    # single-batch LATENCY: unpipelined issue->resolve round trips
    lat = []
    for i in range(min(iters, 8)):
        t1 = time.perf_counter()
        matcher.match_topics(batches[i % len(batches)])
        lat.append(time.perf_counter() - t1)

    # the LATENCY-BOUNDED operating point (SURVEY §7 hard part 4): the
    # largest batch whose single-batch p99 fits
    # the budget, and the pipelined rate it sustains there — the number a
    # latency-sensitive deployment would run at (the staging loop's
    # adaptive controller converges to this point on its own)
    p99_bounded = None
    budget_s = float(os.environ.get("BENCH_P99_BUDGET_MS", "250")) / 1e3
    # sparse size ladder (each new bucket size costs a fresh JIT
    # compile); floor matches the staging controller's min_batch
    for bb in (batch, batch // 4, batch // 16, batch // 64):
        if bb < 64:
            break
        bl = []
        sub = [batches[0][:bb], batches[1][:bb]]
        matcher.match_topics(sub[0])  # warm this bucket's executable (JIT)
        for i in range(4):
            t1 = time.perf_counter()
            matcher.match_topics(sub[i % 2])
            bl.append(time.perf_counter() - t1)
        if max(bl) <= budget_s:
            t1 = time.perf_counter()
            n_it = max(6, min(20, int(2.0 / max(bl))))
            pend = matcher.match_topics_async(sub[0])
            for i in range(1, n_it + 1):
                nxt = matcher.match_topics_async(sub[i % 2]) if i < n_it else None
                pend()
                pend = nxt
            dt = time.perf_counter() - t1
            p99_bounded = {
                "batch": bb,
                "e2e_matches_per_sec": round(n_it * bb / dt),
                "p99_batch_ms": round(pctl(bl, 0.99) * 1e3, 3),
                "budget_ms": round(budget_s * 1e3),
            }
            break
    if p99_bounded is None:
        p99_bounded = {
            "batch": None,
            "note": f"no batch size on the ladder down from {batch} fits "
            f"p99 < {budget_s*1e3:.0f}ms on this link",
        }

    # LINK-NORMALIZED host resolve rate: materialize one already-fetched
    # packed result batch repeatedly (no device dispatch, no transfer) —
    # the rate the host side sustains with the link taken out, i.e. the
    # e2e ceiling the host resolve leg sets
    resolve_rate = None
    materialization_cost = None
    from mqtt_tpu.ops.matcher import _accel

    acc = _accel()
    if (
        acc is not None
        and hasattr(matcher, "csr")
        and matcher.csr is not None
        and matcher.csr.exact_map is None  # exact-map configs never take
        # the device+resolve path in production; this ceiling is theirs
    ):
        from mqtt_tpu.ops.flat import flat_match_packed, pack_tokens
        from mqtt_tpu.topics import Subscribers as _Subscribers

        flat = matcher.csr
        tok = tokenize_topics(batches[0], flat.max_levels, flat.salt)
        packed_dev = flat_match_packed(
            *matcher.device_arrays,
            jnp.asarray(pack_tokens(*tok[:4])),
            max_levels=flat.max_levels,
        )
        packed_np = np.asarray(packed_dev)
        P = flat.pat_depth.shape[0]
        n_it = max(3, min(12, iters))
        t0 = time.perf_counter()
        for _ in range(n_it):
            acc.resolve_batch(
                packed_np, batch, P, flat.subs.snaps, flat.window, _Subscribers
            )
        resolve_rate = round(n_it * batch / (time.perf_counter() - t0))

        # per-hit materialization / consume cost (ISSUE 13): over the
        # SAME already-fetched device result, time (a) the lazy path —
        # build views + consume their (client, sub) plans exactly like
        # _fan_out — against (b) the eager dict expansion. The lazy
        # number is the acceptance bar (< 300 ns/hit); both land in the
        # artifact so the A/B is re-checkable every round.
        if hasattr(acc, "resolve_batch_views"):
            total_hits = int(packed_np[:, 2 * P].sum())
            ovf_rows = int((packed_np[:, 2 * P + 1] != 0).sum())
            n_it2 = max(3, min(12, iters))
            t0 = time.perf_counter()
            for _ in range(n_it2):
                views, _o = acc.resolve_batch_views(
                    packed_np, batch, P, flat.subs.snaps, flat.window,
                    _Subscribers,
                )
                for v in views:
                    if v is not None:
                        v.targets()
            dt_lazy = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(n_it2):
                acc.resolve_batch(
                    packed_np, batch, P, flat.subs.snaps, flat.window,
                    _Subscribers,
                )
            dt_eager = time.perf_counter() - t0
            denom = max(1, n_it2 * total_hits)
            denom_t = max(1, n_it2 * batch)
            materialization_cost = {
                "total_hits": total_hits,
                "overflow_rows": ovf_rows,
                # per-HIT is the acceptance number at dense workloads
                # (~11 hits/topic at 1M subs); per-TOPIC disambiguates
                # sparse runs where per-row view overhead dominates
                "lazy_consume_ns_per_hit": round(dt_lazy * 1e9 / denom, 1),
                "lazy_consume_ns_per_topic": round(
                    dt_lazy * 1e9 / denom_t, 1
                ),
                "eager_materialize_ns_per_hit": round(
                    dt_eager * 1e9 / denom, 1
                ),
                "lazy_speedup": round(dt_eager / max(1e-9, dt_lazy), 2),
                "lazy_consume_topics_per_sec": round(
                    n_it2 * batch / max(1e-9, dt_lazy)
                ),
            }
        else:
            materialization_cost = None

    # device-compute only: resident pre-uploaded inputs, async dispatch
    # with one final sync — the kernel's sustained rate, transfers excluded.
    # Completion is forced by a dependent scalar reduce + D2H: a timed
    # window must end in a value the host actually holds.
    kernel_rate = None
    kernel_best = None
    if hasattr(matcher, "match_tokens"):
        red = jax.jit(lambda o: o.sum())
        salt = matcher.csr.salt
        # the kernel is gather-bound: per-batch cost is ~P*B row-gathers
        # plus a fixed per-dispatch overhead. Measure the sustained rate
        # at a batch large enough to amortize the dispatch floor, like any
        # throughput kernel is measured at its operating point; the e2e
        # and latency numbers above keep the staging batch.
        fast = os.environ.get("BENCH_FAST") == "1"
        kb = max(
            batch,
            int(os.environ.get("BENCH_KERNEL_BATCH", batch if fast else 65536)),
        )
        kbatches = [[topic_gen() for _ in range(kb)] for _ in range(2)]
        resident = [
            tuple(
                jnp.asarray(a)
                for a in tokenize_topics(bt, matcher.max_levels, salt)[:4]
            )
            for bt in kbatches
        ]
        jax.block_until_ready(resident)  # H2D outside the timed loop
        np.asarray(red(matcher.match_tokens(*resident[0])[0]))
        # median of several timed windows: one window can land in a
        # slow patch of a shared host
        kiters = max(4, (max(iters, 50) * batch) // (4 * kb))
        rates = []
        for _w in range(5):
            t0 = time.perf_counter()
            outs = [
                matcher.match_tokens(*resident[i % len(resident)])[0]
                for i in range(kiters)
            ]
            np.asarray(red(outs[-1]))  # dependent scalar D2H = true completion
            rates.append((kiters * kb) / (time.perf_counter() - t0))
        kernel_rate = sorted(rates)[len(rates) // 2]
        kernel_best = max(rates)

    tel_block = telemetry_block(
        lat,
        "device_batch",
        fallbacks={
            "host_fallbacks": fallbacks,
            "overflows": overflows,
            "host_fast": matcher.stats.host_fast,
        },
        fill={"p50": 1.0, "note": "fixed-size bench batches"},
    )
    if profiler.compact_d2h_hist.count:
        # the compaction d2h leg as its own stage row so stage_gate
        # diffs it round over round (a new name passes through its
        # new_stage_names notice on the first post-compaction round)
        h = profiler.compact_d2h_hist
        tel_block["stages"]["compact_d2h"] = {
            "count": h.count,
            "p50_ms": round(h.percentile(0.5) * 1e3, 3),
            "p99_ms": round(h.percentile(0.99) * 1e3, 3),
        }
    return {
        "e2e_matches_per_sec": round((iters * batch) / e2e_dt),
        # recompiles observed during the steady-state pipelined loop
        # (must be 0: fixed-size batches after warmup; nonzero means the
        # PR 11 capacity-churn bug is back — attribution names the
        # kernel/shape so the regression is diagnosable from the artifact)
        "steady_state_recompiles": steady_recompiles,
        "recompile_attribution": (
            LEDGER.attribution(ledger_t0) if steady_recompiles else None
        ),
        # kernel duty cycle / transfer-compute overlap / idle gaps over
        # the pipelined e2e loop (mqtt_tpu.tracing.DeviceProfiler) — the
        # ROADMAP item 1 gap, measured per round; carries the compaction
        # transfer ledger (d2h bytes actual vs padded, reduction ratios)
        "device_pipeline": device_pipeline,
        "telemetry": tel_block,
        "device_kernel_matches_per_sec": round(kernel_rate) if kernel_rate else None,
        # best of the timed windows: median is the headline, best shows
        # the kernel when a window misses the slow patches
        "device_kernel_best_window": round(kernel_best) if kernel_best else None,
        "p99_batch_ms": round(pctl(lat, 0.99) * 1e3, 3),
        "p99_bounded": p99_bounded,
        "batch": batch,
        "avg_hits_per_topic": round(hits / batch, 2),
        "host_fallback_ratio": round(fallbacks / max(1, n_topics), 5),
        "overflow_ratio": round(overflows / max(1, n_topics), 5),
        "host_fast_topics": matcher.stats.host_fast,
        # the host materialization rate with transfers excluded: the e2e
        # ceiling on a directly-attached device (link-normalized)
        "link_normalized_resolve_per_sec": resolve_rate,
        # per-hit consume cost A/B over the same device result (ISSUE
        # 13): lazy targets() vs eager dict expansion; None sans C
        "materialization_cost": materialization_cost,
    }


# -- configs ----------------------------------------------------------------


def run_cfg1(rng, fast, batch):
    from mqtt_tpu.ops import TpuMatcher

    index, topic_gen = build_cfg1(rng)
    host_rate = time_host(index, topic_gen, 2000 if fast else 20000)
    matcher = TpuMatcher(index, max_levels=4, frontier=8, out_slots=32, transfer_slots=8, compact=bench_compact(), lazy=bench_lazy())
    matcher.rebuild()
    parity_check(matcher, index, topic_gen)
    # same batch as the other configs: the per-dispatch overhead swamps
    # sub-4K batches
    m = time_matcher(matcher, index, topic_gen, batch, 10 if fast else 30)
    m["host_matches_per_sec"] = round(host_rate)
    m["device_speedup_vs_host"] = round(m["e2e_matches_per_sec"] / host_rate, 2)
    return m


def run_cfg2(n_subs, batch, iters, rng):
    from mqtt_tpu.ops import TpuMatcher

    index, topic_gen = build_cfg2(n_subs, rng)
    matcher = TpuMatcher(index, max_levels=4, frontier=8, out_slots=64, transfer_slots=16, compact=bench_compact(), lazy=bench_lazy())
    t0 = time.perf_counter()
    matcher.rebuild()
    log(f"cfg2 index build {time.perf_counter()-t0:.1f}s nodes={matcher.csr.num_nodes}")
    parity_check(matcher, index, topic_gen)
    m = time_matcher(matcher, index, topic_gen, batch, iters)
    # the device-observability plane's sampled-path cost (ISSUE 18
    # acceptance: <= 2%), measured on the same warmed matcher by the
    # PR 7/14 interleaved-A/B method
    m["devicestats_overhead"] = _devicestats_overhead_block(
        matcher, topic_gen, batch
    )
    return m


def _devicestats_overhead_block(matcher, topic_gen, batch) -> dict:
    """ISSUE 18 acceptance leg: what the compile watch + per-device
    profiler windows cost on the hot dispatch path. Interleaved best-of-3
    rounds (the PR 7/14 method — sequential arm-then-arm would measure
    host drift, not the plane) of the same pipelined loop with the
    plane fully ON (KernelWatch signatures + per-device fold) vs OFF,
    plus the deterministic micro-number: one signature probe per jitted
    dispatch, the exact added steady-state work."""
    from mqtt_tpu.ops import devicestats
    from mqtt_tpu.tracing import DeviceProfiler

    batches = [[topic_gen() for _ in range(batch)] for _ in range(2)]
    matcher.match_topics(batches[0])  # warm both executables

    def one_round(enabled: bool) -> float:
        devicestats.set_watch_enabled(enabled)
        if hasattr(matcher, "profiler"):
            matcher.profiler = DeviceProfiler() if enabled else None
        n_it = 6
        t0 = time.perf_counter()
        pend = matcher.match_topics_async(batches[0])
        for i in range(1, n_it + 1):
            nxt = (
                matcher.match_topics_async(batches[i % 2])
                if i < n_it
                else None
            )
            pend()
            pend = nxt
        dt = time.perf_counter() - t0
        if hasattr(matcher, "profiler"):
            matcher.profiler = None
        return n_it * batch / dt

    on_rate = off_rate = 0.0
    try:
        for _rep in range(3):
            on_rate = max(on_rate, one_round(True))
            off_rate = max(off_rate, one_round(False))
    finally:
        devicestats.set_watch_enabled(True)

    # deterministic micro: the signature probe a watched kernel pays per
    # DISPATCH (not per message) in steady state — harness-noise-free,
    # the number the <=2% bar is judged against on noisy links
    import jax.numpy as jnp

    probe_args = (
        jnp.zeros((batch, 8), jnp.int32),
        jnp.zeros((64,), jnp.int32),
    )
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        devicestats._sig_of(probe_args, {})
    per_probe_ns = (time.perf_counter() - t0) / n * 1e9
    # ... plus the per-device window fold one profiled batch pays
    # (tracing._DevWindow): dispatch+resolve notes over a stamped record
    from mqtt_tpu.tracing import BatchProfile

    fold_prof = DeviceProfiler()
    nf = 20_000
    t0 = time.perf_counter()
    tb = time.perf_counter()
    for i in range(nf):
        rec = BatchProfile()
        rec.devices = (0,)
        rec.d2h_bytes = 4096
        fold_prof.note_dispatch(rec, tb, tb + 1e-4)
        fold_prof.note_resolve(rec, tb + 2e-4, tb + 3e-4)
    per_fold_ns = (time.perf_counter() - t0) / nf * 1e9
    per_batch_ns = per_probe_ns + per_fold_ns
    out = {
        "enabled_matches_per_sec": round(on_rate),
        "disabled_matches_per_sec": round(off_rate),
        "overhead_pct": round(
            (off_rate - on_rate) / max(1.0, off_rate) * 100, 2
        ),
        "sig_probe_ns_per_dispatch": round(per_probe_ns, 1),
        "device_fold_ns_per_batch": round(per_fold_ns, 1),
    }
    if off_rate > 0:
        # the plane's exact added work as a fraction of one batch's wall
        # budget — harness-noise-free, the <=2% acceptance figure (the
        # macro pct above inherits the harness jitter)
        out["amortized_overhead_pct"] = round(
            per_batch_ns / (1e9 * batch / off_rate) * 100, 4
        )
    return out


def run_cfg3(n_subs, batch, iters, rng):
    from mqtt_tpu.ops import TpuMatcher

    index, topic_gen = build_cfg3(n_subs, rng)
    # deep fan-in: a topic can gather hundreds of '#' subs — bigger output
    # window keeps the device path useful instead of 100% host fallback
    matcher = TpuMatcher(index, max_levels=8, frontier=8, out_slots=256, transfer_slots=32, compact=bench_compact(), lazy=bench_lazy())
    t0 = time.perf_counter()
    matcher.rebuild()
    log(f"cfg3 index build {time.perf_counter()-t0:.1f}s nodes={matcher.csr.num_nodes}")
    parity_check(matcher, index, topic_gen)
    return time_matcher(matcher, index, topic_gen, batch, iters)


def run_cfg4(n_groups, members, batch, iters, rng):
    from mqtt_tpu.ops import TpuMatcher

    index, topic_gen = build_cfg4(n_groups, members, rng)
    matcher = TpuMatcher(index, max_levels=4, frontier=8, out_slots=128, transfer_slots=48, compact=bench_compact(), lazy=bench_lazy())
    t0 = time.perf_counter()
    matcher.rebuild()
    log(f"cfg4 index build {time.perf_counter()-t0:.1f}s nodes={matcher.csr.num_nodes}")
    parity_check(matcher, index, topic_gen)
    return time_matcher(matcher, index, topic_gen, batch, iters, select_shared=True)


def run_cfg5(n_subs, batch, iters, rng):
    """Sub-identifiers + retained scan under live churn via DeltaMatcher."""
    from mqtt_tpu.ops.delta import DeltaMatcher
    from mqtt_tpu.packets import PUBLISH, FixedHeader, Packet, Subscription
    from mqtt_tpu.topics import TopicsIndex

    v0 = [f"region{i}" for i in range(60)]
    v1 = [f"device{i}" for i in range(60)]
    v2 = [f"metric{i}" for i in range(60)]
    index = TopicsIndex()
    for i in range(n_subs):
        parts = [rng.choice(v0), rng.choice(v1), rng.choice(v2)]
        if rng.random() < 0.10:
            parts[rng.randrange(3)] = "+"
        index.subscribe(
            f"cl{i}", Subscription(filter="/".join(parts), qos=i % 3, identifier=i % 200 + 1)
        )
    for i in range(5000):  # retained corpus for the scan
        topic = f"{rng.choice(v0)}/{rng.choice(v1)}/{rng.choice(v2)}"
        index.retain_message(
            Packet(
                fixed_header=FixedHeader(type=PUBLISH, retain=True),
                topic_name=topic,
                payload=b"r",
            )
        )

    def topic_gen():
        return f"{rng.choice(v0)}/{rng.choice(v1)}/{rng.choice(v2)}"

    m = DeltaMatcher(index, max_levels=4, out_slots=64, transfer_slots=16,
                     rebuild_after=256, rebuild_interval=0.2, background=True,
                     compact=bench_compact(), lazy=bench_lazy())

    # same GC posture as the other configs (time_matcher does this): the
    # built index must not be young-gen-scanned every 700 allocations
    # while churn + rebuilds allocate heavily
    from mqtt_tpu.utils.gctune import freeze_index, tune_for_throughput

    tune_for_throughput()
    freeze_index()

    stop = threading.Event()
    mutations = [0]

    def churn():
        r = random.Random(9)
        i = n_subs
        while not stop.is_set():
            parts = [r.choice(v0), r.choice(v1), r.choice(v2)]
            if r.random() < 0.5:
                index.subscribe(f"m{i}", Subscription(filter="/".join(parts), qos=1))
                i += 1
            else:
                index.unsubscribe("/".join(parts), f"m{r.randint(n_subs, max(n_subs + 1, i))}")
            mutations[0] += 1
            time.sleep(0.0005)  # ~2k mutations/s

    th = threading.Thread(target=churn, daemon=True)
    th.start()
    try:
        batches = [[topic_gen() for _ in range(batch)] for _ in range(4)]
        m.match_topics(batches[0])  # warmup
        s0_fall = m.stats.host_fallbacks
        s0_topics = m.stats.topics
        lat, scans = [], 0
        t0 = time.perf_counter()
        pending = m.match_topics_async(batches[0])
        for i in range(1, iters + 1):
            t1 = time.perf_counter()
            nxt = m.match_topics_async(batches[i % len(batches)]) if i < iters else None
            pending()
            # retained-message wildcard scan rides along (processSubscribe path)
            index.messages(f"{rng.choice(v0)}/+/{rng.choice(v2)}")
            scans += 1
            lat.append(time.perf_counter() - t1)
            pending = nxt
        dt = time.perf_counter() - t0
        fallbacks = m.stats.host_fallbacks - s0_fall
        n_topics = m.stats.topics - s0_topics
        out = {
            "e2e_matches_per_sec": round((iters * batch) / dt),
            "telemetry": telemetry_block(
                lat,
                "device_batch",
                fallbacks={"host_fallbacks": fallbacks},
            ),
            "p99_batch_ms": round(pctl(lat, 0.99) * 1e3, 3),
            "batch": batch,
            "mutations_during_run": mutations[0],
            "retained_scans": scans,
            "host_fallback_ratio": round(fallbacks / max(1, n_topics), 5),
            "pending_deltas_at_end": m.pending_deltas,
            "snapshot_rebuilds": m.stats.rebuilds,
            "snapshot_folds": m.stats.folds,
        }
    finally:
        stop.set()
        th.join(timeout=5)
        m.close()
    # final parity after churn stopped
    for t in [topic_gen() for _ in range(16)]:
        assert canon(m.subscribers(t)) == canon(index.subscribers(t))
    return out


def run_cfg9(fast: bool, rng) -> dict:
    """Predicate-selectivity sweep (ISSUE 8 / ROADMAP item 4): device
    rule-table evaluation vs the host interpreter across pass rates.

    One DISTINCT rule per predicated subscription (thresholds uniform in
    [0,1], so a payload value v passes ~v of the population — the pass
    rate IS the payload), evaluated through the same
    ``PredicateEngine.eval_batch_async`` path the staging loop uses, so
    the measured rate is the staged-batch rate (one fused dispatch, one
    packed-bit D2H — no extra round trip). Every rate's batch is fully
    cross-checked against the host interpreter; the artifact carries the
    mismatch count, which must be zero."""
    from mqtt_tpu.predicates import PredicateEngine, eval_rule_host

    n = int(os.environ.get("BENCH_PRED_SUBS", 10_000 if fast else 100_000))
    batch = int(os.environ.get("BENCH_PRED_BATCH", 64))
    iters = 3 if fast else 10
    eng = PredicateEngine(oracle_sample=0)
    suffixes = []
    t0 = time.perf_counter()
    for i in range(n):
        s = "$GT{v:%.9f}" % rng.random()
        eng.register(s)
        suffixes.append(s)
    build_s = time.perf_counter() - t0
    out = {
        "n_rules": eng.rule_count,
        "batch": batch,
        "register_seconds": round(build_s, 3),
        "sweep": {},
        "oracle_mismatches": 0,
    }
    for rate in (0.01, 0.1, 0.5, 0.9):
        payload = json.dumps({"v": rate}).encode()
        feats = [eng.features_for(payload) for _ in range(batch)]
        resolved = eng.eval_batch_async(feats)
        if resolved is None:
            raise RuntimeError("cfg9: device rule evaluation unavailable")
        resolved()  # warmup: jit compile + first transfer
        t0 = time.perf_counter()
        last = None
        for _ in range(iters):
            issued = eng.eval_batch_async(feats)
            last = issued() if issued is not None else None
        dt = time.perf_counter() - t0
        if last is None:
            # the resolver degrades to None on a device fault (it never
            # raises) — here that is a failed measurement, not a result
            raise RuntimeError("cfg9: device rule evaluation degraded")
        rows, _eligible, _gen = last
        # host-interpreter comparison rate (bounded sample: the point is
        # the order-of-magnitude gap, not a long host soak)
        host_n = min(n, 2000 if fast else 20000)
        t0 = time.perf_counter()
        for s in suffixes[:host_n]:
            eval_rule_host(eng._rules[s].spec, payload)
        host_dt = time.perf_counter() - t0
        # full differential oracle over every rule for this payload
        row = rows[0]
        mismatches = 0
        passed = 0
        for s in suffixes:
            rule = eng._rules[s]
            bit = bool((row[rule.idx >> 5] >> np.uint32(rule.idx & 31)) & 1)
            passed += bit
            if bit != eval_rule_host(rule.spec, payload):
                mismatches += 1
        out["oracle_mismatches"] += mismatches
        out["sweep"][str(rate)] = {
            "device_evals_per_sec": round(iters * batch * n / dt),
            "host_evals_per_sec": round(host_n / host_dt) if host_dt else 0,
            "observed_pass_ratio": round(passed / n, 4),
            "transfer_bytes_per_batch": int(rows.nbytes),
            "mismatches": mismatches,
        }
    if out["oracle_mismatches"]:
        log(f"cfg9 ORACLE MISMATCHES: {out['oracle_mismatches']}")
    return out


def _keystream_device_rate(fast: bool):
    """The PR 12 residual (ISSUE 18 satellite): the device keystream's
    raw sustained byte rate — resident inputs, pipelined dispatches, one
    dependent sync — on a REAL accelerator. On CPU-jax the 'device' path
    is the same host silicon the vectorized-host path uses, so the
    number would be a fiction: the zero-headline rule applies and the
    cell records an honest skip instead."""
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform == "cpu":
        return {
            "skipped": True,
            "skip_reason": "CPU-jax backend: device keystream bytes/s "
            "is only meaningful on a real accelerator",
        }
    from mqtt_tpu.ops.recrypt import BLOCK, ctr_counters, keystream
    from mqtt_tpu.tenancy import KeyRegistry

    reg = KeyRegistry()
    for k in range(64):
        reg.set_key("bt0", f"c{k}", bytes([k % 256]) * 16)
    table = reg.table()
    n_blocks = 1 << (12 if fast else 16)  # 64 KiB / 1 MiB of keystream
    kidx = np.arange(n_blocks, dtype=np.int32) % 64
    counters = ctr_counters(b"bnks" * 3, n_blocks)
    args = (jnp.asarray(table), jnp.asarray(kidx), jnp.asarray(counters))
    jax.block_until_ready(args)
    np.asarray(keystream(*args))  # warm the executable
    red = jax.jit(lambda o: o.sum())
    iters = 8 if fast else 32
    rates = []
    for _w in range(3):
        t0 = time.perf_counter()
        outs = [keystream(*args) for _ in range(iters)]
        np.asarray(red(outs[-1]))  # dependent D2H = true completion
        rates.append(iters * n_blocks * BLOCK / (time.perf_counter() - t0))
    return round(sorted(rates)[len(rates) // 2])


def run_cfg10(fast: bool, rng) -> dict:
    """Tenants x keys x fan-out re-encryption matrix (ISSUE 12 /
    ROADMAP item 6): the MQT-TZ stage measured at the engine seam —
    decrypt-once + ONE batched per-subscriber keystream dispatch per
    fan-out tick — against the plaintext fan-out baseline: the
    per-subscriber Packet copy + encode the unencrypted per-subscriber
    delivery path pays (re-encrypted fan-out can never share frames,
    so THAT is the path it displaces). Each cell A/Bs the device
    keystream against the vectorized-host path (the breaker's
    degradation target — on a CPU-jax box the host path is usually the
    deployable config; on a real accelerator the device path wins) and
    the acceptance ratio takes the better deployable path. Sampled
    device dispatches are differentially checked (mismatches must be
    zero)."""
    from mqtt_tpu.packets import ENCODERS, PUBLISH, FixedHeader, Packet
    from mqtt_tpu.tenancy import KeyRegistry, RecryptEngine, TenantPlane

    n_tenants = 2 if fast else 4
    keys_per_tenant = int(
        os.environ.get("BENCH_RECRYPT_KEYS", 16 if fast else 128)
    )
    fanouts = (10, 100)
    payload_sizes = (256, 4096)
    iters = 20 if fast else 100
    reg = KeyRegistry()
    plane = TenantPlane()
    tenants = []
    t0 = time.perf_counter()
    for t in range(n_tenants):
        name = f"bt{t}"
        tenant = plane.register(name, encrypted=("e/",))
        tenants.append(tenant)
        for k in range(keys_per_tenant):
            reg.set_key(name, f"c{k}", bytes([t, k % 256]) * 8)
    build_s = time.perf_counter() - t0
    eng = RecryptEngine(reg, oracle_sample=16, device_min_blocks=1)
    eng.reseed_nonce(b"bnch")
    out: dict = {
        "tenants": n_tenants,
        "keys_per_tenant": keys_per_tenant,
        "key_setup_seconds": round(build_s, 3),
        "matrix": {},
        "oracle_mismatches": 0,
    }
    worst_ratio_at_100 = 0.0
    for size in payload_sizes:
        plaintext = (bytes(range(256)) * (size // 256 + 1))[:size]
        for fanout in fanouts:
            tenant = tenants[0]
            targets = [
                (f"c{i % keys_per_tenant}", (f"c{i % keys_per_tenant}",))
                for i in range(fanout)
            ]
            wire = eng.seal_with_key(bytes([0, 0]) * 8, plaintext)
            # plaintext baseline: per-subscriber Packet copy + encode
            # (what the per-subscriber plaintext delivery path pays; the
            # recrypt path pays the same copies PLUS the crypto)
            pk = Packet(
                fixed_header=FixedHeader(type=PUBLISH),
                topic_name="e/bench/topic",
                payload=plaintext,
            )
            t0 = time.perf_counter()
            for _ in range(iters):
                for _t in targets:
                    o = pk.copy(False)
                    buf = bytearray()
                    ENCODERS[PUBLISH](o, buf)
            base_dt = time.perf_counter() - t0

            def recrypt_leg(engine) -> float:
                # warmup (jit compile / first-touch of the shapes)
                job = engine.decrypt_job(tenant, ("c0",), wire)
                pt = engine.open_publish(tenant, ("c0",), wire, job)
                assert pt == plaintext
                engine.seal_fanout(tenant, pt, targets)
                t0 = time.perf_counter()
                for _ in range(iters):
                    job = engine.decrypt_job(tenant, ("c0",), wire)
                    pt = engine.open_publish(tenant, ("c0",), wire, job)
                    sealed = engine.seal_fanout(tenant, pt, targets)
                    for _t in targets:
                        o = pk.copy(False)
                        o.payload = sealed.get(_t[0], b"")
                        buf = bytearray()
                        ENCODERS[PUBLISH](o, buf)
                return time.perf_counter() - t0

            dev_dt = recrypt_leg(eng)
            # A/B: the vectorized-host keystream path (the breaker's
            # degradation target; usually the deployable config on a
            # CPU-jax box)
            host_eng = RecryptEngine(
                reg, oracle_sample=0, device_min_blocks=1 << 30
            )
            host_eng.reseed_nonce(b"bnhh")
            host_dt = recrypt_leg(host_eng)
            rec_dt, path = min((dev_dt, "device"), (host_dt, "host"))
            base_rate = iters * fanout / base_dt if base_dt else 0.0
            ratio = rec_dt / base_dt if base_dt else float("inf")
            if fanout == 100:
                worst_ratio_at_100 = max(worst_ratio_at_100, ratio)
            out["matrix"][f"payload{size}_fanout{fanout}"] = {
                "plaintext_deliveries_per_sec": round(base_rate),
                "recrypt_deliveries_per_sec": round(
                    iters * fanout / rec_dt
                )
                if rec_dt
                else 0,
                "recrypt_vs_plaintext_ratio": round(ratio, 3),
                "best_path": path,
                "device_path_ratio": round(dev_dt / base_dt, 3)
                if base_dt
                else None,
                "host_path_ratio": round(host_dt / base_dt, 3)
                if base_dt
                else None,
            }
    out["device_batches"] = eng.device_batches
    out["oracle_mismatches"] = eng.oracle_mismatches
    out["kernel_worst_ratio_at_fanout100"] = round(worst_ratio_at_100, 3)
    # real-accelerator keystream byte rate as a TOP-LEVEL scalar so the
    # bench-history ledger keeps it and exp/bench_trend.py gates its
    # trajectory (ISSUE 18 satellite; honest skip dict on CPU-jax)
    out["keystream_device_bytes_per_sec"] = _keystream_device_rate(fast)
    # the acceptance leg: a REAL broker A/B at 100-subscriber fan-out.
    # QoS1 deliveries (the at-least-once class trust-sensitive
    # workloads run on) pay the per-subscriber copy+encode path either
    # way, so the measured ratio is what re-encryption actually costs a
    # deployment: plaintext namespace vs encrypted namespace, same
    # broker, same subscribers.
    out["broker"] = _recrypt_broker_ab(fast)
    ratio = out["broker"]["recrypt_vs_plaintext_ratio"]
    out["within_2x_at_fanout100"] = ratio <= 2.0
    if eng.oracle_mismatches:
        log(f"cfg10 ORACLE MISMATCHES: {eng.oracle_mismatches}")
    return out


def _recrypt_broker_ab(fast: bool) -> dict:
    """The cfg 10 acceptance leg: one in-process broker, 100 QoS1
    subscribers over real TCP, a publisher driving the SAME payloads
    through a plaintext topic and an encrypted-namespace topic; the
    ratio of wall-clock fan-out rates is the re-encryption overhead a
    deployment actually pays."""
    import asyncio

    from mqtt_tpu.hooks.auth import AllowHook
    from mqtt_tpu.listeners import Config as LConfig
    from mqtt_tpu.listeners.tcp import TCP
    from mqtt_tpu.server import Options, Server
    from mqtt_tpu.stress import _connect_bytes, _subscribe_bytes

    port = 18845
    fanout = 100
    msgs = 30 if fast else 120
    payload_size = 256
    pub_key = bytes(range(16))
    sub_key_of = lambda i: bytes([i % 256]) * 16  # noqa: E731

    async def read_publishes(reader, counter, done_evt, want):
        """Count PUBLISH frames off one subscriber connection."""
        try:
            while counter[0] < want:
                first = await reader.readexactly(1)
                rl = 0
                mult = 1
                while True:
                    b = (await reader.readexactly(1))[0]
                    rl += (b & 0x7F) * mult
                    mult *= 128
                    if not (b & 0x80):
                        break
                body = await reader.readexactly(rl) if rl else b""
                if first[0] >> 4 == 3:  # PUBLISH
                    counter[0] += 1
                del body
            done_evt.set()
        except (asyncio.IncompleteReadError, ConnectionError):
            done_evt.set()

    async def main() -> dict:
        tenants = {
            "bt": {
                "encrypted": ["e/"],
                "keys": {"pub": pub_key.hex()},
            }
        }
        users = {"pub": "bt"}
        for i in range(fanout):
            tenants["bt"]["keys"][f"s{i}"] = sub_key_of(i).hex()
            users[f"s{i}"] = "bt"
        opts = Options(
            tenancy=True,
            tenants=tenants,
            tenant_users=users,
            telemetry=False,
            profile=False,
            # the CPU-jax box serves keystreams faster from the
            # vectorized host path (BENCH_RECRYPT_DEVICE=1 forces the
            # device kernel — the right config on a real accelerator)
            recrypt_device_min_blocks=(
                4 if os.environ.get("BENCH_RECRYPT_DEVICE") == "1" else 1 << 30
            ),
        )
        srv = Server(opts)
        srv.add_hook(AllowHook())
        srv.add_listener(
            TCP(LConfig(type="tcp", id="recrypt", address=f"127.0.0.1:{port}"))
        )
        await srv.serve()
        eng = srv._recrypt
        try:
            subs = []
            for i in range(fanout):
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.write(_connect_bytes(f"s{i}", version=4))
                await w.drain()
                await r.readexactly(4)
                w.write(_subscribe_bytes(1, "p/#", qos=1))
                await w.drain()
                await r.readexactly(5)
                w.write(_subscribe_bytes(2, "e/#", qos=1))
                await w.drain()
                await r.readexactly(5)
                subs.append((r, w))
            pr, pw = await asyncio.open_connection("127.0.0.1", port)
            pw.write(_connect_bytes("pub", version=4))
            await pw.drain()
            await pr.readexactly(4)

            plaintext = (bytes(range(256)) * 2)[:payload_size]

            async def leg(topic, payloads) -> float:
                counters = []
                dones = []
                for r, _w in subs:
                    counter = [0]
                    done = asyncio.Event()
                    counters.append(counter)
                    dones.append(done)
                    asyncio.get_running_loop().create_task(
                        read_publishes(r, counter, done, len(payloads))
                    )
                t0 = time.perf_counter()
                tb = topic.encode()
                for i, body in enumerate(payloads):
                    var = (
                        len(tb).to_bytes(2, "big")
                        + tb
                        + (i % 65534 + 1).to_bytes(2, "big")
                        + body
                    )
                    # QoS1 PUBLISH frame
                    hdr = bytearray([0x32])
                    rl = len(var)
                    while True:
                        e = rl % 128
                        rl //= 128
                        hdr.append(e | (0x80 if rl else 0))
                        if not rl:
                            break
                    pw.write(bytes(hdr) + var)
                await pw.drain()
                await asyncio.wait_for(
                    asyncio.gather(*[d.wait() for d in dones]), timeout=120
                )
                return time.perf_counter() - t0

            plain_wall = await leg("p/bench", [plaintext] * msgs)
            enc_wall = await leg(
                "e/bench",
                [eng.seal_with_key(pub_key, plaintext) for _ in range(msgs)],
            )
            total = fanout * msgs
            return {
                "fanout": fanout,
                "msgs": msgs,
                "payload_bytes": payload_size,
                "qos": 1,
                "plaintext_deliveries_per_sec": round(total / plain_wall),
                "recrypt_deliveries_per_sec": round(total / enc_wall),
                "recrypt_vs_plaintext_ratio": round(
                    enc_wall / plain_wall, 3
                ),
                "recrypt_fanouts": eng.fanouts,
                "oracle_mismatches": eng.oracle_mismatches,
                "no_key_drops": eng.no_key_drops,
            }
        finally:
            await srv.close()

    return asyncio.run(main())


def run_cfg11(fast: bool, rng) -> dict:
    """Config 11 (ISSUE 16): the durable session plane. Two legs:

    1. recovery-time vs key count over the log-structured store, A/B
       between pure log replay and snapshot+tail (the checkpoint is the
       whole point: replay cost must scale with the tail, not history);
    2. retained wildcard-scan throughput, device kernel
       (ops/retained.RetainedMatchEngine) vs the host trie walk
       (TopicsIndex.messages), with a full parity check first.
    """
    import shutil
    import tempfile

    from mqtt_tpu.hooks.storage.logkv import LogKVOptions, LogKVStore
    from mqtt_tpu.ops.retained import RetainedMatchEngine
    from mqtt_tpu.packets import PUBLISH, FixedHeader, Packet
    from mqtt_tpu.topics import TopicsIndex

    # -- leg 1: recovery-time sweep --------------------------------------
    scales = [
        int(s)
        for s in os.environ.get(
            "BENCH_DURABLE_KEYS",
            "2000,10000" if fast else "10000,100000,1000000",
        ).split(",")
        if s.strip()
    ]
    tail_every = 20  # after the checkpoint, 5% of keys get a tail update
    recovery = []
    for n in scales:
        row: dict = {"keys": n}
        for label, snap in (("log", False), ("snapshot", True)):
            d = tempfile.mkdtemp(prefix="bench-logkv-")
            try:
                s = LogKVStore()
                s.init(LogKVOptions(path=d, gc_interval=0.0))
                # session-plane shaped records (the restart workload is
                # dominated by SUB_ rows: one per persisted subscription)
                for i in range(n):
                    s._set(f"SUB_cl{i}:bench/c{i}/#", b'{"qos":1}')
                if snap:
                    s.snapshot()
                    for i in range(0, n, tail_every):
                        s._set(f"SUB_cl{i}:bench/c{i}/#", b'{"qos":2}')
                s.stop()
                t0 = time.perf_counter()
                s2 = LogKVStore()
                s2.init(LogKVOptions(path=d, gc_interval=0.0))
                dt = time.perf_counter() - t0
                st = s2.durable_stats()
                s2.stop()
                if st["keys"] != n:
                    raise AssertionError(
                        f"cfg11 recovery lost keys: {st['keys']} != {n}"
                    )
                row[f"recovery_s_{label}"] = round(dt, 4)
                row[f"replayed_keys_{label}"] = st["replayed_keys"]
            finally:
                shutil.rmtree(d, ignore_errors=True)
        row["snapshot_speedup"] = round(
            row["recovery_s_log"] / max(row["recovery_s_snapshot"], 1e-9), 2
        )
        recovery.append(row)
        log(f"cfg11 recovery {row}")
    top = recovery[-1]

    # -- leg 2: retained matching, device kernel vs host walk ------------
    n_ret = 2_000 if fast else 50_000
    idx = TopicsIndex()
    for i in range(n_ret):
        idx.retain_message(
            Packet(
                fixed_header=FixedHeader(type=PUBLISH, retain=True),
                topic_name=(
                    f"region{i % 40}/device{(i // 40) % 50}"
                    f"/metric{i // 2000}"
                ),
                payload=b"r",
            )
        )
    # wildcard shapes only: the engine declines exact filters by design
    # (a host dict hit beats any kernel), so they would bench the
    # fallback path, not the kernel
    filters = []
    for k in range(64):
        filters.append(
            [
                f"region{k % 40}/device{k % 50}/+",
                f"region{k % 40}/+/metric{k % 25}",
                f"region{k % 40}/#",
                f"+/device{k % 50}/metric{k % 25}",
            ][k % 4]
        )
    eng = RetainedMatchEngine(idx, max_levels=8, oracle_sample=0)
    eng.reseed()
    mismatched = 0
    for f in filters:  # parity first: the speed of a wrong scan is noise
        dev = eng.match(f)
        host = {pk.topic_name for pk in idx.messages(f)}
        if dev is None or set(dev) != host:
            mismatched += 1
    rounds = 4 if fast else 20

    t0 = time.perf_counter()
    for _ in range(rounds):
        for f in filters:
            eng.match(f)
    dev_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(rounds):
        for f in filters:
            idx.messages(f)
    host_dt = time.perf_counter() - t0
    scans = rounds * len(filters)

    out = {
        # top-level scalars are what the history ledger keeps (and what
        # exp/bench_trend.py gates): replay throughput at the largest
        # scale + the device scan rate, both higher-is-better
        "recovery_keys_per_sec": round(
            top["keys"] / max(top["recovery_s_snapshot"], 1e-9)
        ),
        "recovery_s_log": top["recovery_s_log"],
        "recovery_s_snapshot": top["recovery_s_snapshot"],
        "snapshot_speedup": top["snapshot_speedup"],
        "max_keys": top["keys"],
        "retained_corpus": n_ret,
        "retained_device_scans_per_sec": round(scans / max(dev_dt, 1e-9)),
        "retained_host_scans_per_sec": round(scans / max(host_dt, 1e-9)),
        "retained_device_vs_host": round(host_dt / max(dev_dt, 1e-9), 3),
        "retained_parity_mismatches": mismatched,
        "recovery": recovery,
    }
    if mismatched:
        log(f"cfg11 RETAINED PARITY MISMATCHES: {mismatched}")
    return out


def run_cfg12(fast: bool, rng) -> dict:
    """Config 12 (ISSUE 17): the mesh predicate push-down gate in
    isolation — no sockets, no jax. One tree-mode Cluster gets a
    hand-installed edge summary whose subtree holds ONLY a predicated
    subscriber (``pp/#$GT{v:50}``): the exact shape where push-down
    earns its keep, because the plain bloom misses and every forward
    hinges on evaluating the interned rule against the payload. Three
    legs over ``_route_edges``:

    1. failing payloads — the edge must be SKIPPED every time (the
       filtered ratio is asserted at 1.0: a silent degradation to
       pass-through is a correctness bug, not a slow round);
    2. passing payloads — the edge must forward every time;
    3. a bloom-miss topic — the PR 9 topic gate, for scale.
    """
    import shutil
    import tempfile

    from mqtt_tpu.cluster import Cluster, _EdgeSummary
    from mqtt_tpu.mesh_topology import BloomBits, CountedBloom
    from mqtt_tpu.predicates import predicate_digest
    from mqtt_tpu.server import Options, Server
    from mqtt_tpu.topics import summary_base

    d = tempfile.mkdtemp(prefix="bench-mesh-pushdown-")
    try:
        srv = Server(
            Options(telemetry=False, profile=False, cluster_topology="tree")
        )
        cl = Cluster(srv, 0, 2, d)
        ep = cl.topo.epoch
        sfx = "$GT{v:50}"
        interest = CountedBloom()
        interest.add(summary_base("pp/#" + sfx))
        cl._edge_summaries[1] = _EdgeSummary(
            interest.bits(),
            1,
            (ep.num, ep.boot, ep.proposer),
            plain=BloomBits.empty(),
            digests=((predicate_digest(sfx), sfx),),
        )

        n = 20_000 if fast else 200_000
        # a pool of distinct payloads so the JSON parse inside the gate
        # is paid on every call, like live traffic — not one hot string
        fails = [
            json.dumps({"v": rng.randint(0, 50), "seq": i}).encode()
            for i in range(256)
        ]
        passes = [
            json.dumps({"v": rng.randint(51, 500), "seq": i}).encode()
            for i in range(256)
        ]
        route = cl._route_edges

        base_filtered = cl.summary_predicate_filtered_forwards
        t0 = time.perf_counter()
        for i in range(n):
            route("pp/x", None, payload=fails[i & 255])
        fail_dt = time.perf_counter() - t0
        filtered = cl.summary_predicate_filtered_forwards - base_filtered

        forwarded = 0
        t0 = time.perf_counter()
        for i in range(n):
            forwarded += len(route("pp/x", None, payload=passes[i & 255]))
        pass_dt = time.perf_counter() - t0

        base_bloom = cl.summary_filtered_forwards
        t0 = time.perf_counter()
        for i in range(n):
            route("zz/x", None, payload=passes[i & 255])
        bloom_dt = time.perf_counter() - t0
        bloom_filtered = cl.summary_filtered_forwards - base_bloom

        ratio = filtered / max(n, 1)
        if ratio != 1.0 or forwarded != n or bloom_filtered != n:
            # a gate that stops filtering (or worse, stops forwarding)
            # must fail the round loudly, not post a smaller number
            raise AssertionError(
                f"cfg12 gate broke: filtered={filtered}/{n} "
                f"forwarded={forwarded}/{n} bloom={bloom_filtered}/{n}"
            )
        out = {
            "pushdown_filter_evals_per_sec": round(n / max(fail_dt, 1e-9)),
            "pushdown_forward_evals_per_sec": round(n / max(pass_dt, 1e-9)),
            "bloom_gate_evals_per_sec": round(n / max(bloom_dt, 1e-9)),
            "pushdown_filtered_ratio": ratio,
            "evals": n,
        }
        log(f"cfg12 pushdown {out}")
        return out
    finally:
        shutil.rmtree(d, ignore_errors=True)


def run_materializer_bench(fast: bool) -> dict:
    """Config 7: the host result materializer in isolation — NO device, no
    jax. Synthetic snapshot tables and packed range rows shaped like cfg2's
    (window 16, P=4, ~11 hits/topic at 1M-sub scale) feed the C extension
    (native/accelmod.c) and the pure-Python oracle: the host
    materialization leg of the north-star path, measured with no device
    in it."""
    import random as _r

    from mqtt_tpu.ops.flat import _LazySubTable
    from mqtt_tpu.ops.matcher import _accel, expand_sids
    from mqtt_tpu.packets import Subscription
    from mqtt_tpu.topics import Subscribers
    from mqtt_tpu.utils.gctune import tune_for_throughput

    tune_for_throughput()
    rng = _r.Random(7)
    window, P = 16, 4
    n_entries = 5_000 if fast else 80_000
    batch = 1024 if fast else 16384
    snaps = []
    for e in range(n_entries):
        n_cli = rng.randint(1, 7)  # E[hits/topic] = 0.7*4*4 ~ 11, matching cfg2
        snaps.append(
            (
                tuple(
                    (
                        f"cl{e}_{i}",
                        Subscription(
                            filter=f"f/{e}", qos=rng.randint(0, 2),
                            identifier=rng.choice([0, 0, 0, e % 200 + 1]),
                        ),
                    )
                    for i in range(n_cli)
                ),
                (),
                (),
            )
        )
    totals = [len(s[0]) for s in snaps]
    packed = np.zeros((batch, 2 * P + 2), dtype=np.int32)
    for i in range(batch):
        for p in range(P):
            if rng.random() < 0.7:
                e = rng.randrange(n_entries)
                packed[i, p] = e * window
                packed[i, P + p] = totals[e]
    hits = int(packed[:, P : 2 * P].sum())
    out = {"batch": batch, "avg_hits_per_topic": round(hits / batch, 2)}
    iters = 3 if fast else 10
    acc = _accel()
    if acc is not None:
        acc.resolve_batch(packed, batch, P, snaps, window, Subscribers)  # warm
        c_lat = []
        t0 = time.perf_counter()
        for _ in range(iters):
            t1 = time.perf_counter()
            acc.resolve_batch(packed, batch, P, snaps, window, Subscribers)
            c_lat.append(time.perf_counter() - t1)
        dt = time.perf_counter() - t0
        out["c_materializer_topics_per_sec"] = round(iters * batch / dt)
        out["c_materializer_subs_per_sec"] = round(iters * hits / dt)
        out["telemetry"] = telemetry_block(c_lat, "materialize")
    # the pure-Python oracle (the pre-round-5 ceiling), on a slice to keep
    # the config cheap
    table = _LazySubTable(window, list(snaps), n_entries * window)
    rows = packed[: max(256, batch // 8)].tolist()
    py_lat = []
    t0 = time.perf_counter()
    for row in rows:
        t1 = time.perf_counter()
        sids = []
        for p in range(P):
            c = row[P + p]
            if c:
                sids.extend(range(row[p], row[p] + c))
        expand_sids(table, sids, Subscribers())
        py_lat.append(time.perf_counter() - t1)
    dt = time.perf_counter() - t0
    out["python_oracle_topics_per_sec"] = round(len(rows) / dt)
    if "telemetry" not in out:  # no C module: the oracle is the stage
        out["telemetry"] = telemetry_block(py_lat, "materialize_oracle")
    if "c_materializer_topics_per_sec" in out:
        out["c_speedup_vs_python"] = round(
            out["c_materializer_topics_per_sec"] / out["python_oracle_topics_per_sec"], 2
        )
    return out


def run_broker_bench(fast: bool) -> dict:
    """The mqtt-stresser analog over real TCP against a broker subprocess
    (reference README.md:474-508): N clients x M QoS0 msgs on own topics,
    per-client publish/receive medians + aggregate. The broker runs in its
    own process (no jax); the load generator runs here. CPU count is
    reported because both timeshare this machine's cores."""
    import subprocess

    from mqtt_tpu.stress import run_stress

    port = 18831
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    # multi-core data plane (mqtt_tpu.cluster): one SO_REUSEPORT worker
    # per core when the host has them — the scale-out the reference gets
    # from goroutine-per-connection; a 1-core host stays single-process
    # (workers would only timeshare the core and pay mesh overhead)
    workers = max(1, int(os.environ.get("BENCH_BROKER_WORKERS", os.cpu_count() or 1)))
    cmd = [sys.executable, "-m", "mqtt_tpu.stress", "--serve", "--broker",
           f"127.0.0.1:{port}"]
    if workers > 1:
        cmd += ["--workers", str(workers)]
    proc = subprocess.Popen(
        cmd,
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        cwd=repo,
        env=env,
    )
    out = {"cpus": os.cpu_count(), "broker_workers": workers}
    try:
        assert proc.stdout.readline().strip() == b"READY"
        # the reference table's exact mqtt-stresser scenarios: 2/10/100
        # clients x 10000 messages each (README.md:482-506)
        scenarios = (
            [(2, 1000), (10, 500)]
            if fast
            else [(2, 10000), (10, 10000), (100, 10000)]
        )
        for n, m in scenarios:
            import asyncio

            r = asyncio.run(run_stress("127.0.0.1", port, n, m))
            out[f"{n}_clients"] = r
            log(f"broker {n}x{m}: {r}")
        # the reference table's 100-client receive median (mochi v2.2.10,
        # M2, 8 cores): 7,274 msg/s (README.md:500-503). Receive is the
        # honest end-to-end rate; QoS0 publish rates on both sides mostly
        # measure socket-buffer writes, so no publish ratio is reported.
        hundred = out.get("100_clients")
        if hundred:
            out["vs_mochi_100c_receive"] = round(
                hundred["receive_median_per_sec"] / 7274, 4
            )
    finally:
        try:
            proc.stdin.close()
            proc.wait(timeout=10)
        except Exception:
            proc.kill()
    return out


def run_conn_rate_qos_matrix(fast: bool) -> dict:
    """Config 8's connections × rate × QoS comparative matrix (the
    PAPERS.md 2603.21600 reporting frame; ISSUE 13): a subprocess
    broker (one SO_REUSEPORT worker per core, the run_broker_bench
    posture) driven through every (clients, msgs/client, QoS) cell.
    Every cell carries its OWN publish/receive medians so rounds diff
    cell by cell; BENCH_LAZY=0 re-runs the whole matrix on the legacy
    eager/per-subscriber path (the serve-side broker honors the knob)."""
    import asyncio
    import subprocess

    from mqtt_tpu.stress import run_stress

    port = 18852
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    workers = max(
        1, int(os.environ.get("BENCH_BROKER_WORKERS", os.cpu_count() or 1))
    )
    cmd = [
        sys.executable, "-m", "mqtt_tpu.stress", "--serve", "--broker",
        f"127.0.0.1:{port}",
    ]
    if workers > 1:
        cmd += ["--workers", str(workers)]
    proc = subprocess.Popen(
        cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=repo, env=env
    )
    cells = (
        [(2, 300, 0), (2, 300, 1), (6, 150, 0), (6, 150, 1)]
        if fast
        else [
            (10, 2000, 0), (10, 2000, 1),
            (100, 600, 0), (100, 600, 1),
            (100, 2000, 0), (100, 2000, 1),
        ]
    )
    matrix = []
    try:
        assert proc.stdout.readline().strip() == b"READY"
        for n, m, q in cells:
            r = asyncio.run(run_stress("127.0.0.1", port, n, m, qos=q))
            matrix.append(r)
            log(
                f"matrix {n}c x {m}m qos{q}: "
                f"{r['aggregate_msgs_per_sec']}/s "
                f"recv_median {r['receive_median_per_sec']}/s"
            )
    finally:
        try:
            proc.stdin.close()
            proc.wait(timeout=10)
        except Exception:
            proc.kill()
    return {
        "lazy": bench_lazy(),
        "broker_workers": workers,
        "cells": matrix,
    }


def run_idle_conn_matrix(fast: bool) -> dict:
    """Config 8's connection-scale axis (ISSUE 15): a subprocess broker
    running the event-loop shard fabric holds a MOSTLY-IDLE device
    population (the 2603.21600 connection axis — 1k/10k attached
    connections that never publish) while a small active set measures
    per-cell receive medians. Each cell's ``receive_flatness_ratio`` is
    its active-receive-median against the 0-idle baseline cell — a flat
    front-end holds ~1.0 as the idle population grows.

    ``BENCH_SHARDS=1`` re-runs the whole matrix on the single-loop
    front-end (the serve-side broker honors the knob); the shard count
    itself comes from ``BENCH_SHARD_COUNT`` (default ``max(2, cpus)``).
    The idle ramp adapts to the bench process's fd budget (2 fds per
    connection in this harness) — dropped cells are recorded, never
    silently skipped."""
    import asyncio
    import resource
    import subprocess

    from mqtt_tpu.stress import ramp_idle, run_stress

    port = 18862
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    shards = 1
    if os.environ.get("BENCH_SHARDS") != "1":
        shards = int(
            os.environ.get("BENCH_SHARD_COUNT", max(2, os.cpu_count() or 1))
        )
        env["MQTT_TPU_LOOP_SHARDS"] = str(shards)
    levels_env = os.environ.get("BENCH_IDLE_LEVELS")
    if levels_env:
        # operator override, e.g. BENCH_IDLE_LEVELS=0,1000,10000 — a
        # fast-mode run can still measure the full connection axis
        idle_levels = [int(x) for x in levels_env.split(",") if x.strip()]
    else:
        idle_levels = [0, 200] if fast else [0, 1000, 10000]
    active, msgs = (4, 150) if fast else (10, 500)

    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < hard:
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
            soft = hard
        except (ValueError, OSError):
            pass
    # the broker runs in a SUBPROCESS with its own fd table: this
    # process pays one fd per idle connection (the client side)
    budget = max(0, soft - 1024)

    proc = subprocess.Popen(
        [
            sys.executable, "-m", "mqtt_tpu.stress", "--serve", "--broker",
            f"127.0.0.1:{port}",
        ],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=repo, env=env,
    )
    cells = []
    dropped = []
    idle_writers: list = []

    async def drive() -> None:
        attached = 0
        baseline = None
        for level in idle_levels:
            if level > budget:
                dropped.append(level)
                log(f"idle-conn cell {level} dropped (fd budget {budget})")
                continue
            if level > attached:
                t0 = time.perf_counter()
                idle_writers.extend(
                    await ramp_idle(
                        "127.0.0.1", port, level - attached,
                        client_prefix=f"bench-idle-{attached}",
                    )
                )
                ramp_s = time.perf_counter() - t0
                attached = level
            else:
                ramp_s = 0.0
            r = await run_stress("127.0.0.1", port, active, msgs)
            cell = {
                "idle_connections": level,
                "clients": active,
                "msgs_per_client": msgs,
                "ramp_seconds": round(ramp_s, 2),
                "publish_median_per_sec": r["publish_median_per_sec"],
                "receive_median_per_sec": r["receive_median_per_sec"],
                "receive_min_per_sec": r["receive_min_per_sec"],
                "aggregate_msgs_per_sec": r["aggregate_msgs_per_sec"],
            }
            if baseline is None:
                baseline = max(1e-9, r["receive_median_per_sec"])
            cell["receive_flatness_ratio"] = round(
                r["receive_median_per_sec"] / baseline, 4
            )
            cells.append(cell)
            log(
                f"idle-conn cell {level}: recv_median "
                f"{r['receive_median_per_sec']}/s flatness "
                f"{cell['receive_flatness_ratio']}"
            )
        for w in idle_writers:
            try:
                w.close()
            except Exception:
                pass

    try:
        assert proc.stdout.readline().strip() == b"READY"
        asyncio.run(drive())
    finally:
        try:
            proc.stdin.close()
            proc.wait(timeout=10)
        except Exception:
            proc.kill()
    return {
        "loop_shards": shards,
        "idle_levels": idle_levels,
        "dropped_levels": dropped,
        "cells": cells,
    }


async def _flatness_profile_block(fast: bool) -> dict:
    """Config 8's host-observatory leg (mqtt_tpu.profiling): the
    per-client receive-rate flatness ratio (10 vs 100 clients — ROADMAP
    item 3's success criterion), the host-profile artifact at the
    100-client point (top contended locks + fan-out amplification), and
    an A/B overhead probe — the same 100-client workload with the
    profiler+lock plane enabled vs disabled (the acceptance bar is
    <=2% aggregate msgs/s; both numbers land in the artifact so the
    claim is re-checkable every round). Device matcher off: the
    collapse under study is the pure broker write path."""
    from mqtt_tpu.hooks.auth import AllowHook
    from mqtt_tpu.listeners import Config as LConfig
    from mqtt_tpu.listeners.tcp import TCP
    from mqtt_tpu.server import Options, Server
    from mqtt_tpu.stress import run_flatness, run_stress

    small, large = (4, 20) if fast else (10, 100)
    m_small, m_large = (300, 120) if fast else (2000, 600)

    from mqtt_tpu.utils.locked import DEFAULT_PLANE

    async def one_round(port: int, profile_on: bool) -> tuple[dict, dict, int]:
        # the lock plane aggregates process-wide by name: reset so this
        # round's top-contended list reflects THIS workload, not the
        # storm phase that ran earlier in the same process
        DEFAULT_PLANE.reset()
        srv = Server(
            Options(
                device_matcher=False,
                profile=profile_on,
                profile_locks=profile_on,
                # broker and load generator share one process+loop here:
                # the generator's starved reads look like slow consumers
                # and the governor would evict the probe itself — this
                # leg measures the write path, not overload control
                overload_control=False,
            )
        )
        srv.add_hook(AllowHook())
        srv.add_listener(
            TCP(LConfig(type="tcp", id="flat", address=f"127.0.0.1:{port}"))
        )
        await srv.serve()
        try:
            # a short warmup so neither arm pays first-connection costs
            await run_stress("127.0.0.1", port, 2, 100)
            flat = await run_flatness(
                "127.0.0.1", port,
                clients_small=small, clients_large=large,
                msgs_small=m_small, msgs_large=m_large,
            )
            # best-of-2 on the large leg for the overhead A/B: a single
            # sub-second round is scheduler noise, not a measurement
            rerun = await run_stress("127.0.0.1", port, large, m_large)
            best = max(
                flat["large"]["aggregate_msgs_per_sec"],
                rerun["aggregate_msgs_per_sec"],
            )
            return flat, srv.host_profile_block(), best
        finally:
            await srv.close()

    flat_on, profile, on_rate = await one_round(18843, True)
    flat_off, _, off_rate = await one_round(18844, False)
    return {
        "clients": flat_on["clients"],
        "receive_flatness_ratio": flat_on["receive_flatness_ratio"],
        # per-cell medians (diffable cell-by-cell across rounds)
        "cells": flat_on.get("cells"),
        "small": flat_on["small"],
        "large": flat_on["large"],
        "host_profile": profile,
        "profiler_overhead": {
            "enabled_msgs_per_sec": on_rate,
            "disabled_msgs_per_sec": off_rate,
            "overhead_pct": round((off_rate - on_rate) / max(1, off_rate) * 100, 2),
        },
    }


async def _slo_overhead_block(fast: bool) -> dict:
    """Config 8's SLO-plane A/B (ISSUE 14 acceptance: SLI-stamping
    overhead <= 2%): the same stress workload against two fresh brokers
    — the SLO observatory fully ON (delivery SLIs + a live burn-rate
    objective evaluating every housekeeping tick) vs ``Options.slo``
    OFF — best-of-2 each so a sub-second scheduler hiccup cannot decide
    the verdict. Production sampling rates (the default 1-in-64): the
    claim under test is the plane's cost as shipped, not under
    sample-everything instrumentation."""
    from mqtt_tpu.hooks.auth import AllowHook
    from mqtt_tpu.listeners import Config as LConfig
    from mqtt_tpu.listeners.tcp import TCP
    from mqtt_tpu.server import Options, Server
    from mqtt_tpu.stress import run_stress

    clients, msgs = (10, 500) if fast else (40, 1500)
    reps = 3 if fast else 4

    async def one_round(port: int, slo_on: bool) -> float:
        srv = Server(
            Options(
                device_matcher=False,
                overload_control=False,  # measure the SLI path, not sheds
                slo=slo_on,
                slo_objectives=(
                    ["p99 delivery < 50ms over 5m", "shed ratio < 0.1%"]
                    if slo_on
                    else None
                ),
            )
        )
        srv.add_hook(AllowHook())
        srv.add_listener(
            TCP(LConfig(type="tcp", id="slo", address=f"127.0.0.1:{port}"))
        )
        await srv.serve()
        try:
            await run_stress("127.0.0.1", port, 2, 100)  # warmup
            res = await run_stress("127.0.0.1", port, clients, msgs)
            if slo_on and srv.slo is not None:
                # prove the engine actually evaluated live objectives
                # during the measured window (a dead engine would make
                # the A/B vacuous)
                srv.slo.evaluate()
            return res["aggregate_msgs_per_sec"]
        finally:
            await srv.close()

    # INTERLEAVED best-of-N: the in-process loopback workload is noisy
    # (±20% between back-to-back identical rounds on a shared box), so
    # sequential arm-then-arm would measure scheduler drift, not the
    # plane; alternating rounds and taking each arm's best bounds the
    # bias to within-pair jitter
    on_rate = off_rate = 0.0
    for rep in range(reps):
        on_rate = max(on_rate, await one_round(18845 + 2 * rep, True))
        off_rate = max(off_rate, await one_round(18846 + 2 * rep, False))
    out = {
        "enabled_msgs_per_sec": on_rate,
        "disabled_msgs_per_sec": off_rate,
        "reps": reps,
        "overhead_pct": round(
            (off_rate - on_rate) / max(1, off_rate) * 100, 2
        ),
    }
    # deterministic micro-measurement of the EXACT added work: one
    # sampled-path observe_delivery call (dict probe + histogram
    # observe), amortized over the 1-in-telemetry_sample publishes that
    # pay it. The macro A/B above inherits the loopback harness's
    # scheduler noise; this number is the stamping cost itself, and the
    # amortized-per-publish figure is what the <=2% acceptance bar is
    # judged against on noisy boxes.
    from mqtt_tpu.telemetry import Telemetry

    tele = Telemetry(sample=64)
    n = 200_000
    t0 = time.perf_counter()
    for i in range(n):
        tele.observe_delivery(1e-4, "", 0, "local")
    per_call_ns = (time.perf_counter() - t0) / n * 1e9
    out["sampled_observe_ns"] = round(per_call_ns, 1)
    out["amortized_ns_per_publish"] = round(per_call_ns / 64, 2)
    if off_rate > 0:
        # the stamping cost as a fraction of the measured per-publish
        # wall budget (1/rate): the harness-noise-free overhead claim
        out["amortized_overhead_pct"] = round(
            (per_call_ns / 64) / (1e9 / off_rate) * 100, 4
        )
    return out


async def _loopwitness_overhead_block(fast: bool) -> dict:
    """Config 8's loop-affinity witness A/B (ISSUE 19 acceptance:
    armed-recording overhead <= 2% amortized): the same stress workload
    against fresh brokers with ``DEFAULT_LOOP_PLANE`` armed (a recording
    LoopWitness noting every OutboundQueue put/get and stage resolve
    seam) vs disarmed — the shipped default outside the test suite.
    Interleaved best-of-N, same rationale as ``_slo_overhead_block``:
    alternating rounds bound scheduler drift to within-pair jitter. The
    disarmed hot path must stay at the LockWitness bar: one plane.active
    attribute read + branch per touch point, no allocation, no lock."""
    import asyncio

    from mqtt_tpu.hooks.auth import AllowHook
    from mqtt_tpu.listeners import Config as LConfig
    from mqtt_tpu.listeners.tcp import TCP
    from mqtt_tpu.server import Options, Server
    from mqtt_tpu.stress import run_stress
    from mqtt_tpu.utils.loopwitness import DEFAULT_LOOP_PLANE

    clients, msgs = (10, 500) if fast else (40, 1500)
    reps = 3 if fast else 4
    witness_edges = 0

    async def one_round(port: int, armed: bool) -> float:
        nonlocal witness_edges
        if armed:
            DEFAULT_LOOP_PLANE.arm_witness()  # recording, non-raising
        srv = Server(Options(device_matcher=False, overload_control=False))
        srv.add_hook(AllowHook())
        srv.add_listener(
            TCP(LConfig(type="tcp", id="loopwit", address=f"127.0.0.1:{port}"))
        )
        await srv.serve()
        try:
            await run_stress("127.0.0.1", port, 2, 100)  # warmup
            res = await run_stress("127.0.0.1", port, clients, msgs)
            if armed and DEFAULT_LOOP_PLANE.witness is not None:
                # the armed arm must actually produce evidence — a dead
                # witness would make the A/B vacuous
                witness_edges = max(
                    witness_edges, len(DEFAULT_LOOP_PLANE.witness.edges)
                )
            return res["aggregate_msgs_per_sec"]
        finally:
            await srv.close()
            DEFAULT_LOOP_PLANE.disarm_witness()

    on_rate = off_rate = 0.0
    try:
        for rep in range(reps):
            on_rate = max(on_rate, await one_round(18870 + 2 * rep, True))
            off_rate = max(off_rate, await one_round(18871 + 2 * rep, False))
    finally:
        DEFAULT_LOOP_PLANE.disarm_witness()
    out = {
        "armed_msgs_per_sec": on_rate,
        "disarmed_msgs_per_sec": off_rate,
        "reps": reps,
        "witness_edges_observed": witness_edges,
        "overhead_pct": round((off_rate - on_rate) / max(1, off_rate) * 100, 2),
    }
    # deterministic micro-measurement of the EXACT added work, free of
    # the loopback harness's scheduler noise. Three legs: a bare bool
    # attribute read (the LockWitness bar), the disarmed guard as the
    # instrumented code writes it (plane.active read + branch), and the
    # armed note_crossing (seam pick + known-edge dict probe). The
    # acceptance bars are judged on these: disarmed_guard_ns must sit at
    # flag_read_ns (no hidden work when off), and the armed per-touch
    # cost amortized over the measured per-publish wall budget must stay
    # under 2%.
    from mqtt_tpu.utils.loopwitness import LoopPlane

    plane = LoopPlane()
    n = 200_000
    flag = plane.active  # noqa: F841 — prime the attribute
    t0 = time.perf_counter()
    for _ in range(n):
        flag = plane.active
    flag_read_ns = (time.perf_counter() - t0) / n * 1e9
    t0 = time.perf_counter()
    for _ in range(n):
        if plane.active:
            w = plane.witness
            if w is not None:
                w.note_crossing("outbound_queue", "put_local", "put_cross", None)
    disarmed_guard_ns = (time.perf_counter() - t0) / n * 1e9
    w = plane.arm_witness()
    # steady state as the broker pays it: the queue HAS a stamped owner
    # and the touch happens ON that loop, so the seam pick runs the
    # loop-identity probe every call (this block is async — the running
    # loop is real)
    own = asyncio.get_running_loop()
    w.note_crossing("outbound_queue", "put_local", "put_cross", own)  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        w.note_crossing("outbound_queue", "put_local", "put_cross", own)
    armed_note_ns = (time.perf_counter() - t0) / n * 1e9
    out["flag_read_ns"] = round(flag_read_ns, 1)
    out["disarmed_guard_ns"] = round(disarmed_guard_ns, 1)
    out["armed_note_ns"] = round(armed_note_ns, 1)
    if off_rate > 0:
        # each delivered publish crosses the witnessed queue seam twice
        # (put + get). The ACCEPTANCE bar (ISSUE 19) is on the DISARMED
        # path — the shipped default: its guard cost amortized over the
        # measured per-publish wall budget must stay under 2%, and the
        # guard itself at the LockWitness bar (one flag test, see
        # flag_read_ns vs disarmed_guard_ns above). The armed figure is
        # recorded telemetry for test-suite/fuzzer budgeting.
        budget_ns = 1e9 / off_rate
        out["amortized_overhead_pct"] = round(
            (2 * disarmed_guard_ns) / budget_ns * 100, 4
        )
        out["armed_amortized_pct"] = round(
            (2 * armed_note_ns) / budget_ns * 100, 4
        )
    return out


def run_storm_bench(fast: bool) -> dict:
    """Config 8: the publish-storm overload drill. An in-process broker
    (tight overload caps, a deliberately slow consumer, the staging loop
    active) takes an offered load far above what
    its consumers drain; the artifact records how it DEGRADES: shed rate
    (0x97-acked QoS1 + dropped QoS0), slow-consumer evictions, the peak
    staging pending depth (must stay at/below its cap), and the
    admitted-traffic delivery p99 — brokers must fail by clean errors,
    not OOM/latency collapse (PAPERS: IoT-edge broker benchmarking)."""
    import asyncio

    from mqtt_tpu.hooks.auth import AllowHook
    from mqtt_tpu.listeners import Config as LConfig
    from mqtt_tpu.listeners.tcp import TCP
    from mqtt_tpu.server import Options, Server
    from mqtt_tpu.stress import _connect_bytes, _subscribe_bytes, run_storm

    port = 18841
    publishers = 4 if fast else 12
    msgs_each = 1500 if fast else 6000

    async def main() -> dict:
        opts = Options(
            # the stage (and its pending-depth signal) needs a matcher
            device_matcher=True,
            matcher_opts={"max_levels": 4, "background": False},
            # tight caps so the storm visibly crosses the bands: the
            # governor is judged on degrading predictably, not on how
            # much a big box can absorb
            overload_stage_max_pending=1024,
            overload_max_outbound_backlog=8192,
            overload_throttle_enter=0.20,
            overload_throttle_exit=0.05,
            overload_shed_enter=0.40,
            overload_shed_exit=0.05,
            overload_eval_interval_ms=50.0,
            overload_min_dwell_ms=300.0,
            overload_publish_quota=500,
            overload_shed_quota=50,
            overload_eviction_grace_ms=300.0,
            overload_client_buffer_limit_bytes=65536,
        )
        srv = Server(opts)
        srv.add_hook(AllowHook())
        srv.add_listener(TCP(LConfig(type="tcp", id="storm", address=f"127.0.0.1:{port}")))
        await srv.serve()
        try:
            # the slow consumer: subscribes to every storm topic, never
            # reads — its bounded queue must fill and, past the grace
            # window, cost it a DISCONNECT 0x97 eviction (not broker RAM)
            slow_r, slow_w = await asyncio.open_connection("127.0.0.1", port)
            slow_w.write(_connect_bytes("storm-slow", version=4))
            await slow_w.drain()
            await slow_r.readexactly(4)  # CONNACK
            # shrink both kernel buffers so the victim's unread backlog
            # surfaces in the broker's transport buffer (where the
            # eviction watermark looks) instead of hiding in TCP buffers
            import socket as _sock

            cs = slow_w.get_extra_info("socket")
            if cs is not None:
                cs.setsockopt(_sock.SOL_SOCKET, _sock.SO_RCVBUF, 4096)
            scl = srv.clients.get("storm-slow")
            if scl is not None and scl.net.writer is not None:
                ss = scl.net.writer.get_extra_info("socket")
                if ss is not None:
                    ss.setsockopt(_sock.SOL_SOCKET, _sock.SO_SNDBUF, 4096)
            slow_w.write(_subscribe_bytes(1, "storm/#"))
            await slow_w.drain()
            await slow_r.readexactly(5)  # SUBACK
            slow_w.transport.pause_reading()  # a truly stalled reader

            storm = await run_storm(
                "127.0.0.1", port,
                publishers=publishers, msgs_each=msgs_each,
                qos1_fraction=0.5, seed=7,
            )
            srv.sweep_overload()  # deterministic final eviction pass
            if srv.overload.gauges()["evictions"] == 0:
                # the backlog may need one more grace-spaced observation
                await asyncio.sleep(0.4)
                srv.sweep_overload()
            gauges = srv.overload.gauges()
            out = dict(storm)
            delivered_rate = storm["delivered"] / max(1e-9, storm["storm_wall_s"])
            out["offered_to_delivered_ratio"] = round(
                storm["offered_rate_per_sec"] / max(1.0, delivered_rate), 2
            )
            out["governor_sheds"] = gauges["sheds"]
            # the TOTAL shed rate (0x97-acked QoS1 AND silently-dropped
            # QoS0, counted broker-side) over the offered load
            out["governor_shed_rate"] = round(
                gauges["sheds"] / max(1, storm["offered"]["total"]), 4
            )
            out["governor_evictions"] = gauges["evictions"]
            out["governor_throttled"] = gauges["throttled"]
            out["governor_transitions"] = gauges["transitions"]
            out["peak_pressure"] = max(
                (v for k, v in gauges.items() if k.startswith("peak/")),
                default=0.0,
            )
            if srv._stage is not None:
                out["peak_pending_depth"] = srv._stage.peak_pending
                out["pending_cap"] = srv._stage.max_pending
                out["stage_admission_fallbacks"] = srv._stage.admission_fallbacks
            if srv.telemetry is not None:
                # the live telemetry plane's per-stage view of the storm:
                # sampled stage p50/p99, batch occupancy, fallback classes
                srv.telemetry.recorder.join_writer()  # dump IO off-thread
                out["telemetry"] = srv.telemetry.bench_block()
                out["flight_dumps"] = srv.telemetry.recorder.dumps
            if srv.profiler is not None:
                # the live broker's device duty-cycle / overlap / idle-gap
                # numbers under storm load (mqtt_tpu.tracing) — ROADMAP
                # item 1's per-round baseline of the staging gap
                out["device_pipeline"] = srv.profiler.bench_block()
            # the storm broker's own host-profile block (stacks, locks,
            # amplification under STORM load, mqtt_tpu.profiling)
            out["host_profile_storm"] = srv.host_profile_block()
            try:
                slow_w.close()
            except Exception:
                pass
            return out
        finally:
            await srv.close()

    out = asyncio.run(main())
    # the flatness + amplification + overhead leg runs on fresh
    # default-cap brokers AFTER the storm broker is fully closed: its
    # deliberately tiny quotas would shed the probe itself, and its
    # still-armed lock plane would contaminate the disabled A/B arm
    out["receive_flatness"] = asyncio.run(_flatness_profile_block(fast))
    # hoisted as a TOP-LEVEL scalar so the bench-history ledger keeps it
    # (_history_config_block) and exp/bench_trend.py can gate the
    # flatness trajectory beside the headline (ISSUE 15)
    out["receive_flatness_ratio"] = out["receive_flatness"][
        "receive_flatness_ratio"
    ]
    # the connection-scale axis (ISSUE 15): 1k/10k mostly-idle clients
    # against the shard-fabric subprocess broker, BENCH_SHARDS=1 A/B
    out["idle_conn_matrix"] = run_idle_conn_matrix(fast)
    # the SLO-plane on/off A/B (ISSUE 14 acceptance: <=2% SLI overhead);
    # BENCH_SLO=0 skips the arm for broker-only sweeps
    if os.environ.get("BENCH_SLO") != "0":
        out["slo_overhead"] = asyncio.run(_slo_overhead_block(fast))
    # the loop-affinity witness on/off A/B (ISSUE 19 acceptance: armed
    # recording <=2% amortized; disarmed cost = one flag test)
    out["loopwitness_overhead"] = asyncio.run(_loopwitness_overhead_block(fast))
    # the connections × rate × QoS comparative matrix runs last, on a
    # subprocess broker (per-core workers) — the 2603.21600 reporting
    # frame for the encode-once write path (ISSUE 13)
    out["conn_rate_qos_matrix"] = run_conn_rate_qos_matrix(fast)
    return out


def main() -> None:
    from mqtt_tpu.ops.backend import device_summary

    fast = os.environ.get("BENCH_FAST") == "1"
    n_subs = int(os.environ.get("BENCH_SUBS", 50_000 if fast else 1_000_000))
    batch = int(os.environ.get("BENCH_BATCH", 1024 if fast else 16384))
    iters = int(os.environ.get("BENCH_ITERS", 5 if fast else 20))
    which = {
        int(c)
        for c in os.environ.get(
            "BENCH_CONFIGS", "1,2,3,4,5,6,7,8,9,10,11,12"
        ).split(",")
        if c.strip()
    }
    rng = random.Random(7)

    # the device every result below names. Initializes the default
    # backend and RAISES if it cannot: no probe, no retry, no fallback.
    # (The broker configs' children are host-only and never import a
    # backend, so this process may hold the chip for the whole run.)
    device = device_summary()
    link = probe_link() if which & {1, 2, 3, 4, 5} else None
    log(f"device={device} fast={fast} subs={n_subs} batch={batch} link={link}")

    configs = {}
    t_all = time.perf_counter()

    def run(key: str, fn, *args) -> None:
        t0 = time.perf_counter()
        configs[key] = fn(*args)
        log(f"{key} {configs[key]} ({time.perf_counter()-t0:.0f}s)")

    if 1 in which:
        run("1_exact_10k", run_cfg1, rng, fast, batch)
    if 2 in which:
        run("2_1m_plus", run_cfg2, n_subs, batch, iters, rng)
    if 3 in which:
        # the flat build walks terminals once, so deep tries need no cap
        n3 = min(n_subs, int(os.environ.get("BENCH_SUBS3", n_subs)))
        run("3_deep_hash", run_cfg3, n3, batch, iters, rng)
        configs["3_deep_hash"]["n_subs"] = n3
    if 4 in which:
        n_groups = int(os.environ.get("BENCH_GROUPS", 5_000 if fast else 100_000))
        run("4_shared_groups", run_cfg4, n_groups, 16, batch, iters, rng)
    if 5 in which:
        n5 = min(n_subs, 20_000 if fast else 200_000)
        run("5_churn_ids_retained", run_cfg5, n5, batch, iters, rng)
    if 6 in which:
        run("broker", run_broker_bench, fast)
    if 7 in which:
        run("7_materializer_host", run_materializer_bench, fast)
    if 8 in which:
        run("8_publish_storm", run_storm_bench, fast)
    if 9 in which:
        # predicate-selectivity sweep (the rule kernel is shape-tiny)
        run("9_predicate_sweep", run_cfg9, fast, rng)
    if 10 in which:
        # tenants x keys x fan-out re-encryption matrix
        run("10_recrypt_matrix", run_cfg10, fast, rng)
    if 11 in which:
        # durable recovery sweep + retained device-vs-host scan rates
        run("11_durable_recovery", run_cfg11, fast, rng)
    if 12 in which:
        # mesh predicate push-down gate (ISSUE 17): pure host, no sockets
        run("12_mesh_pushdown", run_cfg12, fast, rng)
    log(f"total bench wall time {time.perf_counter()-t_all:.0f}s")
    for cfg in configs.values():
        cfg.update(device)

    headline = configs.get("2_1m_plus") or next(
        (c for c in configs.values() if "e2e_matches_per_sec" in c), None
    )
    # headline stays the full-path e2e rate (BASELINE.md's definition);
    # the transfer-free kernel rate is surfaced alongside
    value = (headline or {}).get("e2e_matches_per_sec")
    kernel = (headline or {}).get("device_kernel_matches_per_sec") or 0
    out = {
        "metric": f"publish_topic_matches_per_sec@{n_subs}_wildcard_subs_e2e",
        "value": value,
        "unit": "matches/s",
        **device,
        "link": link,
        "configs": configs,
    }
    if value is not None:
        out["vs_baseline"] = round(value / TARGET_MATCHES_PER_SEC, 4)
        out["device_kernel_matches_per_sec"] = kernel
        out["kernel_vs_baseline"] = round(kernel / TARGET_MATCHES_PER_SEC, 4)
    else:
        # no e2e-producing config was SELECTED: the headline is null,
        # never a silent 0 that poisons trend lines
        out["vs_baseline"] = None
        out["device_kernel_matches_per_sec"] = None
        out["kernel_vs_baseline"] = None
        out["skipped"] = True
        out["skip_reason"] = "no e2e-producing config selected by BENCH_CONFIGS"
    print(json.dumps(out))
    append_history(out)


def _history_config_block(cfg) -> dict:
    """The compact per-config slice a history entry keeps: top-level
    scalars only (rates, ratios, counts) — enough for trend lines
    without duplicating whole artifacts into the ledger."""
    if not isinstance(cfg, dict):
        return {}
    return {
        k: v for k, v in cfg.items() if isinstance(v, (int, float, bool))
    }


def history_entry(doc: dict, round_tag: str = "", time_unix: int = 0) -> dict:
    """The CANONICAL bench-history ledger entry for one bench document
    — the single schema both the live append below and
    exp/bench_trend.py's backfill write, so the two can never drift."""
    return {
        "round": round_tag,
        "time_unix": time_unix,
        "metric": doc.get("metric"),
        "value": doc.get("value"),
        "vs_baseline": doc.get("vs_baseline"),
        "device_kernel_matches_per_sec": doc.get(
            "device_kernel_matches_per_sec"
        ),
        "configs": {
            name: _history_config_block(cfg)
            for name, cfg in (doc.get("configs") or {}).items()
        },
    }


def append_history(out: dict) -> None:
    """Append this round's headline + per-config scalar blocks to the
    bench-history ledger (ISSUE 14 satellite: ``BENCH_HISTORY.jsonl``,
    gated by exp/bench_trend.py in CI). SKIPPED rounds never append —
    a null headline must not enter the trend window (the r05 lesson) —
    and ``BENCH_HISTORY=0`` disables the ledger outright (subprocess
    test runs). ``BENCH_HISTORY_PATH`` overrides the destination."""
    if os.environ.get("BENCH_HISTORY") == "0" or out.get("skipped"):
        return
    path = os.environ.get("BENCH_HISTORY_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.jsonl"
    )
    entry = history_entry(
        out,
        round_tag=os.environ.get("BENCH_ROUND", ""),
        time_unix=int(time.time()),  # ledger stamps are operator-correlatable wall clock
    )
    try:
        with open(path, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError as e:
        log(f"bench-history append failed ({e}); continuing")
    else:
        log(f"bench-history entry appended to {path}")


if __name__ == "__main__":
    main()
