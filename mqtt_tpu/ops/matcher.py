"""The broker-facing device matcher.

``TpuMatcher`` compiles the host trie into a :mod:`flat-hash index
<mqtt_tpu.ops.flat>`, matches PUBLISH-topic batches in one device dispatch,
and merges results host-side — bit-identical to
``TopicsIndex.subscribers`` (reference walk: topics.go:583-628) because
every case the device cannot prove is re-walked on the host trie.

The previous CSR/NFA trie-walk kernel was retired in round 4: it was
gather-bound, and a per-level walk issues orders of magnitude more
gathers per topic than the flat design's one row per probe shape
(ops/flat.py).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

from ..packets import Subscription
from ..topics import Subscribers, TopicsIndex
from ..tracing import span
from .flat import (
    FLAG_OVERFLOW,
    FLAG_WIDE,
    KIND_CLIENT,
    KIND_INLINE,
    KIND_SHARED,
    FlatIndex,
    _bucket,
    build_flat_index,
    flat_match_compact,
    flat_match_packed,
    flat_match_ranges,
    pack_tokens,
)
from .hashing import tokenize_topics

# write-once memo for the C materializer (immutable bindings, not a
# mutable container singleton — brokerlint R8); a racing first resolve
# is benign: native.accel() is itself memoized and returns one module
_ACCEL_MEMO: Optional[object] = None
_ACCEL_RESOLVED = False

_log = logging.getLogger("mqtt_tpu.ops.matcher")


def _accel():
    """The C materializer module (native/accelmod.c) or None; resolved once
    and cached (the native loader itself is also memoized, this just skips
    the call overhead in the per-batch path)."""
    global _ACCEL_MEMO, _ACCEL_RESOLVED
    if not _ACCEL_RESOLVED:
        from .. import native

        _ACCEL_MEMO = native.accel()
        _ACCEL_RESOLVED = True
    return _ACCEL_MEMO


def expand_sids(table: list, sids, subs: Subscribers, seen: Optional[set] = None) -> Subscribers:
    """Merge device sub ids (local to ``table``) into a Subscribers result,
    preserving host gather semantics: per-client merge, shared keyed on the
    group filter, inline keyed on identifier. Shared by the single-device
    and mesh-sharded matchers.

    This is the broker's per-publish result materialization — the hottest
    host loop after the kernel itself. The production path is the C
    materializer (native/accelmod.c), which performs the same merges via
    slot offsets; this Python form is the fallback and the semantic
    source of truth the differential tests pin the C module against:
    a client's first sighting takes ``Subscription.self_merged_copy`` —
    value-identical to ``merge(self, self)`` including the
    shared-and-extended identifiers map — and later sightings call the
    real ``merge``."""
    if seen is None:
        seen = set()
    if not isinstance(sids, list):
        sids = sids.tolist() if hasattr(sids, "tolist") else list(sids)
    n = len(table)
    seen_add = seen.add
    subscriptions = subs.subscriptions
    shared = subs.shared
    inline = subs.inline_subscriptions
    memo_get = getattr(table, "memo", {}).get
    for sid in sids:
        if sid < 0 or sid >= n or sid in seen:
            continue
        seen_add(sid)
        entry = memo_get(sid)
        if entry is None:
            entry = table[sid]
        kind = entry.kind
        if kind == KIND_CLIENT:
            client = entry.client
            sub = entry.subscription
            prev = subscriptions.get(client)
            if prev is None:
                subscriptions[client] = sub.self_merged_copy()
            else:
                subscriptions[client] = prev.merge(sub)
        elif kind == KIND_SHARED:
            group = shared.get(entry.group_filter)
            if group is None:
                group = shared[entry.group_filter] = {}
            group[entry.client] = entry.subscription
        else:
            inline[entry.subscription.identifier] = entry.subscription
    return subs


def subscribers_equal(a: Subscribers, b: Subscribers) -> bool:
    """Value equality of two match results — the differential re-walk
    check the resilience layer (mqtt_tpu.resilience) runs between a
    device result and the live host walk. Compares the three gather maps
    (``Subscription`` is a dataclass, so entries compare by value);
    ``shared_selected`` is derived during fan-out and deliberately
    excluded."""
    return (
        a.subscriptions == b.subscriptions
        and a.shared == b.shared
        and a.inline_subscriptions == b.inline_subscriptions
    )


def pick_compact_capacity(
    pinned: int,
    hits_ewma: float,
    b_padded: int,
    max_hits: int,
    held_caps: dict,
) -> int:
    """The shared pair-buffer capacity policy (single-device and
    mesh-sharded matchers — one implementation so the hysteresis can
    never desynchronize). A pinned capacity is honored at its bucket
    (no floor: the operator chose the overflow/transfer trade-off);
    the adaptive pick sizes EWMA x 1.5 headroom, pow2-bucketed, capped
    at the theoretical hit bound, and STICKY per batch bucket: grow
    the moment the need does (overflows are the expensive path) but
    shrink only once the need sits 4x below the held capacity —
    chasing the EWMA down through every pow2 bucket would pay a fresh
    XLA compile per step, which measurably dwarfs anything the smaller
    transfer saves. ``held_caps`` (batch bucket -> capacity) is the
    caller-owned sticky state."""
    if pinned > 0:
        return _bucket(max(1, min(pinned, max_hits)), minimum=8)
    need = _bucket(
        max(1, min(int(b_padded * hits_ewma * 1.5) + 64, max_hits)),
        minimum=256,
    )
    held = held_caps.get(b_padded, 0)
    if need > held or need * 4 <= held:
        held_caps[b_padded] = held = need
    return held


def fold_hits_ewma(ewma: float, n_hits: int, b: int) -> float:
    """One batch's true hit count folded into the capacity EWMA."""
    if b <= 0:
        return ewma
    return 0.7 * ewma + 0.3 * (n_hits / b)


def resolve_compact_py(
    pair_sid: np.ndarray,
    pair_shard: Optional[np.ndarray],
    totals: np.ndarray,
    host_route: np.ndarray,
    topics: list[str],
    subs_table: Any,
    tables: Optional[list] = None,
    n_hits: Optional[int] = None,
) -> tuple[list, list[int]]:
    """The pure-Python compacted-pair expansion — the semantic source of
    truth the C fast path (accelmod.resolve_compact) is pinned against.
    The pair stream is topic-major; ``totals`` drives the cursor, so each
    pair's topic index is implicit. Host-routed rows skip their pairs and
    land in the overflow index list (the caller re-walks them).

    ``n_hits`` (when given) enforces the same geometry invariant the C
    path checks: the totals must account for exactly the pair stream —
    a mismatch means the caller mixed buffers from different batches
    and raises, never a silent mis-expansion (list slicing would
    quietly truncate otherwise)."""
    if n_hits is not None:
        claimed = int(totals.sum())
        if claimed != n_hits or n_hits > len(pair_sid):
            raise ValueError(
                "compact pair stream and totals disagree "
                f"(totals claim {claimed}, n_hits {n_hits}, "
                f"stream {len(pair_sid)})"
            )
    sids = pair_sid.tolist()
    shards = pair_shard.tolist() if pair_shard is not None else None
    tot = totals.tolist()
    route = host_route.tolist()
    results: list = []
    ovf_idx: list[int] = []
    cursor = 0
    n = len(topics)
    for i, t in enumerate(tot):
        if i >= n:
            break  # bucket-padding rows: nothing to materialize
        if route[i]:
            ovf_idx.append(i)
            results.append(None)
            cursor += t
            continue
        subs = Subscribers()
        if shards is None:
            expand_sids(subs_table, sids[cursor : cursor + t], subs)
        else:
            assert tables is not None
            # group this topic's pairs by shard run (pairs are emitted
            # shard-major within a topic; sid spaces are shard-local)
            j = cursor
            end = cursor + t
            while j < end:
                s = shards[j]
                k = j
                while k < end and shards[k] == s:
                    k += 1
                expand_sids(tables[s], sids[j:k], subs, seen=set())
                j = k
        results.append(subs)
        cursor += t
    return results, ovf_idx


def materialize_compact_pairs(
    stats: "MatcherStats",
    host_walk: Callable[[str], Subscribers],
    pair_sid: np.ndarray,
    pair_shard: Optional[np.ndarray],
    totals: np.ndarray,
    host_route: np.ndarray,
    n_hits: int,
    topics: list[str],
    subs_table: Any,
    window: int,
    true_overflow: np.ndarray,
    tables: Optional[list] = None,
    lazy: bool = False,
) -> list[Subscribers]:
    """Expand one device-compacted batch into Subscribers results —
    shared by the single-device and mesh-sharded matchers. ``totals``
    drives a cursor over the topic-major pair stream (padded rows
    included); host-routed topics skip their pairs and re-walk the live
    trie. ``pair_shard``/``tables`` serve the sharded form.

    ``lazy=True`` (and the C module present) returns
    ``SubscribersView`` results instead of materialized dicts: the pair
    stream stays the result currency and per-hit objects are built only
    when fan-out (or any dict-semantics consumer) actually asks
    (ISSUE 13). Host-routed rows still carry real Subscribers from the
    live trie walk; without the C module the eager expansion serves —
    laziness is an optimization, never a semantic."""
    acc = _accel()
    results: Optional[list] = None
    ovf_idx: list[int] = []
    if lazy and acc is not None and hasattr(acc, "resolve_compact_views"):
        try:
            results, ovf_idx = acc.resolve_compact_views(
                np.ascontiguousarray(pair_sid),
                None if pair_shard is None
                else np.ascontiguousarray(pair_shard),
                np.ascontiguousarray(totals),
                np.ascontiguousarray(host_route.astype(np.int32)),
                int(n_hits),
                len(topics),
                subs_table.snaps if tables is None
                else [t.snaps for t in tables],
                window,
                Subscribers,
            )
        except ValueError:
            # the same geometry tripwire as the eager path: mixed-batch
            # buffers must never degrade to a silent mis-expansion
            raise
        except Exception:  # pragma: no cover - C/py parity is pinned
            _log.exception("C resolve_compact_views failed; eager path")
            results = None
    if results is None and acc is not None and hasattr(acc, "resolve_compact"):
        try:
            results, ovf_idx = acc.resolve_compact(
                np.ascontiguousarray(pair_sid),
                None if pair_shard is None
                else np.ascontiguousarray(pair_shard),
                np.ascontiguousarray(totals),
                np.ascontiguousarray(host_route.astype(np.int32)),
                int(n_hits),
                len(topics),
                subs_table.snaps if tables is None
                else [t.snaps for t in tables],
                window,
                Subscribers,
            )
        except ValueError:
            # the C path's geometry tripwire (mixed-batch buffers):
            # deliberate and NOT recoverable — the Python expansion
            # would silently truncate on the same inputs, which is
            # exactly the mis-expansion the check exists to prevent
            raise
        except Exception:  # pragma: no cover - C/py parity is pinned
            # a genuine C-side fault (layout/runtime): the Python
            # expansion is the bit-identical fallback, and it re-checks
            # the geometry invariant itself so nothing degrades silently
            _log.exception("C resolve_compact failed; python expansion")
            results = None
    if results is None:
        results, ovf_idx = resolve_compact_py(
            pair_sid, pair_shard, totals, host_route, topics, subs_table,
            tables, n_hits=int(n_hits),
        )
    for i in ovf_idx:
        topic = topics[i]
        if topic:
            stats.host_fallbacks += 1
            # routed-only rows are fallbacks but not device overflows
            stats.overflows += int(bool(true_overflow[i]))
            results[i] = host_walk(topic)
        else:
            results[i] = Subscribers()
    if "" in topics:  # empty topic never matches (host-walk parity)
        for i, topic in enumerate(topics):
            if not topic:
                results[i] = Subscribers()
    return results


@dataclass
class MatcherStats:
    """Observability counters for a device matcher (SURVEY §5 tracing
    note). Exported as retained ``$SYS/broker/matcher/...`` topics by the
    server's $SYS loop when a device matcher is active (server.py).

    ``host_fallbacks`` counts topics re-walked on the host for any reason;
    ``overflows`` counts the subset caused by device-side routing
    (saturated buckets, over-deep topics) rather than delta-overlay
    routes.
    """

    batches: int = 0
    topics: int = 0
    host_fallbacks: int = 0
    overflows: int = 0
    rebuilds: int = 0
    rebuild_seconds: float = 0.0
    folds: int = 0  # incremental folds that avoided a full rebuild
    # bulk loads of the trie (TopicsIndex.bulk_load) that closed under a
    # delta overlay, and the background wake-ups (threshold or interval)
    # that an open one put off: the load ends in ONE build (ops/delta.py)
    bulk_loads: int = 0
    rebuilds_held: int = 0
    # topics served by the exact-map host fast path (wildcard-free filter
    # sets answer from one dict probe; no device round trip)
    host_fast: int = 0
    # device-resident hit compaction (ROADMAP item 1): batches whose
    # results transferred as packed (topic_idx, sid) pairs, batches whose
    # hit count overflowed the compaction capacity (served by the padded
    # path for that batch only), and the actual D2H result bytes moved
    compact_batches: int = 0
    compact_overflows: int = 0
    d2h_bytes: int = 0
    # wide entries (ops/flat.py: a filter more subscribers hold than the
    # window, laid over consecutive ordinals): how many the served index
    # holds, and the topics whose DEVICE answer, served, held the hit of
    # one — counted once a batch from the flags word the program returns
    wide_entries: int = 0
    wide_topics: int = 0
    # the LAST full rebuild, split into its host and device halves: the
    # flat-index build, the (completed) H2D upload, and the host bytes
    # of the arrays uploaded — what chip_smoke.py holds HBM in use to
    table_bytes: int = 0
    build_seconds: float = 0.0
    upload_seconds: float = 0.0
    # optional per-rebuild duration observer (the telemetry plane's
    # compile/rebuild histogram — mqtt_tpu.telemetry); set by the server
    rebuild_observer: Optional[Callable[[float], None]] = None

    def note_rebuild(self, dt: float) -> None:
        """Account one rebuild/fold wall time (and feed the observer)."""
        self.rebuild_seconds += dt
        cb = self.rebuild_observer
        if cb is not None:
            try:
                cb(dt)
            except Exception:  # pragma: no cover  # brokerlint: ok=R4 telemetry observer must not wedge the rebuild path; histogram loss is acceptable
                pass

    def as_dict(self) -> dict:
        out = {
            "batches": self.batches,
            "topics": self.topics,
            "host_fallbacks": self.host_fallbacks,
            "overflows": self.overflows,
            "rebuilds": self.rebuilds,
            "rebuild_seconds": round(self.rebuild_seconds, 3),
            "folds": self.folds,
            "bulk_loads": self.bulk_loads,
            "rebuilds_held": self.rebuilds_held,
            "host_fast": self.host_fast,
            "compact_batches": self.compact_batches,
            "compact_overflows": self.compact_overflows,
            "d2h_bytes": self.d2h_bytes,
            "wide_entries": self.wide_entries,
            "wide_topics": self.wide_topics,
        }
        out["fallback_ratio"] = (
            round(self.host_fallbacks / self.topics, 6) if self.topics else 0.0
        )
        return out


class TpuMatcher:
    """Broker-facing device matcher over the flat-hash index.

    Wildcard-shape fan-out is a build-time property of the filter set
    (ops/flat.py). ``out_slots`` caps the per-topic device result on the
    slot-expanding core (the mesh-sharded form); ``window`` is the ids an
    ordinal holds: a filter path with more is a wide entry over several
    consecutive ones, answered from the device like any other. The
    packed path transfers per-probe RANGES, which carry the complete
    result in 2P+2 ints per topic.
    """

    def __init__(
        self,
        topics: TopicsIndex,
        max_levels: int = 8,
        out_slots: int = 64,
        window: int = 16,
        cooperative: bool = False,
        compact: bool = True,
        compact_capacity: int = 0,
        hits_estimate: float = 2.0,
        lazy: bool = True,
    ) -> None:
        self.topics = topics
        self.max_levels = max_levels
        self.out_slots = out_slots
        self.window = window
        # cooperative rebuilds yield the GIL periodically — set by owners
        # that rebuild on a background thread while another thread serves
        self.cooperative = cooperative
        # device-resident hit compaction (ROADMAP item 1): results come
        # back as packed (topic_idx, sid) pairs sized for the hits that
        # exist. compact_capacity pins the pair buffer (0 = adaptive from
        # the observed hits-per-topic EWMA, seeded by hits_estimate —
        # the server wires TopicSketch's avg_hits_per_topic here).
        self.compact = compact
        self.compact_capacity = max(0, compact_capacity)
        # zero-materialization fan-out (ISSUE 13): results come back as
        # lazy SubscribersView objects over the device pair stream /
        # ranges rows instead of eagerly-built dicts; any consumer that
        # needs dict semantics transparently materializes (bit-identical
        # — the eager path remains the differential oracle). No C module
        # = no views; the flag simply has no effect then.
        self.lazy = lazy
        self._hits_ewma = max(1.0, float(hits_estimate))
        # sticky per-batch-bucket capacities (see _compact_capacity_for):
        # every distinct capacity is one XLA executable, so the pick must
        # not chase the EWMA through pow2 buckets compile after compile
        self._caps: dict[int, int] = {}
        self.stats = MatcherStats()
        # device pipeline profiler (mqtt_tpu.tracing.DeviceProfiler) or
        # None; set by the server. match_topics_async
        # feeds it the dispatch window, the resolver the D2H sync —
        # duty cycle / overlap / idle-gap accounting lives there.
        self.profiler: Optional[Any] = None
        # one (flat_index, device_arrays, built_version) tuple, swapped
        # atomically by rebuild() so a concurrent match never mixes
        # arrays and salt from different generations
        self._state: Optional[tuple] = None
        # True while the np table may diverge from the device table (an
        # aborted fold); only a full rebuild clears it
        self._fold_poisoned = False

    # -- index lifecycle ---------------------------------------------------

    def rebuild(self) -> None:
        """Recompile the host trie into device arrays. Shapes are
        power-of-two bucketed (ops/flat.py) so successive rebuilds under
        churn reuse the jitted executable."""
        import jax.numpy as jnp

        t0 = time.perf_counter()
        version = self.topics.version
        flat = build_flat_index(
            self.topics,
            max_levels=self.max_levels,
            window=self.window,
            cooperative=self.cooperative,
        )
        t_built = time.perf_counter()
        host_arrays = (flat.table, flat.pat_kind, flat.pat_depth, flat.pat_mask)
        device_arrays = tuple(jnp.asarray(a) for a in host_arrays)
        for arr in device_arrays:
            # the upload belongs to the rebuild, not to the first match
            # that would otherwise wait for it
            arr.block_until_ready()
        t_up = time.perf_counter()
        self._state = (flat, device_arrays, version)
        self._fold_poisoned = False
        stats = self.stats
        stats.rebuilds += 1
        stats.wide_entries = flat.n_wide
        stats.table_bytes = sum(int(a.nbytes) for a in host_arrays)
        stats.build_seconds = t_built - t0
        stats.upload_seconds = t_up - t_built
        stats.note_rebuild(t_up - t0)
        # warm the C materializer off the publish path: its first use
        # otherwise triggers a synchronous cc compile inside the first
        # batch's resolve (seconds of publish latency on a cold host)
        _accel()

    def fold(self, filters) -> bool:
        """Incrementally fold mutations for ``filters`` into the compiled
        index: copy-on-write host edits plus a bucket-row scatter on
        device (~KB uploaded) instead of a seconds-long full rebuild +
        table upload. Returns False when a full rebuild is required
        (FlatIndex.fold documents the cases).

        Concurrency: the fold mutates a CLONE of the sub table and swaps
        a new FlatIndex, so resolvers that captured earlier state — even
        ones issued generations before the mutation being folded — keep
        decoding against their own snapshots. The np bucket table is
        shared and edited in place (resolvers never read it); an aborted
        fold leaves it diverged from the device table, so folding poisons
        itself until the full rebuild that MUST follow a False return has
        rebuilt both from scratch."""
        import jax.numpy as jnp

        from .flat import scatter_rows

        st = self._state
        if st is None or self._fold_poisoned:
            return False
        flat, arrays, _ = st
        t0 = time.perf_counter()
        version = self.topics.version
        flat = flat.clone_for_fold()
        self._fold_poisoned = True  # cleared on success or by rebuild()
        res = flat.fold(self.topics, filters)
        if res is None:
            return False
        updates, pats_changed = res
        new_table = arrays[0]
        if updates:
            k = _bucket(len(updates), minimum=8)
            idx = np.full(k, updates[-1][0], dtype=np.int32)
            rows = np.tile(updates[-1][1], (k, 1))
            for i, (s, r) in enumerate(updates):
                idx[i] = s
                rows[i] = r
            new_table = scatter_rows(
                arrays[0], jnp.asarray(idx), jnp.asarray(rows)
            )
        new_pats = (
            tuple(
                jnp.asarray(a)
                for a in (flat.pat_kind, flat.pat_depth, flat.pat_mask)
            )
            if pats_changed
            else arrays[1:]
        )
        self._state = (flat, (new_table, *new_pats), version)
        self._fold_poisoned = False
        self.stats.folds += 1
        self.stats.wide_entries = flat.n_wide
        self.stats.note_rebuild(time.perf_counter() - t0)
        return True

    @property
    def csr(self) -> Optional[FlatIndex]:
        """The compiled index (named for continuity with the CSR era)."""
        st = self._state
        return st[0] if st is not None else None

    index = csr

    @property
    def stale(self) -> bool:
        st = self._state
        return st is None or st[2] != self.topics.version

    @property
    def device_arrays(self) -> tuple:
        """The flat index as device arrays (built on demand)."""
        st = self._state
        if st is None or self.stale:
            self.rebuild()
            st = self._state
        assert st is not None  # rebuild() always swaps in a state
        return st[1]

    def match_tokens(self, tok1, tok2, lengths, is_dollar):
        """Raw device match over pre-tokenized topics; returns device
        ``(starts[B,P], cnts[B,P], totals[B], overflow[B])`` — the
        production ranges kernel (flat_match_ranges_core). The benchmark
        path."""
        if self._state is None or self.stale:
            self.rebuild()
        flat, arrays, _ = self._state
        return flat_match_ranges(
            *arrays,
            tok1,
            tok2,
            lengths,
            is_dollar,
            max_levels=flat.max_levels,
        )

    # -- matching ----------------------------------------------------------

    def match_topics_async(self, topics: list[str], route_to_host=None, profile=None):
        """Issue one device match batch and return a zero-arg resolver.

        The device call is dispatched asynchronously (JAX async dispatch);
        calling the resolver performs the D2H sync and the host-side
        expansion, returning ``list[Subscribers]``. Keeping a second batch
        in flight while the first resolves hides the host<->device round
        trip — the broker's staging loop and the benchmark both rely on it.

        ``route_to_host`` forces extra topics onto the host walk. It is
        either a plain ``topic -> bool`` predicate or an object exposing
        ``affected(topic)`` plus ``affected_batch(topics) -> indices`` (the
        delta overlay, ops/delta._Gen) — the batch form lets the C
        materializer skip the per-topic Python predicate loop entirely
        when no mutations are pending.

        ``profile`` is an optional per-batch
        :class:`mqtt_tpu.tracing.BatchProfile` the caller (the staging
        loop) holds; with a profiler attached this method fills its
        tokenize and H2D + dispatch spans and the resolver its D2H sync
        and resolve spans — the batch's own record, immune to
        concurrent/out-of-order resolution; each boundary is one
        ``perf_counter_ns`` read and the profiler's windows are fed from
        the same reads. While a profiler session keeps the record the
        spans are also ``TraceAnnotation`` blocks. When the profiler is
        attached but no record is passed (resilience probes), a
        private one is opened so the aggregates still see the batch.
        """
        import jax.numpy as jnp

        st = self._state
        if st is None or self.stale:
            self.rebuild()
            st = self._state
        assert st is not None  # rebuild() always swaps in a state
        flat, arrays, _ = st
        if flat.exact_map is not None:
            # wildcard-free filter set: one host dict probe per topic beats
            # any device round trip (SURVEY §7 hard part 4) — serve
            # synchronously, return a pre-resolved resolver
            return self._match_exact_fast(topics, flat, route_to_host)
        # pad ragged batches (the staging loop's windows) to a power-of-two
        # bucket so every batch size reuses one jitted executable; padded
        # rows are ignored at resolve time
        prof = self.profiler
        rec = None
        if prof is not None:
            rec = profile if profile is not None else prof.open_batch()
        b = len(topics)
        with span(rec, "tokenize"):
            padded = topics + [""] * (_bucket(max(1, b), minimum=16) - b)
            tok1, tok2, lengths, is_dollar, len_overflow = tokenize_topics(
                padded, flat.max_levels, flat.salt
            )
            # the host copy stays alive for the overflow fallback's re-upload
            host_tokens = pack_tokens(tok1, tok2, lengths, is_dollar)
        P = flat.pat_depth.shape[0]
        use_compact = self.compact and P > 0 and self._compact_pays(P)
        capacity = 0
        with span(rec, "h2d_dispatch"):
            if use_compact:
                capacity = self._compact_capacity_for(len(padded), flat)
                out_dev = flat_match_compact(
                    *arrays,
                    jnp.asarray(host_tokens),
                    max_levels=flat.max_levels,
                    capacity=capacity,
                )
            else:
                out_dev = flat_match_packed(
                    *arrays,
                    jnp.asarray(host_tokens),
                    max_levels=flat.max_levels,
                )
            # start the D2H as soon as the kernel finishes instead of when
            # the resolver blocks: the transfer overlaps the pipeline's
            # other in-flight batches
            out_dev.copy_to_host_async()
        if prof is not None:
            # the issue leg (tokenize + H2D + async dispatch) ends here;
            # the batch's in-flight window opens now. Stamp which chip
            # ran the batch first so the per-device window replicas
            # (ISSUE 18) attribute it correctly.
            dev = getattr(out_dev, "device", None)
            did = getattr(dev() if callable(dev) else dev, "id", None)
            rec.devices = (did,) if did is not None else None
            rec.bucket = len(padded)
            prof.note_dispatch(
                rec, rec.tokenize[0] / 1e9, rec.h2d_dispatch[1] / 1e9
            )
        if route_to_host is None:
            pred = batch_pred = None
        elif hasattr(route_to_host, "affected_batch"):
            pred = route_to_host.affected
            batch_pred = route_to_host.affected_batch
        else:
            pred = route_to_host
            batch_pred = None

        if not use_compact:

            def resolve() -> list[Subscribers]:
                with span(rec, "d2h_sync"):
                    # brokerlint: ok=R15 the blessed resolve seam: ONE batched D2H after copy_to_host_async, [B, 2P+2]
                    packed = np.asarray(out_dev)
                if prof is not None:
                    # the blocking D2H sync just completed: close the
                    # in-flight window (kernel + transfer) on this record
                    self._stamp_bytes(rec, packed.nbytes, False)
                    self._note_sync(prof, rec)
                with span(rec, "resolve"):
                    stats = self.stats
                    stats.batches += 1
                    stats.topics += len(topics)
                    stats.d2h_bytes += int(packed.nbytes)
                    # the ranges row carries per-topic totals: feed the same
                    # hits EWMA the compact path uses, so the encoding pick
                    # (_compact_pays) keeps adapting from EITHER path
                    self._observe_hits(
                        int(packed[: len(topics), 2 * P].sum()), len(topics)
                    )
                    packed = packed[: len(topics)]  # drop bucket-padding rows
                    return self._resolve_ranges(
                        packed, topics, flat, P,
                        len_overflow[: len(topics)], pred, batch_pred,
                    )

            return resolve

        def resolve_compact() -> list[Subscribers]:
            with span(rec, "d2h_sync"):
                # brokerlint: ok=R15 the blessed resolve seam: ONE batched D2H after copy_to_host_async, [2 + 2B + 2K] ints
                out = np.asarray(out_dev)
            with span(rec, "resolve"):
                return decode_compact(out)

        def decode_compact(out) -> list[Subscribers]:
            bp = len(padded)
            n_hits = int(out[0])
            batch_ovf = bool(out[1])
            stats = self.stats
            stats.batches += 1
            stats.topics += len(topics)
            self._observe_hits(n_hits, b)
            if batch_ovf:
                # hits outgrew the pair buffer: THIS batch re-runs on the
                # padded-ranges path (one extra dispatch+sync, still
                # bit-identical); the EWMA above already absorbed the
                # true hit count, so the next capacity pick fits
                stats.compact_overflows += 1
                self._hits_ewma = max(self._hits_ewma, n_hits / max(1, b))
                packed = np.asarray(
                    flat_match_packed(
                        *arrays,
                        jnp.asarray(host_tokens),
                        max_levels=flat.max_levels,
                    )
                )
                d2h_bytes = int(out.nbytes + packed.nbytes)
                stats.d2h_bytes += d2h_bytes
                if prof is not None:
                    # the re-run (dispatch + second sync) is part of this
                    # batch's resolve span, not of its in-flight window
                    self._stamp_bytes(rec, d2h_bytes, True)
                    self._note_sync(prof, rec)
                return self._resolve_ranges(
                    packed[: len(topics)], topics, flat, P,
                    len_overflow[: len(topics)], pred, batch_pred,
                )
            if prof is not None:
                self._stamp_bytes(rec, int(out.nbytes), True)
                self._note_sync(prof, rec)
            stats.compact_batches += 1
            stats.d2h_bytes += int(out.nbytes)
            totals = out[2 : 2 + bp]
            flags = out[2 + bp : 2 + 2 * bp]
            true_overflow = ((flags & FLAG_OVERFLOW) != 0) | len_overflow
            pair_sid = out[2 + 2 * bp : 2 + 2 * bp + capacity]
            if batch_pred is not None:
                routed = batch_pred(topics)
            elif pred is not None:
                routed = [i for i, t in enumerate(topics) if t and pred(t)]
            else:
                routed = ()
            host_route = true_overflow.copy()
            if len(routed):
                host_route[np.asarray(routed, dtype=np.int64)] = True
            self._count_wide(flags[:b], host_route[:b])
            return self._materialize_pairs(
                pair_sid, None, totals, host_route, n_hits, topics, flat,
                true_overflow,
            )

        return resolve_compact

    def _compact_pays(self, P: int) -> bool:
        """The transfer-optimal encoding pick. The padded-ranges row
        costs ``2P+2`` ints/topic regardless of hits; the compacted
        stream costs ~``hits x 1.5`` (headroom) + 2 ints/topic. Dense
        workloads (hits/topic high vs the probe count — cfg 2's 1M
        `+`-subs measures ~11 hits at P=4) are ALREADY optimally encoded
        by the contiguous synthetic-sid ranges, and expanding them to
        pairs would transfer MORE; sparse workloads (deep/`#` mixes,
        exact-heavy sets, most real MQTT subscription shapes) win with
        pairs. Both paths stay bit-identical and both feed the same
        hits EWMA, so the pick adapts with the workload. A pinned
        ``compact_capacity`` forces the compact path (the operator
        chose)."""
        if self.compact_capacity > 0:
            return True
        return self._hits_ewma * 1.5 + 2.0 < 2.0 * P + 2.0

    def _compact_capacity_for(self, b_padded: int, flat) -> int:
        """The pair-buffer capacity for one batch (pick_compact_capacity:
        pinned-or-adaptive with sticky pow2 buckets), capped at the hits
        a batch of this index can hold: P probes a topic, each of at
        most the widest entry's ids (the window where no entry is wide)."""
        width = max(flat.window, flat.max_width)
        max_hits = b_padded * int(flat.pat_depth.shape[0]) * width
        return pick_compact_capacity(
            self.compact_capacity, self._hits_ewma, b_padded, max_hits,
            self._caps,
        )

    def _observe_hits(self, n_hits: int, b: int) -> None:
        """Feed one batch's true hit count into the capacity EWMA."""
        self._hits_ewma = fold_hits_ewma(self._hits_ewma, n_hits, b)

    def _count_wide(self, flags: np.ndarray, host_route) -> None:
        """Count the batch's topics whose device answer held a wide
        entry's hit and was served (not re-walked): one pass over the
        flags word in hand, nothing a topic in Python."""
        wide = (flags & FLAG_WIDE) != 0
        if wide.any():
            self.stats.wide_topics += int(np.count_nonzero(wide & ~host_route))

    @staticmethod
    def _note_sync(prof, rec) -> None:
        """The blocking D2H sync is done: the profiler's in-flight
        window closes on the record's own span, from the same reads."""
        t0_ns, t1_ns = rec.d2h_sync
        prof.note_resolve(rec, t0_ns / 1e9, t1_ns / 1e9)

    @staticmethod
    def _stamp_bytes(rec, d2h_bytes: int, compact: bool) -> None:
        """Stamp one batch's transfer accounting onto its BatchProfile
        (mqtt_tpu.tracing) — the device profiler folds these into the
        per-device D2H byte totals and the compact-sync histogram."""
        if rec is None:
            return
        rec.d2h_bytes = d2h_bytes
        rec.compact = compact

    def _materialize_pairs(
        self,
        pair_sid: np.ndarray,
        pair_shard: Optional[np.ndarray],
        totals: np.ndarray,
        host_route: np.ndarray,
        n_hits: int,
        topics: list[str],
        flat,
        true_overflow: np.ndarray,
        tables: Optional[list] = None,
    ) -> list[Subscribers]:
        return materialize_compact_pairs(
            self.stats,
            self.topics.subscribers,
            pair_sid,
            pair_shard,
            totals,
            host_route,
            n_hits,
            topics,
            flat.subs,
            flat.window,
            true_overflow,
            tables=tables,
            lazy=self.lazy,
        )

    def _resolve_ranges(
        self, packed, topics, flat, P, len_overflow, pred, batch_pred
    ) -> list[Subscribers]:
        """Materialize one already-synced padded-ranges batch (the
        pre-compaction production form, and the compact path's per-batch
        overflow fallback): C materializer when available, the Python
        loop otherwise."""
        acc = _accel()
        if acc is not None:
            return self._resolve_native(
                acc, packed, topics, flat, P, len_overflow, pred, batch_pred
            )
        stats = self.stats
        # the ONLY host-route class left: device overflow (a saturated
        # bucket) or >max_levels topics — ranges carry the COMPLETE
        # result, so every fallback is also an overflow
        flags = packed[:, 2 * P + 1]
        overflow = ((flags & FLAG_OVERFLOW) != 0) | len_overflow
        route = overflow
        if pred is not None:
            route = overflow | np.fromiter(
                (bool(t) and pred(t) for t in topics), bool, len(topics)
            )
        self._count_wide(flags, route)
        overflow = overflow.tolist()
        route = route.tolist()
        # one bulk C conversion: per-row numpy slicing costs ~10us of
        # fixed overhead per topic, plain list walks are ~10x cheaper
        out_rows = packed[:, : 2 * P].tolist()
        results = []
        results_append = results.append
        table = flat.subs
        for i, topic in enumerate(topics):
            if not topic:
                results_append(Subscribers())  # empty topic never matches
            elif route[i]:
                stats.host_fallbacks += 1
                stats.overflows += int(overflow[i])
                results_append(self.topics.subscribers(topic))  # host fallback
            else:
                row = out_rows[i]
                sids = []
                for p in range(P):
                    c = row[P + p]
                    if c:
                        s0 = row[p]
                        sids.extend(range(s0, s0 + c))
                results_append(expand_sids(table, sids, Subscribers()))
        return results

    def _match_exact_fast(self, topics: list[str], flat, route_to_host):
        """Serve a batch from the exact-map (wildcard-free filter sets):
        every topic is one dict probe + one snapshot expansion, covering
        over-deep paths and saturated buckets too — no fallback classes,
        no device dispatch. Results are bit-identical to the host walk: in an
        exact-only trie the walk gathers exactly the literal path's node.

        The work happens when the RESOLVER runs, not at issue time: the
        staging loop issues on the event loop and resolves in an executor
        thread, and a large-fan-out batch materialized at issue time would
        stall every connected client's I/O for the duration."""

        def resolve() -> list[Subscribers]:
            stats = self.stats
            stats.batches += 1
            stats.topics += len(topics)
            if route_to_host is None:
                routed = ()
            elif hasattr(route_to_host, "affected_batch"):
                routed = frozenset(route_to_host.affected_batch(topics))
            else:
                routed = frozenset(
                    i for i, t in enumerate(topics) if t and route_to_host(t)
                )
            get = flat.exact_map.get
            acc = _accel()
            if acc is not None:
                expand_c = acc.expand_snap

                def expand(snap):
                    return expand_c(snap, Subscribers)

            else:
                expand = self._expand_snap
            subscribers = self.topics.subscribers
            results = []
            results_append = results.append
            n_fast = 0
            for i, topic in enumerate(topics):
                if not topic:
                    results_append(Subscribers())
                elif i in routed:
                    stats.host_fallbacks += 1
                    results_append(subscribers(topic))
                else:
                    n_fast += 1
                    snap = get(topic)
                    results_append(
                        expand(snap) if snap is not None else Subscribers()
                    )
            stats.host_fast += n_fast
            return results

        return resolve

    @staticmethod
    def _expand_snap(snap) -> Subscribers:
        """Materialize one node snapshot tuple into a Subscribers result —
        the single-node case of the host gather (topics.go:631-678): each
        client appears at most once per node, so the per-client entry is
        the inlined self-merge copy from ``expand_sids``; shared entries
        are referenced (not copied) keyed on the group filter; inline
        entries key on identifier."""
        subs = Subscribers()
        cli, shr, inl = snap
        subscriptions = subs.subscriptions
        for client, sub in cli:
            subscriptions[client] = sub.self_merged_copy()
        if shr:
            shared = subs.shared
            for client, sub in shr:
                group = shared.get(sub.filter)
                if group is None:
                    group = shared[sub.filter] = {}
                group[client] = sub
        if inl:
            inline = subs.inline_subscriptions
            for isub in inl:
                inline[isub.identifier] = isub
        return subs

    def _resolve_native(
        self, acc, packed, topics, flat, P, len_overflow, pred, batch_pred
    ) -> list[Subscribers]:
        """Materialize one resolved batch through the C extension
        (native/accelmod.c), byte-identical to the Python loop above:
        overflow rows and delta-routed topics re-walk the host trie, empty
        topics yield empty results, everything else expands from the packed
        sid ranges."""
        stats = self.stats
        col = 2 * P + 1
        # every host-route class — device overflow, over-deep topics, and
        # delta-routed topics — is merged into the overflow column BEFORE
        # the C call, so routed rows are never materialized just to be
        # thrown away by a patch-up loop
        flags = packed[:, col]
        true_overflow = ((flags & FLAG_OVERFLOW) != 0) | len_overflow
        if batch_pred is not None:
            routed = batch_pred(topics)
        elif pred is not None:
            routed = [i for i, t in enumerate(topics) if t and pred(t)]
        else:
            routed = ()
        host_route = true_overflow
        if len(routed):
            host_route = true_overflow.copy()
            host_route[np.asarray(routed, dtype=np.int64)] = True
        self._count_wide(flags, host_route)
        if (flags != host_route).any():
            # the C materializer reads the column as "re-walk on the
            # host": hand it the routes alone, without the wide flag
            packed = packed.copy()
            packed[:, col] = host_route
        if self.lazy and hasattr(acc, "resolve_batch_views"):
            # lazy ranges views (ISSUE 13): the packed row itself is the
            # result; per-hit objects build on demand at fan-out. The
            # buffer is pinned by the views, so hand them a contiguous
            # copy-independent array (packed may be a slice).
            results, ovf_idx = acc.resolve_batch_views(
                np.ascontiguousarray(packed), len(topics), P,
                flat.subs.snaps, flat.window, Subscribers,
            )
        else:
            results, ovf_idx = acc.resolve_batch(
                packed, len(topics), P, flat.subs.snaps, flat.window,
                Subscribers,
            )
        subscribers = self.topics.subscribers
        for i in ovf_idx:
            topic = topics[i]
            if topic:
                stats.host_fallbacks += 1
                # routed-only rows are fallbacks but not device overflows
                stats.overflows += int(bool(true_overflow[i]))
                results[i] = subscribers(topic)
            else:
                results[i] = Subscribers()
        if "" in topics:  # empty topic never matches (host-walk parity)
            for i, topic in enumerate(topics):
                if not topic:
                    results[i] = Subscribers()
        return results

    def match_topics(self, topics: list[str], route_to_host=None) -> list[Subscribers]:
        """Match a batch of topics; every result is bit-identical to the
        host trie (overflowing topics are re-walked on host).

        ``route_to_host`` optionally forces extra topics onto the host walk
        (the delta overlay's affected-check in mqtt_tpu.ops.delta); the
        host path is always correct, so any predicate preserves parity.
        """
        return self.match_topics_async(topics, route_to_host)()

    def subscribers(self, topic: str) -> Subscribers:
        """Drop-in for ``TopicsIndex.subscribers`` (batch of one)."""
        return self.match_topics([topic])[0]
